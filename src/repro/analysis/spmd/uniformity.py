"""Replica-uniformity dataflow over shard_map bodies (SP01–SP03).

The lattice value of a variable is the set of mesh axes along which it
may *vary* per rank — ``frozenset()`` means replica-uniform.  Seeds come
from the shard_map declaration itself: an input split along axes varies
along them, an unsplit input is uniform, ``axis_index(a)`` varies along
``a``.  Propagation is the obvious union join, with reducing collectives
(``psum``/``pmin``/``pmax``/``all_gather``) *subtracting* the axes they
reduce over — exactly the operation the paper's asynchronous relaxation
relies on to keep every replica-uniform quantity identical on all ranks.

Checks:

  SP01  a replica-varying value reaching a replica-uniform sink: a
        shard_map output whose out_spec omits an axis the value varies
        along (telemetry channels, convergence counters), or a
        ``while_loop`` predicate that varies along any mesh axis (ranks
        would disagree on the iteration count — collective deadlock).
  SP02  a collective inside a shard_map body over an axis that is not a
        mesh axis (e.g. a vmap-bound name — the reduction silently drops
        the mesh axis it was meant to cover).
  SP03  a collective under a ``cond`` whose predicate varies along one
        of the collective's own axes: ranks of the same group take
        different branches, so the collective deadlocks (or worse,
        pairs mismatched participants) on a real multi-host mesh.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from jax.core import DropVar
from jax.extend import core as jax_core

from repro.analysis.spmd.jaxpr_tools import (
    REDUCING_COLLECTIVES,
    Violation,
    collective_axes,
    sub_jaxprs,
)

Axes = frozenset
_EMPTY: Axes = frozenset()


class _Env:
    """Var → varying-axes map with Literal handling."""

    def __init__(self) -> None:
        self._m: Dict[jax_core.Var, Axes] = {}

    def read(self, atom) -> Axes:
        if isinstance(atom, jax_core.Literal):
            return _EMPTY
        return self._m.get(atom, _EMPTY)

    def write(self, var, axes: Axes) -> None:
        if not isinstance(var, DropVar):
            self._m[var] = axes


def _mesh_axis_names(mesh) -> tuple:
    names = getattr(mesh, "axis_names", None)
    if names is not None:
        return tuple(names)
    shape = getattr(mesh, "shape", {})
    return tuple(shape)


def _spec_axes(spec) -> Axes:
    """Mesh axes named by one in_specs/out_specs PartitionSpec."""
    out = set()
    for axes in spec or ():
        if axes is None:
            continue
        if isinstance(axes, str):
            out.add(axes)
        else:
            out.update(axes)
    return frozenset(out)


def check_shard_map(eqn, out: List[Violation]) -> List[Axes]:
    """Analyzes one shard_map equation; returns outvar varying sets."""
    mesh_axes = frozenset(_mesh_axis_names(eqn.params.get("mesh")))
    jaxpr = eqn.params["jaxpr"]
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        consts, jaxpr = list(jaxpr.consts), jaxpr.jaxpr
    else:
        consts = []
    in_specs = eqn.params.get("in_specs", ())
    out_specs = eqn.params.get("out_specs", ())
    seed = [_spec_axes(spec) & mesh_axes for spec in in_specs]
    # pad for closed-over consts (replicated) if arity differs
    while len(seed) < len(jaxpr.invars):
        seed.insert(0, _EMPTY)
    analyzer = _Uniformity(mesh_axes, out)
    out_varying = analyzer.run(jaxpr, seed[: len(jaxpr.invars)], consts)
    for i, (ovar_axes, spec) in enumerate(zip(out_varying, out_specs)):
        declared = _spec_axes(spec)
        leaked = (ovar_axes - declared) & mesh_axes
        if leaked:
            producer = _producer_of(jaxpr, i) or eqn
            out.append(
                Violation(
                    rule="SP01",
                    message=(
                        f"shard_map output {i} is declared replicated "
                        f"along mesh axis(es) {sorted(leaked)} but the "
                        f"computed value varies per rank there — ranks "
                        f"disagree on a replica-uniform quantity; reduce "
                        f"with psum/pmin/all_gather before returning"
                    ),
                    eqn=producer,
                )
            )
    return out_varying


def _producer_of(jaxpr: jax_core.Jaxpr, out_index: int):
    """The equation producing outvar ``out_index`` (provenance anchor)."""
    var = jaxpr.outvars[out_index]
    if isinstance(var, jax_core.Literal):
        return None
    for eqn in reversed(jaxpr.eqns):
        if any(v is var for v in eqn.outvars):
            return eqn
    return None


class _Uniformity:
    def __init__(self, mesh_axes: Axes, out: List[Violation]) -> None:
        self.mesh_axes = mesh_axes
        self.out = out

    def run(
        self,
        jaxpr: jax_core.Jaxpr,
        in_varying: Sequence[Axes],
        consts: Sequence = (),
    ) -> List[Axes]:
        env = _Env()
        for var in jaxpr.constvars:
            env.write(var, _EMPTY)  # concrete consts are rank-identical
        for var, axes in zip(jaxpr.invars, in_varying):
            env.write(var, axes)
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env)
        return [env.read(v) for v in jaxpr.outvars]

    # -- dispatch ----------------------------------------------------------

    def _eqn(self, eqn, env: _Env) -> None:
        name = eqn.primitive.name
        ins = [env.read(v) for v in eqn.invars]
        joined: Axes = frozenset().union(*ins) if ins else _EMPTY

        if name == "axis_index":
            axis = eqn.params.get("axis_name")
            env.write(eqn.outvars[0], frozenset({axis} if isinstance(axis, str) else axis))
            return
        axes = collective_axes(eqn)
        if axes is not None:
            unknown = [a for a in axes if a not in self.mesh_axes]
            if unknown:
                self.out.append(
                    Violation(
                        rule="SP02",
                        message=(
                            f"collective over axis(es) {unknown} inside a "
                            f"shard_map whose mesh axes are "
                            f"{sorted(self.mesh_axes)} — the reduction "
                            f"drops the mesh axis it was meant to cover "
                            f"(axis name mismatch)"
                        ),
                        eqn=eqn,
                    )
                )
            result = joined
            if name in REDUCING_COLLECTIVES and not eqn.params.get(
                "axis_index_groups"
            ):
                result = joined - frozenset(axes)
            if name == "ppermute":
                result = joined | (frozenset(axes) & self.mesh_axes)
            for var in eqn.outvars:
                env.write(var, result)
            return
        if name == "while":
            self._while(eqn, env, ins)
            return
        if name == "cond":
            self._cond(eqn, env, ins)
            return
        if name == "scan":
            self._scan(eqn, env, ins)
            return
        handled = self._generic_higher_order(eqn, env, ins)
        if handled:
            return
        for var in eqn.outvars:
            env.write(var, joined)

    # -- higher-order primitives ------------------------------------------

    def _subrun(self, jaxpr, consts, in_varying) -> List[Axes]:
        return _Uniformity(self.mesh_axes, self.out).run(
            jaxpr, in_varying, consts
        )

    def _generic_higher_order(self, eqn, env: _Env, ins) -> bool:
        """jit / closed_call / remat / custom_* — one body, args map 1:1.

        Returns False (caller falls back to the union join) when the
        sub-jaxpr arity doesn't line up (e.g. pallas_call, whose invars
        are memory refs, not the eqn operands)."""
        subs = list(sub_jaxprs(eqn))
        if len(subs) != 1:
            return False
        _, jaxpr, consts = subs[0]
        if len(jaxpr.invars) != len(ins):
            return False
        outs = self._subrun(jaxpr, consts, ins)
        if len(outs) != len(eqn.outvars):
            return False
        for var, axes in zip(eqn.outvars, outs):
            env.write(var, axes)
        return True

    def _while(self, eqn, env: _Env, ins) -> None:
        cond_j = eqn.params["cond_jaxpr"]
        body_j = eqn.params["body_jaxpr"]
        nc = eqn.params.get("cond_nconsts", 0)
        nb = eqn.params.get("body_nconsts", 0)
        cond_consts = ins[:nc]
        body_consts = ins[nc: nc + nb]
        carry = list(ins[nc + nb:])
        for _ in range(len(carry) * len(self.mesh_axes) + 2):
            outs = _Uniformity(self.mesh_axes, []).run(
                body_j.jaxpr, body_consts + carry, body_j.consts
            )
            new_carry = [c | o for c, o in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        # re-run the body once WITH reporting, at the carry fixpoint
        self._subrun(body_j.jaxpr, body_j.consts, body_consts + carry)
        pred = _Uniformity(self.mesh_axes, self.out).run(
            cond_j.jaxpr, cond_consts + carry, cond_j.consts
        )
        pred_varying = pred[0] & self.mesh_axes if pred else _EMPTY
        if pred_varying:
            self.out.append(
                Violation(
                    rule="SP01",
                    message=(
                        f"while_loop predicate varies along mesh axis(es) "
                        f"{sorted(pred_varying)} — ranks disagree on the "
                        f"iteration count, deadlocking any collective in "
                        f"the body; reduce the convergence predicate "
                        f"(pmax/psum) before the loop test"
                    ),
                    eqn=eqn,
                )
            )
        for var, axes in zip(eqn.outvars, carry):
            env.write(var, axes | pred_varying)

    def _cond(self, eqn, env: _Env, ins) -> None:
        pred_varying = ins[0] & self.mesh_axes
        branch_ins = ins[1:]
        branches = eqn.params.get("branches", ())
        outs: List[Axes] = [_EMPTY] * len(eqn.outvars)
        for br in branches:
            b_out = self._subrun(br.jaxpr, br.consts, branch_ins)
            outs = [o | b for o, b in zip(outs, b_out)]
            if pred_varying:
                self._flag_divergent_collectives(br.jaxpr, pred_varying)
        for var, axes in zip(eqn.outvars, outs):
            env.write(var, axes | pred_varying)

    def _flag_divergent_collectives(self, jaxpr, pred_varying: Axes) -> None:
        from repro.analysis.spmd.jaxpr_tools import walk_eqns

        for sub in walk_eqns(jaxpr):
            axes = collective_axes(sub)
            if axes is None or sub.primitive.name == "axis_index":
                continue
            overlap = frozenset(axes) & pred_varying
            if overlap:
                self.out.append(
                    Violation(
                        rule="SP03",
                        message=(
                            f"collective over {sorted(overlap)} under a "
                            f"cond whose predicate varies along the same "
                            f"axis(es) — ranks of one group take "
                            f"different branches, so the collective "
                            f"deadlocks on a real mesh; hoist it out of "
                            f"the branch or make the predicate uniform"
                        ),
                        eqn=sub,
                    )
                )

    def _scan(self, eqn, env: _Env, ins) -> None:
        body = eqn.params["jaxpr"]
        n_consts = eqn.params.get("num_consts", 0)
        n_carry = eqn.params.get("num_carry", 0)
        consts = ins[:n_consts]
        carry = list(ins[n_consts: n_consts + n_carry])
        xs = ins[n_consts + n_carry:]
        ys: List[Axes] = []
        for _ in range(n_carry * max(1, len(self.mesh_axes)) + 2):
            outs = _Uniformity(self.mesh_axes, []).run(
                body.jaxpr, consts + carry + xs, body.consts
            )
            new_carry = [c | o for c, o in zip(carry, outs[:n_carry])]
            ys = outs[n_carry:]
            if new_carry == carry:
                break
            carry = new_carry
        self._subrun(body.jaxpr, body.consts, consts + carry + xs)
        for var, axes in zip(eqn.outvars, carry + ys):
            env.write(var, axes)


def analyze(closed_jaxpr) -> List[Violation]:
    """All SP violations in a traced executable: every shard_map eqn in
    the (recursively walked) jaxpr is checked; code outside shard_map is
    single-logical-device and has no replica structure to violate."""
    out: List[Violation] = []
    _walk(closed_jaxpr.jaxpr, out)
    return out


def _walk(jaxpr: jax_core.Jaxpr, out: List[Violation]) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            check_shard_map(eqn, out)
            continue  # the body was analyzed with replica context
        for _, sub, _consts in sub_jaxprs(eqn):
            _walk(sub, out)
