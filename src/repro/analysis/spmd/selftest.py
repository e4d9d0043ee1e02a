"""Seeded-violation programs: one deliberately-broken jaxpr per rule.

Each seed reconstructs a *historical* bug class in miniature and must be
caught by the analyzer — they are the spmd layer's answer to the ast
layer's fixture files, and CI runs them (``--seed-violation RULE``) to
prove the gate actually fires before trusting its green runs:

  SP01  a per-rank partial sum returned through a replicated out_spec
        without psum (the unreduced-telemetry-channel bug).
  SP02  a collective whose axis name is not a mesh axis of its
        shard_map.  jax refuses to *trace* a genuinely unbound name, so
        this seed rewrites the axes of a legally-traced psum post hoc —
        the analyzer sees exactly the jaxpr a name mix-up would produce.
  SP03  a collective under a branch selected by ``axis_index`` — ranks
        diverge, the collective deadlocks on a real mesh.
  NU01  iota(70000) cast to int16 (the PR-5 ``lab_i16`` overflow).
  NU02  integers past 2^24 cast to float32 (exactness loss).
  DN01  a buffer donated to an inner jit and then read again (the PR-7
        EllPatcher read-after-donation).
"""

from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.analysis.spmd.harness import analyze_jaxpr
from repro.launch.mesh import make_mesh

SEEDABLE_RULES = ("SP01", "SP02", "SP03", "NU01", "NU02", "DN01")


def _mesh_1d():
    from jax.sharding import PartitionSpec as P

    return make_mesh((1,), ("data",)), P


def _trace(fn, *args):
    return jax.jit(fn).trace(*args).jaxpr


def _seed_sp01():
    mesh, P = _mesh_1d()

    def body(x):
        return jnp.sum(x)  # per-rank partial — never psum'd

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False,
    )
    return _trace(fn, jnp.arange(16.0))


def _seed_sp02():
    mesh, P = _mesh_1d()

    def body(x):
        return jax.lax.psum(jnp.sum(x), "data")

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False,
    )
    jaxpr = _trace(fn, jnp.arange(16.0))
    # Rewrite the (legally traced) psum to name an axis outside the mesh:
    # jax won't trace an unbound name, but THIS jaxpr is what an axis-name
    # mix-up produces, and it's what the analyzer must catch.
    from repro.analysis.spmd.jaxpr_tools import walk_eqns

    for eqn in walk_eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        body_jaxpr = eqn.params["jaxpr"]
        open_jaxpr = (
            body_jaxpr.jaxpr if hasattr(body_jaxpr, "jaxpr") else body_jaxpr
        )
        for i, sub in enumerate(open_jaxpr.eqns):
            if sub.primitive.name == "psum":
                open_jaxpr.eqns[i] = sub.replace(
                    params=dict(sub.params, axes=("batch",))
                )
                return jaxpr
    raise AssertionError("no psum eqn found to rewrite")


def _seed_sp03():
    mesh, P = _mesh_1d()

    def body(x):
        rank = jax.lax.axis_index("data")
        return jax.lax.cond(
            rank == 0,
            lambda v: jax.lax.psum(v, "data"),  # only rank 0 enters
            lambda v: v,
            jnp.sum(x),
        )

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False,
    )
    return _trace(fn, jnp.arange(16.0))


def _seed_nu01():
    def f():
        labels = jax.lax.iota(jnp.int32, 70000)
        return labels.astype(jnp.int16)  # 69999 > 32767: silent wrap

    return _trace(f)


def _seed_nu02():
    def f():
        idx = jax.lax.iota(jnp.int32, 8) + jnp.int32(1 << 25)
        return idx.astype(jnp.float32)  # 2^25 > 2^24: inexact integers

    return _trace(f)


def _seed_dn01():
    @partial(jax.jit, donate_argnums=0)
    def relabel(buf):
        return buf * 2.0

    def outer(x):
        y = relabel(x)
        return y + x  # x was donated to relabel — stale read

    return _trace(outer, jnp.ones(8, jnp.float32))


_SEEDS = {
    "SP01": _seed_sp01,
    "SP02": _seed_sp02,
    "SP03": _seed_sp03,
    "NU01": _seed_nu01,
    "NU02": _seed_nu02,
    "DN01": _seed_dn01,
}


def seed_findings(rule: str) -> List[Finding]:
    """Analyzer output on the seeded program for ``rule``.

    The caller (CLI ``--seed-violation``, CI, tests) asserts that the
    expected rule id is present — an empty result means the analyzer
    lost the bug class and the gate is blind."""
    if rule not in _SEEDS:
        raise KeyError(
            f"no seeded program for {rule!r}; seedable: {SEEDABLE_RULES}"
        )
    jaxpr = _SEEDS[rule]()
    return analyze_jaxpr(jaxpr, context=f"selftest/{rule}")


def run_selftest(rule: str) -> bool:
    """True iff the seeded program for ``rule`` is caught (rule id among
    the findings)."""
    return any(f.rule == rule for f in seed_findings(rule))
