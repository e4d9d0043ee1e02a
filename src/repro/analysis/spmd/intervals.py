"""Value-range abstract interpretation over jaxprs (NU01–NU02).

Each variable carries an interval ``[lo, hi]`` (floats; ±inf = unknown).
The domain is deliberately *whitelist-sound*: only primitives with an
implemented transfer function produce finite bounds, everything else
falls to ⊤ ``(-inf, +inf)``.  Both rules therefore fire only on **proven**
violations — an interval the analyzer can fully justify that provably
escapes the target representation — never on "might be big" guesses, so
a clean codebase stays clean without baseline churn.

  NU01  ``convert_element_type`` to a narrower integer dtype whose range
        the operand's proven interval exceeds (the PR-5 bug class:
        ``lab_i16`` labels overflowing int16 once ``S >= 32768``).
  NU02  integer → float32 cast where the proven magnitude exceeds 2^24,
        past which f32 cannot represent every integer exactly (ghost-row
        index arithmetic, fuse_gather packing).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.core import DropVar
from jax.extend import core as jax_core

from repro.analysis.spmd.jaxpr_tools import Violation, sub_jaxprs

Interval = Tuple[float, float]
TOP: Interval = (-math.inf, math.inf)
_F32_EXACT = float(2 ** 24)
_CONST_SCAN_LIMIT = 1 << 22  # don't min/max giant embedded constants


def _is_int(dtype) -> bool:
    try:
        return np.issubdtype(np.dtype(dtype), np.integer)
    except TypeError:
        return False


def _int_range(dtype) -> Optional[Interval]:
    try:
        info = np.iinfo(np.dtype(dtype))
    except ValueError:
        return None
    return float(info.min), float(info.max)


def _const_interval(value) -> Interval:
    try:
        arr = np.asarray(value)
        if arr.size == 0 or arr.size > _CONST_SCAN_LIMIT:
            return TOP
        if arr.dtype == bool:
            return (0.0, 1.0)
        if not np.issubdtype(arr.dtype, np.number):
            return TOP
        lo = float(np.min(arr))
        hi = float(np.max(arr))
        if math.isnan(lo) or math.isnan(hi):
            return TOP
        return lo, hi
    except Exception:
        return TOP


def _join(a: Interval, b: Interval) -> Interval:
    return min(a[0], b[0]), max(a[1], b[1])


def _nelems(aval) -> int:
    shape = getattr(aval, "shape", ())
    n = 1
    for d in shape:
        n *= int(d)
    return n


class _Env:
    def __init__(self) -> None:
        self._m: Dict[jax_core.Var, Interval] = {}

    def read(self, atom) -> Interval:
        if isinstance(atom, jax_core.Literal):
            return _const_interval(atom.val)
        return self._m.get(atom, TOP)

    def write(self, var, iv: Interval) -> None:
        if not isinstance(var, DropVar):
            self._m[var] = iv


_PASS_THROUGH = frozenset(
    {
        "broadcast_in_dim", "reshape", "transpose", "squeeze", "rev",
        "slice", "dynamic_slice", "copy", "stop_gradient", "gather",
        "reduce_min", "reduce_max", "pmin", "pmax", "all_gather",
        "sort", "expand_dims", "real", "convert_element_type_p",
    }
)
_BOOL_OUT = frozenset(
    {
        "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not", "xor",
        "is_finite", "reduce_and", "reduce_or",
    }
)


class _Intervals:
    def __init__(self, axis_sizes: Dict[str, int], out: List[Violation]):
        self.axis_sizes = dict(axis_sizes)
        self.out = out

    def run(
        self,
        jaxpr: jax_core.Jaxpr,
        in_ivs: Sequence[Interval],
        consts: Sequence = (),
    ) -> List[Interval]:
        env = _Env()
        for var, c in zip(jaxpr.constvars, consts):
            env.write(var, _const_interval(c))
        for var in jaxpr.constvars[len(consts):]:
            env.write(var, TOP)
        for var, iv in zip(jaxpr.invars, in_ivs):
            env.write(var, iv)
        for var in jaxpr.invars[len(in_ivs):]:
            env.write(var, TOP)
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env)
        return [env.read(v) for v in jaxpr.outvars]

    # -- transfer functions ------------------------------------------------

    def _eqn(self, eqn, env: _Env) -> None:
        name = eqn.primitive.name
        ins = [env.read(v) for v in eqn.invars]

        if name == "convert_element_type":
            self._convert(eqn, env, ins[0])
            return
        if name in _BOOL_OUT:
            env.write(eqn.outvars[0], (0.0, 1.0))
            return
        if name in _PASS_THROUGH:
            iv = ins[0] if ins else TOP
            for var in eqn.outvars:
                env.write(var, iv)
            return
        out = self._arith(name, eqn, ins)
        if out is not None:
            env.write(eqn.outvars[0], out)
            return
        if name in ("while", "scan"):
            self._loop(eqn, env, ins, name)
            return
        if name == "cond":
            self._cond(eqn, env, ins)
            return
        if name in ("jit", "closed_call", "core_call", "remat", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr"):
            if self._call(eqn, env, ins):
                return
        # unknown primitive: sound default is ⊤
        for var in eqn.outvars:
            env.write(var, TOP)

    def _arith(self, name, eqn, ins) -> Optional[Interval]:
        if name == "iota":
            dim = eqn.params.get("dimension", 0)
            shape = eqn.params.get("shape") or getattr(
                eqn.outvars[0].aval, "shape", (1,)
            )
            n = int(shape[dim]) if shape else 1
            return (0.0, float(max(0, n - 1)))
        if name in ("argmin", "argmax"):
            axes = eqn.params.get("axes", ())
            in_shape = getattr(eqn.invars[0].aval, "shape", ())
            hi = 0
            for ax in axes:
                if 0 <= ax < len(in_shape):
                    hi = max(hi, int(in_shape[ax]) - 1)
            return (0.0, float(hi))
        if name == "add":
            (a, b), (c, d) = ins
            return (a + c, b + d)
        if name == "sub":
            (a, b), (c, d) = ins
            return (a - d, b - c)
        if name == "neg":
            (a, b) = ins[0]
            return (-b, -a)
        if name == "abs":
            (a, b) = ins[0]
            if a >= 0:
                return (a, b)
            if b <= 0:
                return (-b, -a)
            return (0.0, max(-a, b))
        if name == "mul":
            (a, b), (c, d) = ins
            prods = [a * c, a * d, b * c, b * d]
            prods = [0.0 if math.isnan(p) else p for p in prods]
            return (min(prods), max(prods))
        if name == "max":
            (a, b), (c, d) = ins
            return (max(a, c), max(b, d))
        if name == "min":
            (a, b), (c, d) = ins
            return (min(a, c), min(b, d))
        if name == "clamp":
            # sound (if loose): the result is always one of the operands
            lo_iv, x_iv, hi_iv = ins
            return _join(_join(lo_iv, x_iv), hi_iv)
        if name == "select_n":
            out = ins[1]
            for iv in ins[2:]:
                out = _join(out, iv)
            return out
        if name == "reduce_sum":
            (a, b) = ins[0]
            n_in = _nelems(eqn.invars[0].aval)
            n_out = _nelems(eqn.outvars[0].aval)
            count = max(1, n_in // max(1, n_out))
            return (min(a * count, a), max(b * count, b))
        if name == "psum":
            (a, b) = ins[0]
            count = 1
            axes = eqn.params.get("axes", ())
            if isinstance(axes, str):
                axes = (axes,)
            for ax in axes:
                count *= self.axis_sizes.get(ax, 1) if isinstance(ax, str) else 1
            return (min(a * count, a), max(b * count, b))
        if name in ("rem", "mod"):
            (_, _), (c, d) = ins
            m = max(abs(c), abs(d))
            if math.isinf(m):
                return TOP
            return (-m, m)
        return None

    def _convert(self, eqn, env: _Env, iv: Interval) -> None:
        new_dtype = eqn.params.get("new_dtype")
        src_aval = getattr(eqn.invars[0], "aval", None)
        src_dtype = getattr(src_aval, "dtype", None)
        lo, hi = iv
        proven = math.isfinite(lo) and math.isfinite(hi)
        if proven and _is_int(new_dtype):
            rng = _int_range(new_dtype)
            if rng and (lo < rng[0] or hi > rng[1]):
                self.out.append(
                    Violation(
                        rule="NU01",
                        message=(
                            f"narrowing cast to {np.dtype(new_dtype).name}: "
                            f"operand proven in [{lo:.0f}, {hi:.0f}] but the "
                            f"target holds only [{rng[0]:.0f}, {rng[1]:.0f}] "
                            f"— values wrap silently (int16-label bug class)"
                        ),
                        eqn=eqn,
                    )
                )
        if (
            proven
            and src_dtype is not None
            and _is_int(src_dtype)
            and np.dtype(new_dtype) == np.dtype(np.float32)
            and max(abs(lo), abs(hi)) > _F32_EXACT
        ):
            self.out.append(
                Violation(
                    rule="NU02",
                    message=(
                        f"int→float32 cast with proven magnitude up to "
                        f"{max(abs(lo), abs(hi)):.0f} > 2^24: float32 cannot "
                        f"represent every integer past 16777216, so index/"
                        f"key arithmetic silently loses exactness"
                    ),
                    eqn=eqn,
                )
            )
        env.write(eqn.outvars[0], iv)

    # -- higher-order ------------------------------------------------------

    def _sub(self, jaxpr, consts, ins) -> List[Interval]:
        return _Intervals(self.axis_sizes, self.out).run(jaxpr, ins, consts)

    def _call(self, eqn, env: _Env, ins) -> bool:
        subs = list(sub_jaxprs(eqn))
        if len(subs) != 1:
            return False
        _, jaxpr, consts = subs[0]
        if len(jaxpr.invars) != len(ins):
            return False
        outs = self._sub(jaxpr, consts, ins)
        if len(outs) != len(eqn.outvars):
            return False
        for var, iv in zip(eqn.outvars, outs):
            env.write(var, iv)
        return True

    def _cond(self, eqn, env: _Env, ins) -> None:
        branch_ins = ins[1:]
        outs: Optional[List[Interval]] = None
        for br in eqn.params.get("branches", ()):
            b_out = self._sub(br.jaxpr, br.consts, branch_ins)
            outs = b_out if outs is None else [
                _join(a, b) for a, b in zip(outs, b_out)
            ]
        for var, iv in zip(eqn.outvars, outs or []):
            env.write(var, iv)

    def _loop(self, eqn, env: _Env, ins, name: str) -> None:
        """Fixpoint with aggressive widening: any carry bound still moving
        after two body passes goes straight to ±inf (keeps NU proofs sound
        without per-loop invariant inference)."""
        if name == "while":
            body = eqn.params["body_jaxpr"]
            nc = eqn.params.get("cond_nconsts", 0)
            nb = eqn.params.get("body_nconsts", 0)
            consts = ins[nc: nc + nb]
            carry = list(ins[nc + nb:])
            mk_in = lambda c: consts + c  # noqa: E731
            n_carry = len(carry)
            xs: List[Interval] = []
        else:
            body = eqn.params["jaxpr"]
            n_consts = eqn.params.get("num_consts", 0)
            n_carry = eqn.params.get("num_carry", 0)
            consts = ins[:n_consts]
            carry = list(ins[n_consts: n_consts + n_carry])
            xs = list(ins[n_consts + n_carry:])
            mk_in = lambda c: consts + c + xs  # noqa: E731
        for attempt in range(3):
            outs = _Intervals(self.axis_sizes, []).run(
                body.jaxpr, mk_in(carry), body.consts
            )
            new_carry = [_join(c, o) for c, o in zip(carry, outs[:n_carry])]
            if new_carry == carry:
                break
            if attempt == 1:  # widen
                new_carry = [
                    c if c == n else TOP for c, n in zip(carry, new_carry)
                ]
            carry = new_carry
        outs = self._sub(body.jaxpr, body.consts, mk_in(carry))
        final = carry + outs[n_carry:] if name == "scan" else carry
        for var, iv in zip(eqn.outvars, final):
            env.write(var, iv)


def analyze(closed_jaxpr, axis_sizes: Optional[Dict[str, int]] = None) -> List[Violation]:
    """All NU violations in a traced executable.

    ``axis_sizes`` maps mesh axis names to their *production* sizes so a
    psum's growth factor reflects the real deployment even when the
    analysis traces on a tiny forced-host mesh."""
    out: List[Violation] = []
    interp = _Intervals(axis_sizes or {}, out)
    jaxpr = closed_jaxpr.jaxpr
    interp.run(jaxpr, [TOP] * len(jaxpr.invars), closed_jaxpr.consts)
    _walk_nested(jaxpr, interp)
    return out


def _walk_nested(jaxpr: jax_core.Jaxpr, interp: _Intervals) -> None:
    """Analyze sub-jaxprs the top-level run bypassed (shard_map bodies,
    pallas grids): inputs are unknown there, but literal/iota-derived
    narrowing casts inside still get proven."""
    for eqn in jaxpr.eqns:
        handled = eqn.primitive.name in (
            "while", "scan", "cond", "jit", "closed_call", "remat",
        )
        for _, sub, consts in sub_jaxprs(eqn):
            if not handled:
                interp.run(sub, [TOP] * len(sub.invars), consts)
            _walk_nested(sub, interp)
