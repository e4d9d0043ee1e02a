"""Donated-buffer liveness check over jaxprs (DN01).

``jax.jit(..., donate_argnums=...)`` lets XLA reuse an argument's buffer
for an output — after the call, the Python-side array is invalid.  Inside
a traced program a donating jit shows up as a ``jit`` equation whose
``donated_invars`` tuple marks which operands were given away.  Reading
such a variable in any *later* equation of the same scope (or returning
it) is the EllPatcher bug class from PR 7: the read observes whatever the
donated buffer was overwritten with — silently wrong on real devices,
often accidentally fine under ``interpret=True``, which is exactly why a
static check is worth having.
"""

from __future__ import annotations

from typing import Dict, List

from jax.extend import core as jax_core

from repro.analysis.spmd.jaxpr_tools import Violation, sub_jaxprs


def analyze(closed_jaxpr) -> List[Violation]:
    out: List[Violation] = []
    _scope(closed_jaxpr.jaxpr, out)
    return out


def _scope(jaxpr: jax_core.Jaxpr, out: List[Violation]) -> None:
    donated: Dict[jax_core.Var, object] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, jax_core.Var) and v in donated:
                out.append(
                    Violation(
                        rule="DN01",
                        message=(
                            f"`{eqn.primitive.name}` reads a buffer already "
                            f"donated to an earlier jit call — the buffer "
                            f"may have been overwritten by the callee's "
                            f"output (read-after-donation)"
                        ),
                        eqn=eqn,
                    )
                )
        donation = eqn.params.get("donated_invars")
        if eqn.primitive.name == "jit" and donation and any(donation):
            for v, gone in zip(eqn.invars, donation):
                if gone and isinstance(v, jax_core.Var):
                    donated[v] = eqn
        for _, sub, _consts in sub_jaxprs(eqn):
            _scope(sub, out)
    for i, v in enumerate(jaxpr.outvars):
        if isinstance(v, jax_core.Var) and v in donated:
            out.append(
                Violation(
                    rule="DN01",
                    message=(
                        f"output {i} returns a buffer already donated to an "
                        f"inner jit call — the caller receives memory the "
                        f"callee was free to overwrite (read-after-donation)"
                    ),
                    eqn=donated[v],
                )
            )
