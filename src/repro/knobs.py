"""The single source of truth for which solver knobs are compile-time static.

Every jitted solver executable needs a ``static_argnames`` declaration,
and before this module existed the tuples were hand-copied across ~10
jit sites in :mod:`repro.solver.backends` and :mod:`repro.core.voronoi`.
Hand-copied tuples drift: a knob consumed as a Python value inside a
traced region but missing from its executable's ``static_argnames``
either retraces silently per value or — worse — traces the Python branch
once and bakes the wrong path in (the PR-4 traced-``delta`` bug family).

Here every :class:`~repro.solver.config.SolverConfig` field is classified
exactly once (``STATIC_KNOBS`` / ``TRACED_KNOBS``), and
:func:`solver_jit` *derives* each executable's ``static_argnames`` from
its keyword-only signature against that classification — an unclassified
keyword raises at import time, so drift is impossible by construction.
The static analyzer's rule TS06 (:mod:`repro.analysis`) enforces the
same contract on jit sites that still declare literal tuples (the
kernels, whose extra statics like ``vb``/``edge_block`` are not config
knobs).

This module lives at the top of the package (``repro.knobs``, not
``repro.solver.knobs``) and imports nothing from repro: the jitted
executables in :mod:`repro.core.voronoi` need :func:`solver_jit` and
importing anything under ``repro.solver`` from core code is circular.
:mod:`repro.analysis` reads the declaration without touching jax.
"""

from __future__ import annotations

import functools
import inspect
from typing import Callable, Optional, Tuple

# SolverConfig fields that are compile-time constants of every executable
# consuming them.  Passing one of these as a traced operand is a trace-
# safety bug (rule TS06).  ``delta`` moved here from TRACED_KNOBS: a
# traced bucket width silently bypassed eager validation (PR 4) and, at
# Δ <= 0, stalled the bucket loop; as a static it is validated on the
# host path, always.
STATIC_KNOBS = frozenset(
    {
        "backend",
        "mode",
        "mst_algo",
        "delta",
        "max_iters",
        "ell_width",
        "ell_pad_rows",
        "frontier_size",
        "block_rows",
        "pallas_frontier",
        "batch_size",
        "mesh_shape",
        "local_steps",
        "pair_chunks",
        "fuse_gather",
        "lab_i16",
        "telemetry_rounds",
        "telemetry_per_rank",
    }
)

# SolverConfig fields consumed as traced operands.  Empty today — delta
# was the last one — but the classification stays total so a future
# traced knob must be added HERE deliberately, not forgotten.
TRACED_KNOBS: frozenset = frozenset()

# Executable keyword parameters whose name differs from the SolverConfig
# field they carry (classification follows the aliased field).
KNOB_ALIASES = {
    "frontier": "pallas_frontier",  # _pallas_static_kw flattens the name
    "max_rounds": "max_iters",  # voronoi_cells_frontier's round cap
}

# Keyword-only parameters of solver executables that are static but not
# SolverConfig fields (per-call shape-like constants).
EXTRA_STATIC_PARAMS = frozenset({"num_seeds"})

# Keyword-only parameters that are traced operands, not knobs.
TRACED_PARAMS = frozenset({"init", "seeds"})


def canonical_knob(name: str) -> str:
    """Resolves a parameter name to its SolverConfig field name."""
    return KNOB_ALIASES.get(name, name)


def classify(name: str) -> Optional[str]:
    """``"static"`` / ``"traced"`` / None (not a known solver parameter)."""
    canon = canonical_knob(name)
    if canon in STATIC_KNOBS or name in EXTRA_STATIC_PARAMS:
        return "static"
    if canon in TRACED_KNOBS or name in TRACED_PARAMS:
        return "traced"
    return None


def static_argnames_of(fn: Callable) -> Tuple[str, ...]:
    """The derived ``static_argnames`` of one executable: its keyword-only
    parameters classified static, in signature order.

    Raises:
      TypeError: a keyword-only parameter is not classified — add it to
        the declaration above (deliberately) before it can ship.
    """
    names = []
    for p in inspect.signature(fn).parameters.values():
        if p.kind is not inspect.Parameter.KEYWORD_ONLY:
            continue
        kind = classify(p.name)
        if kind is None:
            raise TypeError(
                f"{fn.__qualname__}: keyword parameter {p.name!r} is not "
                f"classified in repro.solver.knobs — declare it in "
                f"STATIC_KNOBS/TRACED_KNOBS (or the param sets) so its "
                f"trace-time role is explicit"
            )
        if kind == "static":
            names.append(p.name)
    return tuple(names)


def solver_jit(fn: Callable = None, *, donate_argnums=()):
    """``jax.jit`` with ``static_argnames`` derived from the declaration.

    Usage::

        @solver_jit
        def _exec(g, seeds, *, num_seeds, mode, max_iters): ...

    is exactly ``jax.jit(_exec, static_argnames=("num_seeds", "mode",
    "max_iters"))`` — but the tuple can never drift from the signature or
    the knob classification.
    """
    if fn is None:
        return functools.partial(solver_jit, donate_argnums=donate_argnums)
    import jax

    return jax.jit(
        fn,
        static_argnames=static_argnames_of(fn),
        donate_argnums=donate_argnums,
    )


def validate_config_coverage(fields) -> None:
    """Asserts every SolverConfig field is classified static-or-traced.

    Called at class-definition time from :mod:`repro.solver.config`; a
    new field without a classification fails the import, not a solve.
    """
    names = set(fields)
    unclassified = names - STATIC_KNOBS - TRACED_KNOBS
    if unclassified:
        raise TypeError(
            f"SolverConfig fields not classified in repro.solver.knobs: "
            f"{sorted(unclassified)} — add each to STATIC_KNOBS or "
            f"TRACED_KNOBS"
        )
    ghosts = (STATIC_KNOBS | TRACED_KNOBS) - names
    if ghosts:
        raise TypeError(
            f"repro.solver.knobs classifies knobs that are not "
            f"SolverConfig fields: {sorted(ghosts)} — remove the stale "
            f"entries"
        )
    overlap = STATIC_KNOBS & TRACED_KNOBS
    if overlap:
        raise TypeError(
            f"knobs classified both static and traced: {sorted(overlap)}"
        )
