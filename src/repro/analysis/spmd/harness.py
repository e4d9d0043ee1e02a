"""Trace-and-analyze harness: every backend×mode combo, one tiny trace.

The combos come from the live solver registry (``BACKEND_MODES``), the
jaxprs from :func:`repro.solver.backends.trace_for_analysis` — the SAME
jitted executables / shard_map builders the solve path runs, AOT-traced
on a tiny 16-vertex ring.  Tracing is shape-polymorphic in everything the
analyses look at (collective structure, cast chains, donation), so the
tiny graph is enough; and the mesh combos trace on a (1, 1) mesh, which
keeps the jaxprs — and hence the committed baseline — identical on a
1-device laptop and an 8-device CI host (collective eqns are emitted
even over size-1 axes).
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from repro.analysis.findings import Finding, sort_findings
from repro.analysis.spmd import donation, intervals, uniformity
from repro.analysis.spmd.jaxpr_tools import Violation

_TINY_N = 16
_TINY_SEEDS = (0, 5, 11)


def tiny_graph():
    """16-vertex weighted ring + chords: every mode's loop does real work."""
    from repro.core.graph import from_edges

    n = _TINY_N
    src = list(range(n)) + [0, 4, 8]
    dst = [(i + 1) % n for i in range(n)] + [8, 12, 2]
    w = [1.0 + 0.25 * (i % 3) for i in range(len(src))]
    return from_edges(
        np.asarray(src), np.asarray(dst), np.asarray(w), n, pad_to=8
    )


def combos() -> Iterator[Tuple[str, str]]:
    """(backend, mode) pairs from the live registry, deterministic order."""
    from repro.solver.config import BACKEND_MODES

    for backend in sorted(BACKEND_MODES):
        for mode in BACKEND_MODES[backend]:
            yield backend, mode


def _combo_config(backend: str, mode: str):
    from repro.solver.config import SolverConfig

    kw: Dict[str, object] = dict(
        backend=backend,
        mode=mode,
        max_iters=8,
        telemetry_rounds=2,
        ell_width=4,
    )
    if backend in ("mesh1d", "mesh2d"):
        kw["mesh_shape"] = (1, 1)
    if backend == "mesh1d" and mode != "frontier":
        kw["local_steps"] = 2  # frontier must exchange top-K every round
    if mode == "pallas":
        kw["block_rows"] = 8
    if mode in ("frontier", "pallas"):
        kw["frontier_size"] = 8
    return SolverConfig(**kw)


def trace_combo(backend: str, mode: str):
    """The ClosedJaxpr of one combo's real executable."""
    from repro.solver.backends import trace_for_analysis

    cfg = _combo_config(backend, mode)
    g = tiny_graph()
    seeds = np.asarray(_TINY_SEEDS, np.int32)
    traced = trace_for_analysis(cfg, g, seeds)
    return traced.jaxpr


def analyze_jaxpr(
    closed_jaxpr, context: str,
    axis_sizes: Optional[Dict[str, int]] = None,
) -> List[Finding]:
    """All three semantic analyses over one ClosedJaxpr → Findings."""
    violations: List[Violation] = []
    violations += uniformity.analyze(closed_jaxpr)
    violations += intervals.analyze(closed_jaxpr, axis_sizes=axis_sizes)
    violations += donation.analyze(closed_jaxpr)
    findings = [v.to_finding(context) for v in violations]
    return [f for f in findings if f is not None]


def analyze_combo(backend: str, mode: str) -> List[Finding]:
    jaxpr = trace_combo(backend, mode)
    return analyze_jaxpr(jaxpr, context=f"{backend}/{mode}")


def analyze_all(
    only: Optional[Tuple[str, str]] = None, quiet: bool = True, echo=print
) -> List[Finding]:
    """Findings across every registered combo (or one, with ``only``)."""
    out: List[Finding] = []
    for backend, mode in combos():
        if only is not None and (backend, mode) != only:
            continue
        if not quiet:
            echo(f"tracing {backend}/{mode} ...")
        out.extend(analyze_combo(backend, mode))
    return sort_findings(out)
