"""String-keyed backend registry + the uniform solve result.

A backend is a singleton object wrapping one execution strategy of the
pipeline.  It declares its preprocessing needs (``preprocessing``), the
seed rank it consumes (``seeds_ndim``), and three methods:

  validate(cfg)                      — backend-specific config checks
  prepare(cfg, graph) -> artifacts   — one-time preprocessing (padding,
                                       ELL view, partition, mesh,
                                       device placement, executable cache)
  solve(cfg, artifacts, seeds, S)    — dispatch one query (or batch) to a
                                       cached jitted / shard_mapped
                                       executable → :class:`SolveOutput`

Register with ``@register_backend("name")``; look up with
``get_backend(name)``.  The four built-in strategies live in
:mod:`repro.solver.backends` and register themselves on import.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

_REGISTRY: Dict[str, Any] = {}


@dataclasses.dataclass(frozen=True)
class SolveTelemetry:
    """Uniform convergence telemetry of one solve (the paper's §VI
    per-rank measurements, backend-independent).

    Every counter is an exact Python int on every backend; for the
    "batch" backend they aggregate over lanes (iterations = max, the rest
    = sums).  The loops carry int32 rows; the totals are summed from them
    on the host (rows and spill slot), so they are exact until a single
    round's count or the spill sum passes 2**31 - 1, where the device
    saturates it.  Without rows (telemetry_rounds=0) ``relaxations`` and
    ``messages`` come from the loop's own saturating int32 totals.

    Attributes:
      iterations: global relaxation rounds until the fixpoint.
      relaxations: vertex-state improvements across all rounds, one
        winning edge relaxation each (the same meaning in every schedule).
      messages: the paper's message count (Fig. 6): dense, bucket and
        pallas charge each improved vertex its out-degree; frontier,
        pallas_frontier and the mesh engines count the finite candidates
        they relaxed.
      per_round: (R, 4) int32 array, one row per round in
        ``repro.obs.ROUND_CHANNELS`` order (frontier, messages,
        relaxations, unreached), R = min(iterations,
        config.telemetry_rounds); None when telemetry_rounds=0.
        Batch solves sum the buffer across lanes (converged lanes stop
        writing, so short lanes contribute zero rows).
      per_rank: (R, n_ranks, 4) int32 flight-recorder buffer — one
        channel row per mesh device per round, trimmed like
        ``per_round``; rank rows sum exactly to the global channels
        (ghost padding corrected per block).  None unless the solve ran
        with ``SolverConfig.telemetry_per_rank=True`` (mesh backends).
      scanned: edges the relaxation kernels read over all rounds: the
        schedule's static edges per round × iterations (summed over a
        batch's lanes); ``relaxations / scanned`` is the share of the
        scan that improved a vertex.  None where the backend states no
        per-round scan.
      segmin_scatters: the relaxation's segment-min passes over all
        rounds: the schedule's static passes per round (2 with the packed
        (lab, src) tie-break key, else 3) × iterations, summed over a
        batch's lanes like ``scanned``.  None where the backend states no
        passes (the mesh engines).
    """

    iterations: int
    relaxations: int
    messages: int
    per_round: Optional[np.ndarray] = None
    per_rank: Optional[np.ndarray] = None
    scanned: Optional[int] = None
    segmin_scatters: Optional[int] = None


def telemetry_from_counts(
    iterations, relaxations, messages, history, telemetry_rounds: int,
    per_rank=None, scan_per_round: Optional[int] = None,
    segmin_passes: Optional[int] = None,
) -> SolveTelemetry:
    """Builds a :class:`SolveTelemetry` from loop-carried counters.

    ``iterations`` is a count, or a batch's per-lane counts; ``history``
    is the raw (H+1, 4) buffer (or None; a batch passes its lanes' sum);
    rows beyond the round count are trimmed here, on the host, and the
    totals summed from the rows plus the spill slot H.  ``per_rank`` is
    the raw (H+1, n_ranks, 4) flight-recorder buffer (or None), trimmed
    identically.  ``scan_per_round`` is the schedule's static edges read
    per round, ``segmin_passes`` its static segment-min passes per round
    (0 or None: not stated).

    This is the solve's one device→host crossing, so it is *explicit*
    (``jax.device_get``, one batched fetch) rather than five implicit
    ``int()``/``np.asarray`` syncs — the runtime sanitizer
    (:mod:`repro.analysis.sanitize`) treats unnamed transfers on the
    warm path as errors, and one fetch beats five on a real accelerator.
    """
    import jax

    from repro.obs import ROUND_CHANNELS

    iterations, relaxations, messages, history, per_rank = jax.device_get(
        (iterations, relaxations, messages, history, per_rank)
    )
    iters = int(np.max(iterations))
    per_round = None
    totals = {"relaxations": int(relaxations), "messages": int(messages)}
    if history is not None and telemetry_rounds > 0:
        hist = np.asarray(history)
        per_round = hist[: min(iters, telemetry_rounds)]
        rows = hist[: min(iters, telemetry_rounds + 1)].astype(np.int64)
        for name in totals:
            totals[name] = int(rows[:, ROUND_CHANNELS.index(name)].sum())
    rank_rows = None
    if per_rank is not None and telemetry_rounds > 0:
        rank_rows = np.asarray(per_rank)[: min(iters, telemetry_rounds)]
    lane_rounds = int(np.sum(iterations, dtype=np.int64))
    scanned = None
    if scan_per_round is not None:
        scanned = int(scan_per_round) * lane_rounds
    segmin_scatters = None
    if segmin_passes:
        segmin_scatters = int(segmin_passes) * lane_rounds
    return SolveTelemetry(
        iterations=iters,
        per_round=per_round,
        per_rank=rank_rows,
        scanned=scanned,
        segmin_scatters=segmin_scatters,
        **totals,
    )


@dataclasses.dataclass(frozen=True)
class SolveOutput:
    """Backend-independent view of one solve.

    Attributes:
      total_distance: D(G_S) — float for "single"/"mesh1d"/"mesh2d",
        (B,) float ndarray for "batch".
      num_edges: |E_S| — int, or (B,) int ndarray for "batch".
      raw: the backend-native result for callers that need the full
        state (``SteinerResult`` for single/batch lanes,
        ``DistSteinerResult`` for the mesh engines).  Digging convergence
        counters out of ``raw`` is deprecated — read ``telemetry``.
      telemetry: uniform :class:`SolveTelemetry` (Python-int counters +
        optional per-round buffer) across every backend.
    """

    total_distance: Any
    num_edges: Any
    raw: Any
    telemetry: Optional[SolveTelemetry] = None


def register_backend(name: str):
    """Class decorator: instantiate + register the backend under ``name``."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def get_backend(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
