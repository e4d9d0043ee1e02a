"""One frozen config for every execution strategy of the one algorithm.

The paper's pipeline is a single algorithm (Voronoi cells → distance graph
G'1 → MST G'2 → bridge pruning → predecessor walk) with many execution
strategies.  Historically each strategy grew its own front door with its
own knob names (``steiner_tree(**kw)``, ``DistSteinerConfig``,
``ServeConfig``); :class:`SolverConfig` subsumes all of them so that
strategy is a *parameter* of one solver, mirroring how the related
literature treats it (Saikia & Karmakar; Sun et al. — see PAPERS.md).

Every field is validated at construction — a bad knob combination fails
here with a readable error instead of deep inside a trace.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro import knobs

BACKENDS: Tuple[str, ...] = ("single", "mesh1d", "mesh2d", "batch")
MODES: Tuple[str, ...] = ("dense", "bucket", "frontier", "pallas")
MST_ALGOS: Tuple[str, ...] = ("prim", "boruvka")

# Which Voronoi schedules each backend can execute.  "frontier" and
# "pallas" need the ELL view: the single-device pipelines (jitted /
# vmapped) consume the resident EllGraph, and "mesh1d" consumes a
# per-block sharded EllPartition (top-K prioritized schedule inside the
# shard_map body — the paper's §IV message prioritization).  "mesh2d"
# stays dense/Δ-bucket: its (src-row × dst-col) layout splits one
# source's adjacency across the column axis, so a source-major ELL row
# has no single owning device (see DESIGN.md §Adaptation).  "pallas"
# remains single-device (kernels run under jit, not shard_map).
BACKEND_MODES = {
    "single": ("dense", "bucket", "frontier", "pallas"),
    "batch": ("dense", "bucket", "pallas"),
    "mesh1d": ("dense", "bucket", "frontier"),
    "mesh2d": ("dense", "bucket"),
}


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Static configuration of the unified Steiner solver.

    Attributes:
      backend: execution strategy — "single" (one device, jitted),
        "mesh1d" (dst-block shard_map, the paper's MPI design),
        "mesh2d" (src×dst 2D decomposition), "batch" (vmap over a
        leading (B,) query axis against one resident graph).
      mode: Voronoi relaxation schedule — "dense" | "bucket" | "frontier"
        | "pallas" (the min-plus kernel of :mod:`repro.kernels.minplus`).
      mst_algo: replicated MST on G'1 — "prim" | "boruvka".
      delta: Δ-bucket width (mode="bucket"); None → mean edge weight.
      max_iters: safety cap on relaxation rounds (None → 4n + 64).
      ell_width: ELL row width when building the frontier/pallas view.
      ell_pad_rows: round the ELL row count up to a multiple of this
        when preparing from a :class:`~repro.graphstore.GraphStore`.
        Padding rows are inert (+inf weights), but a stable padded shape
        keeps the compiled frontier/pallas executables valid across
        ``refresh()`` after small delta batches — without it any row-
        count drift forces an XLA retrace that can dwarf the warm
        re-solve it feeds.  1 (default) disables padding.
      frontier_size: top-K frontier rows per round (mode="frontier", and
        mode="pallas" with ``pallas_frontier=True``); per *device* on
        backend="mesh1d" (each block runs its own priority queue).
      block_rows: ELL rows per Pallas grid step (mode="pallas").  The
        kernel is compiled on TPU/GPU and interpreted on CPU; the
        platform decides, no knob does.
      pallas_frontier: run the top-K work-compacted kernel schedule
        (O(K·k) per round) instead of full-adjacency kernel rounds
        (mode="pallas" only).
      batch_size: preferred micro-batch lane count B for the "batch"
        backend (warmup / serving); ``solve`` accepts any leading B.
      mesh_shape: device mesh shape — (n_replica, n_blocks) for "mesh1d",
        (R, C) for "mesh2d".  Ignored by "single"/"batch".
      local_steps: collective-free local relaxations per global exchange
        (mesh1d only — async-style amortization, paper §IV).
      pair_chunks: chunked Allreduce(MIN) on the S² pair table (mesh1d
        only — paper §V-F).
      fuse_gather: pack (dist, lab) into one f32 all-gather (mesh1d).
      lab_i16: gather labels as int16 (mesh1d, |S| < 32768).
      telemetry_rounds: static H — every fixpoint loop carries a
        (H+1, 4) int32 per-round telemetry buffer
        (``repro.obs.ROUND_CHANNELS`` rows: frontier, messages,
        relaxations, unreached), surfaced as
        ``SolveOutput.telemetry.per_round``.  Rounds beyond H add up in
        the last slot (aggregate counters stay exact).  0 disables the
        buffer entirely.  H is baked into the executable, so toggling
        the host-side obs recorder never retraces or changes trees.
      telemetry_per_rank: static flag (mesh backends only) — additionally
        carry a (H+1, n_ranks, 4) per-rank flight-recorder buffer whose
        per-round rank rows sum exactly to the global channels (ghost
        padding corrected per block), surfaced as
        ``SolveOutput.telemetry.per_rank`` and analyzed by
        :mod:`repro.obs.flight`.  Swaps an ``all_gather`` in for the
        ``psum`` only on the per-rank path; disabled (default) the buffer
        has zero rank slots and the executable is unchanged.
    """

    backend: str = "single"
    mode: str = "bucket"
    mst_algo: str = "prim"
    delta: Optional[float] = None
    max_iters: Optional[int] = None
    # mode="frontier" / mode="pallas"
    ell_width: int = 32
    ell_pad_rows: int = 1
    frontier_size: int = 1024
    # mode="pallas"
    block_rows: int = 256
    pallas_frontier: bool = False
    # backend="batch"
    batch_size: int = 8
    # backend="mesh1d"/"mesh2d"
    mesh_shape: Tuple[int, int] = (1, 1)
    local_steps: int = 1
    pair_chunks: int = 1
    fuse_gather: bool = True
    lab_i16: bool = False
    # per-round telemetry buffer depth (0 disables)
    telemetry_rounds: int = 256
    # per-rank flight recorder (mesh1d/mesh2d; needs telemetry_rounds >= 1)
    telemetry_per_rank: bool = False

    def __post_init__(self) -> None:
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend: {self.backend!r} (use one of {BACKENDS})"
            )
        if self.mode not in MODES:
            raise ValueError(
                f"unknown mode: {self.mode!r} "
                f"(use 'dense' | 'bucket' | 'frontier' | 'pallas')"
            )
        if self.mode not in BACKEND_MODES[self.backend]:
            raise ValueError(
                f"mode {self.mode!r} is not supported by backend "
                f"{self.backend!r} (supported: {BACKEND_MODES[self.backend]})"
            )
        if self.mst_algo not in MST_ALGOS:
            raise ValueError(
                f"unknown mst_algo: {self.mst_algo!r} (use 'prim' | 'boruvka')"
            )
        if self.delta is not None and not self.delta > 0:
            raise ValueError(f"delta must be positive, got {self.delta}")
        if self.max_iters is not None and self.max_iters < 1:
            raise ValueError(f"max_iters must be >= 1, got {self.max_iters}")
        for name in ("ell_width", "ell_pad_rows", "frontier_size",
                     "batch_size", "local_steps", "pair_chunks",
                     "block_rows"):
            v = getattr(self, name)
            if not (isinstance(v, int) and v >= 1):
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        if not (isinstance(self.telemetry_rounds, int) and self.telemetry_rounds >= 0):
            raise ValueError(
                f"telemetry_rounds must be an int >= 0, "
                f"got {self.telemetry_rounds!r}"
            )
        if self.telemetry_per_rank:
            if self.backend not in ("mesh1d", "mesh2d"):
                raise ValueError(
                    f"telemetry_per_rank records one row per mesh device "
                    f"and requires backend 'mesh1d' or 'mesh2d'; "
                    f"got backend={self.backend!r}"
                )
            if self.telemetry_rounds < 1:
                raise ValueError(
                    "telemetry_per_rank requires telemetry_rounds >= 1 "
                    "(the per-rank flight recorder rides the round buffer)"
                )
        if self.pallas_frontier and self.mode != "pallas":
            raise ValueError(
                f"pallas_frontier=True requires mode='pallas', "
                f"got mode={self.mode!r}"
            )
        if (
            self.backend == "mesh1d"
            and self.mode == "frontier"
            and self.local_steps != 1
        ):
            raise ValueError(
                f"local_steps > 1 is not supported with mode='frontier' "
                f"(top-K candidates must cross devices every round); "
                f"got local_steps={self.local_steps}"
            )
        ms = self.mesh_shape
        if (
            not isinstance(ms, tuple)
            or len(ms) != 2
            or not all(isinstance(d, int) and d >= 1 for d in ms)
        ):
            raise ValueError(
                f"mesh_shape must be a (int, int) tuple of positive dims, "
                f"got {ms!r}"
            )
        if self.backend == "mesh2d":
            # the 2D engine always packs its row gather and has no
            # local-steps / pair-chunk / i16 variants — reject silently
            # ignored knobs instead of pretending they took effect
            for name, default in (
                ("local_steps", 1),
                ("pair_chunks", 1),
                ("fuse_gather", True),
                ("lab_i16", False),
            ):
                if getattr(self, name) != default:
                    raise ValueError(
                        f"{name} is a mesh1d-only knob (backend='mesh2d' "
                        f"got {name}={getattr(self, name)!r})"
                    )

    def replace(self, **kw) -> "SolverConfig":
        """Functional update (re-validates)."""
        return dataclasses.replace(self, **kw)


# Every field must be classified static-or-traced in repro.solver.knobs
# (the single source of truth the jitted executables and the TS06 lint
# rule both derive from) — an unclassified field fails here, at import.
knobs.validate_config_coverage(
    f.name for f in dataclasses.fields(SolverConfig)
)
