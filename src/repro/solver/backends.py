"""The four built-in execution strategies behind the solver registry.

Each backend wraps one existing pipeline implementation:

  "single"  — :func:`repro.core.steiner.run_pipeline`, jitted per static
              (shape, mode) on one device; mode="frontier" additionally
              consumes the ELL adjacency view.
  "batch"   — the same pipeline vmapped over a leading (B,) query axis
              (the serving layer's executable, :mod:`repro.serve.batch`).
  "mesh1d"  — the paper's MPI design on a (replica × vertex-block) device
              mesh (:mod:`repro.core.dist_steiner`).
  "mesh2d"  — the beyond-paper (src-block × dst-block) decomposition
              (:mod:`repro.core.dist_steiner_2d`).

The jitted single/batch executables are module-level, so every consumer —
the :class:`~repro.solver.api.SteinerSolver` facade, the legacy shims, the
serve engine, benchmarks — shares ONE compiled artifact per static
(shape, config) instead of re-tracing per call site.  Each trace bumps a
counter (:func:`trace_count`) so tests can assert the reuse.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import steiner as smod
from repro.core import voronoi as vmod
from repro.core.graph import EllGraph, Graph, ell_view_cached
from repro.kernels.minplus import ops as kops
from repro.solver.config import BACKEND_MODES, SolverConfig
from repro.knobs import solver_jit
from repro.solver.registry import (
    SolveOutput,
    register_backend,
    telemetry_from_counts,
)

# ----------------------------------------------------------------------------
# Trace bookkeeping — every jit trace of a solver executable bumps a counter,
# making "prepare once, solve many, re-trace zero times" a testable claim.
# ----------------------------------------------------------------------------

_TRACE_COUNTS: Dict[str, int] = {}


def _bump(key: str) -> None:
    _TRACE_COUNTS[key] = _TRACE_COUNTS.get(key, 0) + 1


def trace_count(key: Optional[str] = None) -> int:
    """Traces of solver executables since process start (per backend key
    when given).  Mesh backends count shard_map executable *builds* — one
    build is one trace at first call."""
    if key is not None:
        return _TRACE_COUNTS.get(key, 0)
    return sum(_TRACE_COUNTS.values())


# ----------------------------------------------------------------------------
# Module-level jitted executables (single / batch) — shared by all consumers.
# Each executable's static_argnames are DERIVED from its keyword-only
# signature against the repro.solver.knobs classification (one source of
# truth; hand-copied tuples drift — rule TS06 in repro.analysis).
# ----------------------------------------------------------------------------


@solver_jit
def _exec_single_coo(
    g, seeds, *, num_seeds, mode, mst_algo, delta, max_iters, telemetry_rounds,
    init=None,
):
    _bump("single")
    return smod.run_pipeline(
        g,
        seeds,
        num_seeds=num_seeds,
        mode=mode,
        mst_algo=mst_algo,
        delta=delta,
        max_iters=max_iters,
        telemetry_rounds=telemetry_rounds,
        init=init,
    )


@solver_jit
def _exec_single_frontier(
    g, ell, seeds, *, num_seeds, mst_algo, frontier_size, max_iters,
    telemetry_rounds, init=None,
):
    _bump("single")
    st, stats = vmod.voronoi_cells_frontier(
        ell,
        seeds,
        frontier_size=frontier_size,
        max_rounds=max_iters,
        telemetry_rounds=telemetry_rounds,
        init=init,
    )
    return smod.finish_pipeline(g, st, stats, num_seeds, mst_algo)


def _pallas_voronoi(ell, seeds, cfg_kw):
    """Trace-level dispatch between the full-adjacency and top-K-compacted
    kernel schedules (``cfg_kw`` carries the static kernel knobs)."""
    if cfg_kw["frontier"]:
        return kops.voronoi_cells_pallas_frontier(
            ell,
            seeds,
            frontier_size=cfg_kw["frontier_size"],
            block_rows=cfg_kw["block_rows"],
            max_iters=cfg_kw["max_iters"],
            telemetry_rounds=cfg_kw["telemetry_rounds"],
        )
    return kops.voronoi_cells_pallas(
        ell,
        seeds,
        block_rows=cfg_kw["block_rows"],
        max_iters=cfg_kw["max_iters"],
        telemetry_rounds=cfg_kw["telemetry_rounds"],
    )


@solver_jit
def _exec_single_pallas(
    g,
    ell,
    seeds,
    *,
    num_seeds,
    mst_algo,
    block_rows,
    frontier,
    frontier_size,
    max_iters,
    telemetry_rounds,
):
    _bump("single")
    st, stats = _pallas_voronoi(
        ell,
        seeds,
        dict(
            frontier=frontier,
            frontier_size=frontier_size,
            block_rows=block_rows,
            max_iters=max_iters,
            telemetry_rounds=telemetry_rounds,
        ),
    )
    return smod.finish_pipeline(g, st, stats, num_seeds, mst_algo)


@solver_jit
def _exec_batch_pallas(
    g,
    ell,
    seeds,
    *,
    num_seeds,
    mst_algo,
    block_rows,
    frontier,
    frontier_size,
    max_iters,
    telemetry_rounds,
):
    _bump("batch")
    kw = dict(
        frontier=frontier,
        frontier_size=frontier_size,
        block_rows=block_rows,
        max_iters=max_iters,
        telemetry_rounds=telemetry_rounds,
    )

    def one(row):
        st, stats = _pallas_voronoi(ell, row, kw)
        return smod.finish_pipeline(g, st, stats, num_seeds, mst_algo)

    return jax.vmap(one)(seeds)


def _pallas_static_kw(cfg: SolverConfig) -> dict:
    """The static kernel knobs of one config.  The platform alone picks
    compiled kernels or the interpreter (:func:`default_interpret`), so
    a TPU never runs the interpreter on the solver path."""
    return dict(
        block_rows=cfg.block_rows,
        frontier=cfg.pallas_frontier,
        frontier_size=cfg.frontier_size,
        max_iters=cfg.max_iters,
        telemetry_rounds=cfg.telemetry_rounds,
    )


@solver_jit
def _exec_batch(
    g, seeds, *, num_seeds, mode, mst_algo, delta, max_iters, telemetry_rounds
):
    _bump("batch")

    def one(row):
        return smod.run_pipeline(
            g,
            row,
            num_seeds=num_seeds,
            mode=mode,
            mst_algo=mst_algo,
            delta=delta,
            max_iters=max_iters,
            telemetry_rounds=telemetry_rounds,
        )

    return jax.vmap(one)(seeds)


# ----------------------------------------------------------------------------
# Backends
# ----------------------------------------------------------------------------


def _as_graph_and_store(graph):
    """Splits prepare()'s input into (Graph-or-None, GraphStore-or-None).

    Accepting :class:`repro.graphstore.GraphStore` here (instead of at
    the facade) lets each backend choose the cheapest path off disk: the
    COO materialization, the chunked ELL build, or a per-shard partition
    load that never expands the edge list at all.
    """
    from repro.graphstore.loader import GraphStore

    if isinstance(graph, GraphStore):
        return None, graph
    return graph, None


class _Backend:
    """Shared validation: config/backend cross-checks beyond the dataclass."""

    name = "?"
    preprocessing: tuple = ()
    seeds_ndim = 1
    # modes whose executables consume the ELL view (single-device backends)
    ell_modes: tuple = ()

    def validate(self, cfg: SolverConfig) -> None:
        if cfg.backend != self.name:
            raise ValueError(
                f"config targets backend {cfg.backend!r}, "
                f"dispatched to {self.name!r}"
            )
        if cfg.mode not in BACKEND_MODES[self.name]:
            raise ValueError(
                f"mode {cfg.mode!r} is not supported by backend {self.name!r}"
            )

    def prepare(self, cfg: SolverConfig, g) -> dict:
        """Single-device preprocessing: the resident COO graph, plus the
        ELL view when ``cfg.mode`` is in :attr:`ell_modes`.

        GraphStore inputs materialize the COO once and build the ELL view
        chunkwise straight off the memmaps (skipping both the COO
        round-trip and the host re-sort of ``to_ell``); in-memory graphs
        go through the bounded ``ell_view_cached`` memo, so repeated
        ``prepare()`` of one resident graph is free.  The mesh backends
        override this wholesale (partition + device placement).
        """
        g, store = _as_graph_and_store(g)
        if store is not None:
            with obs.span("prepare:materialize", backend=self.name):
                art: dict = {"graph": store.to_graph(), "store": store}
            if cfg.mode in self.ell_modes:
                with obs.span("prepare:ell_build", backend=self.name):
                    art["ell"] = store.ell(
                        cfg.ell_width, pad_rows_to=cfg.ell_pad_rows
                    )
            return art
        art = {"graph": g}
        if cfg.mode in self.ell_modes:
            with obs.span("prepare:ell_build", backend=self.name):
                art["ell"] = ell_view_cached(g, cfg.ell_width)
        return art


@register_backend("single")
class SingleBackend(_Backend):
    """One query, one device, jitted; all four Voronoi schedules."""

    preprocessing = ("ell_view [mode=frontier|pallas]",)
    seeds_ndim = 1
    ell_modes = ("frontier", "pallas")

    def solve(self, cfg, artifacts, seeds, num_seeds, warm_state=None) -> SolveOutput:
        with obs.span("solve:dispatch"):
            res = self.solve_raw(
                cfg, artifacts["graph"], seeds, num_seeds,
                ell=artifacts.get("ell"), init=warm_state,
            )
        # explicit device→host fetches (TS03 hygiene: the sanitizer
        # forbids implicit transfers on the warm path)
        with obs.span("solve:fetch"):
            td, ne = jax.device_get(
                (res.tree.total_distance, res.tree.num_edges)
            )
            telem = telemetry_from_counts(
                res.stats.iterations,
                res.stats.relaxations,
                res.stats.messages,
                res.stats.history,
                cfg.telemetry_rounds,
                scan_per_round=res.stats.scan_per_round,
                segmin_passes=res.stats.segmin_passes,
            )
        return SolveOutput(
            total_distance=float(td), num_edges=int(ne), raw=res,
            telemetry=telem,
        )

    def dispatch(
        self,
        cfg: SolverConfig,
        g: Graph,
        seeds,
        num_seeds: int,
        ell: Optional[EllGraph] = None,
        init=None,
    ):
        """(jitted_fn, args, kwargs) for one config — the single source of
        the executable/argument pairing, shared by :meth:`solve_raw`
        (calls it) and :func:`trace_for_analysis` (AOT-traces it)."""
        seeds = jnp.asarray(seeds, jnp.int32)
        if init is not None and cfg.mode not in ("dense", "bucket", "frontier"):
            raise ValueError(
                f"warm-start init is only supported for mode "
                f"'dense'|'bucket'|'frontier', not {cfg.mode!r}"
            )
        if cfg.mode == "frontier":
            if ell is None:
                ell = ell_view_cached(g, cfg.ell_width)
            return _exec_single_frontier, (g, ell, seeds), dict(
                num_seeds=num_seeds,
                mst_algo=cfg.mst_algo,
                frontier_size=cfg.frontier_size,
                max_iters=cfg.max_iters,
                telemetry_rounds=cfg.telemetry_rounds,
                init=init,
            )
        if cfg.mode == "pallas":
            if ell is None:
                ell = ell_view_cached(g, cfg.ell_width)
            return _exec_single_pallas, (g, ell, seeds), dict(
                num_seeds=num_seeds,
                mst_algo=cfg.mst_algo,
                **_pallas_static_kw(cfg),
            )
        return _exec_single_coo, (g, seeds), dict(
            num_seeds=num_seeds,
            mode=cfg.mode,
            mst_algo=cfg.mst_algo,
            delta=cfg.delta,
            max_iters=cfg.max_iters,
            telemetry_rounds=cfg.telemetry_rounds,
            init=init,
        )

    def solve_raw(
        self,
        cfg: SolverConfig,
        g: Graph,
        seeds,
        num_seeds: int,
        ell: Optional[EllGraph] = None,
        init=None,
    ) -> smod.SteinerResult:
        """Dispatch to the shared jitted executable; returns the native
        :class:`SteinerResult` (the legacy ``steiner_tree`` contract).

        ``init`` warm-starts the Voronoi loop (the delta layer's
        affected-cell re-solve).  Dense/bucket re-relax everything each
        round from the warm values; frontier seeds its dirty-row set
        with one violated-edge sweep, so its warm work is proportional
        to the reset region.  Pallas has no warm path.
        """
        fn, args, kw = self.dispatch(cfg, g, seeds, num_seeds, ell, init)
        return fn(*args, **kw)


@register_backend("batch")
class BatchBackend(_Backend):
    """B queries / launch, vmapped against one resident graph."""

    preprocessing = ("ell_view [mode=pallas]",)
    seeds_ndim = 2
    ell_modes = ("pallas",)

    def solve(self, cfg, artifacts, seeds, num_seeds) -> SolveOutput:
        with obs.span("solve:dispatch"):
            res = self.solve_raw(
                cfg, artifacts["graph"], seeds, num_seeds,
                ell=artifacts.get("ell"),
            )
        # Lane aggregation: iterations = slowest lane, counters = sums
        # (int64 on the host).  The vmapped while_loop freezes converged
        # lanes' carries, so a lane-sum of the (B, H+1, 4) histories only
        # accumulates rows each lane actually wrote.
        stats = res.stats
        # one explicit, batched device→host fetch for the whole lane
        # aggregation (TS03 hygiene — no implicit per-field syncs)
        with obs.span("solve:fetch"):
            iterations, relaxations, messages, history, td, ne = jax.device_get(
                (stats.iterations, stats.relaxations, stats.messages,
                 stats.history, res.tree.total_distance, res.tree.num_edges)
            )
        telem = telemetry_from_counts(
            iterations,
            np.sum(relaxations, dtype=np.int64),
            np.sum(messages, dtype=np.int64),
            None if history is None else np.sum(history, axis=0, dtype=np.int64),
            cfg.telemetry_rounds,
            scan_per_round=stats.scan_per_round,
            segmin_passes=stats.segmin_passes,
        )
        return SolveOutput(
            total_distance=np.asarray(td),
            num_edges=np.asarray(ne),
            raw=res,
            telemetry=telem,
        )

    def dispatch(
        self,
        cfg: SolverConfig,
        g: Graph,
        seeds,
        num_seeds: int,
        ell: Optional[EllGraph] = None,
    ):
        """(jitted_fn, args, kwargs) — see :meth:`SingleBackend.dispatch`."""
        seeds = jnp.asarray(seeds, jnp.int32)
        if seeds.ndim != 2:
            raise ValueError(f"seeds must be (B, S), got shape {seeds.shape}")
        if cfg.mode == "pallas":
            if ell is None:
                ell = ell_view_cached(g, cfg.ell_width)
            return _exec_batch_pallas, (g, ell, seeds), dict(
                num_seeds=num_seeds,
                mst_algo=cfg.mst_algo,
                **_pallas_static_kw(cfg),
            )
        return _exec_batch, (g, seeds), dict(
            num_seeds=num_seeds,
            mode=cfg.mode,
            mst_algo=cfg.mst_algo,
            delta=cfg.delta,
            max_iters=cfg.max_iters,
            telemetry_rounds=cfg.telemetry_rounds,
        )

    def solve_raw(
        self,
        cfg: SolverConfig,
        g: Graph,
        seeds,
        num_seeds: int,
        ell: Optional[EllGraph] = None,
    ) -> smod.SteinerResult:
        fn, args, kw = self.dispatch(cfg, g, seeds, num_seeds, ell)
        return fn(*args, **kw)


def _device_mesh(shape, axes):
    """mesh_shape → device mesh, with an eager device-count check."""
    from repro.launch.mesh import make_mesh

    need = int(np.prod(shape))
    have = len(jax.devices())
    if need > have:
        raise ValueError(
            f"mesh_shape {tuple(shape)} needs {need} devices, "
            f"only {have} available"
        )
    return make_mesh(shape, axes)


def _place_edges(mesh, arrays, axes):
    """device_put the flat edge arrays sharded as ``P((*axes,))``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = NamedSharding(mesh, P(tuple(axes)))
    return tuple(jax.device_put(a, spec) for a in arrays)


def _place_replicated(mesh, x):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(x, NamedSharding(mesh, P()))


@register_backend("mesh1d")
class Mesh1DBackend(_Backend):
    """The paper's design: dst-block 1D partition over a device mesh.

    ``mode="frontier"`` swaps the edge partition for a per-block sharded
    ELL view (:class:`repro.core.dist_steiner.EllPartition`) driving the
    prioritized top-K schedule; everything else (mesh, placement,
    executable cache) is shared.
    """

    preprocessing = ("mesh", "partition_1d [or ell_partition]", "device_put")
    seeds_ndim = 1

    @staticmethod
    def _part_arrays(cfg: SolverConfig, part):
        """The three flat device arrays of either partition flavour."""
        if cfg.mode == "frontier":
            return (part.nbr, part.wgt, part.row2v)
        return (part.src, part.dst, part.w)

    @staticmethod
    def build_executable(
        cfg: SolverConfig,
        mesh,
        part,
        num_seeds: int,
        *,
        vert_axis: str = "model",
        replica_axes: Sequence[str] = ("data",),
    ):
        """The jitted shard_map executable one (config, mesh, partition)
        pair runs — shared by :meth:`solve_prepared` (compile + execute)
        and :func:`trace_for_analysis` (jaxpr only)."""
        from repro.core.dist_steiner import DistSteinerConfig, make_dist_steiner

        dcfg = DistSteinerConfig(
            n=part.n,
            nb=part.nb,
            num_seeds=num_seeds,
            mode=cfg.mode,
            mst_algo=cfg.mst_algo,
            local_steps=cfg.local_steps,
            pair_chunks=cfg.pair_chunks,
            max_iters=cfg.max_iters,
            delta=cfg.delta,
            fuse_gather=cfg.fuse_gather,
            lab_i16=cfg.lab_i16,
            frontier_size=cfg.frontier_size,
            telemetry_rounds=cfg.telemetry_rounds,
            telemetry_per_rank=cfg.telemetry_per_rank,
        )
        return make_dist_steiner(
            mesh, dcfg, vert_axis=vert_axis, replica_axes=tuple(replica_axes)
        )

    def _prepare_frontier(self, cfg: SolverConfig, g, store, mesh):
        """Sharded-ELL artifacts for the prioritized schedule.

        Stores with a matching prebuilt 1D ELL partition load per-shard
        (the edge list is never expanded on the host); other stores build
        the global ELL chunkwise off the memmapped CSR; in-memory graphs
        go through the bounded ``ell_view_cached`` memo.
        """
        from repro.core.dist_steiner import partition_ell

        n_replica, n_blocks = cfg.mesh_shape
        if store is not None:
            meta = store.partition_meta
            if (
                meta
                and meta.get("scheme") == "1d"
                and (meta["n_replica"], meta["n_blocks"]) == (n_replica, n_blocks)
                and meta.get("ell", {}).get("k") == cfg.ell_width
                and store.partition_fresh  # shards predating deltas are stale
            ):
                with obs.span("prepare:shard_load", backend=self.name):
                    ellpart = store.load_partition_ell()
            else:
                with obs.span("prepare:partition", backend=self.name):
                    ellpart = partition_ell(
                        store.ell(cfg.ell_width),
                        n_replica=n_replica,
                        n_blocks=n_blocks,
                    )
            graph_art = store
        else:
            with obs.span("prepare:partition", backend=self.name):
                ellpart = partition_ell(
                    ell_view_cached(g, cfg.ell_width),
                    n_replica=n_replica,
                    n_blocks=n_blocks,
                )
            graph_art = g
        with obs.span("prepare:place", backend=self.name):
            edges = _place_edges(
                mesh, (ellpart.nbr, ellpart.wgt, ellpart.row2v), ("data", "model")
            )
        return {
            "graph": graph_art,
            "mesh": mesh,
            "ellpart": ellpart,
            "edges": edges,
            "executables": {},
        }

    def prepare(self, cfg: SolverConfig, g) -> dict:
        from repro.core.dist_steiner import partition_edges

        g, store = _as_graph_and_store(g)
        n_replica, n_blocks = cfg.mesh_shape
        mesh = _device_mesh(cfg.mesh_shape, ("data", "model"))
        if cfg.mode == "frontier":
            return self._prepare_frontier(cfg, g, store, mesh)
        if store is not None:
            meta = store.partition_meta
            if (
                meta
                and meta.get("scheme") == "1d"
                and (meta["n_replica"], meta["n_blocks"]) == (n_replica, n_blocks)
                and store.partition_fresh  # shards predating deltas are stale
            ):
                # per-shard load of the prebuilt partition: the full edge
                # list is never expanded on the host
                with obs.span("prepare:shard_load", backend=self.name):
                    part = store.load_partition()
            else:
                with obs.span("prepare:partition", backend=self.name):
                    cs, cd, cw = store.coo()  # already both directions
                    part = partition_edges(
                        cs, cd, cw, store.n,
                        n_replica=n_replica, n_blocks=n_blocks, symmetrize=False,
                    )
            with obs.span("prepare:place", backend=self.name):
                edges = _place_edges(
                    mesh, (part.src, part.dst, part.w), ("data", "model")
                )
            return {
                "graph": store,
                "mesh": mesh,
                "part": part,
                "edges": edges,
                "executables": {},
            }
        # g is already symmetric + padded; padding edges (0, 0, +inf) stay
        # inert through the partition (they can never win a relaxation)
        with obs.span("prepare:partition", backend=self.name):
            part = partition_edges(
                np.asarray(g.src),
                np.asarray(g.dst),
                np.asarray(g.w),
                g.n,
                n_replica=n_replica,
                n_blocks=n_blocks,
                symmetrize=False,
            )
        with obs.span("prepare:place", backend=self.name):
            edges = _place_edges(
                mesh, (part.src, part.dst, part.w), ("data", "model")
            )
        return {
            "graph": g,
            "mesh": mesh,
            "part": part,
            "edges": edges,
            "executables": {},
        }

    def solve(self, cfg, artifacts, seeds, num_seeds) -> SolveOutput:
        part = (
            artifacts["ellpart"] if cfg.mode == "frontier" else artifacts["part"]
        )
        res = self.solve_prepared(
            cfg,
            artifacts["mesh"],
            part,
            seeds,
            edges=artifacts["edges"],
            executables=artifacts["executables"],
        )
        return SolveOutput(
            total_distance=res.total_distance,
            num_edges=res.num_edges,
            raw=res,
            telemetry=telemetry_from_counts(
                res.iterations,
                res.relaxations,
                res.messages,
                res.history,
                cfg.telemetry_rounds,
                per_rank=res.per_rank,
                scan_per_round=res.scan_per_round,
            ),
        )

    def solve_prepared(
        self,
        cfg: SolverConfig,
        mesh,
        part,
        seeds,
        *,
        vert_axis: str = "model",
        replica_axes: Sequence[str] = ("data",),
        edges=None,
        executables: Optional[dict] = None,
    ):
        """Runs on a prebuilt (mesh, Partition | EllPartition) pair — the
        legacy ``run_dist_steiner`` path and the prepared-handle path
        share it.  ``executables``/``edges`` come from the handle when
        present; the legacy path passes neither and pays placement +
        trace per call."""
        from repro.core.dist_steiner import EllPartition, result_from_device

        if cfg.mode == "frontier" and not isinstance(part, EllPartition):
            raise TypeError(
                "mesh1d mode='frontier' runs on an EllPartition (the "
                "sharded ELL view) — prepare the graph through "
                "SteinerSolver(cfg).prepare(graph); the legacy "
                "run_dist_steiner edge-Partition path has no ELL view"
            )
        seeds = np.asarray(seeds, np.int32)
        replica_axes = tuple(replica_axes)
        key = (len(seeds), vert_axis, replica_axes)
        fn = None if executables is None else executables.get(key)
        if fn is None:
            fn = self.build_executable(
                cfg, mesh, part, len(seeds),
                vert_axis=vert_axis, replica_axes=replica_axes,
            )
            _bump("mesh1d")
            if executables is not None:
                executables[key] = fn
        if edges is None:
            edges = _place_edges(
                mesh, self._part_arrays(cfg, part), (*replica_axes, vert_axis)
            )
        out = fn(*edges, _place_replicated(mesh, seeds))
        # every device reads its shard local_steps times a round, or its
        # top-K ELL rows (frontier; K as the engine caps it)
        per_dev = (
            min(cfg.frontier_size, part.rb) * part.k
            if cfg.mode == "frontier" else part.eb * cfg.local_steps
        )
        scan = per_dev * part.n_replica * part.n_blocks
        return result_from_device(out, part.n, scan_per_round=scan)


@register_backend("mesh2d")
class Mesh2DBackend(_Backend):
    """Beyond-paper (src-block × dst-block) 2D decomposition."""

    preprocessing = ("mesh", "partition_2d", "device_put")
    seeds_ndim = 1

    @staticmethod
    def build_executable(
        cfg: SolverConfig,
        mesh,
        part,
        num_seeds: int,
        *,
        row_axis: str = "data",
        col_axis: str = "model",
    ):
        """See :meth:`Mesh1DBackend.build_executable`."""
        from repro.core.dist_steiner_2d import make_dist_steiner_2d

        return make_dist_steiner_2d(
            mesh,
            n=part.n,
            nf=part.nf,
            num_seeds=num_seeds,
            mode=cfg.mode,
            mst_algo=cfg.mst_algo,
            max_iters=cfg.max_iters,
            delta=cfg.delta,
            row_axis=row_axis,
            col_axis=col_axis,
            telemetry_rounds=cfg.telemetry_rounds,
            telemetry_per_rank=cfg.telemetry_per_rank,
        )

    def prepare(self, cfg: SolverConfig, g) -> dict:
        from repro.core.dist_steiner_2d import partition_edges_2d

        g, store = _as_graph_and_store(g)
        R, C = cfg.mesh_shape
        mesh = _device_mesh(cfg.mesh_shape, ("data", "model"))
        if store is not None:
            meta = store.partition_meta
            if (
                meta
                and meta.get("scheme") == "2d"
                and (meta["R"], meta["C"]) == (R, C)
                and store.partition_fresh  # shards predating deltas are stale
            ):
                with obs.span("prepare:shard_load", backend=self.name):
                    part = store.load_partition_2d()
            else:
                with obs.span("prepare:partition", backend=self.name):
                    cs, cd, cw = store.coo()
                    part = partition_edges_2d(
                        cs, cd, cw, store.n, R=R, C=C, symmetrize=False
                    )
            with obs.span("prepare:place", backend=self.name):
                edges = _place_edges(
                    mesh, (part.src_row, part.dst_col, part.w), ("data", "model")
                )
            return {
                "graph": store,
                "mesh": mesh,
                "part": part,
                "edges": edges,
                "executables": {},
            }
        with obs.span("prepare:partition", backend=self.name):
            part = partition_edges_2d(
                np.asarray(g.src),
                np.asarray(g.dst),
                np.asarray(g.w),
                g.n,
                R=R,
                C=C,
                symmetrize=False,
            )
        with obs.span("prepare:place", backend=self.name):
            edges = _place_edges(
                mesh, (part.src_row, part.dst_col, part.w), ("data", "model")
            )
        return {
            "graph": g,
            "mesh": mesh,
            "part": part,
            "edges": edges,
            "executables": {},
        }

    def solve(self, cfg, artifacts, seeds, num_seeds) -> SolveOutput:
        res = self.solve_prepared(
            cfg,
            artifacts["mesh"],
            artifacts["part"],
            seeds,
            edges=artifacts["edges"],
            executables=artifacts["executables"],
        )
        return SolveOutput(
            total_distance=res.total_distance,
            num_edges=res.num_edges,
            raw=res,
            telemetry=telemetry_from_counts(
                res.iterations,
                res.relaxations,
                res.messages,
                res.history,
                cfg.telemetry_rounds,
                per_rank=res.per_rank,
                scan_per_round=res.scan_per_round,
            ),
        )

    def solve_prepared(
        self,
        cfg: SolverConfig,
        mesh,
        part,
        seeds,
        *,
        row_axis: str = "data",
        col_axis: str = "model",
        edges=None,
        executables: Optional[dict] = None,
    ):
        from repro.core.dist_steiner import result_from_device

        seeds = np.asarray(seeds, np.int32)
        key = (len(seeds), row_axis, col_axis)
        fn = None if executables is None else executables.get(key)
        if fn is None:
            fn = self.build_executable(
                cfg, mesh, part, len(seeds),
                row_axis=row_axis, col_axis=col_axis,
            )
            _bump("mesh2d")
            if executables is not None:
                executables[key] = fn
        if edges is None:
            edges = _place_edges(
                mesh, (part.src_row, part.dst_col, part.w), (row_axis, col_axis)
            )
        out = fn(*edges, _place_replicated(mesh, seeds))
        # every device reads its whole edge block each round
        scan = part.eb * part.R * part.C
        return result_from_device(out, part.n, scan_per_round=scan)


# ----------------------------------------------------------------------------
# Trace-for-analysis hook — the spmd analyzer's entry into REAL executables.
# ----------------------------------------------------------------------------


def trace_for_analysis(cfg: SolverConfig, graph, seeds, num_seeds=None):
    """AOT-trace the exact executable ``cfg`` would run — no compile, no
    execution — and return jax's ``Traced`` stage (``.jaxpr`` is the
    ClosedJaxpr).  :mod:`repro.analysis.spmd` analyzes these jaxprs, so
    its verdicts are about the solver's real programs, not hand-written
    mockups of them.

    Single/batch trace the shared module-level executables through the
    same ``dispatch()`` the solve path uses; mesh backends build their
    shard_map executable through the same ``build_executable()`` the
    prepared-handle path caches.  Partitioning runs on the host exactly
    as in ``prepare()`` but nothing is device_put — tracing only needs
    avals, which keeps the hook runnable on a 1-device CPU host.
    """
    from repro.solver.registry import get_backend

    seeds = np.asarray(seeds, np.int32)
    if num_seeds is None:
        num_seeds = int(seeds.shape[-1])
    backend = get_backend(cfg.backend)
    if cfg.backend == "single":
        ell = (
            ell_view_cached(graph, cfg.ell_width)
            if cfg.mode in ("frontier", "pallas")
            else None
        )
        fn, args, kw = backend.dispatch(cfg, graph, seeds, num_seeds, ell=ell)
        return fn.trace(*args, **kw)
    if cfg.backend == "batch":
        if seeds.ndim != 2:
            seeds = seeds[None, :]
        ell = (
            ell_view_cached(graph, cfg.ell_width)
            if cfg.mode == "pallas"
            else None
        )
        fn, args, kw = backend.dispatch(cfg, graph, seeds, num_seeds, ell=ell)
        return fn.trace(*args, **kw)
    mesh = _device_mesh(cfg.mesh_shape, ("data", "model"))
    if cfg.backend == "mesh1d":
        from repro.core.dist_steiner import partition_edges, partition_ell

        n_replica, n_blocks = cfg.mesh_shape
        if cfg.mode == "frontier":
            part = partition_ell(
                ell_view_cached(graph, cfg.ell_width),
                n_replica=n_replica,
                n_blocks=n_blocks,
            )
            arrays = (part.nbr, part.wgt, part.row2v)
        else:
            part = partition_edges(
                np.asarray(graph.src),
                np.asarray(graph.dst),
                np.asarray(graph.w),
                graph.n,
                n_replica=n_replica,
                n_blocks=n_blocks,
                symmetrize=False,
            )
            arrays = (part.src, part.dst, part.w)
        fn = backend.build_executable(cfg, mesh, part, len(seeds))
        return fn.trace(*arrays, seeds)
    if cfg.backend == "mesh2d":
        from repro.core.dist_steiner_2d import partition_edges_2d

        R, C = cfg.mesh_shape
        part = partition_edges_2d(
            np.asarray(graph.src),
            np.asarray(graph.dst),
            np.asarray(graph.w),
            graph.n,
            R=R,
            C=C,
            symmetrize=False,
        )
        fn = backend.build_executable(cfg, mesh, part, len(seeds))
        return fn.trace(part.src_row, part.dst_col, part.w, seeds)
    raise ValueError(f"unknown backend {cfg.backend!r}")
