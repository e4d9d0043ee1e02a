"""The unified solver facade: one config, one prepare, many solves.

Usage::

    from repro.solver import SolverConfig, SteinerSolver

    solver = SteinerSolver(SolverConfig(backend="single", mode="bucket"))
    handle = solver.prepare(graph)        # preprocessing happens ONCE
    out = handle.solve(seeds)             # cached jitted executable
    out.total_distance                    # D(G_S)

``prepare`` computes every preprocessing artifact the chosen backend
needs — the ELL view for frontier mode, the edge partition + device
placement + mesh for the distributed backends — exactly once, and returns
a :class:`PreparedGraph` whose repeated ``solve`` calls dispatch to a
cached jitted/shard_mapped executable (zero re-traces; asserted in
``tests/test_solver.py``).

``prepare`` also accepts an on-disk :class:`repro.graphstore.GraphStore`
(from ``open_store``) for every backend: single/batch materialize the
padded COO from the memmapped CSR, mode="frontier" builds its ELL view
chunkwise from disk (skipping the O(E)-Python path), and the mesh
backends load the store's per-device shards directly when a matching
partition was prebuilt — see DESIGN.md §Graphstore.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import Graph
from repro.solver.config import SolverConfig
from repro.solver.registry import SolveOutput, get_backend

if TYPE_CHECKING:  # pragma: no cover
    from repro.graphstore.loader import GraphStore


class PreparedGraph:
    """A graph bound to one backend with its preprocessing done.

    Created by :meth:`SteinerSolver.prepare`; do not construct directly.
    Holds the preprocessing artifacts (ELL view / partition / mesh /
    device-placed edge arrays) and the per-|S| executable cache.
    """

    def __init__(self, config: SolverConfig, backend, graph, artifacts):
        self.config = config
        # what prepare() was given: a Graph, or a GraphStore for handles
        # prepared straight off disk
        self.graph = graph
        self._backend = backend
        self._artifacts = artifacts
        # delta-log epoch of the store at prepare time (None for in-memory
        # graphs): refresh() compares it against the store's current epoch
        self.epoch = getattr(graph, "epoch", None)
        # hub-sorted stores relabel vertices; solve() takes ORIGINAL ids
        # and translates through the persisted permutation
        perm = getattr(graph, "vertex_perm", None)
        self._vertex_perm = None if perm is None else np.asarray(perm)

    @property
    def backend(self) -> str:
        return self._backend.name

    @property
    def preprocessing(self) -> Tuple[str, ...]:
        """What :meth:`SteinerSolver.prepare` computed for this backend."""
        return tuple(self._backend.preprocessing)

    def artifact(self, name: str):
        """One preprocessing artifact by name (e.g. "ell", "part", "mesh");
        None when the backend did not compute it."""
        return self._artifacts.get(name)

    @property
    def num_executables(self) -> int:
        """Distinct compiled executables this handle holds (mesh backends;
        single/batch share process-wide jit caches keyed on static args)."""
        ex = self._artifacts.get("executables")
        return len(ex) if ex is not None else 0

    def refresh(self) -> dict:
        """Re-prepares only what the store's delta log changed.

        For handles prepared from a :class:`~repro.graphstore.GraphStore`
        whose epoch moved on (``append_deltas``/``compact`` since
        prepare), this reloads the store and rebuilds the epoch-dependent
        artifacts — the resident COO graph, the ELL view, the partition
        and its device placement.  Epoch-*invariant* artifacts are kept:
        the device mesh and, crucially, the compiled mesh executables
        (their static geometry — n, block sizes, seed counts — does not
        depend on edge content), so a refresh never re-traces.

        Returns a report ``{"refreshed": (...), "from_epoch", "epoch"}``;
        a no-op (same epoch, or an in-memory graph) returns
        ``refreshed=()``.
        """
        from repro.graphstore.loader import GraphStore

        if not isinstance(self.graph, GraphStore):
            return {"refreshed": (), "from_epoch": self.epoch,
                    "epoch": self.epoch}
        store = self.graph
        store.reload(verify=False)
        if store.epoch == self.epoch:
            return {"refreshed": (), "from_epoch": self.epoch,
                    "epoch": store.epoch}
        with obs.span(
            "refresh", backend=self.backend,
            from_epoch=self.epoch, to_epoch=store.epoch,
        ):
            new = self._backend.prepare(self.config, store)
        old = self._artifacts
        for keep in ("executables", "mesh"):
            if keep in old and keep in new:
                new[keep] = old[keep]
        refreshed = tuple(
            sorted(k for k in new if k not in ("store", "executables", "mesh"))
        )
        self._artifacts = new
        prev, self.epoch = self.epoch, store.epoch
        return {"refreshed": refreshed, "from_epoch": prev,
                "epoch": store.epoch}

    def solve(self, seeds, *, warm_state=None) -> SolveOutput:
        """Solves one query — (S,) seed ids, or (B, S) for backend="batch".

        The static seed count is taken from the trailing axis; repeated
        calls with the same shape reuse one compiled executable.  Seed
        ids are always in the graph's *original* numbering: handles
        prepared from a hub-sorted store translate them through the
        stored ``vertex_perm`` here.

        ``warm_state``: optional :class:`~repro.core.voronoi.VoronoiState`
        warm start (backend="single", mode "dense"|"bucket" only) — see
        :func:`repro.delta.resolve.reset_affected` for how to build a
        sound one from a previous epoch's converged state.
        """
        if warm_state is not None and self.backend != "single":
            raise ValueError(
                f"warm_state is only supported by backend 'single', "
                f"not {self.backend!r}"
            )
        if self._vertex_perm is not None:
            seeds = self._vertex_perm[np.asarray(seeds, np.int64)]
        if self._backend.seeds_ndim == 2:
            seeds = jnp.asarray(seeds, jnp.int32)
            if seeds.ndim != 2:
                raise ValueError(
                    f'backend "batch" expects (B, S) seeds, '
                    f"got shape {seeds.shape}"
                )
            num_seeds = int(seeds.shape[1])
        else:
            seeds = np.asarray(seeds, np.int32)
            if seeds.ndim != 1:
                raise ValueError(
                    f"backend {self.backend!r} expects (S,) seeds, "
                    f"got shape {seeds.shape}"
                )
            num_seeds = int(seeds.shape[0])
        kw = {} if warm_state is None else {"warm_state": warm_state}
        if not obs.enabled():
            return self._backend.solve(
                self.config, self._artifacts, seeds, num_seeds, **kw
            )
        cfg = self.config
        t0 = obs.now()
        with obs.span(
            "solve", backend=self.backend, mode=cfg.mode, num_seeds=num_seeds
        ):
            out = self._backend.solve(
                cfg, self._artifacts, seeds, num_seeds, **kw
            )
        t1 = obs.now()
        hist = obs.histogram(
            "solver_solve_seconds",
            "wall time of one PreparedGraph.solve",
            labels={"backend": self.backend, "mode": cfg.mode},
        )
        if hist is not None:
            hist.observe(t1 - t0)
        if out.telemetry is not None:
            ctr = obs.counter(
                "solver_messages_total",
                "candidate transmissions attempted across solves",
                labels={"backend": self.backend, "mode": cfg.mode},
            )
            if ctr is not None:
                ctr.inc(out.telemetry.messages)
            label = f"{self.backend}/{cfg.mode}"
            obs.emit_round_telemetry(
                out.telemetry.per_round,
                t0,
                t1,
                label=label,
                per_rank=out.telemetry.per_rank,
            )
            # the solve's exact totals, one sample per solve
            t = out.telemetry
            totals = {"iterations": t.iterations, "messages": t.messages,
                      "relaxations": t.relaxations, "scanned": t.scanned,
                      "segmin_scatters": t.segmin_scatters}
            obs.add_counter(
                f"solve_totals[{label}]", t1,
                {k: v for k, v in totals.items() if v is not None},
            )
        return out


class SteinerSolver:
    """Facade over the backend registry: validates the config, prepares
    graphs, and hands out solve handles."""

    def __init__(self, config: SolverConfig = SolverConfig()):
        self.config = config
        self._backend = get_backend(config.backend)
        self._backend.validate(config)

    def prepare(self, graph: Union[Graph, "GraphStore"]) -> PreparedGraph:
        """Runs the backend's one-time preprocessing for ``graph``.

        ``graph`` may be an in-memory :class:`~repro.core.graph.Graph` or
        an on-disk :class:`repro.graphstore.GraphStore`; stores are
        materialized / shard-loaded by the backend exactly once here.
        """
        with obs.span(
            "prepare", backend=self.config.backend, mode=self.config.mode
        ):
            artifacts = self._backend.prepare(self.config, graph)
        return PreparedGraph(self.config, self._backend, graph, artifacts)
