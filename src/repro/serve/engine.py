"""Micro-batching query engine over one resident graph.

The serving loop the ROADMAP's "heavy traffic" north star needs: queries
arrive one at a time, the engine canonicalizes and bucket-pads them
(:mod:`repro.serve.plan`), answers repeats from an LRU result cache, and
drains the rest through one prepared ``"batch"``-backend solver handle
(:mod:`repro.solver`) in fixed-shape micro-batches so the whole service
runs on |buckets| warm executables.

Lifecycle::

    server = SteinerServer(g, ServeConfig(max_batch=8))
    server.warmup()                  # optional: compile before traffic
    t = server.submit([3, 17, 42])   # enqueue, returns a ticket
    results = server.flush()         # run pending micro-batches
    results[t].total_distance

or one-shot: ``server.query([3, 17, 42])``. Counters (QPS, p50/p99
latency, cache hit rate, padding waste) via ``server.stats()``.

Store-backed servers (``graph_path=`` or a ``GraphStore`` as ``g``) are
*epoch-aware*: :meth:`SteinerServer.apply_deltas` appends edge deltas to
the store's log (:mod:`repro.delta`), refreshes the solver handle, and
re-validates the result cache against the changed vertices instead of
flushing it — an entry whose converged Voronoi labels show every changed
vertex unreached is provably still exact and keeps serving; the rest are
evicted (counted in ``cache_invalidations_total``) and, on their next
query, re-solved *warm* from the retained per-key Voronoi state
(:func:`repro.delta.resolve.reset_affected`) so only the affected cells
are re-relaxed.

Future scaling PRs plug in here: sharded execution swaps the handle's
backend ("batch" → "mesh1d") behind the same queue; landmark caching and
async prefetch hook the admission path.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import Graph
from repro.core.tree import tree_edge_sets
from repro.core.voronoi import VoronoiState
from repro.obs import MetricsRegistry
from repro.serve import plan as planmod
from repro.solver import SolverConfig, SteinerSolver


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static service configuration (fixes the executable set)."""

    buckets: Tuple[int, ...] = planmod.DEFAULT_BUCKETS
    max_batch: int = 8  # B — lanes per micro-batch executable
    cache_capacity: int = 4096  # LRU entries (0 disables caching)
    mode: str = "bucket"  # Voronoi schedule: "dense" | "bucket" | "pallas"
    mst_algo: str = "prim"
    delta: Optional[float] = None
    max_iters: Optional[int] = None
    materialize_edges: bool = False  # host-side edge sets in results
    # retained per-key Voronoi states for warm affected-cell re-solves
    # after apply_deltas (store-backed servers; 0 disables retention and
    # every invalidated entry re-solves cold through the batch path)
    state_capacity: int = 64


@dataclasses.dataclass(frozen=True)
class QueryResult:
    """One served query (cache-hit results are the cached object)."""

    key: Tuple[int, ...]
    bucket: int
    total_distance: float
    num_edges: int
    # immutable so cached entries can be shared across repeat queries
    edges: Optional[FrozenSet[Tuple[int, int]]]  # None unless materialize_edges
    from_cache: bool
    latency_s: float

    def with_latency(self, latency_s: float, from_cache: bool) -> "QueryResult":
        return dataclasses.replace(
            self, latency_s=latency_s, from_cache=from_cache
        )


class LRUCache:
    """Plain OrderedDict LRU keyed on the canonical seed tuple."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._d: "collections.OrderedDict[Tuple[int, ...], QueryResult]" = (
            collections.OrderedDict()
        )

    def get(self, key) -> Optional[QueryResult]:
        if self.capacity <= 0:
            return None
        hit = self._d.get(key)
        if hit is not None:
            self._d.move_to_end(key)
        return hit

    def put(self, key, value: QueryResult) -> None:
        if self.capacity <= 0:
            return
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.capacity:
            self._d.popitem(last=False)

    def keys(self) -> List[Tuple[int, ...]]:
        """Snapshot of resident keys (for the epoch-bump validity scan)."""
        return list(self._d.keys())

    def pop(self, key) -> None:
        """Evicts one entry (no-op when absent)."""
        self._d.pop(key, None)

    def __contains__(self, key) -> bool:
        return key in self._d

    def __len__(self) -> int:
        return len(self._d)


@dataclasses.dataclass
class _Pending:
    ticket: int
    plan: planmod.QueryPlan
    t_submit: float


class SteinerServer:
    """Batched Steiner query server over one resident :class:`Graph`.

    The graph can come from memory (``g``) or straight off disk
    (``graph_path`` naming a ``.gstore`` directory built with
    ``python -m repro.graphstore build`` — the server boots from the
    memmapped CSR without any caller-side edge-list materialization).
    A :class:`repro.graphstore.GraphStore` instance is also accepted
    as ``g``.  Stores are handed to ``SteinerSolver.prepare`` as-is, so
    the backend keeps its off-disk fast paths (``mode="pallas"`` builds
    its ELL view chunkwise from the memmaps) and hub-sorted stores stay
    transparent to callers: the prepared handle translates submitted
    ORIGINAL seed ids through the store's ``vertex_perm`` at solve time
    (``materialize_edges`` output, if enabled, is in the store's
    relabeled id space).
    """

    def __init__(
        self,
        g: Optional[Graph] = None,
        config: ServeConfig = ServeConfig(),
        *,
        graph_path: Optional[str] = None,
    ):
        if (g is None) == (graph_path is None):
            raise ValueError("pass exactly one of g= or graph_path=")
        if graph_path is not None:
            from repro.graphstore import open_store

            g = open_store(graph_path)
        self.config = config
        # one prepared solver handle: every micro-batch launch dispatches
        # to the "batch" backend's cached executables (one per bucket)
        self._handle = SteinerSolver(
            SolverConfig(
                backend="batch",
                mode=config.mode,
                mst_algo=config.mst_algo,
                delta=config.delta,
                max_iters=config.max_iters,
                batch_size=config.max_batch,
            )
        ).prepare(g)
        # the resident COO graph — prepare() already materialized it for
        # GraphStore inputs, so reuse that artifact instead of a second
        # O(M) expansion
        self.g = (
            self._handle.artifact("graph") if hasattr(g, "to_graph") else g
        )
        # epoch awareness: store-backed servers track the delta-log epoch
        # and keep per-key converged Voronoi states for warm re-solves
        self._store = g if hasattr(g, "to_graph") else None
        self.epoch = self._handle.epoch  # None for in-memory graphs
        perm = getattr(self._store, "vertex_perm", None)
        self._vertex_perm = None if perm is None else np.asarray(perm)
        # key -> (epoch, bucket, dist, lab, pred) numpy snapshots of the
        # converged state, LRU-bounded by config.state_capacity
        self._states: "collections.OrderedDict[Tuple[int, ...], tuple]" = (
            collections.OrderedDict()
        )
        # (from_epoch, to_epoch, changed | None) per bump_epoch call —
        # warm re-solves union the changed sets since a state's epoch; a
        # None entry (unknown changed set) blocks warm starts across it
        self._changed_log: List[Tuple[int, int, Optional[np.ndarray]]] = []
        self._warm_handle = None  # lazy single-backend handle on self.g
        self.cache = LRUCache(config.cache_capacity)
        self._queues: Dict[int, "collections.deque[_Pending]"] = {
            b: collections.deque() for b in sorted(config.buckets)
        }
        self._next_ticket = 0
        # results computed by a flush() that failed part-way (a later
        # batch raised): delivered by the next flush instead of being
        # lost with the exception
        self._ready: Dict[int, QueryResult] = {}
        # Service counters live on a PER-SERVER MetricsRegistry (always
        # on, independent of the global repro.obs switch — stats() must
        # work on a server that never called obs.enable(), and two
        # servers in one process must not share counters).  Histogram
        # reservoirs are bounded (newest 16384): cache hits are ready at
        # batch assembly while fresh solves wait for the executable, so
        # the two latency populations get separate streams.
        self.metrics = MetricsRegistry()
        self._m_completed = self.metrics.counter(
            "serve_queries_completed_total", "queries answered (fresh + cached)"
        )
        self._m_hits = self.metrics.counter(
            "serve_cache_hits_total", "queries answered from the LRU result cache"
        )
        self._m_lanes = self.metrics.counter(
            "serve_lanes_run_total", "micro-batch lanes launched (incl. padding)"
        )
        self._m_padded = self.metrics.counter(
            "serve_lanes_padded_total", "inert padding lanes launched"
        )
        self._m_lat = {
            path: self.metrics.histogram(
                "serve_latency_seconds",
                "submit-to-result latency of one query",
                labels={"path": path},
            )
            for path in ("fresh", "cached")
        }
        self._m_batches = {
            b: self.metrics.counter(
                "serve_batches_total",
                "fixed-shape micro-batches executed",
                labels={"bucket": str(b)},
            )
            for b in config.buckets
        }
        self._m_invalidated = self.metrics.counter(
            "cache_invalidations_total",
            "cache entries evicted by an epoch bump (deltas touched a cell)",
        )
        self._m_revalidated = self.metrics.counter(
            "serve_cache_revalidations_total",
            "cache entries proven still exact across an epoch bump",
        )
        self._m_warm = self.metrics.counter(
            "serve_warm_resolves_total",
            "queries re-solved warm from a retained prior-epoch state",
        )
        self._g_epoch = self.metrics.gauge(
            "delta_epoch", "delta-log epoch this server is serving"
        )
        self._g_epoch.set(float(self.epoch or 0))
        # pad_waste and queue depth existed only as derived stats() values;
        # as gauges they ride the scrape endpoint alongside the counters
        self._g_pad_waste = self.metrics.gauge(
            "serve_pad_waste",
            "fraction of executed lanes that were padding",
        )
        self._g_queue_depth = self.metrics.gauge(
            "serve_queue_depth", "queries currently queued across buckets"
        )
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def submit(self, seeds: Sequence[int]) -> int:
        """Enqueues one seed-set query; returns its ticket id.

        Raises ValueError on seeds outside [0, n) — jax scatters would
        silently drop them and a garbage result would poison the cache.
        """
        arr = np.asarray(seeds, np.int64)
        if arr.size and (arr.min() < 0 or arr.max() >= self.g.n):
            raise ValueError(
                f"seed ids must be in [0, {self.g.n}), got "
                f"[{arr.min()}, {arr.max()}]"
            )
        # queues/cache keys stay in ORIGINAL ids; hub-sorted stores are
        # translated by the prepared handle at solve time
        p = planmod.plan_query(seeds, self.config.buckets)
        t = self._next_ticket
        self._next_ticket += 1
        now = time.perf_counter()
        if self._t_first is None:
            self._t_first = now
        self._queues[p.bucket].append(_Pending(ticket=t, plan=p, t_submit=now))
        self._g_queue_depth.set(float(self.pending()))
        return t

    def pending(self) -> int:
        return sum(len(q) for q in self._queues.values())

    # ------------------------------------------------------------------
    # mutation (store-backed servers)
    # ------------------------------------------------------------------

    def apply_deltas(self, records: Sequence, *, map_ids: bool = True) -> dict:
        """Appends edge deltas to the backing store and bumps the epoch.

        One call = one log segment (``repro.delta.append_deltas``) + one
        :meth:`bump_epoch` with the exact changed-vertex set of that
        segment: the solver handle refreshes, surviving cache entries
        keep serving, the rest are evicted and later re-solved warm.

        Returns the :meth:`bump_epoch` report plus ``"records"``.
        """
        if self._store is None:
            raise ValueError(
                "apply_deltas needs a store-backed server "
                "(graph_path= or a GraphStore as g)"
            )
        from repro.delta import append_deltas, read_segment

        info = append_deltas(self._store, records, map_ids=map_ids)
        seg = read_segment(
            self._store.path / info["file"], info["epoch"]
        )
        # endpoints are already in stored-id space (append mapped them),
        # matching the id space of retained Voronoi labels
        changed = np.unique(
            np.concatenate([seg.u, seg.v]).astype(np.int64)
        )
        report = self.bump_epoch(changed)
        report["records"] = info["count"]
        return report

    def bump_epoch(self, changed: Optional[Sequence[int]] = None) -> dict:
        """Adopts the store's current epoch; re-validates the cache.

        ``changed`` is the union of delta-record endpoints (stored-id
        space) appended since this server's epoch.  Every cached entry
        whose retained converged labels show ALL changed vertices
        unreached (the S sentinel) is provably still exact — an edge
        touching only unreached vertices cannot alter any seed-rooted
        path — and keeps serving with its state stamp advanced.  Every
        other entry (including entries whose state was LRU-dropped) is
        evicted and counted in ``cache_invalidations_total``.

        ``changed=None`` means "unknown": the whole cache is flushed and
        warm starts across this bump are disabled.

        Call this directly only after mutating the store externally
        (another process ran ``append_deltas``/``compact``);
        :meth:`apply_deltas` does the whole dance in-process.
        """
        if self._store is None:
            raise ValueError(
                "bump_epoch needs a store-backed server "
                "(graph_path= or a GraphStore as g)"
            )
        from repro.delta import entry_survives

        prev = self.epoch
        refreshed = self._handle.refresh()
        self.epoch = refreshed["epoch"]
        # the resident COO graph and the warm handle bound to it are
        # epoch-dependent — rebind both to the refreshed artifacts
        self.g = self._handle.artifact("graph")
        self._warm_handle = None
        if changed is not None:
            changed = np.unique(np.asarray(changed, np.int64))
        self._changed_log.append((prev, self.epoch, changed))
        invalidated = revalidated = 0
        with obs.span(
            "serve:bump_epoch",
            from_epoch=prev,
            epoch=self.epoch,
            changed=0 if changed is None else int(changed.size),
        ):
            for key, rec in list(self._states.items()):
                epoch0, bucket, dist, lab, pred = rec
                if (
                    changed is not None
                    and epoch0 == prev
                    and entry_survives(lab, changed, bucket)
                ):
                    # still the exact fixpoint at the new epoch
                    self._states[key] = (self.epoch, bucket, dist, lab, pred)
                    if key in self.cache:
                        revalidated += 1
            for key in self.cache.keys():
                rec = self._states.get(key)
                if rec is None or rec[0] != self.epoch:
                    self.cache.pop(key)
                    invalidated += 1
        self._m_invalidated.inc(invalidated)
        self._m_revalidated.inc(revalidated)
        self._g_epoch.set(float(self.epoch or 0))
        return {
            "epoch": self.epoch,
            "from_epoch": prev,
            "invalidated": invalidated,
            "revalidated": revalidated,
            "refreshed": refreshed["refreshed"],
        }

    def _changed_since(self, epoch0: int) -> Optional[np.ndarray]:
        """Union of changed vertices over epochs (epoch0, self.epoch];
        None when the log does not cover that range (warm start unsound)."""
        if epoch0 == self.epoch:
            return np.empty(0, np.int64)
        parts = []
        lo = None
        for fr, to, ch in self._changed_log:
            if to <= epoch0:
                continue
            if ch is None:
                return None
            parts.append(ch)
            lo = fr if lo is None else min(lo, fr)
        if lo is None or lo > epoch0:
            return None  # gap: the state predates the retained log
        return np.unique(np.concatenate(parts))

    def _store_state(self, key, bucket: int, dist, lab, pred) -> None:
        """Retains one converged Voronoi state (numpy, current epoch)."""
        if self._store is None or self.config.state_capacity <= 0:
            return
        self._states[key] = (
            self.epoch,
            int(bucket),
            np.asarray(dist),
            np.asarray(lab),
            np.asarray(pred),
        )
        self._states.move_to_end(key)
        while len(self._states) > self.config.state_capacity:
            self._states.popitem(last=False)

    def _warm_prepared(self):
        """Lazy single-backend handle over the resident graph for warm
        affected-cell re-solves (rebuilt after every epoch bump)."""
        if self._warm_handle is None:
            mode = (
                self.config.mode
                if self.config.mode in ("dense", "bucket")
                else "dense"
            )
            self._warm_handle = SteinerSolver(
                SolverConfig(
                    backend="single",
                    mode=mode,
                    mst_algo=self.config.mst_algo,
                    delta=self.config.delta,
                    max_iters=self.config.max_iters,
                )
            ).prepare(self.g)
        return self._warm_handle

    def _warm_resolve(self, plan: planmod.QueryPlan) -> Optional[QueryResult]:
        """Re-solves one invalidated query warm from its retained state.

        Resets only the delta-affected Voronoi cells
        (:func:`repro.delta.resolve.reset_affected`) and relaxes from
        there — bit-exact vs a cold solve, but the kept cells start
        converged.  Returns None (caller falls through to a cold batch
        lane) when no usable state is retained.
        """
        if self._store is None or self.config.state_capacity <= 0:
            return None
        if self.config.materialize_edges:
            return None  # edge materialization runs on the batch path
        rec = self._states.get(plan.key)
        if rec is None:
            return None
        epoch0, bucket, dist, lab, pred = rec
        if bucket != plan.bucket:
            return None
        changed = self._changed_since(epoch0)
        if changed is None:
            return None
        from repro.delta import reset_affected

        self._states.move_to_end(plan.key)
        seeds = plan.padded.astype(np.int64)
        if self._vertex_perm is not None:
            seeds = self._vertex_perm[seeds]
        st = VoronoiState(
            dist=jnp.asarray(dist), lab=jnp.asarray(lab), pred=jnp.asarray(pred)
        )
        warm, cells, n_reset = reset_affected(st, seeds, changed, bucket)
        with obs.span(
            "serve:warm_resolve",
            bucket=plan.bucket,
            cells=int(cells.size),
            reset=n_reset,
        ):
            out = self._warm_prepared().solve(
                seeds.astype(np.int32), warm_state=warm
            )
        result = QueryResult(
            key=plan.key,
            bucket=plan.bucket,
            total_distance=float(out.total_distance),
            num_edges=int(out.num_edges),
            edges=None,
            from_cache=False,
            latency_s=0.0,
        )
        self.cache.put(plan.key, result)
        s = out.raw.state
        self._store_state(plan.key, bucket, s.dist, s.lab, s.pred)
        self._m_warm.inc()
        return result

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def warmup(self) -> None:
        """Compiles every bucket executable before traffic arrives."""
        lo = int(np.argmax(np.isfinite(np.asarray(self.g.w))))
        u = int(np.asarray(self.g.src)[lo])
        v = int(np.asarray(self.g.dst)[lo])
        for b in self.config.buckets:
            batch = np.tile(
                planmod.pad_seed_set((min(u, v), max(u, v)), b),
                (self._handle.config.batch_size, 1),
            )
            with obs.span("serve:warmup", bucket=b):
                self._execute(b, batch)

    def _execute(
        self, bucket: int, seed_batch: np.ndarray, n_real: Optional[int] = None
    ):
        """One fixed-shape (max_batch, bucket) pipeline launch.

        ``n_real`` bounds host-side edge materialization to the lanes that
        carry distinct queries (the rest are inert batch padding).
        """
        out = self._handle.solve(seed_batch)
        res = out.raw
        totals = np.asarray(out.total_distance)
        nedges = np.asarray(out.num_edges)
        edges = None
        if self.config.materialize_edges:
            edges = tree_edge_sets(
                res.state,
                res.tree,
                seed_batch.shape[0] if n_real is None else n_real,
            )
        return totals, nedges, edges, res

    def flush(self) -> Dict[int, QueryResult]:
        """Drains every bucket queue; returns {ticket: QueryResult}.

        Exception-safe: if a solver failure interrupts a batch, that
        batch's tickets go back on their queue, results of batches that
        already completed in this call are held for the next ``flush``,
        and the exception propagates — no ticket is ever dropped.
        """
        with obs.span("serve:flush"):
            return self._flush()

    def _flush(self) -> Dict[int, QueryResult]:
        # deliver results stranded by a previously failed flush first
        out: Dict[int, QueryResult] = self._ready
        self._ready = {}
        # the solver config owns the lane count (ServeConfig.max_batch is
        # copied into it at construction)
        B = self._handle.config.batch_size
        for bucket, queue in self._queues.items():
            while queue:
                # Assemble up to B *distinct uncached* keys; duplicate and
                # already-cached tickets ride along without a lane.
                lanes: List[np.ndarray] = []
                lane_of: Dict[Tuple[int, ...], int] = {}
                # (pending, result-or-None, from_cache): result is None
                # for lanes awaiting the batch execute; a non-None result
                # with from_cache=False came from a warm re-solve during
                # assembly
                riders: List[
                    Tuple[_Pending, Optional[QueryResult], bool]
                ] = []
                with obs.span("serve:assemble", bucket=bucket):
                    while queue and len(lanes) < B:
                        p = queue.popleft()
                        hit = self.cache.get(p.plan.key)
                        from_cache = hit is not None
                        if hit is None:
                            # invalidated by an epoch bump but state
                            # retained: re-solve warm (affected cells
                            # only) instead of burning a cold batch lane
                            hit = self._warm_resolve(p.plan)
                        if hit is None and p.plan.key not in lane_of:
                            lane_of[p.plan.key] = len(lanes)
                            lanes.append(p.plan.padded)
                        riders.append((p, hit, from_cache))
                t_assembled = time.perf_counter()
                t_done = t_assembled
                if obs.tracing():
                    # retroactive queue-wait span per ticket in this batch
                    for p, _, _ in riders:
                        obs.add_span(
                            "serve:queue_wait",
                            p.t_submit,
                            t_assembled,
                            ticket=p.ticket,
                            bucket=bucket,
                        )
                fresh_by_key: Dict[Tuple[int, ...], QueryResult] = {}
                if lanes:
                    n_real = len(lanes)
                    while len(lanes) < B:  # inert batch-dim padding
                        lanes.append(lanes[0])
                    try:
                        with obs.span(
                            "serve:solve", bucket=bucket, lanes=n_real
                        ):
                            totals, nedges, edges, res = self._execute(
                                bucket, np.stack(lanes), n_real
                            )
                    except Exception:
                        # the riders were already popped — put them back
                        # (original order) and stash the results of the
                        # batches this call already completed, so a
                        # solver failure drops no tickets; then surface
                        # the failure to the caller
                        for p, _, _ in reversed(riders):
                            queue.appendleft(p)
                        self._ready = out
                        self._g_queue_depth.set(float(self.pending()))
                        raise
                    t_done = time.perf_counter()
                    self._m_batches[bucket].inc()
                    self._m_lanes.inc(B)
                    self._m_padded.inc(B - n_real)
                    self._g_pad_waste.set(
                        self._m_padded.value / self._m_lanes.value
                    )
                    capture = (
                        self._store is not None
                        and self.config.state_capacity > 0
                    )
                    if capture:
                        # one host pull of the real lanes' converged
                        # states — the raw material for warm re-solves
                        # after future epoch bumps
                        st_dist = np.asarray(res.state.dist)[:n_real]
                        st_lab = np.asarray(res.state.lab)[:n_real]
                        st_pred = np.asarray(res.state.pred)[:n_real]
                    for key, i in lane_of.items():
                        fresh = QueryResult(
                            key=key,
                            bucket=bucket,
                            total_distance=float(totals[i]),
                            num_edges=int(nedges[i]),
                            edges=edges[i] if edges is not None else None,
                            from_cache=False,
                            latency_s=0.0,
                        )
                        fresh_by_key[key] = fresh
                        self.cache.put(key, fresh)
                        if capture:
                            self._store_state(
                                key, bucket,
                                st_dist[i], st_lab[i], st_pred[i],
                            )
                with obs.span(
                    "serve:stash", bucket=bucket, results=len(riders)
                ):
                    for p, hit, from_cache in riders:
                        if hit is None:
                            hit = fresh_by_key[p.plan.key]
                            ready_at = t_done  # waited for the batch execute
                        else:
                            # cache hits AND warm re-solves were ready once
                            # assembly finished
                            ready_at = t_assembled
                        if from_cache:
                            self._m_hits.inc()
                        self._m_completed.inc()
                        lat = ready_at - p.t_submit
                        self._m_lat["cached" if from_cache else "fresh"].observe(lat)
                        out[p.ticket] = hit.with_latency(lat, from_cache)
                self._t_last = t_done
        self._g_queue_depth.set(float(self.pending()))
        return out

    # ------------------------------------------------------------------
    # convenience front-ends
    # ------------------------------------------------------------------

    def query(self, seeds: Sequence[int]) -> QueryResult:
        """Synchronous single query (micro-batch of one).

        The internal flush may also drain tickets submitted by other
        callers (or stranded by an earlier failed flush); those results
        are held for their own ``flush`` consumers, not discarded.
        """
        t = self.submit(seeds)
        results = self.flush()
        mine = results.pop(t)
        self._ready.update(results)
        return mine

    def query_many(self, seed_sets: Sequence[Sequence[int]]) -> List[QueryResult]:
        """Submits a burst, flushes once, returns results in input order.

        As with :meth:`query`, results for tickets that are not part of
        this burst are held for their own ``flush`` consumers.
        """
        tickets = [self.submit(s) for s in seed_sets]
        results = self.flush()
        out = [results.pop(t) for t in tickets]
        self._ready.update(results)
        return out

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------

    def stats(self) -> dict:
        """Service counters — a dict view over the per-server registry
        (``self.metrics``; :meth:`prometheus_text` exposes the same
        series in scrape format).

        Latency percentiles are ``None`` until the matching population
        has served at least one query — an idle server reports no
        latency rather than a fabricated 0.0 ms.  ``latency_*`` covers
        all completed queries; ``fresh_*`` / ``cached_*`` split the
        solve path from the cache path (their distributions differ by
        orders of magnitude, so one merged stream is misleading).
        """

        def pcts(vals):
            if not vals:
                return None, None
            lat = np.asarray(vals)
            return (
                float(np.percentile(lat, 50) * 1e3),
                float(np.percentile(lat, 99) * 1e3),
            )

        fresh = self._m_lat["fresh"].values()
        cached = self._m_lat["cached"].values()
        p50, p99 = pcts(fresh + cached)
        fresh_p50, fresh_p99 = pcts(fresh)
        cached_p50, cached_p99 = pcts(cached)
        completed = int(self._m_completed.value)
        cache_hits = int(self._m_hits.value)
        lanes_run = int(self._m_lanes.value)
        lanes_padded = int(self._m_padded.value)
        span = (
            (self._t_last - self._t_first)
            if (self._t_first is not None and self._t_last is not None)
            else 0.0
        )
        return {
            "completed": completed,
            "cache_hits": cache_hits,
            "cache_hit_rate": (cache_hits / completed if completed else 0.0),
            "cache_entries": len(self.cache),
            "qps": completed / span if span > 0 else 0.0,
            "latency_p50_ms": p50,
            "latency_p99_ms": p99,
            "fresh_p50_ms": fresh_p50,
            "fresh_p99_ms": fresh_p99,
            "cached_p50_ms": cached_p50,
            "cached_p99_ms": cached_p99,
            "lanes_run": lanes_run,
            "lanes_padded": lanes_padded,
            "pad_waste": (lanes_padded / lanes_run if lanes_run else 0.0),
            "batches_per_bucket": {
                b: int(c.value) for b, c in self._m_batches.items()
            },
            # delta-epoch serving state (trivial on in-memory servers:
            # epoch None, counters 0)
            "epoch": self.epoch,
            "cache_invalidations": int(self._m_invalidated.value),
            "cache_revalidations": int(self._m_revalidated.value),
            "warm_resolves": int(self._m_warm.value),
            "retained_states": len(self._states),
        }

    def prometheus_text(self) -> str:
        """This server's counters in Prometheus text exposition format."""
        return self.metrics.prometheus_text()
