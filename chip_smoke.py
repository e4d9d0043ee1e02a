#!/usr/bin/env python3
"""Smoke run of the Steiner solver and server on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --chips 4 [--seed N]  # the four-chip mesh phase only

Drives the entry points a user calls — ``SteinerSolver(cfg).prepare(g)
.solve(seeds)`` and ``SteinerServer`` — on Graph500-shaped RMAT graphs
(a, b, c = 0.57, 0.19, 0.19; edge factor 16; integer weights 1-100) made
from ``--seed`` by ``repro.data.graphs.rmat_edges``, with uniformly drawn
seed vertices, and checks every answer on the host.

One chip, in order:

  solve   single/bucket at RMAT scale 20 (1M vertices, ~35.7M directed
          edges), |S| = 16 and 1,024: Voronoi distances equal scipy's
          multi-source Dijkstra exactly; every tree edge is a graph edge
          with its weight; the tree is acyclic, connected and spans the
          seeds; ``total_distance`` is the sum of its edge weights.  One
          warm repeat must not retrace.
  kernel  mode="pallas" on the same graph and 16 seeds: the compiled
          executable holds the Mosaic kernel (``tpu_custom_call``) and its
          total is bit-equal to mode="bucket".
  parity  every single-device mode at scale 12 equals the sequential
          Mehlhorn reference (``repro.core.ref``) in tree and total.
  serve   ``SteinerServer`` at scale 14, buckets (16, 64), 8 lanes: 40
          queries, 10 of them repeats; every answer equals a single-device
          solve of its canonical seeds, and every repeat is a cache hit.

``--chips 4`` runs only the mesh phase: backend="mesh1d" on a (1, 4)
mesh in bucket and frontier mode at scale 18, with the edge shards on
four distinct chips, bit-equal in total to a one-chip single/bucket solve
on device 0.

Sizes.  A relaxation round over the COO edge list costs about 93 ns per
directed edge on a TPU v5e (XLA's element gathers and scatter-mins; one
round at Graph500 scale 22, 142.6M directed edges, took 13 s) and a
bucket solve takes about 40-50 rounds, so the graphs are cut from the
Graph500 scale 22 until the whole run stays well inside 20 minutes.

Each phase prints one JSON line (sizes, seconds with compile time apart,
``peak_bytes_in_use``); these are smoke observations, not benchmark
numbers.  The last line is ``{"ok": true, "device": {...}}``.  With no TPU,
or on any failed check, the script exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

EDGE_FACTOR = 16
MAX_WEIGHT = 100
SOLVE_SCALE = 20
PARITY_SCALE = 12
SERVE_SCALE = 14
MESH_SCALE = 18


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def expect(ok, *what) -> None:
    """Raises unless ``ok`` (not ``assert``: that vanishes under -O)."""
    if not ok:
        raise SmokeFailure(*what)


class CompileWatch:
    """Compile seconds and persistent-cache hits, from jax.monitoring."""

    # lowering and XLA compile; tracing is left out, since the traces of
    # nested jitted functions are timed inside their caller's
    _COMPILE_EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._COMPILE_EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


WATCH: CompileWatch


def peak_bytes() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class Phase:
    """Times one phase and prints its JSON line on success."""

    def __init__(self, name: str, **fields):
        self.fields = dict(phase=name, **fields)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = WATCH.seconds
        self.h0 = WATCH.cache_hits
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            emit(
                **self.fields,
                seconds=time.perf_counter() - self.t0,
                compile_seconds=WATCH.seconds - self.c0,
                compile_cache_hits=WATCH.cache_hits - self.h0,
                peak_bytes_in_use=peak_bytes(),
            )
        return False


# ----------------------------------------------------------------------------
# host-side references
# ----------------------------------------------------------------------------


def rmat(scale: int, seed: int):
    from repro.data.graphs import rmat_edges

    return rmat_edges(scale, EDGE_FACTOR, max_weight=MAX_WEIGHT, seed=seed)


def uniform_seeds(n, src, dst, k: int, seed: int) -> np.ndarray:
    from repro.data.graphs import select_seeds

    return select_seeds(n, src, dst, k, strategy="uniform", seed=seed)


def host_csr(src, dst, w, n):
    """scipy CSR of the undirected edges, for the Dijkstra reference.

    Parallel edges stay separate entries, so the shortest one wins
    (``coo_matrix`` would sum them).
    """
    import scipy.sparse as sp

    s2 = np.concatenate([src, dst])
    order = np.argsort(s2)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s2, minlength=n), out=indptr[1:])
    d2 = np.concatenate([dst, src])[order]
    w2 = np.concatenate([w, w]).astype(np.float64)[order]
    return sp.csr_matrix((w2, d2, indptr), shape=(n, n))


def check_tree(src, dst, w, n, seeds, out) -> int:
    """The tree of a single-device solve ``out``: its edges exist with
    their weights; it is acyclic, connected and spans the seeds; its
    ``total_distance`` is the sum of the edge weights."""
    import networkx as nx

    st, tree = out.raw.state, out.raw.tree
    dist = np.asarray(st.dist, np.float64)
    pred = np.asarray(st.pred)
    v = np.flatnonzero(np.asarray(tree.path_edge))
    j = np.flatnonzero(np.asarray(tree.bridge_valid))
    tu = np.concatenate([pred[v], np.asarray(tree.bridge_u)[j]]).astype(np.int64)
    tv = np.concatenate([v, np.asarray(tree.bridge_v)[j]]).astype(np.int64)
    tw = np.concatenate([dist[v] - dist[pred[v]], np.asarray(tree.bridge_w, np.float64)[j]])
    # lightest parallel edge of every tree pair (inf if no such edge)
    qkey = np.minimum(tu, tv) * n + np.maximum(tu, tv)
    uq, inv = np.unique(qkey, return_inverse=True)
    expect(len(uq) == len(qkey), "tree lists an edge twice")
    ekey = np.minimum(src, dst).astype(np.int64) * n + np.maximum(src, dst)
    pos = np.minimum(np.searchsorted(uq, ekey), len(uq) - 1)
    hit = uq[pos] == ekey
    lightest = np.full(len(uq), np.inf)
    np.minimum.at(lightest, pos[hit], w[hit].astype(np.float64))
    bad = np.flatnonzero(lightest[inv] != tw)
    if bad.size:
        b = bad[0]
        raise SmokeFailure(
            f"{bad.size} tree edges are not graph edges of their weight, "
            f"e.g. ({tu[b]}, {tv[b]}, {tw[b]}) vs {lightest[inv][b]}"
        )
    t = nx.Graph()
    t.add_nodes_from(np.asarray(seeds).tolist())
    t.add_edges_from(zip(tu.tolist(), tv.tolist()))
    expect(nx.is_tree(t), "tree is cyclic or disconnected")
    total = float(out.total_distance)
    expect(float(np.sum(tw)) == total, float(np.sum(tw)), total)
    return len(tw)


# ----------------------------------------------------------------------------
# one-chip phases
# ----------------------------------------------------------------------------


def phase_solve(scale: int, seed: int, seed_counts=(16, 1024)):
    """Returns the graph, the first seed set and its bucket solve."""
    from scipy.sparse.csgraph import dijkstra

    from repro.core import from_edges
    from repro.solver import SolverConfig, SteinerSolver, trace_count

    t0 = time.perf_counter()
    src, dst, w, n = rmat(scale, seed)
    g = from_edges(src, dst, w, n, pad_to=1024)
    gen_s = time.perf_counter() - t0
    handle = SteinerSolver(SolverConfig(backend="single", mode="bucket")).prepare(g)
    t0 = time.perf_counter()
    csr = host_csr(src, dst, w, n)
    csr_s = time.perf_counter() - t0
    first = None
    for k in seed_counts:
        seeds = uniform_seeds(n, src, dst, k, seed + k)
        with Phase("solve", n=n, edges=int(g.num_edges), seeds=k, mode="bucket",
                   graph_seconds=gen_s, reference_csr_seconds=csr_s) as ph:
            t = time.perf_counter()
            out = handle.solve(seeds)
            ph.fields["solve_cold_seconds"] = time.perf_counter() - t
            if first is None:  # the one warm repeat
                traces = trace_count()
                t = time.perf_counter()
                again = handle.solve(seeds)
                ph.fields["solve_warm_seconds"] = time.perf_counter() - t
                expect(trace_count() == traces, "warm repeat retraced")
                expect(again.total_distance == out.total_distance)
                first = (seeds, out)
            t = time.perf_counter()
            ref = dijkstra(csr, indices=seeds, min_only=True)
            dist = np.asarray(out.raw.state.dist, np.float64)
            expect(np.array_equal(dist, ref),
                   int(np.sum(dist != ref)), "Voronoi distances differ from Dijkstra")
            tree_edges = check_tree(src, dst, w, n, seeds, out)
            ph.fields.update(
                check_seconds=time.perf_counter() - t,
                total_distance=out.total_distance, tree_edges=tree_edges,
                rounds=out.telemetry.iterations,
            )
        gen_s = csr_s = 0.0
    return (g, *first)


def phase_parity(scale: int, seed: int, k: int = 16) -> None:
    from repro.core import from_edges, ref, tree_edge_list
    from repro.solver import SolverConfig, SteinerSolver

    src, dst, w, n = rmat(scale, seed)
    g = from_edges(src, dst, w, n, pad_to=256)
    seeds = uniform_seeds(n, src, dst, k, seed)
    edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
    t_ref, d_ref = ref.mehlhorn_ref(n, edges, seeds.tolist())
    for mode in ("dense", "bucket", "frontier", "pallas"):
        with Phase("parity", n=n, edges=int(g.num_edges), seeds=k, mode=mode):
            cfg = SolverConfig(backend="single", mode=mode)
            out = SteinerSolver(cfg).prepare(g).solve(seeds)
            expect(out.total_distance == d_ref, mode, out.total_distance, d_ref)
            expect(tree_edge_list(out.raw.state, out.raw.tree) == t_ref, mode)


def phase_kernel(g, seeds, bucket) -> None:
    """Compiled min-plus kernel vs the Δ-bucket solve ``bucket`` of the
    same graph and seeds."""
    import jax

    from repro.solver import SolverConfig, SteinerSolver, get_backend

    k = len(seeds)
    with Phase("kernel", n=g.n, edges=int(g.num_edges), seeds=k, mode="pallas") as ph:
        cfg = SolverConfig(backend="single", mode="pallas")
        t = time.perf_counter()
        handle = SteinerSolver(cfg).prepare(g)  # the ELL view, built once
        ell = handle.artifact("ell")
        ph.fields.update(ell_rows=int(ell.nbr.shape[0]), ell_seconds=time.perf_counter() - t)
        fn, args, kw = get_backend("single").dispatch(cfg, g, seeds, k, ell=ell)
        hlo = fn.lower(*args, **kw).compile().as_text()
        expect(jax.default_backend() != "tpu" or "tpu_custom_call" in hlo,
               "pallas executable holds no Mosaic kernel")
        out = handle.solve(seeds)
        expect(out.total_distance == bucket.total_distance,
               out.total_distance, bucket.total_distance)
        expect(out.num_edges == bucket.num_edges)
        ph.fields.update(total_distance=out.total_distance, rounds=out.telemetry.iterations)


def phase_serve(g, seed: int, buckets=(16, 64), sizes=(10, 16, 40, 64),
                fresh=30, repeats=10) -> None:
    from repro.serve import ServeConfig, SteinerServer
    from repro.solver import SolverConfig, SteinerSolver

    rng = np.random.default_rng(seed)
    queries = [
        rng.choice(g.n, size=int(s), replace=False)
        for s in rng.choice(sizes, size=fresh)
    ]
    again = [rng.permutation(queries[i]) for i in rng.choice(fresh, repeats, replace=False)]
    with Phase("serve", n=g.n, edges=int(g.num_edges), seeds=list(buckets),
               queries=fresh + repeats) as ph:
        server = SteinerServer(g, ServeConfig(buckets=tuple(buckets), max_batch=8))
        t = time.perf_counter()
        server.warmup()
        ph.fields["warmup_seconds"] = time.perf_counter() - t
        t = time.perf_counter()
        first = server.query_many(queries)
        second = server.query_many(again)
        ph.fields["serve_seconds"] = time.perf_counter() - t
        expect(not any(r.from_cache for r in first))
        expect(all(r.from_cache for r in second), "a repeated query missed the cache")
        single = SteinerSolver(SolverConfig(backend="single", mode="bucket")).prepare(g)
        want = {}
        for r in first:
            want[r.key] = single.solve(np.asarray(r.key, np.int32))
        for r in first + second:
            w = want[r.key]
            expect((r.total_distance, r.num_edges)
                   == (w.total_distance, w.num_edges), r.key, r, w)
        st = server.stats()
        ph.fields.update(cache_hits=int(st["cache_hits"]), pad_waste=st["pad_waste"])


def run_one_chip(seed: int) -> None:
    from repro.core import from_edges

    g, seeds, bucket = phase_solve(SOLVE_SCALE, seed)
    phase_kernel(g, seeds, bucket)
    del g, bucket
    gc.collect()  # the solve graph leaves the device before serving
    phase_parity(PARITY_SCALE, seed)
    src, dst, w, n = rmat(SERVE_SCALE, seed)
    phase_serve(from_edges(src, dst, w, n, pad_to=1024), seed)


# ----------------------------------------------------------------------------
# four-chip phase
# ----------------------------------------------------------------------------


def phase_mesh(seed: int, scale: int = MESH_SCALE, k: int = 16) -> None:
    import jax

    from repro.core import from_edges
    from repro.solver import SolverConfig, SteinerSolver

    expect(len(jax.devices()) >= 4, jax.devices())
    src, dst, w, n = rmat(scale, seed)
    g = from_edges(src, dst, w, n, pad_to=1024)
    seeds = uniform_seeds(n, src, dst, k, seed)
    with Phase("mesh_reference", n=n, edges=int(g.num_edges), seeds=k,
               backend="single", mode="bucket") as ph:
        one = SteinerSolver(SolverConfig(backend="single", mode="bucket")).prepare(g)
        base = one.solve(seeds)
        ph.fields.update(total_distance=base.total_distance,
                         device=str(g.src.devices().pop()))
    for mode in ("bucket", "frontier"):
        with Phase("mesh", n=n, edges=int(g.num_edges), seeds=k, backend="mesh1d",
                   mode=mode, mesh_shape=[1, 4]) as ph:
            handle = SteinerSolver(
                SolverConfig(backend="mesh1d", mode=mode, mesh_shape=(1, 4))
            ).prepare(g)
            ph.fields["shard_devices"] = _check_shards(handle)
            out = handle.solve(seeds)
            expect(out.total_distance == base.total_distance,
                   mode, out.total_distance, base.total_distance)
            ph.fields.update(total_distance=out.total_distance,
                             rounds=out.telemetry.iterations)


def _check_shards(handle) -> list:
    """The edge shards sit on four distinct chips, a quarter each."""
    devices = set()
    for arr in handle.artifact("edges"):
        shards = arr.addressable_shards
        expect(len({s.device for s in shards}) == 4, [s.device for s in shards])
        expect(all(s.data.shape[0] * 4 == arr.shape[0] for s in shards))
        devices |= {s.device for s in shards}
    expect(len(devices) == 4, devices)
    return sorted(str(d) for d in devices)


# ----------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, JAX found {len(jax.devices())}",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    entries = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    emit(phase="compile_cache", dir=str(cache), entries_at_start=entries,
         warm=entries > 0)
    global WATCH
    WATCH = CompileWatch()

    if args.chips == 4:
        phase_mesh(args.seed)
    else:
        run_one_chip(args.seed)
    emit(ok=True, device={"platform": dev.platform, "kind": dev.device_kind,
                          "count": len(jax.devices())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
