"""Out-of-core graphs: build a .gstore on disk, then serve queries off it.

    PYTHONPATH=src python examples/build_store.py [--scale 14]

Streams a scale-14 RMAT graph (~16K vertices, ~260K directed edges; crank
``--scale`` up as far as your disk allows — ingest memory stays bounded
by the chunk size, never O(edges)) into a ``.gstore`` directory, reopens
it with checksum verification, proves solver parity against the fully
in-memory path, and boots a :class:`repro.serve.SteinerServer` straight
off the store.

The equivalent CLI:

    python -m repro.graphstore build /tmp/g14.gstore --source rmat \\
        --scale 14 --edge-factor 8
    python -m repro.graphstore info /tmp/g14.gstore
"""

import argparse
import tempfile
from pathlib import Path

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import from_edges
from repro.data.graphs import rmat_edges
from repro.graphstore import RmatEdgeSource, build_store, open_store
from repro.serve import ServeConfig, SteinerServer
from repro.solver import SolverConfig, SteinerSolver


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=14, help="RMAT n = 2^scale")
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--out", default=None, help=".gstore path (default: temp)")
    args = ap.parse_args()

    out = Path(args.out) if args.out else (
        Path(tempfile.mkdtemp()) / f"rmat_s{args.scale}.gstore"
    )

    # 1) stream the graph to disk — two passes, bounded chunk memory
    source = RmatEdgeSource(args.scale, args.edge_factor, seed=0)
    path, stats = build_store(source, out)
    print(
        f"built {path}\n"
        f"  n={stats.n:,} directed edges={stats.m_directed:,} "
        f"in {stats.seconds:.2f}s ({stats.edges_per_sec:,.0f} edges/s)\n"
        f"  peak chunk transient: {stats.peak_chunk_bytes / 2**20:.1f} MiB "
        f"(vs {stats.m_directed * 8 / 2**20:.0f} MiB of edge payload on disk)"
    )

    # 2) reopen with checksum verification; lazy memmapped views
    store = open_store(path)

    # 3) parity: a handle prepared from disk answers exactly like one
    #    prepared from RAM (the acceptance bar for the storage layer)
    rng = np.random.default_rng(0)
    seeds = rng.choice(store.n, size=16, replace=False).astype(np.int32)
    cfg = SolverConfig(backend="single", mode="bucket")
    disk = SteinerSolver(cfg).prepare(store).solve(seeds)
    src, dst, w, n = rmat_edges(args.scale, args.edge_factor, seed=0)
    mem = SteinerSolver(cfg).prepare(from_edges(src, dst, w, n)).solve(seeds)
    assert disk.total_distance == mem.total_distance
    print(f"  solver parity (disk vs RAM): D = {disk.total_distance}")

    # 4) serve queries straight off the store
    server = SteinerServer(
        graph_path=path, config=ServeConfig(buckets=(16,), max_batch=4)
    )
    for q in range(8):
        qs = np.random.default_rng(100 + q).choice(
            store.n, size=16, replace=False
        )
        r = server.query(qs.tolist())
        print(f"  query {q}: D={r.total_distance:9.1f} "
              f"({'cache' if r.from_cache else 'fresh'})")
    s = server.stats()
    print(f"served {s['completed']} queries, p50 {s['latency_p50_ms']:.1f}ms")


if __name__ == "__main__":
    main()
