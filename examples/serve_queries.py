"""Serving Steiner queries: batched multi-query engine over one graph.

    PYTHONPATH=src python examples/serve_queries.py

Stands up a :class:`repro.serve.SteinerServer` on an RMAT graph, then
plays a small Zipfian query stream through it — the paper's workload (a
network scientist issuing repeated seed-set queries against one fixed
graph) turned into a service: shape-bucketed compilation, micro-batched
execution, LRU result caching.
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import from_edges
from repro.data.graphs import rmat_edges
from repro.serve import ServeConfig, SteinerServer


def main() -> None:
    enable_compile_cache()
    # 1) one resident graph, shared by every query
    src, dst, w, n = rmat_edges(10, 8, max_weight=100, seed=42)
    g = from_edges(src, dst, w, n, pad_to=64)
    print(f"graph: {n} vertices, {int(g.num_edges)} directed edges")

    # 2) the server: 3 shape buckets -> 3 warm executables, batches of 8
    server = SteinerServer(
        g, ServeConfig(buckets=(8, 16, 32), max_batch=8)
    )
    server.warmup()
    print("warmed 3 bucket executables")

    # 3) a Zipfian stream over 30 distinct queries (hot queries repeat)
    rng = np.random.default_rng(0)
    pool = [
        rng.choice(n, size=int(rng.integers(3, 24)), replace=False).tolist()
        for _ in range(30)
    ]
    p = 1.0 / np.arange(1, 31) ** 1.1
    p /= p.sum()
    stream = [pool[i] for i in rng.choice(30, size=120, p=p)]

    # 4) submit in bursts of 8, flush each burst through the micro-batcher
    for burst_start in range(0, len(stream), 8):
        tickets = [
            server.submit(q) for q in stream[burst_start : burst_start + 8]
        ]
        results = server.flush()
        for t in tickets[:1]:  # print one per burst
            r = results[t]
            src_tag = "cache" if r.from_cache else f"bucket {r.bucket}"
            print(
                f"  |S|={len(r.key):2d} -> D(G_S)={r.total_distance:7.0f} "
                f"({r.num_edges} edges, {src_tag}, "
                f"{r.latency_s * 1e3:.1f} ms)"
            )

    # 5) service counters
    s = server.stats()
    print(
        f"served {s['completed']} queries: QPS={s['qps']:.1f}, "
        f"p50={s['latency_p50_ms']:.1f}ms, p99={s['latency_p99_ms']:.1f}ms, "
        f"cache hit rate={s['cache_hit_rate']:.0%}, "
        f"pad waste={s['pad_waste']:.0%}"
    )


if __name__ == "__main__":
    main()
