"""Synthetic query-stream benchmark for the serving subsystem.

Mirrors the paper's Table III workload family: an RMAT (Graph500-style)
scale-free graph, repeated seed-set queries against it. Query popularity
is Zipfian over a pool of distinct seed sets (heavy-traffic realism: a
few hot queries dominate), seed-set sizes are drawn log-uniform across
the shape-bucket ladder so every bucket sees traffic.

Reports QPS, p50/p99 latency, cache hit rate, and padding waste — overall
and per bucket — and writes ``BENCH_serve.json`` at the repo root so later
PRs have a throughput trajectory to optimize against.

Usage: PYTHONPATH=src python -m benchmarks.perf_serve
         [--scale 9] [--edge-factor 8] [--queries 200] [--pool 40]
         [--zipf 1.1] [--batch 8] [--buckets 8,16,32] [--no-cache]
"""

import argparse
import json
import platform
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
OUT = ROOT / "BENCH_serve.json"


def build_query_pool(n, rng, pool_size, buckets):
    """Distinct seed sets, sizes log-uniform over the bucket ladder."""
    lo, hi = 2, max(buckets)
    sizes = np.exp(
        rng.uniform(np.log(lo), np.log(hi + 1), size=pool_size)
    ).astype(int)
    sizes = np.clip(sizes, lo, hi)
    return [
        rng.choice(n, size=int(k), replace=False).tolist() for k in sizes
    ]


def zipf_stream(rng, pool_size, num_queries, s):
    """Zipfian rank-popularity sample over pool indices (rank 0 hottest)."""
    p = 1.0 / np.arange(1, pool_size + 1) ** s
    p /= p.sum()
    return rng.choice(pool_size, size=num_queries, p=p)


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=9, help="RMAT n = 2^scale")
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--pool", type=int, default=40, help="distinct seed sets")
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--buckets", default="8,16,32")
    ap.add_argument("--flush-every", type=int, default=8)
    ap.add_argument("--mode", default="bucket", choices=("dense", "bucket"))
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome trace (queue-wait / assemble / "
                         "solve spans per micro-batch; Perfetto-loadable)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="dump the server's Prometheus metrics "
                         "(plus the global obs registry)")
    args = ap.parse_args()

    from repro import obs
    from repro.core import from_edges
    from repro.data.graphs import rmat_edges
    from repro.serve import ServeConfig, SteinerServer

    if args.trace or args.metrics:
        obs.enable(trace=args.trace is not None,
                   metrics=args.metrics is not None)

    buckets = tuple(int(b) for b in args.buckets.split(","))
    rng = np.random.default_rng(args.seed)

    t0 = time.perf_counter()
    src, dst, w, n = rmat_edges(
        args.scale, args.edge_factor, max_weight=100, seed=args.seed
    )
    g = from_edges(src, dst, w, n, pad_to=8)
    t_build = time.perf_counter() - t0
    print(
        f"graph: RMAT scale={args.scale} n={n} "
        f"directed_edges={int(g.num_edges)} build={t_build:.2f}s",
        flush=True,
    )

    cfg = ServeConfig(
        buckets=buckets,
        max_batch=args.batch,
        cache_capacity=0 if args.no_cache else 4096,
        mode=args.mode,
    )
    server = SteinerServer(g, cfg)
    t0 = time.perf_counter()
    server.warmup()
    t_warm = time.perf_counter() - t0
    print(f"warmup (compile {len(buckets)} bucket executables): {t_warm:.2f}s",
          flush=True)

    pool = build_query_pool(n, rng, args.pool, buckets)
    stream = zipf_stream(rng, args.pool, args.queries, args.zipf)

    per_bucket = {}
    t0 = time.perf_counter()
    for i, qi in enumerate(stream):
        t = server.submit(pool[qi])
        if (i + 1) % args.flush_every == 0:
            for r in server.flush().values():
                b = per_bucket.setdefault(
                    r.bucket, {"n": 0, "hits": 0, "lat": []}
                )
                b["n"] += 1
                b["hits"] += r.from_cache
                b["lat"].append(r.latency_s)
    for r in server.flush().values():
        b = per_bucket.setdefault(r.bucket, {"n": 0, "hits": 0, "lat": []})
        b["n"] += 1
        b["hits"] += r.from_cache
        b["lat"].append(r.latency_s)
    t_stream = time.perf_counter() - t0

    stats = server.stats()
    stats["qps"] = args.queries / t_stream  # full-stream wall clock
    bucket_rows = {}
    for bkt in sorted(per_bucket):
        b = per_bucket[bkt]
        lat = np.asarray(b["lat"])
        bucket_rows[str(bkt)] = {
            "queries": b["n"],
            "cache_hit_rate": b["hits"] / b["n"],
            "latency_p50_ms": float(np.percentile(lat, 50) * 1e3),
            "latency_p99_ms": float(np.percentile(lat, 99) * 1e3),
        }
        print(
            f"bucket {bkt:3d}: {b['n']:4d} queries  "
            f"hit_rate={b['hits'] / b['n']:.2f}  "
            f"p50={bucket_rows[str(bkt)]['latency_p50_ms']:.1f}ms  "
            f"p99={bucket_rows[str(bkt)]['latency_p99_ms']:.1f}ms",
            flush=True,
        )
    print(
        f"stream: {args.queries} queries in {t_stream:.2f}s  "
        f"QPS={stats['qps']:.1f}  hit_rate={stats['cache_hit_rate']:.2f}  "
        f"p50={stats['latency_p50_ms']:.1f}ms  "
        f"p99={stats['latency_p99_ms']:.1f}ms  "
        f"pad_waste={stats['pad_waste']:.2f}",
        flush=True,
    )

    record = {
        "bench": "serve",
        "workload": {
            "graph": f"rmat_scale{args.scale}_ef{args.edge_factor}",
            "n_vertices": int(n),
            "n_directed_edges": int(g.num_edges),
            "queries": args.queries,
            "pool": args.pool,
            "zipf_s": args.zipf,
            "buckets": list(buckets),
            "max_batch": args.batch,
            "flush_every": args.flush_every,
            "mode": args.mode,
            "cache": not args.no_cache,
            "seed": args.seed,
        },
        "env": {
            "platform": platform.platform(),
            "backend": _backend(),
        },
        "warmup_s": round(t_warm, 3),
        "stream_s": round(t_stream, 3),
        "overall": stats,
        "per_bucket": bucket_rows,
    }
    OUT.write_text(json.dumps(record, indent=1))
    print(f"wrote {OUT}")
    if args.trace:
        obs.export_chrome_trace(args.trace)
        print(f"wrote {args.trace}")
    if args.metrics:
        # the server's own registry plus whatever the global one gathered
        Path(args.metrics).write_text(
            server.prometheus_text() + obs.prometheus_text()
        )
        print(f"wrote {args.metrics}")


def _backend() -> str:
    import jax

    return jax.default_backend()


if __name__ == "__main__":
    main()
