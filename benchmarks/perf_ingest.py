"""Perf driver for graphstore streaming ingestion + delta mutation.

``--bench ingest`` (default) builds RMAT ``.gstore`` stores across a
ladder of scales and records the throughput trajectory (edges/sec), the
measured bounded-memory transient (``IngestStats.peak_chunk_bytes``),
and process peak RSS.

``--bench delta`` measures the incremental-update path at one scale:
cold solve on the base store, then ~1k localized mixed deltas applied
through an :class:`repro.delta.IncrementalSession` (append → in-place
ELL row patch → affected-cell warm frontier rounds → spliced pair-table
repair), against the from-scratch alternative (full re-ingest via
``compact`` + cold prepare + cold solve).  Records the speedup and the
warm/cold relaxation counts; the incremental result is asserted
bit-identical to the post-compact cold solve.

Both write ``BENCH_ingest.json`` at the repo root (same family as
``BENCH_steiner.json`` / ``BENCH_serve.json``), each preserving the
other's section.

Usage:
  PYTHONPATH=src python -m benchmarks.perf_ingest [--scales 12,14,16,18]
      [--edge-factor 8] [--chunk-edges 65536] [--keep DIR]
  PYTHONPATH=src python -m benchmarks.perf_ingest --bench delta
      [--delta-scale 18] [--delta-count 1000] [--delta-seeds 128]

``--keep DIR`` leaves the largest store on disk (so a follow-up
``perf_steiner --store`` run can benchmark solves off it); by default
stores are built in a temp dir and deleted.
"""

import argparse
import json
import platform
import resource
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
OUT = ROOT / "BENCH_ingest.json"


def peak_rss_mb() -> float:
    # ru_maxrss is KiB on Linux, bytes on macOS
    v = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return v / 1024 if platform.system() != "Darwin" else v / 2**20


def run(args) -> None:
    from repro.graphstore import RmatEdgeSource, build_store, open_store

    scales = [int(s) for s in args.scales.split(",")]
    keep = Path(args.keep) if args.keep else None
    tmp = Path(tempfile.mkdtemp(prefix="perf_ingest_"))
    rows = []
    try:
        for scale in scales:
            dest = (keep if keep and scale == max(scales) else tmp)
            dest.mkdir(parents=True, exist_ok=True)
            path, stats = build_store(
                RmatEdgeSource(
                    scale,
                    args.edge_factor,
                    seed=args.seed,
                    chunk_edges=args.chunk_edges,
                ),
                dest / f"rmat_s{scale}_ef{args.edge_factor}.gstore",
            )
            store = open_store(path, verify=False)
            disk_mb = sum(
                (store.path / e["file"]).stat().st_size
                for e in store.manifest["arrays"].values()
            ) / 2**20
            row = {
                "scale": scale,
                "n_vertices": stats.n,
                "edges_in": stats.edges_in,
                "m_directed": stats.m_directed,
                "seconds": round(stats.seconds, 3),
                "edges_per_sec": round(stats.edges_per_sec, 1),
                "peak_chunk_mb": round(stats.peak_chunk_bytes / 2**20, 2),
                "fixed_mb": round(stats.fixed_bytes / 2**20, 2),
                "store_mb": round(disk_mb, 1),
                "peak_rss_mb": round(peak_rss_mb(), 1),
            }
            rows.append(row)
            print(
                f"scale={scale:2d} n={row['n_vertices']:>9,} "
                f"m={row['m_directed']:>11,} {row['seconds']:6.2f}s "
                f"{row['edges_per_sec']:>12,.0f} e/s "
                f"chunk={row['peak_chunk_mb']:6.2f}MB "
                f"store={row['store_mb']:7.1f}MB rss={row['peak_rss_mb']:.0f}MB",
                flush=True,
            )
            if dest is tmp:
                shutil.rmtree(path, ignore_errors=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    record = {
        "bench": "ingest",
        "workload": {
            "generator": "rmat",
            "edge_factor": args.edge_factor,
            "chunk_edges": args.chunk_edges,
            "seed": args.seed,
        },
        "env": {"platform": platform.platform()},
        "scales": rows,
    }
    _write_merged(record)


def _write_merged(record: dict) -> None:
    """Writes BENCH_ingest.json, preserving the other bench's section."""
    if OUT.exists():
        old = json.loads(OUT.read_text())
        for k in ("scales", "workload", "delta"):
            if k not in record and k in old:
                record[k] = old[k]
    OUT.write_text(json.dumps(record, indent=1))
    print(f"wrote {OUT}")


def run_delta(args) -> None:
    from repro.delta import IncrementalSession, append_deltas, compact
    from repro.graphstore import RmatEdgeSource, build_store, open_store
    from repro.solver import SolverConfig, SteinerSolver

    scale = args.delta_scale
    tmp = Path(tempfile.mkdtemp(prefix="perf_delta_"))
    try:
        path, istats = build_store(
            RmatEdgeSource(scale, args.edge_factor, seed=args.seed,
                           chunk_edges=args.chunk_edges),
            tmp / f"rmat_s{scale}.gstore",
        )
        store = open_store(path, verify=False)
        n = int(store.n)
        rng = np.random.default_rng(args.seed)
        seeds = rng.choice(
            n, size=args.delta_seeds, replace=False
        ).astype(np.int32)
        cfg = SolverConfig(
            backend="single", mode="frontier", ell_pad_rows=4096,
            frontier_size=4096,
        )
        handle = SteinerSolver(cfg).prepare(store)
        out0 = handle.solve(seeds)  # compile the cold executable
        float(out0.total_distance)
        t = time.perf_counter()
        cold = handle.solve(seeds)
        d_cold = float(cold.total_distance)
        t_cold_solve = time.perf_counter() - t
        relax_cold = int(cold.telemetry.relaxations)
        # the incremental side: a resident session (patched ELL + warm
        # frontier rounds + spliced pair-table repair); built cold once,
        # then every epoch costs work proportional to the delta
        session = IncrementalSession(
            store, seeds, ell_width=cfg.ell_width,
            ell_pad_rows=cfg.ell_pad_rows,
            frontier_size=cfg.frontier_size,
        )
        assert session.total_distance == d_cold, (
            session.total_distance, d_cold
        )

        # ~1k mixed deltas confined to the smallest Voronoi cells of the
        # cold solve — a genuinely localized region update.  (A naive
        # id-range locality doesn't localize on RMAT: every id range
        # attaches to the hub core, whose giant cells cover ~99% of the
        # graph.)  Adds pair random member vertices; deletes/reweights
        # hit real base edges with BOTH endpoints in the region.
        lab = np.asarray(cold.raw.state.lab)
        sizes = np.bincount(
            lab[lab < args.delta_seeds], minlength=args.delta_seeds
        )
        chosen, total = [], 0
        for c in np.argsort(sizes):
            if sizes[c] == 0:
                continue
            chosen.append(int(c))
            total += int(sizes[c])
            if total >= 2048:
                break
        member_mask = np.isin(lab, np.asarray(chosen))
        members = np.where(member_mask)[0]
        indptr = np.asarray(store.indptr)
        indices = np.asarray(store.indices[:])
        local_edges = []
        for u in members:
            nb = indices[indptr[u]:indptr[u + 1]]
            for v in nb[member_mask[nb]]:
                if u < v:
                    local_edges.append((int(u), int(v)))
        rng.shuffle(local_edges)
        k_mut = min(2 * (args.delta_count // 4), len(local_edges))
        records = []
        for _ in range(args.delta_count - k_mut):
            u = int(members[rng.integers(0, members.size)])
            v = int(members[rng.integers(0, members.size)])
            if u == v:
                continue
            records.append(("add", u, v, float(rng.integers(1, 100))))
        for i, (u, v) in enumerate(local_edges[:k_mut]):
            if i % 2 == 0:
                records.append(("delete", u, v))
            else:
                records.append(("reweight", u, v, float(rng.integers(1, 100))))

        # incremental path: append + patched-ELL affected-cell re-solve
        # (warm frontier rounds + spliced pair-table repair — no O(E)
        # refresh, no O(E) finish rescan)
        changed = np.unique(np.asarray(
            [r[1] for r in records] + [r[2] for r in records], np.int64
        ))
        # pre-trace every epoch executable (patched-ELL scatter at the
        # right bucket, warm frontier init signature, table finish) with
        # an inert resolve: on the unchanged store the same rows refill
        # with identical content and the affected cells re-converge to
        # the identical fixpoint, so this is a no-op apart from XLA
        pre = session.resolve(changed)
        assert pre.total_distance == d_cold, (pre.total_distance, d_cold)
        t = time.perf_counter()
        append_deltas(store, records)
        t_append = time.perf_counter() - t
        t1 = time.perf_counter()
        store.reload()
        res = session.resolve(changed)
        d_warm = res.total_distance
        t_resolve = time.perf_counter() - t1
        t_incremental = time.perf_counter() - t
        relax_warm = res.relaxations

        # from-scratch path: full re-ingest of the effective edge set
        # (compact streams every edge through the two-pass CSR builder)
        # + cold prepare + cold solve
        t = time.perf_counter()
        compact(store)
        t_compact = time.perf_counter() - t
        t1 = time.perf_counter()
        fresh = SteinerSolver(cfg).prepare(store)
        t_prepare = time.perf_counter() - t1
        t1 = time.perf_counter()
        cold2 = fresh.solve(seeds)
        d_cold2 = float(cold2.total_distance)
        t_cold2_solve = time.perf_counter() - t1
        t_full = time.perf_counter() - t
        assert d_warm == d_cold2, (d_warm, d_cold2)

        row = {
            "scale": scale,
            "n_vertices": n,
            "m_directed": int(store.m),
            "num_seeds": args.delta_seeds,
            "num_deltas": len(records),
            "changed_vertices": int(changed.size),
            "affected_cells": res.affected_cells,
            "vertices_reset": res.vertices_reset,
            "cells_recomputed": res.cells_recomputed,
            "member_vertices": res.member_vertices,
            "warm_iterations": res.iterations,
            "append_s": round(t_append, 4),
            "resolve_s": round(t_resolve, 3),
            "incremental_s": round(t_incremental, 3),
            "compact_s": round(t_compact, 3),
            "prepare_s": round(t_prepare, 3),
            "cold2_solve_s": round(t_cold2_solve, 3),
            "full_reingest_s": round(t_full, 3),
            "speedup": round(t_full / t_incremental, 2),
            "cold_solve_s": round(t_cold_solve, 3),
            "relax_cold": relax_cold,
            "relax_warm": relax_warm,
            "d_cold_before": d_cold,
            "d_after": d_warm,
        }
        print(
            f"delta bench scale={scale}: {len(records)} deltas, "
            f"{res.affected_cells} affected cells "
            f"({res.vertices_reset:,} vertices reset) | "
            f"incremental {t_incremental:.3f}s vs full {t_full:.3f}s "
            f"({row['speedup']:.1f}x) | relax warm/cold "
            f"{relax_warm:,.0f}/{relax_cold:,.0f}",
            flush=True,
        )
        if relax_warm >= relax_cold:
            print("WARNING: warm relaxations not below cold")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    _write_merged({
        "bench": "ingest",
        "env": {"platform": platform.platform()},
        "delta": {
            "workload": {
                "generator": "rmat",
                "edge_factor": args.edge_factor,
                "seed": args.seed,
                "locality":
                    "deltas confined to the smallest Voronoi cells "
                    "covering >= 2048 vertices (localized region update)",
            },
            "row": row,
        },
    })


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", choices=("ingest", "delta"), default="ingest")
    ap.add_argument("--scales", default="12,14,16,18")
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--chunk-edges", type=int, default=1 << 16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keep", default=None,
                    help="keep the largest store in this directory")
    ap.add_argument("--delta-scale", type=int, default=18)
    ap.add_argument("--delta-count", type=int, default=1000)
    ap.add_argument("--delta-seeds", type=int, default=128)
    args = ap.parse_args()
    if args.bench == "delta":
        run_delta(args)
    else:
        run(args)


if __name__ == "__main__":
    main()
