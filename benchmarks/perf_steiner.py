"""Perf drivers for the Steiner core pipeline.

Two benches:

``--bench handle`` (default)
    Real execution on the local backend: for each Voronoi mode
    (dense / bucket / frontier / pallas) measure the COLD first solve
    (trace + compile + run) against steady-state solves through a
    prepared :class:`repro.solver.SteinerSolver` handle, plus the
    one-time ``prepare()`` cost (ELL build for frontier/pallas; the
    pallas row is the kernel path — compiled on TPU/GPU, interpreter
    fallback on CPU).  Two additional rows run the mesh1d backend on a
    (1, 1) mesh — ``mesh_bucket`` vs ``mesh_frontier`` — recording the
    paper's Fig. 5/6 messages/relaxations counters so the distributed
    message-prioritization work reduction is tracked alongside the
    latencies (the bench asserts frontier does strictly fewer messages
    and lands on the bit-identical total).  Writes
    ``BENCH_steiner.json`` at the repo root (same shape as
    ``BENCH_serve.json``) so the perf trajectory covers the core
    pipeline, not just serving.

``--bench roofline``
    §Perf hillclimb: compiles dry-run variants of the ukw_1k / clw_10k
    production cells on a forced 512-device host mesh and extracts the
    per-round roofline terms for each candidate change:

      base        : bucket, fused f32 gather, local_steps=1, Prim MST
      unfused     : two separate (dist, lab) gathers        [ablation]
      lab_i16     : int16 label gather (6 B/vertex/round)
      ls2 / ls4   : 2 / 4 local relaxations per exchange
      boruvka     : parallel MST (replicated-compute trade)
      2d          : (src × dst)-block 2D partition

    Writes benchmarks/results/perf/steiner_<cell>.json.

Usage:
  PYTHONPATH=src python -m benchmarks.perf_steiner [--scale 10] [--queries 12]
  PYTHONPATH=src python -m benchmarks.perf_steiner --store g14.gstore
  PYTHONPATH=src python -m benchmarks.perf_steiner --bench roofline [--cell ukw_1k]
"""

import argparse
import json
import os
import platform
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
OUT_HANDLE = ROOT / "BENCH_steiner.json"
OUT_ROOFLINE = Path(__file__).resolve().parent / "results" / "perf"

MODES = ("dense", "bucket", "frontier", "pallas")


# ----------------------------------------------------------------------------
# --bench handle: cold trace vs prepared-handle solve
# ----------------------------------------------------------------------------


def run_handle_bench(args) -> None:
    import numpy as np

    from repro import obs
    from repro.core.graph import from_edges
    from repro.data.graphs import rmat_edges, select_seeds
    from repro.solver import SolverConfig, SteinerSolver, trace_count

    if args.trace or args.metrics:
        # spans/metrics record the run; telemetry itself always rides the
        # loops (SolverConfig.telemetry_rounds), so enabling obs changes
        # no executables — the retrace assertions below still hold
        obs.enable(trace=args.trace is not None,
                   metrics=args.metrics is not None)
    rng_seed = args.seed
    t0 = time.perf_counter()
    if args.store:
        # benchmark solves straight off a memmapped .gstore (built with
        # `python -m repro.graphstore build`): prepare() materializes /
        # ELL-builds from disk, so t_prepare includes the load
        from repro.graphstore import open_store

        g = open_store(args.store, verify=False)
        n, m = g.n, g.m
        graph_desc = f"gstore:{args.store}"
    else:
        src, dst, w, n = rmat_edges(
            args.scale, args.edge_factor, max_weight=100, seed=rng_seed
        )
        g = from_edges(src, dst, w, n, pad_to=8)
        m = int(g.num_edges)
        graph_desc = f"rmat_scale{args.scale}_ef{args.edge_factor}"
    t_build = time.perf_counter() - t0
    print(
        f"graph: {graph_desc} n={n} directed_edges={m} build={t_build:.2f}s",
        flush=True,
    )

    # one fixed |S| per run: every mode sees identical queries (uniform —
    # the only strategy that needs no edge arrays on the --store path)
    empty = np.zeros(0, np.int32)
    seed_sets = [
        select_seeds(n, empty, empty, args.num_seeds, strategy="uniform",
                     seed=1000 + q)
        for q in range(args.queries)
    ]

    first_out = {}  # name -> cold-solve SolveOutput (flight dump source)

    def bench_row(name, cfg, mesh_stats=False):
        """One prepare → cold solve → warm-loop measurement (shared by
        the single-backend and mesh rows so every BENCH row is measured
        identically).  ``mesh_stats`` adds the paper's Fig. 5/6
        messages/relaxations counters from the distributed result."""
        t0 = time.perf_counter()
        handle = SteinerSolver(cfg).prepare(g)
        t_prepare = time.perf_counter() - t0

        c0 = trace_count()
        t0 = time.perf_counter()
        first = handle.solve(seed_sets[0])
        t_cold = time.perf_counter() - t0
        first_out[name] = first

        lat = []
        for s in seed_sets:
            t0 = time.perf_counter()
            out = handle.solve(s)
            lat.append(time.perf_counter() - t0)
        assert out.total_distance > 0
        retraces = trace_count() - c0 - 1  # the cold solve traces once
        lat_ms = np.asarray(lat) * 1e3
        row = {
            "prepare_s": round(t_prepare, 4),
            "cold_solve_s": round(t_cold, 4),
            "warm_p50_ms": float(np.percentile(lat_ms, 50)),
            "warm_p99_ms": float(np.percentile(lat_ms, 99)),
            "cold_over_warm": round(t_cold * 1e3 / float(np.median(lat_ms)), 1),
            "retraces_after_cold": int(retraces),
            "total_distance_q0": float(first.total_distance),
        }
        extra = ""
        if mesh_stats:
            # uniform SolveOutput.telemetry (Python ints) — no more
            # digging backend-native f32 counters out of .raw
            t = first.telemetry
            row["iterations_q0"] = int(t.iterations)
            row["relaxations_q0"] = float(t.relaxations)
            row["messages_q0"] = float(t.messages)
            extra = (
                f"messages={row['messages_q0']:.3e} "
                f"relaxations={row['relaxations_q0']:.3e} "
            )
        print(
            f"mode={name:13s} prepare={row['prepare_s']:7.3f}s "
            f"cold={row['cold_solve_s']:6.3f}s "
            f"warm_p50={row['warm_p50_ms']:7.2f}ms "
            f"cold/warm={row['cold_over_warm']:6.1f}x "
            f"{extra}retraces={retraces}",
            flush=True,
        )
        return row

    mode_rows = {}
    for mode in MODES:
        mode_rows[mode] = bench_row(mode, SolverConfig(backend="single", mode=mode))

    # --- mesh1d rows: the distributed schedules on a (1, 1) mesh, with
    # the messages/relaxations counters (paper Fig. 5/6 work metrics)
    mesh_specs = {
        "mesh_bucket": SolverConfig(
            backend="mesh1d", mode="bucket", mesh_shape=(1, 1),
            telemetry_per_rank=args.per_rank,
        ),
        "mesh_frontier": SolverConfig(
            backend="mesh1d", mode="frontier", mesh_shape=(1, 1),
            ell_width=32, frontier_size=256,
            telemetry_per_rank=args.per_rank,
        ),
    }
    for name, cfg in mesh_specs.items():
        mode_rows[name] = bench_row(name, cfg, mesh_stats=True)
    # the acceptance contract: identical tree, strictly less message work
    fr, bk = mode_rows["mesh_frontier"], mode_rows["mesh_bucket"]
    assert fr["total_distance_q0"] == bk["total_distance_q0"], (fr, bk)
    assert fr["messages_q0"] < bk["messages_q0"], (fr, bk)
    print(
        f"mesh frontier/bucket message ratio: "
        f"{fr['messages_q0'] / bk['messages_q0']:.3f}"
    )

    import jax

    record = {
        "bench": "steiner",
        "workload": {
            "graph": graph_desc,
            "n_vertices": int(n),
            "n_directed_edges": int(m),
            "num_seeds": args.num_seeds,
            "queries": args.queries,
            "backend": "single + mesh1d(1,1)",
            "seed": rng_seed,
        },
        "env": {
            "platform": platform.platform(),
            "backend": jax.default_backend(),
        },
        "modes": mode_rows,
    }
    OUT_HANDLE.write_text(json.dumps(record, indent=1))
    print(f"wrote {OUT_HANDLE}")
    if args.trace:
        obs.export_chrome_trace(args.trace)
        print(f"wrote {args.trace}")
    if args.metrics:
        Path(args.metrics).write_text(obs.prometheus_text())
        print(f"wrote {args.metrics}")
    if args.flight:
        from repro.obs import flight as flightmod

        t = first_out["mesh_frontier"].telemetry
        if t is None or t.per_rank is None:
            raise SystemExit("--flight requires --per-rank")
        flightmod.dump_flight(
            args.flight,
            t.per_rank,
            label="mesh1d/frontier",
            per_round=t.per_round,
            extra={"graph": graph_desc, "num_seeds": args.num_seeds},
        )
        print(f"wrote {args.flight}")


# ----------------------------------------------------------------------------
# --bench roofline: production-mesh variant hillclimb
# ----------------------------------------------------------------------------


def run_variant(cell: str, name: str, **cfg_kw) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_arch
    from repro.configs.steiner import solver_preset
    from repro.core.dist_steiner import DistSteinerConfig, make_dist_steiner
    from repro.core.dist_steiner_2d import make_dist_steiner_2d
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=False)
    arch = get_arch("steiner")
    shape = [s for s in arch.shapes if s.name == cell][0]
    preset = solver_preset(cell)
    dp = ("data",)
    n_blocks = mesh.shape["model"]
    n_rep = mesh.shape["data"]
    n, e, S = shape.n_nodes, shape.n_edges, shape.batch
    nb = -(-(-(-n // n_blocks)) // 8) * 8
    eb = -(-e // (n_rep * n_blocks) // 8 + 1) * 8
    total_e = n_rep * n_blocks * eb
    partition_2d = cfg_kw.pop("partition_2d", False)
    base = dict(
        mode=preset.mode, mst_algo=preset.mst_algo, max_iters=10_000
    )
    base.update(cfg_kw)
    cfg = DistSteinerConfig(n=n, nb=nb, num_seeds=S, **base)
    with jax.set_mesh(mesh):
        if partition_2d:
            nf = -(-(-(-n // (n_rep * n_blocks))) // 8) * 8
            fn = make_dist_steiner_2d(
                mesh, n=n, nf=nf, num_seeds=S, max_iters=10_000,
                mode=preset.mode, mst_algo=preset.mst_algo,
            )
        else:
            fn = make_dist_steiner(mesh, cfg, replica_axes=dp)
        espec = NamedSharding(mesh, P(("data", "model")))
        rep = NamedSharding(mesh, P())
        lowered = fn.lower(
            jax.ShapeDtypeStruct((total_e,), jnp.int32, sharding=espec),
            jax.ShapeDtypeStruct((total_e,), jnp.int32, sharding=espec),
            jax.ShapeDtypeStruct((total_e,), jnp.float32, sharding=espec),
            jax.ShapeDtypeStruct((S,), jnp.int32, sharding=rep),
        )
        compiled = lowered.compile()
    roof = rl.analyze(compiled, model_flops_total=5.0 * e, n_chips=256)
    mem = rl.memory_report(compiled)
    ls = base.get("local_steps", 1)
    row = roof.row()
    row["wire_bytes_per_relax_pass"] = roof.bytes_wire / ls
    row["t_total_per_relax_pass"] = (
        max(roof.t_compute, roof.t_memory) / 1  # compute/memory scale with ls
        + roof.t_collective / ls
    )
    return {"variant": name, "cfg": cfg_kw, "roofline": row,
            "peak_gb": mem["peak_est_gb"]}


def run_roofline_bench(args) -> None:
    variants = {
        "base": {},
        "unfused": dict(fuse_gather=False),
        "lab_i16": dict(lab_i16=True),
        "ls2": dict(local_steps=2),
        "ls4": dict(local_steps=4),
        "ls2_i16": dict(local_steps=2, lab_i16=True),
        "boruvka": dict(mst_algo="boruvka"),
        "2d": dict(partition_2d=True),
    }
    OUT_ROOFLINE.mkdir(parents=True, exist_ok=True)
    rows = []
    for name in args.variants.split(","):
        r = run_variant(args.cell, name, **variants[name])
        rows.append(r)
        rr = r["roofline"]
        print(
            f"{name:10s} t_c={rr['t_compute_s']:.3e} t_m={rr['t_memory_s']:.3e} "
            f"t_x={rr['t_collective_s']:.3e} wire={rr['bytes_wire']:.3e} "
            f"wire/relax={rr['wire_bytes_per_relax_pass']:.3e} "
            f"peak={r['peak_gb']:.1f}GB",
            flush=True,
        )
    (OUT_ROOFLINE / f"steiner_{args.cell}.json").write_text(
        json.dumps(rows, indent=1)
    )


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="handle", choices=("handle", "roofline"))
    # handle bench
    ap.add_argument("--scale", type=int, default=10, help="RMAT n = 2^scale")
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--store", default=None, metavar="PATH",
                    help="benchmark solves off a memmapped .gstore instead "
                         "of building the RMAT graph in RAM")
    ap.add_argument("--num-seeds", type=int, default=16)
    ap.add_argument("--queries", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome trace (prepare/solve spans + "
                         "per-round convergence counters; Perfetto-loadable)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="dump obs metrics in Prometheus text format")
    ap.add_argument("--per-rank", action="store_true",
                    help="record the per-rank flight buffer on the mesh "
                         "rows (SolverConfig.telemetry_per_rank)")
    ap.add_argument("--flight", default=None, metavar="PATH",
                    help="dump the mesh_frontier flight recording as JSON "
                         "(for `python -m repro.obs report`; needs "
                         "--per-rank)")
    # roofline bench
    ap.add_argument("--cell", default="ukw_1k")
    ap.add_argument("--variants", default="base,unfused,lab_i16,ls2,ls4,boruvka")
    args = ap.parse_args()
    if args.bench == "roofline":
        # must land before the first jax import in this process
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        run_roofline_bench(args)
    else:
        run_handle_bench(args)


if __name__ == "__main__":
    main()
