"""Subprocess body for multi-device distributed-Steiner tests.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8. Exits nonzero
on any mismatch; prints one OK line per case.
"""

import numpy as np


def main() -> None:
    import jax

    assert len(jax.devices()) == 8, jax.devices()

    from repro.core import ref
    from repro.core.dist_steiner import partition_edges, run_dist_steiner
    from repro.data.graphs import er_edges, rmat_edges
    from repro.launch.mesh import make_mesh

    mesh2 = make_mesh((2, 4), ("data", "model"))
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))

    for trial in range(4):
        if trial % 2 == 0:
            src, dst, w, n = er_edges(50, 0.1, max_weight=9, seed=trial)
        else:
            src, dst, w, n = rmat_edges(6, 6, max_weight=20, seed=trial)
        rng = np.random.default_rng(trial)
        sd = rng.choice(n, size=6, replace=False).astype(np.int32)
        edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
        t_ref, d_ref = ref.mehlhorn_ref(n, edges, sd.tolist())

        # single-pod mesh, bucket mode, fused gather
        part = partition_edges(src, dst, w, n, n_replica=2, n_blocks=4)
        r = run_dist_steiner(mesh2, part, sd, mode="bucket")
        assert abs(r.total_distance - d_ref) < 1e-4, (r.total_distance, d_ref)
        assert r.edge_set() == t_ref

        # multi-pod mesh, dense mode, local-steps + chunked pair collectives.
        # Borůvka may break G'1 MST ties differently from Prim, yielding a
        # different (sometimes cheaper, never worse-bounded) valid tree —
        # so assert validity + bound instead of edge equality.
        part3 = partition_edges(src, dst, w, n, n_replica=4, n_blocks=2)
        r3 = run_dist_steiner(
            mesh3,
            part3,
            sd,
            replica_axes=("pod", "data"),
            mode="dense",
            local_steps=3,
            pair_chunks=4,
            mst_algo="boruvka",
        )
        assert ref.tree_is_valid(n, edges, sd.tolist(), r3.edge_set())
        opt = ref.dreyfus_wagner(n, edges, sd.tolist())
        bound = 2.0 * (1.0 - 1.0 / len(sd)) * opt + 1e-4
        assert opt - 1e-4 <= r3.total_distance <= bound, (r3.total_distance, opt)
        print(f"OK trial={trial} D={d_ref} iters2={r.iterations} iters3={r3.iterations}")

    # 2D (src×dst) partition: bit-identical output (beyond-paper engine)
    from repro.core.dist_steiner_2d import partition_edges_2d, run_dist_steiner_2d

    src, dst, w, n = er_edges(60, 0.1, max_weight=15, seed=21)
    sd = np.random.default_rng(21).choice(n, size=6, replace=False).astype(np.int32)
    edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
    t_ref, d_ref = ref.mehlhorn_ref(n, edges, sd.tolist())
    p2 = partition_edges_2d(src, dst, w, n, R=2, C=4)
    r2 = run_dist_steiner_2d(mesh2, p2, sd, mode="bucket")
    assert abs(r2.total_distance - d_ref) < 1e-4, (r2.total_distance, d_ref)
    assert r2.edge_set() == t_ref
    print(f"OK 2D partition: D={r2.total_distance} rounds={r2.iterations}")

    # local-steps reduces global rounds (async amortization, paper §IV)
    src, dst, w, n = rmat_edges(8, 6, max_weight=50, seed=9)
    sd = np.random.default_rng(9).choice(n, size=8, replace=False).astype(np.int32)
    part = partition_edges(src, dst, w, n, n_replica=2, n_blocks=4)
    r1 = run_dist_steiner(mesh2, part, sd, mode="dense", local_steps=1)
    r4 = run_dist_steiner(mesh2, part, sd, mode="dense", local_steps=4)
    assert abs(r1.total_distance - r4.total_distance) < 1e-4
    assert r4.iterations <= r1.iterations, (r4.iterations, r1.iterations)
    print(f"OK local-steps: {r1.iterations} -> {r4.iterations} global rounds")

    # mesh-frontier (sharded-ELL prioritized schedule, paper §IV message
    # prioritization): bit-identical tree to Δ-bucket on a real 2×4 mesh,
    # with strictly fewer messages per solve
    from repro.core.graph import from_edges
    from repro.solver import SolverConfig, SteinerSolver, trace_count

    src, dst, w, n = rmat_edges(7, 6, max_weight=30, seed=13)
    sd = np.random.default_rng(13).choice(n, size=6, replace=False).astype(np.int32)
    edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
    t_ref, d_ref = ref.mehlhorn_ref(n, edges, sd.tolist())
    g = from_edges(src, dst, w, n, pad_to=8)
    outs = {}
    for mode in ("bucket", "frontier"):
        cfg = SolverConfig(
            backend="mesh1d", mode=mode, mesh_shape=(2, 4),
            ell_width=8, frontier_size=16,
        )
        outs[mode] = SteinerSolver(cfg).prepare(g).solve(sd)
        assert abs(outs[mode].total_distance - d_ref) < 1e-4, (
            mode, outs[mode].total_distance, d_ref,
        )
        assert outs[mode].raw.edge_set() == t_ref, mode
    mb, mf = outs["bucket"].raw.messages, outs["frontier"].raw.messages
    assert mf < mb, (mf, mb)
    print(f"OK mesh-frontier 2x4: D={d_ref} messages {mb:.0f} -> {mf:.0f}")

    # prepared frontier handle: same-|S| queries re-trace zero times, and
    # duplicate-seed padding (the serve planner contract) stays inert
    handle = SteinerSolver(cfg).prepare(g)
    base = handle.solve(sd)
    t0 = trace_count("mesh1d")
    roll = handle.solve(np.roll(sd, 2))
    assert trace_count("mesh1d") == t0, "same-|S| mesh solve re-traced"
    assert roll.total_distance == base.total_distance
    padded = np.concatenate([sd, np.full(3, sd[0], np.int32)])
    rp = handle.solve(padded)
    assert rp.total_distance == base.total_distance
    assert rp.num_edges == base.num_edges
    np.testing.assert_array_equal(
        np.asarray(rp.raw.dist), np.asarray(base.raw.dist)
    )
    assert rp.raw.edge_set() == base.raw.edge_set()
    print("OK mesh-frontier trace-once + inert dup-seed padding")

    # per-rank flight recorder on real 2×4 meshes (paper §VI measurement
    # granularity): the (rounds, 8, 4) buffer must sum bit-exactly to the
    # global channels, and enabling it must change nothing else — same
    # tree, same counters, no extra retraces
    from repro.obs import flight

    for backend, mode, mkcfg in (
        (
            "mesh1d", "frontier",
            lambda pr: SolverConfig(
                backend="mesh1d", mode="frontier", mesh_shape=(2, 4),
                ell_width=8, frontier_size=16, telemetry_per_rank=pr,
            ),
        ),
        (
            "mesh2d", "bucket",
            lambda pr: SolverConfig(
                backend="mesh2d", mode="bucket", mesh_shape=(2, 4),
                telemetry_per_rank=pr,
            ),
        ),
    ):
        base_out = SteinerSolver(mkcfg(False)).prepare(g).solve(sd)
        assert base_out.telemetry.per_rank is None
        c0 = trace_count(backend)
        h = SteinerSolver(mkcfg(True)).prepare(g)
        pr_out = h.solve(sd)
        pr_out2 = h.solve(np.roll(sd, 1))
        assert trace_count(backend) == c0 + 1, "per-rank solve re-traced"
        t = pr_out.telemetry
        assert t.per_rank is not None and t.per_rank.shape[1] == 8, (
            t.per_rank.shape
        )
        assert t.per_rank.shape[0] == t.per_round.shape[0]
        # bit-exact attribution: rank rows sum to the global channels
        flight.check_consistency(
            t.per_rank, t.per_round, label=f"{backend}/{mode}"
        )
        flight.check_consistency(
            pr_out2.telemetry.per_rank, pr_out2.telemetry.per_round,
            label=f"{backend}/{mode} q2",
        )
        # the knob is observability-only: identical tree and counters
        assert pr_out.raw.edge_set() == base_out.raw.edge_set()
        assert pr_out.total_distance == base_out.total_distance
        assert t.messages == base_out.telemetry.messages
        assert t.relaxations == base_out.telemetry.relaxations
        assert t.iterations == base_out.telemetry.iterations
        np.testing.assert_array_equal(t.per_round, base_out.telemetry.per_round)
        rep = flight.analyze(t.per_rank, label=f"{backend}/{mode}")
        assert rep.n_ranks == 8 and rep.rounds == t.per_round.shape[0]
        assert np.all(rep.imbalance >= 1.0 - 1e-12)
        print(
            f"OK per-rank 2x4 {backend}/{mode}: rounds={rep.rounds} "
            f"msg_skew={rep.message_skew:.2f} "
            f"straggler={rep.stragglers[0] if rep.stragglers else None}"
        )


if __name__ == "__main__":
    main()
