"""One benchmark per paper table/figure, CPU-sized.

Paper artifact → bench:
  Table I    (APSP vs Voronoi-cell runtime)        → bench_table1
  Fig. 3     (strong scaling, devices)             → bench_fig3 (1/2/4 devices)
  Fig. 4     (|S| sweep, runtime breakdown)        → bench_fig4
  Fig. 5/6   (FIFO vs priority queue, msgs)        → bench_fig56
  Fig. 7     (edge-weight range sensitivity)       → bench_fig7
  Table V    (seed-selection strategies)           → bench_table5
  Table VI   (vs sequential Mehlhorn / KMB)        → bench_table6
  Table VII  (approximation quality vs exact)      → bench_table7

Each returns a list of CSV rows: (name, us_per_call, derived).
"""

from __future__ import annotations

import time

import numpy as np


def _timeit(fn, *, reps=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6  # µs


def _graph(scale=12, ef=8, maxw=100, seed=0):
    from repro.data.graphs import rmat_edges

    return rmat_edges(scale, ef, max_weight=maxw, seed=seed)


def _seeds(n, src, dst, k, seed=0):
    from repro.data.graphs import select_seeds

    return select_seeds(n, src, dst, k, strategy="bfs_level", seed=seed)


def bench_table1():
    """APSP (scipy multi-source Dijkstra over all seed pairs) vs VC."""
    import jax.numpy as jnp
    import scipy.sparse.csgraph as csg

    from repro.core import from_edges
    from repro.core.ref import _min_csr
    from repro.core.voronoi import voronoi_cells

    rows = []
    src, dst, w, n = _graph(scale=12)
    edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
    g = from_edges(src, dst, w, n, pad_to=64)
    m = _min_csr(n, edges)
    for S in (10, 100, 1000):
        seeds = _seeds(n, src, dst, S, seed=1)
        t_apsp = _timeit(lambda: csg.dijkstra(m, indices=seeds), reps=1)
        sj = jnp.asarray(seeds)
        t_vc = _timeit(
            lambda: voronoi_cells(g, sj, mode="bucket")[0].dist.block_until_ready(),
            reps=1,
        )
        rows.append((f"table1/apsp_S{S}", t_apsp, f"n={n}"))
        rows.append((f"table1/voronoi_S{S}", t_vc, f"speedup={t_apsp / t_vc:.2f}x"))
    return rows


def bench_fig3():
    """Strong scaling: distributed pipeline on meshes over the first 1, 2
    and 4 devices of this process (counts the process lacks are skipped;
    on a CPU host, run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

    Everything runs in this one process: a chip belongs to the process
    that first touched it.  Derived column = speedup over 1 device.
    """
    import jax

    from repro.core.dist_steiner import partition_edges, run_dist_steiner
    from repro.launch.mesh import make_mesh

    src, dst, w, n = _graph(scale=13)
    seeds = _seeds(n, src, dst, 64, seed=1)
    devices = jax.devices()
    rows = []
    base = None
    for ndev, shape in ((1, (1, 1)), (2, (1, 2)), (4, (2, 2))):
        if ndev > len(devices):
            break
        mesh = make_mesh(shape, ("data", "model"), devices=devices[:ndev])
        part = partition_edges(
            src, dst, w, n, n_replica=shape[0], n_blocks=shape[1]
        )
        run_dist_steiner(mesh, part, seeds)  # warm (compile)
        t0 = time.perf_counter()
        r = run_dist_steiner(mesh, part, seeds)
        us = (time.perf_counter() - t0) * 1e6
        base = base or us
        rows.append(
            (f"fig3/ndev{ndev}", us,
             f"speedup={base / us:.2f}x D={r.total_distance} "
             f"iters={r.iterations}")
        )
    return rows


def bench_fig4():
    """Runtime vs |S| (10 → 1000), single device jit pipeline."""
    import jax.numpy as jnp

    from repro.core import from_edges, steiner_tree

    rows = []
    src, dst, w, n = _graph(scale=13)
    g = from_edges(src, dst, w, n, pad_to=64)
    for S in (10, 100, 1000):
        seeds = jnp.asarray(_seeds(n, src, dst, S, seed=2))
        res = steiner_tree(g, seeds, num_seeds=S)  # warm per-S shape
        t = _timeit(
            lambda: steiner_tree(g, seeds, num_seeds=S).tree.total_distance.block_until_ready(),
            reps=1,
        )
        rows.append(
            (f"fig4/S{S}", t,
             f"edges={int(res.tree.num_edges)} iters={int(res.stats.iterations)}")
        )
    return rows


def bench_fig56():
    """FIFO (dense) vs priority (bucket): runtime and message traffic.

    Two regimes: scale-free RMAT (low diameter — BSP rounds already dedup
    most redundant messages, so prioritization adds little) and a
    120×120 grid (high diameter — dense BF propagates many soon-corrected
    estimates; bucketing cuts generated messages >2×, the paper's Fig. 6
    effect). See EXPERIMENTS.md §Priority-queue-adaptation.
    """
    import jax.numpy as jnp

    from repro.core import from_edges
    from repro.core.voronoi import voronoi_cells
    from repro.data.graphs import grid_edges

    rows = []
    cases = {}
    src, dst, w, n = _graph(scale=13, maxw=1000, seed=4)
    cases["rmat"] = (from_edges(src, dst, w, n, pad_to=64),
                     jnp.asarray(_seeds(n, src, dst, 64, seed=4)))
    src, dst, w, n = grid_edges(120, 120, max_weight=1000, seed=1)
    rng = np.random.default_rng(0)
    cases["grid"] = (
        from_edges(src, dst, w, n, pad_to=64),
        jnp.asarray(rng.choice(n, 16, replace=False).astype(np.int32)),
    )
    for gname, (g, seeds) in cases.items():
        out = {}
        for mode in ("dense", "bucket"):
            st, stats = voronoi_cells(g, seeds, mode=mode)
            st.dist.block_until_ready()
            t = _timeit(
                lambda: voronoi_cells(g, seeds, mode=mode)[0].dist.block_until_ready(),
                reps=1,
            )
            out[mode] = (t, float(stats.messages), float(stats.relaxations))
            rows.append(
                (f"fig5/{gname}_{mode}", t,
                 f"messages={out[mode][1]:.0f} updates={out[mode][2]:.0f}")
            )
        rows.append(
            (f"fig6/{gname}_message_reduction", 0.0,
             f"priority_cuts_messages={out['dense'][1] / max(out['bucket'][1], 1):.2f}x")
        )
    return rows


def bench_fig7():
    """Edge-weight-range sensitivity of both queue modes."""
    import jax.numpy as jnp

    from repro.core import from_edges
    from repro.core.voronoi import voronoi_cells

    rows = []
    for maxw in (100, 1000, 10000):
        src, dst, w, n = _graph(scale=12, maxw=maxw, seed=5)
        g = from_edges(src, dst, w, n, pad_to=64)
        seeds = jnp.asarray(_seeds(n, src, dst, 64, seed=5))
        for mode in ("dense", "bucket"):
            _, stats = voronoi_cells(g, seeds, mode=mode)
            rows.append(
                (f"fig7/w{maxw}_{mode}", float(stats.iterations),
                 f"messages={float(stats.messages):.0f}")
            )
    return rows


def bench_table5():
    """Seed-selection strategies → tree size/distance (paper Table V)."""
    import jax.numpy as jnp

    from repro.core import from_edges, steiner_tree
    from repro.data.graphs import select_seeds

    rows = []
    src, dst, w, n = _graph(scale=12, seed=6)
    g = from_edges(src, dst, w, n, pad_to=64)
    for strat in ("bfs_level", "uniform", "eccentric", "proximate"):
        seeds = jnp.asarray(
            select_seeds(n, src, dst, 32, strategy=strat, seed=6)
        )
        res = steiner_tree(g, seeds)
        rows.append(
            (f"table5/{strat}", 0.0,
             f"D={float(res.tree.total_distance):.0f} edges={int(res.tree.num_edges)}")
        )
    return rows


def bench_table6():
    """Ours (jit, 1 device) vs sequential Mehlhorn and KMB references."""
    import jax.numpy as jnp

    from repro.core import from_edges, steiner_tree
    from repro.core import ref

    rows = []
    src, dst, w, n = _graph(scale=11, seed=7)
    edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
    g = from_edges(src, dst, w, n, pad_to=64)
    for S in (10, 100):
        seeds = _seeds(n, src, dst, S, seed=7)
        sj = jnp.asarray(seeds)
        steiner_tree(g, sj, num_seeds=S)  # warm
        t_ours = _timeit(
            lambda: steiner_tree(g, sj, num_seeds=S).tree.total_distance.block_until_ready(),
            reps=1,
        )
        t_meh = _timeit(lambda: ref.mehlhorn_ref(n, edges, seeds.tolist()), reps=1)
        t_kmb = _timeit(lambda: ref.kmb_ref(n, edges, seeds.tolist()), reps=1)
        rows.append((f"table6/ours_S{S}", t_ours, ""))
        rows.append(
            (f"table6/mehlhorn_S{S}", t_meh, f"ours_speedup={t_meh / t_ours:.1f}x")
        )
        rows.append(
            (f"table6/kmb_S{S}", t_kmb, f"ours_speedup={t_kmb / t_ours:.1f}x")
        )
    return rows


def bench_table7():
    """Approximation quality vs exact Dreyfus-Wagner (paper: mean 1.0527)."""
    import jax.numpy as jnp

    from repro.core import from_edges, steiner_tree
    from repro.core import ref
    from repro.data.graphs import er_edges

    ratios = []
    for trial in range(20):
        src, dst, w, n = er_edges(40 + trial, 0.12, max_weight=12, seed=trial)
        rng = np.random.default_rng(trial)
        seeds = rng.choice(n, size=6, replace=False).astype(np.int32)
        edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
        res = steiner_tree(from_edges(src, dst, w, n, pad_to=8), jnp.asarray(seeds))
        d = float(res.tree.total_distance)
        opt = ref.dreyfus_wagner(n, edges, seeds.tolist())
        ratios.append(d / opt)
    r = np.asarray(ratios)
    return [
        ("table7/approx_ratio_mean", 0.0,
         f"mean={r.mean():.4f} max={r.max():.4f} bound=2(1-1/6)={2 * (1 - 1 / 6):.3f}"),
        ("table7/error_pct", 0.0, f"{100 * (r.mean() - 1):.2f}%"),
    ]


def bench_frontier():
    """Beyond-paper: top-K compacted frontier (work-proportional priority).

    Verifies bit-identical Voronoi state vs dense BF and reports the
    edge-relaxation work cut (the §Perf memory-term lever).
    """
    import jax.numpy as jnp

    from repro.core import from_edges, to_ell
    from repro.core.voronoi import voronoi_cells, voronoi_cells_frontier
    from repro.data.graphs import grid_edges

    rows = []
    cases = {
        "rmat13": (_graph(scale=13, maxw=1000, seed=4), 64),
        "grid120": (grid_edges(120, 120, max_weight=1000, seed=1), 16),
    }
    for gname, ((src, dst, w, n), k) in cases.items():
        g = from_edges(src, dst, w, n, pad_to=64)
        seeds = jnp.asarray(_seeds(n, src, dst, k, seed=4))
        st_d, sd = voronoi_cells(g, seeds, mode="dense")
        dense_work = float(jnp.sum(jnp.isfinite(g.w))) * float(sd.iterations)
        ell = to_ell(g, k=32, pad_rows_to=64)
        st_f, sf = voronoi_cells_frontier(ell, seeds, frontier_size=512)
        match = bool(
            jnp.array_equal(st_d.dist, st_f.dist)
            & jnp.array_equal(st_d.lab, st_f.lab)
        )
        rows.append(
            (f"frontier/{gname}", float(sf.iterations),
             f"match={match} work_cut={dense_work / float(sf.messages):.1f}x")
        )
    return rows
