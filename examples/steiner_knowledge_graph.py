"""End-to-end distributed driver: interactive seed exploration at scale.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/steiner_knowledge_graph.py

The paper's motivating workflow (§I): a network scientist repeatedly asks
for the relationship structure between sets of entities in a knowledge
graph.  This driver uses the unified solver's ``"mesh1d"`` backend:

  1. ``SteinerSolver.prepare(g)`` partitions the scale-free graph across
     a (data × model) mesh with the paper's dst-block layout and places
     the edge shards on devices — ONCE,
  2. repeated ``handle.solve(seeds)`` calls answer seed-set queries with
     the distributed pipeline (async-amortized local-steps relaxation,
     Δ-bucket prioritization), reusing one compiled executable per |S|,
  3. prints per-query runtime, tree size, message statistics.
"""

import time


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    ndev = len(jax.devices())
    shapes = {1: (1, 1), 2: (1, 2), 4: (2, 2), 8: (2, 4)}
    mesh_shape = shapes.get(ndev, (2, ndev // 2))
    print(f"mesh: {dict(zip(('data', 'model'), mesh_shape))} on {ndev} devices")

    from repro.core import ref
    from repro.core.graph import from_edges
    from repro.data.graphs import rmat_edges, select_seeds
    from repro.solver import SolverConfig, SteinerSolver

    src, dst, w, n = rmat_edges(13, 8, max_weight=500, seed=11)
    print(f"graph: {n} vertices, {2 * len(src)} directed edges")

    solver = SteinerSolver(
        SolverConfig(
            backend="mesh1d",
            mode="bucket",
            mst_algo="prim",
            local_steps=2,
            mesh_shape=mesh_shape,
        )
    )
    t0 = time.time()
    handle = solver.prepare(from_edges(src, dst, w, n))
    part = handle.artifact("part")
    print(
        f"prepared in {time.time() - t0:.1f}s "
        f"({handle.preprocessing}; block={part.nb} vertices, "
        f"{part.eb} edges/device)"
    )

    edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
    for qi, (k, strat) in enumerate([(8, "uniform"), (64, "bfs_level"),
                                     (256, "bfs_level")]):
        seeds = select_seeds(n, src, dst, k, strategy=strat, seed=100 + qi)
        t0 = time.time()
        out = handle.solve(seeds)
        r = out.raw
        dt = time.time() - t0
        print(
            f"query {qi}: |S|={k:4d} ({strat:9s}) → D={out.total_distance:9.0f} "
            f"|E_S|={out.num_edges:5d} rounds={r.iterations:3d} "
            f"msgs={r.messages:9.0f} [{dt:5.1f}s incl. compile]"
        )
        if k <= 64:  # verify small queries against the oracle
            _, d_ref = ref.mehlhorn_ref(n, edges, seeds.tolist())
            assert abs(out.total_distance - d_ref) < 1e-3, (out.total_distance, d_ref)
            print(f"         verified against sequential Mehlhorn (D={d_ref:.0f})")

    # a repeated |S| hits the handle's executable cache — no re-trace
    seeds = select_seeds(n, src, dst, 64, strategy="uniform", seed=999)
    t0 = time.time()
    out = handle.solve(seeds)
    print(
        f"repeat |S|=64 (warm executable): D={out.total_distance:.0f} "
        f"[{time.time() - t0:.2f}s; {handle.num_executables} cached executables]"
    )


if __name__ == "__main__":
    main()
