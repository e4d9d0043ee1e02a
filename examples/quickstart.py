"""Quickstart: 2-approximate Steiner minimal tree on a scale-free graph.

    PYTHONPATH=src python examples/quickstart.py

Builds an RMAT graph (the paper's evaluation family), picks seeds with the
paper's BFS-level strategy, solves through the unified solver API
(``SolverConfig → SteinerSolver.prepare → handle.solve``), and verifies
the result against the sequential Mehlhorn oracle.
"""

from repro.compile_cache import enable_compile_cache
from repro.core import ref, tree_edge_list
from repro.core.graph import from_edges
from repro.data.graphs import rmat_edges, select_seeds
from repro.solver import SolverConfig, SteinerSolver


def main() -> None:
    enable_compile_cache()
    # 1) a weighted scale-free graph (paper Table III family)
    src, dst, w, n = rmat_edges(12, 8, max_weight=100, seed=42)
    print(f"graph: {n} vertices, {2 * len(src)} directed edges")

    # 2) seed vertices (paper §V: BFS-level stratified selection)
    seeds = select_seeds(n, src, dst, 32, strategy="bfs_level", seed=7)
    print(f"seeds: {len(seeds)} vertices, e.g. {seeds[:6].tolist()}")

    # 3) the paper's Alg. 2 through the unified solver: preprocessing
    #    happens once in prepare(); solve() hits a cached executable
    g = from_edges(src, dst, w, n, pad_to=64)
    solver = SteinerSolver(SolverConfig(backend="single", mode="bucket"))
    handle = solver.prepare(g)
    out = handle.solve(seeds)
    res = out.raw
    print(
        f"Steiner tree: D(G_S) = {out.total_distance:.0f}, "
        f"|E_S| = {out.num_edges}, "
        f"{int(res.stats.iterations)} relaxation rounds, "
        f"{float(res.stats.messages):.0f} generated messages"
    )

    # 3b) repeated queries reuse the compiled executable — no re-trace
    seeds2 = select_seeds(n, src, dst, 32, strategy="uniform", seed=8)
    out2 = handle.solve(seeds2)
    print(f"second query (warm executable): D(G_S) = {out2.total_distance:.0f}")

    # 4) cross-check against the sequential Mehlhorn reference
    edges = list(zip(src.tolist(), dst.tolist(), w.tolist()))
    t_ref, d_ref = ref.mehlhorn_ref(n, edges, seeds.tolist())
    assert abs(out.total_distance - d_ref) < 1e-3, (out.total_distance, d_ref)
    assert tree_edge_list(res.state, res.tree) == t_ref
    print(f"matches sequential Mehlhorn reference exactly (D = {d_ref:.0f})")

    # 5) seeds all connected, tree is valid
    assert ref.tree_is_valid(n, edges, seeds.tolist(), t_ref)
    print("tree validity: OK (acyclic, connected, spans all seeds)")


if __name__ == "__main__":
    main()
