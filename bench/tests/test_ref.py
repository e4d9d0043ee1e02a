"""The plain reference against the program's own sequential oracle, and
the checks that decide ``correct`` against answers broken by hand."""

import numpy as np
import pytest

from bench.lib import check, graphs, ref, traffic as tmod

SPEC = {"generator": "graph500_rmat", "scale": 8, "edge_factor": 16, "a": 0.57,
        "b": 0.19, "c": 0.19, "max_weight": 100, "graph_seed": 21}


@pytest.fixture(scope="module")
def graph():
    return graphs.make_graph(SPEC, 21)


@pytest.mark.parametrize("k", [2, 5, 16, 40])
def test_reference_equals_the_sequential_oracle(graph, k):
    """Same tree and total as ``repro.core.ref.mehlhorn_ref``, written
    independently (the oracle is a heap Dijkstra and a dict pass)."""
    from repro.core.ref import mehlhorn_ref

    src, dst, w, n, comp = graph
    g = ref.Graph.from_edges(src, dst, w, n)
    rng = np.random.default_rng(k)
    for _ in range(3):
        seeds = rng.choice(comp, size=k, replace=False)
        edges, total = mehlhorn_ref(n, list(zip(src.tolist(), dst.tolist(), w.tolist())),
                                    seeds.tolist())
        r = ref.steiner(g, seeds)
        assert r.total == total
        assert r.edges == edges
        assert r.num_edges == len(edges)


def test_round_bf16_matches_ml_dtypes():
    import ml_dtypes

    x = np.random.default_rng(0).uniform(0, 5000, 10000).astype(np.float32)
    x[:4] = [257.0, 259.0, 0.0, np.inf]
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.array_equal(ref.round_bf16(x), want)


def _host_tree(g, seeds):
    r = ref.steiner(g, seeds)
    return check.HostTree(
        seeds=np.asarray(seeds), dist=r.dist.astype(np.float32), lab=r.lab, pred=r.pred,
        path_edge=r.path_edge, bridge_u=r.bridge_u, bridge_v=r.bridge_v,
        bridge_w=r.bridge_w, bridge_valid=np.ones(r.bridge_u.size, bool), total=r.total,
        num_edges=r.num_edges)


def test_checks_read_zero_on_the_reference_and_catch_faults(graph):
    src, dst, w, n, comp = graph
    g = ref.Graph.from_edges(src, dst, w, n)
    stream = tmod.ClosedStream({"seeds_per_query": 16}, comp, 3)
    good = [_host_tree(g, stream.next()) for _ in range(3)]
    values = check.compare_solve(g, good)
    assert values == dict(voronoi_bad=0, tree_diff=0, total_gap=0.0, tree_invalid=0)
    assert check.verdict(values)[0]

    bad = _host_tree(g, good[0].seeds)
    bad.total += 1.0  # an answer altered where it is produced
    assert not check.verdict(check.compare_solve(g, [bad]))[0]

    bad = _host_tree(g, good[0].seeds)
    v = np.flatnonzero(bad.path_edge)[0]
    bad.dist = bad.dist.copy()
    bad.dist[v] += 1  # one distance off
    values = check.compare_solve(g, [bad])
    assert values["voronoi_bad"] == 1 and not check.verdict(values)[0]

    served = [(t.seeds, t.total, t.num_edges) for t in good]
    assert check.verdict(check.compare_served(g, served))[0]
    served[1] = (served[1][0], served[1][1], served[1][2] + 1)
    assert not check.verdict(check.compare_served(g, served))[0]
