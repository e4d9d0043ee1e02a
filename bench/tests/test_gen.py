"""The generators: the same seed gives the same graph and stream, another
seed another; every seed gets the same work, under other names and, in an
open mix, in another order."""

import hashlib

import numpy as np
import pytest

from bench.lib import graphs, traffic as tmod

SPEC = {"generator": "graph500_rmat", "scale": 9, "edge_factor": 16, "a": 0.57,
        "b": 0.19, "c": 0.19, "max_weight": 100, "graph_seed": 9}
OPEN = {"loop": "open", "rate_qps": 9.0, "sizes": {"law": "log_uniform", "min": 3, "max": 64}}
BIG = 2**31 + 12345  # seeds past 32 signed bits


def same_graph_renamed(spec, a, c):
    """``a`` and ``c``, two seeds' graphs of ``spec``, are one graph under
    other names, and a closed loop sends them the same vertices."""
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[4], c[4])
    assert np.array_equal(a[2], c[2])
    name = np.zeros(a[3], np.int64)
    name[a[4]] = c[4]  # a's id of a component vertex -> c's id of it
    assert np.array_equal(name[a[0][np.isin(a[0], a[4])]], c[0][np.isin(c[0], c[4])])
    mix = {"seeds_per_query": 16}
    qa = tmod.ClosedStream(mix, a[4], spec["graph_seed"]).next()
    assert np.array_equal(name[qa], tmod.ClosedStream(mix, c[4], spec["graph_seed"]).next())


def test_graph_is_a_function_of_the_seed():
    a = graphs.make_graph(SPEC, BIG)
    b = graphs.make_graph(SPEC, BIG)
    c = graphs.make_graph(SPEC, BIG + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    # the same graph under other names: the seed only permutes the ids
    same_graph_renamed(SPEC, a, c)
    other = graphs.make_graph(dict(SPEC, graph_seed=10), BIG)
    assert not np.array_equal(np.sort(a[2]), np.sort(other[2]))
    src, dst, w, n, _ = a
    assert n == 512 and src.dtype == np.int32 and w.dtype == np.float32
    assert np.all(src != dst) and w.min() >= 1 and w.max() <= 100
    assert np.all(w == np.round(w))
    # Graph500 edge factor 16 before self-loops are dropped; no connecting path
    assert 0.95 * 16 * n < src.size <= 16 * n


def digest(graph) -> str:
    src, dst, w, _, comp = graph
    h = hashlib.sha256()
    for x in (src, dst, w, comp):
        h.update(x.dtype.str.encode())
        h.update(np.ascontiguousarray(x).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,want", [
    (5, "681756cc9d04883c84b202b0a3a111fd44cba96b20b2d0f8949d6b7498b59b13"),
    (BIG, "476c759212c36f332e367b25ff0e6b45d6888e4edb9cc1990b385e35122c7915"),
])
@pytest.mark.parametrize("blocks", [None, 1])
def test_default_ids_are_pinned(seed, want, blocks):
    """Without ``id_blocks`` (or with 1) the arrays are those the cells
    measured before the key existed, byte for byte."""
    spec = SPEC if blocks is None else dict(SPEC, id_blocks=blocks)
    assert digest(graphs.make_graph(spec, seed)) == want


def test_id_blocks_fix_each_vertex_block():
    n, blocks = 512, 4
    nb = n // blocks
    pa = graphs.id_permutation(n, blocks, SPEC["graph_seed"], BIG)
    pb = graphs.id_permutation(n, blocks, SPEC["graph_seed"], 5)
    assert np.array_equal(np.sort(pa), np.arange(n)) and np.array_equal(np.sort(pb), np.arange(n))
    assert np.array_equal(pa // nb, pb // nb) and not np.array_equal(pa, pb)
    # the blocks follow graph_seed
    other = graphs.id_permutation(n, blocks, SPEC["graph_seed"] + 1, BIG)
    assert not np.array_equal(pa // nb, other // nb)
    # every block gets the same edges by destination, both directions, on every seed
    counts = []
    for seed in (BIG, 5):
        src, dst, *_ = graphs.make_graph(dict(SPEC, id_blocks=blocks), seed)
        counts.append(np.bincount(np.concatenate([src, dst]) // nb, minlength=blocks))
    assert np.array_equal(counts[0], counts[1]) and counts[0].sum() > 0


def test_id_blocks_same_graph_renamed():
    spec = dict(SPEC, id_blocks=4)
    a = graphs.make_graph(spec, BIG)
    assert all(np.array_equal(x, y) for x, y in zip(a, graphs.make_graph(spec, BIG)))
    c = graphs.make_graph(spec, BIG + 1)
    same_graph_renamed(spec, a, c)
    # the component's vertices keep their block under both seeds' names
    assert np.array_equal(a[4] // (a[3] // 4), c[4] // (c[3] // 4))


@pytest.mark.parametrize("blocks", [3, 0, -4])
def test_id_blocks_must_divide_the_ids(blocks):
    with pytest.raises(ValueError):
        graphs.make_graph(dict(SPEC, id_blocks=blocks), 5)


def test_id_blocks_fix_the_mesh_partition():
    """The mesh engine's partition over four vertex blocks pads every chip
    to the same edge count ``eb`` on every seed, and gives each chip the
    same edges."""
    from repro.core.dist_steiner import partition_edges

    spec = dict(SPEC, id_blocks=4)
    parts = []
    for seed in (BIG, 5, 2**33 + 1):
        src, dst, w, n, _ = graphs.make_graph(spec, seed)
        parts.append(partition_edges(src, dst, w, n, n_replica=1, n_blocks=4))
    assert len({p.eb for p in parts}) == 1 and len({p.nb for p in parts}) == 1
    real = [np.isfinite(p.w.reshape(4, -1)).sum(axis=1) for p in parts]
    assert all(np.array_equal(real[0], r) for r in real)
    assert parts[0].nb == 128


def test_largest_component():
    import networkx as nx

    src, dst, _, n, comp = graphs.make_graph(SPEC, 5)
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(src.tolist(), dst.tolist()))
    want = max(nx.connected_components(g), key=len)
    assert set(comp.tolist()) == want and np.unique(comp).size == comp.size
    assert comp.size < n  # Graph500 graphs have isolated vertices


def test_closed_stream_is_a_function_of_the_seed():
    comp = np.arange(1000, 3000, dtype=np.int32)
    mix = {"loop": "closed", "seeds_per_query": 16}
    a = tmod.ClosedStream(mix, comp, BIG)
    b = tmod.ClosedStream(mix, comp, BIG)
    c = tmod.ClosedStream(mix, comp, BIG + 1)
    qa = [a.next() for _ in range(5)]
    assert all(np.array_equal(x, b.next()) for x in qa)
    assert not np.array_equal(qa[0], c.next())
    assert all(np.unique(q).size == 16 and np.isin(q, comp).all() for q in qa)
    warm = tmod.ClosedStream(mix, comp, BIG, tmod.STREAM_WARMUP).next()
    assert not np.array_equal(warm, qa[0])


def test_open_schedule_same_work_other_order():
    comp = np.arange(0, 5000, dtype=np.int32)
    due, q = tmod.open_arrivals(OPEN, 45, comp, BIG, 9)
    due2, q2 = tmod.open_arrivals(OPEN, 45, comp, BIG, 9)
    due3, q3 = tmod.open_arrivals(OPEN, 45, comp, BIG + 1, 9)
    assert np.array_equal(due, due2) and all(np.array_equal(a, b) for a, b in zip(q, q2))
    assert len(due) == round(9.0 * 45) == len(due3) == len(q) == len(q3)
    assert np.all(np.diff(due) > 0) and 0 < due[0] and due[-1] < 45
    assert np.all(np.diff(due3) > 0) and due3[-1] < 45
    # the same gaps in another order: the last arrival falls at the same time
    assert np.isclose(due[-1], due3[-1]) and not np.allclose(due, due3)
    # the same seed sets in another order
    key = sorted(tuple(x.tolist()) for x in q)
    assert key == sorted(tuple(x.tolist()) for x in q3)
    assert not all(np.array_equal(a, b) for a, b in zip(q, q3))


def test_size_law_covers_every_bucket():
    k = tmod.size_quantiles(OPEN["sizes"], 405)
    assert k.min() == 3 and k.max() == 64
    for lo, hi in ((3, 8), (9, 16), (17, 32), (33, 64)):
        assert np.any((k >= lo) & (k <= hi))
    # log-uniform: as many sizes in [3, 8) as in [24, 64)
    assert abs(np.sum(k < 8) - np.sum(k >= 24)) < 0.1 * k.size


def test_open_queries_never_repeat_and_follow_the_seed():
    comp = np.arange(0, 50, dtype=np.int32)
    small = {"law": "log_uniform", "min": 3, "max": 3}
    q = tmod.open_pool({"sizes": small}, 300, comp, BIG)
    keys = {tuple(np.sort(x).tolist()) for x in q}
    assert len(keys) == 300
    q2 = tmod.open_pool({"sizes": small}, 300, comp, BIG)
    q3 = tmod.open_pool({"sizes": small}, 300, comp, BIG + 1)
    assert all(np.array_equal(a, b) for a, b in zip(q, q2))
    assert not all(np.array_equal(a, b) for a, b in zip(q, q3))


def test_check_sample():
    assert np.array_equal(tmod.check_sample(5, {}, 1), np.arange(5))
    s = tmod.check_sample(400, {"check_sample": 128}, BIG)
    assert s.size == 128 and np.unique(s).size == 128
    assert np.array_equal(s, tmod.check_sample(400, {"check_sample": 128}, BIG))
