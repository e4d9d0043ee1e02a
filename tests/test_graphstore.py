"""repro.graphstore: on-disk round-trips, bounded-memory ingest, solver
parity off disk, partition/hub-sort alignment, manifest error handling.

The scale-18 bounded-memory ingest (the ISSUE acceptance bar) runs in
tier-1; the scale-20 tier is behind the ``slow`` marker.
"""

import json

import numpy as np
import pytest

from repro.core import from_edges
from repro.core.dist_steiner import partition_edges
from repro.core.dist_steiner_2d import partition_edges_2d
from repro.core.graph import to_ell
from repro.data.graphs import build_csr, er_edges, rmat_edges
from repro.graphstore import (
    ArraySource,
    ChecksumError,
    RmatEdgeSource,
    StoreFormatError,
    TsvEdgeSource,
    build_store,
    csr_from_chunks,
    hub_sort_store,
    open_store,
    partition_ell_store,
    partition_store,
    partition_store_2d,
)
from repro.graphstore.format import MANIFEST_NAME
from repro.solver import SolverConfig, SteinerSolver


def _canon_coo(g):
    """Padding-stripped, lexicographically sorted COO of a Graph."""
    s, d, w = np.asarray(g.src), np.asarray(g.dst), np.asarray(g.w)
    real = np.isfinite(w)
    s, d, w = s[real], d[real], w[real]
    o = np.lexsort((w, d, s))
    return s[o], d[o], w[o]


def _rmat_store(tmp_path, scale=8, ef=6, seed=3, **kw):
    path, stats = build_store(
        RmatEdgeSource(scale, ef, seed=seed, **kw), tmp_path / "g.gstore"
    )
    return open_store(path), stats


# ----------------------------------------------------------------------------
# round-trips
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("trial", range(3))
def test_roundtrip_bit_for_bit_vs_from_edges(tmp_path, trial):
    """ingest → store → to_graph() carries exactly from_edges' edges."""
    if trial == 0:
        src, dst, w, n = er_edges(60, 0.1, seed=trial)
    else:
        src, dst, w, n = rmat_edges(7, 5, seed=trial)
    path, _ = build_store(
        ArraySource(src, dst, w, n, chunk_edges=97), tmp_path / "g.gstore"
    )
    g_mem = from_edges(src, dst, w, n, symmetrize=True)
    g_store = open_store(path).to_graph()
    assert g_store.n == g_mem.n
    for a, b in zip(_canon_coo(g_store), _canon_coo(g_mem)):
        assert a.dtype == b.dtype
        assert np.array_equal(a, b)  # exact — no tolerance


def test_rmat_source_invariant_to_chunk_regrouping():
    def cat(source):
        chunks = list(source)
        return [np.concatenate([c[i] for c in chunks]) for i in range(3)]

    a = cat(RmatEdgeSource(7, 5, seed=9, chunk_edges=501))
    b = cat(RmatEdgeSource(7, 5, seed=9, chunk_edges=1 << 14))
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_rmat_blocks_drawn_on_threads_keep_stream_order():
    """Blocks drawn concurrently come out in block order: the stream
    equals drawing every block one after another."""
    source = RmatEdgeSource(9, 8, seed=5, block_edges=64, connect=False)
    perm = source._perm()
    spans = range(0, source.m_target, source.block_edges)
    want = [source._block(i, lo, lo + source.block_edges, perm)
            for i, lo in enumerate(spans)]
    assert len(want) > 32  # more blocks than one window of the pool
    got = list(source._blocks())
    assert len(got) == len(want)
    for a, b in zip(got, want):
        for x, y in zip(a, b):
            assert np.array_equal(x, y)


def test_rmat_edges_is_the_chunked_source_concatenated():
    src, dst, w, n = rmat_edges(7, 5, seed=11)
    chunks = list(RmatEdgeSource(7, 5, seed=11))
    assert np.array_equal(src, np.concatenate([c[0] for c in chunks]))
    assert np.array_equal(dst, np.concatenate([c[1] for c in chunks]))
    assert np.array_equal(w, np.concatenate([c[2] for c in chunks]))
    assert n == 1 << 7


def test_tsv_source(tmp_path):
    f = tmp_path / "edges.txt"
    f.write_text("# snap header\n0 1 2.5\n1 2\n2 0 7\n")
    src = TsvEdgeSource(f)
    assert src.n == 3
    path, stats = build_store(src, tmp_path / "t.gstore")
    store = open_store(path)
    assert store.m == 6  # symmetrized
    assert stats.weight_min == 1.0 and stats.weight_max == 7.0


def test_build_csr_matches_legacy_stable_sort():
    def legacy(n, src, dst):
        s, d = np.r_[src, dst], np.r_[dst, src]
        order = np.argsort(s, kind="stable")
        s, d = s[order], d[order]
        indptr = np.zeros(n + 1, np.int64)
        np.add.at(indptr, s + 1, 1)
        return np.cumsum(indptr), d.astype(np.int32)

    rng = np.random.default_rng(4)
    src = rng.integers(0, 40, 300)
    dst = rng.integers(0, 40, 300)
    indptr, indices = build_csr(40, src, dst)
    li, ld = legacy(40, src, dst)
    assert indptr.dtype == li.dtype and indices.dtype == ld.dtype
    assert np.array_equal(indptr, li)
    assert np.array_equal(indices, ld)


def test_csr_from_chunks_multi_chunk_weights():
    src, dst, w, n = er_edges(50, 0.15, seed=2)
    one = csr_from_chunks(n, ArraySource(src, dst, w, n, chunk_edges=10**9))
    # multi-chunk arrival interleaves rows differently but keeps the
    # same (indptr, per-row neighbor multiset)
    many = csr_from_chunks(n, ArraySource(src, dst, w, n, chunk_edges=37))
    assert np.array_equal(one[0], many[0])
    for v in range(n):
        lo, hi = one[0][v], one[0][v + 1]
        assert sorted(zip(one[1][lo:hi], one[2][lo:hi])) == sorted(
            zip(many[1][lo:hi], many[2][lo:hi])
        )


def test_ell_from_store_matches_to_ell(tmp_path):
    store, _ = _rmat_store(tmp_path)
    g = store.to_graph()
    a = store.ell(8, rows_per_chunk=13)
    b = to_ell(g, 8)
    for f in ("nbr", "wgt", "row2v"):
        assert np.array_equal(np.asarray(getattr(a, f)), np.asarray(getattr(b, f)))
    assert a.n == b.n


def _to_ell_loop(g, k, pad_rows_to=1):
    """The per-vertex loop ``to_ell`` once was: the reference layout."""
    src, dst, w = (np.asarray(x) for x in (g.src, g.dst, g.w))
    real = np.isfinite(w)
    src, dst, w = src[real], dst[real], w[real]
    order = np.argsort(src, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
    counts = np.bincount(src, minlength=g.n)
    n_rows = int(np.maximum(1, (counts + k - 1) // k).sum())
    rows = -(-n_rows // pad_rows_to) * pad_rows_to
    nbr = np.zeros((rows, k), np.int32)
    wgt = np.full((rows, k), np.inf, np.float32)
    row2v = np.zeros(rows, np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    r = 0
    for v in range(g.n):
        lo, hi = starts[v], starts[v + 1]
        for off in range(0, max(1, hi - lo), k):
            m = min(lo + off + k, hi) - (lo + off)
            nbr[r, :m] = dst[lo + off : lo + off + m]
            wgt[r, :m] = w[lo + off : lo + off + m]
            row2v[r] = v
            r += 1
    return nbr, wgt, row2v


@pytest.mark.parametrize("k,pad_rows_to", [(4, 1), (8, 64)])
def test_to_ell_matches_per_vertex_loop(k, pad_rows_to):
    src, dst, w, n = rmat_edges(8, 6, seed=4)
    g = from_edges(src, dst, w, n, pad_to=16)
    ell = to_ell(g, k, pad_rows_to=pad_rows_to)
    for got, want in zip((ell.nbr, ell.wgt, ell.row2v), _to_ell_loop(g, k, pad_rows_to)):
        assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("high", [1 << 12, 1 << 22])
def test_stable_argsort_is_numpy_stable_argsort(high):
    from repro.core.graph import stable_argsort

    keys = np.random.default_rng(high).integers(0, high, 50_000)
    assert np.array_equal(stable_argsort(keys), np.argsort(keys, kind="stable"))


def test_empty_chunks_are_skipped(tmp_path):
    class Gappy:
        n = 5
        describe = "gappy"

        def __iter__(self):
            e = np.zeros(0, np.int32)
            yield e, e, e.astype(np.float32)
            yield (np.array([0, 1], np.int32), np.array([1, 2], np.int32),
                   np.array([3.0, 4.0], np.float32))
            yield e, e, e.astype(np.float32)

    path, stats = build_store(Gappy(), tmp_path / "gap.gstore")
    store = open_store(path)
    assert store.m == 4 and stats.edges_in == 2


def test_tsv_indented_comment_skipped(tmp_path):
    f = tmp_path / "edges.txt"
    f.write_text("  # indented comment\n0 1\n\n  \n1 2\n")
    assert sum(c[0].shape[0] for c in TsvEdgeSource(f)) == 2


def test_empty_edge_source_builds_valid_empty_store(tmp_path):
    e = np.zeros(0, np.int32)
    path, stats = build_store(
        ArraySource(e, e, None, 4), tmp_path / "empty.gstore"
    )
    store = open_store(path)  # checksums of zero-byte arrays verify
    assert store.n == 4 and store.m == 0
    assert store.to_graph().num_edges == 0


def test_out_of_range_ids_rejected(tmp_path):
    with pytest.raises(ValueError, match="out of range"):
        build_store(
            ArraySource(np.array([0, 9]), np.array([1, 2]), None, 5),
            tmp_path / "bad.gstore",
        )


# ----------------------------------------------------------------------------
# bounded-memory ingest (acceptance bar: RMAT scale-18, capped chunk bytes)
# ----------------------------------------------------------------------------


def test_scale18_ingest_memory_bounded_by_chunk(tmp_path):
    chunk_edges = 1 << 16
    # raw bytes of one yielded chunk: (src i32 + dst i32 + w f32) per edge
    chunk_bytes_cap = chunk_edges * 12
    path, stats = build_store(
        RmatEdgeSource(18, 8, seed=0, chunk_edges=chunk_edges),
        tmp_path / "g18.gstore",
    )
    assert stats.n == 1 << 18
    assert stats.m_directed > 4_000_000  # ~4.7M directed after self-loop drop
    # peak transient host memory is a small known multiple of the chunk:
    # the chunk itself, its symmetrized copy, and argsort scratch
    assert stats.peak_chunk_bytes <= 16 * chunk_bytes_cap
    # and far below the O(M) edge payload that stayed on disk
    on_disk = stats.m_directed * 8  # indices i32 + weights f32
    assert stats.peak_chunk_bytes < on_disk / 3
    # O(n) fixed state only (degrees + cursors + indptr)
    assert stats.fixed_bytes <= 3 * (stats.n + 1) * 8
    store = open_store(path)
    assert store.m == stats.m_directed
    deg = store.degrees()
    assert int(deg.sum()) == store.m
    assert deg.min() >= 1  # connect path touches every vertex


@pytest.mark.slow
def test_scale20_ingest_tier(tmp_path):
    """The documented slow-marker tier: scale 20, same memory bound."""
    chunk_edges = 1 << 16
    path, stats = build_store(
        RmatEdgeSource(20, 8, seed=0, chunk_edges=chunk_edges),
        tmp_path / "g20.gstore",
    )
    assert stats.n == 1 << 20
    assert stats.peak_chunk_bytes <= 16 * chunk_edges * 12
    assert open_store(path).m == stats.m_directed


# ----------------------------------------------------------------------------
# solver parity: stored vs in-memory, every backend
# ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def parity_setup(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("gstore")
    scale, ef, seed = 9, 6, 7
    path, _ = build_store(
        RmatEdgeSource(scale, ef, seed=seed), tmp / "g.gstore"
    )
    src, dst, w, n = rmat_edges(scale, ef, seed=seed)
    g = from_edges(src, dst, w, n)
    rng = np.random.default_rng(0)
    seeds = rng.choice(n, size=8, replace=False).astype(np.int32)
    return path, g, seeds


@pytest.mark.parametrize("mode", ["dense", "bucket", "frontier", "pallas"])
def test_single_backend_parity_stored_vs_memory(parity_setup, mode):
    path, g, seeds = parity_setup
    cfg = SolverConfig(backend="single", mode=mode)
    mem = SteinerSolver(cfg).prepare(g).solve(seeds)
    handle = SteinerSolver(cfg).prepare(open_store(path))
    disk = handle.solve(seeds)
    assert disk.total_distance == mem.total_distance
    assert disk.num_edges == mem.num_edges
    if mode in ("frontier", "pallas"):
        assert handle.artifact("ell") is not None  # chunked disk-side build


@pytest.mark.parametrize("mode", ["bucket", "pallas"])
def test_batch_backend_parity_stored_vs_memory(parity_setup, mode):
    path, g, seeds = parity_setup
    cfg = SolverConfig(backend="batch", mode=mode)
    batch = np.stack([seeds, seeds[::-1]])
    mem = SteinerSolver(cfg).prepare(g).solve(batch)
    disk = SteinerSolver(cfg).prepare(open_store(path)).solve(batch)
    assert np.array_equal(
        np.asarray(mem.total_distance), np.asarray(disk.total_distance)
    )


def test_mesh_backends_prepare_from_store(parity_setup):
    path, g, seeds = parity_setup
    store = open_store(path)
    partition_store(store, n_replica=1, n_blocks=1)
    store = open_store(path, verify=False)

    cfg = SolverConfig(backend="mesh1d", mode="bucket", mesh_shape=(1, 1))
    mem = SteinerSolver(cfg).prepare(g).solve(seeds)
    handle = SteinerSolver(cfg).prepare(store)  # per-shard load path
    disk = handle.solve(seeds)
    assert disk.total_distance == mem.total_distance
    assert handle.artifact("part").nb == store.partition_meta["nb"]

    cfg2 = SolverConfig(backend="mesh2d", mode="bucket", mesh_shape=(1, 1))
    mem2 = SteinerSolver(cfg2).prepare(g).solve(seeds)
    disk2 = SteinerSolver(cfg2).prepare(store).solve(seeds)  # COO fallback
    assert disk2.total_distance == mem2.total_distance


def test_serve_engine_boots_from_graph_path(parity_setup):
    from repro.serve import ServeConfig, SteinerServer

    path, g, seeds = parity_setup
    server = SteinerServer(
        graph_path=path, config=ServeConfig(buckets=(8,), max_batch=2)
    )
    got = server.query(seeds.tolist()).total_distance
    want = (
        SteinerSolver(SolverConfig(backend="single", mode="bucket"))
        .prepare(g)
        .solve(seeds)
        .total_distance
    )
    assert got == want
    with pytest.raises(ValueError, match="exactly one"):
        SteinerServer(g, graph_path=path)
    with pytest.raises(ValueError, match="exactly one"):
        SteinerServer()


def test_serve_pallas_boots_off_disk_without_to_ell(parity_setup, monkeypatch):
    """mode="pallas" off disk must take the chunked store.ell build — the
    O(E)-Python to_ell loop never runs on the graph_path boot."""
    import repro.core.graph as graphmod
    from repro.serve import ServeConfig, SteinerServer

    path, g, seeds = parity_setup
    calls = {"n": 0}
    real = graphmod.to_ell

    def counting(gg, k, **kw):
        calls["n"] += 1
        return real(gg, k, **kw)

    monkeypatch.setattr(graphmod, "to_ell", counting)
    server = SteinerServer(
        graph_path=path,
        config=ServeConfig(mode="pallas", buckets=(8,), max_batch=2),
    )
    got = server.query(seeds.tolist()).total_distance
    assert calls["n"] == 0, "disk boot fell back to the host-Python ELL build"
    want = (
        SteinerSolver(SolverConfig(backend="single", mode="pallas"))
        .prepare(g)
        .solve(seeds)
        .total_distance
    )
    assert got == want


# ----------------------------------------------------------------------------
# partitions + hub sort
# ----------------------------------------------------------------------------


def test_partition_1d_matches_partition_edges(tmp_path):
    store, _ = _rmat_store(tmp_path)
    cs, cd, cw = store.coo()
    partition_store(store, n_replica=2, n_blocks=4)
    store = open_store(store.path, verify=False)
    got = store.load_partition()
    want = partition_edges(
        cs, cd, cw, store.n, n_replica=2, n_blocks=4, symmetrize=False
    )
    for f in ("src", "dst", "w", "n", "nb", "eb", "n_blocks", "n_replica"):
        a, b = getattr(got, f), getattr(want, f)
        assert np.array_equal(a, b) if isinstance(a, np.ndarray) else a == b, f


def test_partition_2d_matches_partition_edges_2d(tmp_path):
    store, _ = _rmat_store(tmp_path)
    cs, cd, cw = store.coo()
    partition_store_2d(store, R=2, C=2)
    store = open_store(store.path, verify=False)
    got = store.load_partition_2d()
    want = partition_edges_2d(cs, cd, cw, store.n, R=2, C=2, symmetrize=False)
    for f in ("src_row", "dst_col", "w", "n", "nf", "R", "C", "eb"):
        a, b = getattr(got, f), getattr(want, f)
        assert np.array_equal(a, b) if isinstance(a, np.ndarray) else a == b, f


def test_repartition_is_idempotent(tmp_path):
    """Re-running partition_store must not append onto old shard files."""
    store, _ = _rmat_store(tmp_path)
    partition_store(store, n_replica=1, n_blocks=2)
    first = open_store(store.path, verify=False).load_partition()
    partition_store(open_store(store.path, verify=False), n_replica=1, n_blocks=2)
    second = open_store(store.path, verify=False).load_partition()
    assert np.array_equal(first.src, second.src)
    assert np.array_equal(first.w, second.w)


def test_repartition_fewer_blocks_drops_stale_manifest_entries(tmp_path):
    """Shrinking the block count must not leave manifest rows pointing at
    deleted shard files (which would fail every later checksummed open)."""
    store, _ = _rmat_store(tmp_path)
    partition_store(store, n_replica=1, n_blocks=8)
    partition_store(open_store(store.path, verify=False), n_replica=1, n_blocks=2)
    reopened = open_store(store.path)  # verify=True walks every array
    part = reopened.load_partition()
    assert part.n_blocks == 2
    assert not any(
        k.startswith("shard_1d_") and "_b2_" in k
        for k in reopened.manifest["arrays"]
    )


def test_load_partition_without_shards_raises(tmp_path):
    store, _ = _rmat_store(tmp_path)
    with pytest.raises(StoreFormatError, match="no 1D partition"):
        store.load_partition()


# ----------------------------------------------------------------------------
# ELL shards (mesh frontier mode)
# ----------------------------------------------------------------------------


def test_partition_ell_store_matches_partition_ell(tmp_path):
    """Streamed ELL shards == host partition of the store's global ELL,
    bit for bit (small chunk_vertices exercises the chunked writer)."""
    from repro.core.dist_steiner import partition_ell

    store, _ = _rmat_store(tmp_path)
    partition_store(store, n_replica=2, n_blocks=4)
    partition_ell_store(store, k=8, chunk_vertices=50)
    store = open_store(store.path)  # verify=True checksums the ELL shards
    got = store.load_partition_ell()
    want = partition_ell(store.ell(8), n_replica=2, n_blocks=4)
    for f in ("n", "nb", "rb", "k", "n_blocks", "n_replica"):
        assert getattr(got, f) == getattr(want, f), f
    np.testing.assert_array_equal(got.nbr, want.nbr)
    np.testing.assert_array_equal(got.wgt, want.wgt)
    np.testing.assert_array_equal(got.row2v, want.row2v)


def test_mesh_frontier_prepare_from_store_no_edge_expansion(tmp_path):
    """Disk-vs-RAM parity for the mesh frontier mode: a store with a
    matching prebuilt ELL partition loads per-shard — neither the COO
    expansion nor the chunked global ELL build runs on the host."""
    store, _ = _rmat_store(tmp_path, scale=9, ef=6, seed=7)
    partition_store(store, n_replica=1, n_blocks=1)
    partition_ell_store(store, k=8)
    store = open_store(store.path, verify=False)

    src, dst, w, n = rmat_edges(9, 6, seed=7)
    g = from_edges(src, dst, w, n)
    seeds = np.random.default_rng(0).choice(n, size=8, replace=False).astype(
        np.int32
    )
    cfg = SolverConfig(
        backend="mesh1d", mode="frontier", mesh_shape=(1, 1),
        ell_width=8, frontier_size=64,
    )
    mem = SteinerSolver(cfg).prepare(g).solve(seeds)

    def boom(*a, **k):
        raise AssertionError("host edge expansion on the shard-load path")

    store.coo = boom
    store.ell = boom
    handle = SteinerSolver(cfg).prepare(store)
    disk = handle.solve(seeds)
    assert disk.total_distance == mem.total_distance
    assert disk.num_edges == mem.num_edges
    assert handle.artifact("ellpart").k == 8


def test_mesh_frontier_prepare_falls_back_to_chunked_ell(tmp_path):
    """No prebuilt ELL shards (or a width mismatch) → the chunked
    off-disk global ELL build, never the O(M) COO expansion."""
    store, _ = _rmat_store(tmp_path, scale=8, ef=6, seed=4)

    def boom(*a, **k):
        raise AssertionError("COO expansion on the frontier prepare path")

    store.coo = boom
    cfg = SolverConfig(
        backend="mesh1d", mode="frontier", mesh_shape=(1, 1),
        ell_width=8, frontier_size=64,
    )
    handle = SteinerSolver(cfg).prepare(store)
    seeds = np.arange(2, 20, 3, dtype=np.int32)
    src, dst, w, n = rmat_edges(8, 6, seed=4)
    g = from_edges(src, dst, w, n)
    mem = SteinerSolver(cfg).prepare(g).solve(seeds)
    assert handle.solve(seeds).total_distance == mem.total_distance


def test_repartition_drops_stale_ell_shards(tmp_path):
    """Re-partitioning replaces the geometry the ELL shards derive from:
    they must disappear from disk AND manifest (else checksummed opens
    break or a stale layout gets silently loaded)."""
    store, _ = _rmat_store(tmp_path)
    partition_store(store, n_replica=1, n_blocks=4)
    partition_ell_store(store, k=8)
    assert "ell" in open_store(store.path, verify=False).partition_meta
    partition_store(
        open_store(store.path, verify=False), n_replica=1, n_blocks=2
    )
    reopened = open_store(store.path)  # verify=True walks every array
    assert "ell" not in reopened.partition_meta
    assert not any(
        k.startswith("shard_ell_") for k in reopened.manifest["arrays"]
    )
    with pytest.raises(StoreFormatError, match="no 1D ELL partition"):
        reopened.load_partition_ell()


def test_partition_ell_store_requires_1d_partition(tmp_path):
    store, _ = _rmat_store(tmp_path)
    with pytest.raises(StoreFormatError, match="1D partition"):
        partition_ell_store(store, k=8)
    partition_store(store, n_replica=1, n_blocks=2)
    with pytest.raises(ValueError, match="row width"):
        partition_ell_store(store, k=0)


def test_hub_sort_reorders_and_preserves_solutions(tmp_path):
    store, _ = _rmat_store(tmp_path, scale=8, ef=6, seed=5)
    hpath, perm = hub_sort_store(store, tmp_path / "h.gstore")
    hub = open_store(hpath)
    deg = np.asarray(hub.degrees())
    assert np.all(deg[:-1] >= deg[1:])  # degree-descending
    assert np.array_equal(np.sort(perm), np.arange(store.n))
    assert np.array_equal(hub.map_ids(np.arange(store.n)), perm)

    rng = np.random.default_rng(1)
    seeds = rng.choice(store.n, size=6, replace=False).astype(np.int32)
    cfg = SolverConfig(backend="single", mode="bucket")
    a = SteinerSolver(cfg).prepare(store).solve(seeds)
    # handles prepared from a hub-sorted store take ORIGINAL seed ids —
    # solve() translates through vertex_perm itself
    b = SteinerSolver(cfg).prepare(hub).solve(seeds)
    assert a.total_distance == b.total_distance


def test_serve_translates_seeds_on_hub_sorted_store(tmp_path):
    """graph_path on a hub-sorted store takes ORIGINAL ids transparently."""
    from repro.serve import ServeConfig, SteinerServer

    store, _ = _rmat_store(tmp_path, scale=8, ef=6, seed=5)
    hpath, _ = hub_sort_store(store, tmp_path / "h.gstore")
    seeds = np.random.default_rng(2).choice(
        store.n, size=6, replace=False
    ).tolist()
    scfg = ServeConfig(buckets=(8,), max_batch=2)
    plain = SteinerServer(graph_path=store.path, config=scfg).query(seeds)
    hub = SteinerServer(graph_path=hpath, config=scfg).query(seeds)
    assert hub.total_distance == plain.total_distance


# ----------------------------------------------------------------------------
# manifest / integrity errors
# ----------------------------------------------------------------------------


def test_corrupted_checksum_raises(tmp_path):
    store, _ = _rmat_store(tmp_path)
    wpath = store.path / "weights.bin"
    raw = bytearray(wpath.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    wpath.write_bytes(bytes(raw))
    with pytest.raises(ChecksumError, match="crc32"):
        open_store(store.path)
    # verify=False defers, explicit verify still catches it
    lazy = open_store(store.path, verify=False)
    with pytest.raises(ChecksumError):
        lazy.verify()


def test_truncated_array_raises(tmp_path):
    store, _ = _rmat_store(tmp_path)
    ipath = store.path / "indices.bin"
    ipath.write_bytes(ipath.read_bytes()[:-8])
    with pytest.raises(StoreFormatError, match="size"):
        open_store(store.path, verify=False).indices


def test_version_mismatch_raises(tmp_path):
    store, _ = _rmat_store(tmp_path)
    mf = store.path / MANIFEST_NAME
    manifest = json.loads(mf.read_text())
    manifest["format_version"] = 999
    mf.write_text(json.dumps(manifest))
    with pytest.raises(StoreFormatError, match="format_version 999"):
        open_store(store.path)


def test_missing_manifest_raises(tmp_path):
    with pytest.raises(StoreFormatError, match="no manifest"):
        open_store(tmp_path / "nope.gstore")


# ----------------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------------


def test_cli_build_info_partition(tmp_path, capsys):
    from repro.graphstore.__main__ import main

    out = tmp_path / "cli.gstore"
    assert main(
        ["build", str(out), "--source", "rmat", "--scale", "7",
         "--edge-factor", "5", "--seed", "1", "--hub-sort"]
    ) == 0
    assert main(
        ["partition", str(out), "--scheme", "1d", "--replicas", "1",
         "--blocks", "2"]
    ) == 0
    assert main(["info", str(out)]) == 0
    captured = capsys.readouterr()
    # progress lines ride the repro.graphstore logger on stderr; the
    # info summary (the command's deliverable) stays on stdout
    assert "built" in captured.err and "partitioned" in captured.err
    assert "1d" in captured.out
    assert (tmp_path / "cli.hub.gstore").is_dir()
    store = open_store(out, verify=False)
    assert store.partition_meta["scheme"] == "1d"
    src, dst, w, n = rmat_edges(7, 5, seed=1)
    assert store.m == 2 * len(src)
