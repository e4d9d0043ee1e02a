"""Unified solver API: config validation, backend parity, executable reuse.

The mesh backends run on a (1, 1) mesh here — conftest keeps the main
process at one host device; multi-device parity is covered by the slow
subprocess test (tests/test_dist_steiner.py).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import sanitize
from repro.core import from_edges, ref, steiner_tree
from repro.core.graph import ell_view_cached
from repro.solver import (
    SolveOutput,
    SolverConfig,
    SteinerSolver,
    available_backends,
    get_backend,
    trace_count,
)

from helpers import random_instance


def _instance(trial):
    src, dst, w, n, seeds, edges = random_instance(trial)
    return from_edges(src, dst, w, n, pad_to=8), n, seeds, edges


# ----------------------------------------------------------------------------
# config validation
# ----------------------------------------------------------------------------


def test_registry_has_all_four_backends():
    assert available_backends() == ("batch", "mesh1d", "mesh2d", "single")


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        SolverConfig(backend="mpi")
    with pytest.raises(KeyError, match="unknown backend"):
        get_backend("mpi")


def test_unknown_mode_rejected():
    with pytest.raises(ValueError, match="unknown mode"):
        SolverConfig(mode="fifo")


def test_unknown_mst_algo_rejected():
    with pytest.raises(ValueError, match="unknown mst_algo"):
        SolverConfig(mst_algo="kruskal")


def test_mode_backend_cross_validation():
    with pytest.raises(ValueError, match="frontier.*not supported"):
        SolverConfig(backend="batch", mode="frontier")
    with pytest.raises(ValueError, match="frontier.*not supported"):
        SolverConfig(backend="mesh2d", mode="frontier")
    with pytest.raises(ValueError, match="pallas.*not supported"):
        SolverConfig(backend="mesh1d", mode="pallas")
    with pytest.raises(ValueError, match="pallas.*not supported"):
        SolverConfig(backend="mesh2d", mode="pallas")
    # the sharded-ELL prioritized schedule is a supported combination
    SolverConfig(backend="mesh1d", mode="frontier")
    # ... but cannot amortize collectives: candidates must cross devices
    with pytest.raises(ValueError, match="local_steps"):
        SolverConfig(backend="mesh1d", mode="frontier", local_steps=2)


def test_pallas_knobs_validated():
    with pytest.raises(ValueError, match="block_rows"):
        SolverConfig(mode="pallas", block_rows=0)
    with pytest.raises(ValueError, match="pallas_frontier"):
        SolverConfig(mode="bucket", pallas_frontier=True)
    # the platform, not a knob, picks compiled kernels or the interpreter
    for gone in ("src_block", "interpret"):
        with pytest.raises(TypeError, match=gone):
            SolverConfig(mode="pallas", **{gone: None})
    # valid combinations construct fine
    SolverConfig(mode="pallas", pallas_frontier=True, block_rows=64)
    SolverConfig(backend="batch", mode="pallas")


def test_scalar_knobs_validated():
    with pytest.raises(ValueError, match="delta"):
        SolverConfig(delta=-1.0)
    with pytest.raises(ValueError, match="batch_size"):
        SolverConfig(batch_size=0)
    with pytest.raises(ValueError, match="mesh_shape"):
        SolverConfig(mesh_shape=(0, 2))
    with pytest.raises(ValueError, match="mesh_shape"):
        SolverConfig(mesh_shape=(2, 2, 2))


def test_mesh2d_rejects_mesh1d_only_knobs():
    with pytest.raises(ValueError, match="local_steps"):
        SolverConfig(backend="mesh2d", local_steps=2)
    with pytest.raises(ValueError, match="lab_i16"):
        SolverConfig(backend="mesh2d", lab_i16=True)


def test_replace_revalidates():
    cfg = SolverConfig()
    assert cfg.replace(mode="dense").mode == "dense"
    with pytest.raises(ValueError, match="unknown mode"):
        cfg.replace(mode="fifo")


def test_prepare_rejects_oversized_mesh():
    g, n, seeds, edges = _instance(0)
    cfg = SolverConfig(backend="mesh1d", mesh_shape=(64, 64))
    with pytest.raises(ValueError, match="devices"):
        SteinerSolver(cfg).prepare(g)


# ----------------------------------------------------------------------------
# backend parity — one algorithm, five execution strategies
# ----------------------------------------------------------------------------

PARITY_SPECS = [
    ("single", "dense"),
    ("single", "bucket"),
    ("single", "frontier"),
    ("single", "pallas"),
    ("mesh1d", "dense"),
    ("mesh1d", "bucket"),
    ("mesh1d", "frontier"),
    ("mesh2d", "bucket"),
]


@pytest.mark.parametrize("trial", range(3))
def test_total_distance_identical_across_backends(trial):
    g, n, seeds, edges = _instance(trial)
    _, d_ref = ref.mehlhorn_ref(n, edges, seeds.tolist())
    for backend, mode in PARITY_SPECS:
        cfg = SolverConfig(backend=backend, mode=mode, mesh_shape=(1, 1))
        out = SteinerSolver(cfg).prepare(g).solve(seeds)
        assert isinstance(out, SolveOutput)
        assert out.total_distance == pytest.approx(d_ref, abs=1e-4), (
            backend,
            mode,
        )


@pytest.mark.parametrize("trial", range(2))
def test_pallas_frontier_variant_parity(trial):
    """The top-K compacted kernel schedule hits the same fixpoint."""
    g, n, seeds, edges = _instance(trial)
    _, d_ref = ref.mehlhorn_ref(n, edges, seeds.tolist())
    cfg = SolverConfig(
        backend="single",
        mode="pallas",
        pallas_frontier=True,
        frontier_size=48,
        block_rows=16,
    )
    out = SteinerSolver(cfg).prepare(g).solve(seeds)
    assert out.total_distance == pytest.approx(d_ref, abs=1e-4)


def test_batch_pallas_matches_single():
    g, n, seeds, edges = _instance(0)
    rng = np.random.default_rng(4)
    batch = np.stack(
        [rng.choice(n, size=5, replace=False) for _ in range(3)]
    ).astype(np.int32)
    cfg = SolverConfig(backend="batch", mode="pallas")
    out = SteinerSolver(cfg).prepare(g).solve(batch)
    assert out.total_distance.shape == (3,)
    for i in range(3):
        single = steiner_tree(g, jnp.asarray(batch[i]), mode="pallas")
        assert out.total_distance[i] == float(single.tree.total_distance)


def test_batch_backend_matches_single():
    g, n, seeds, edges = _instance(0)
    rng = np.random.default_rng(3)
    batch = np.stack(
        [rng.choice(n, size=5, replace=False) for _ in range(3)]
    ).astype(np.int32)
    cfg = SolverConfig(backend="batch", mode="bucket")
    out = SteinerSolver(cfg).prepare(g).solve(batch)
    assert out.total_distance.shape == (3,)
    for i in range(3):
        single = steiner_tree(g, jnp.asarray(batch[i]))
        assert out.total_distance[i] == float(single.tree.total_distance)


def test_solve_rejects_wrong_rank():
    g, n, seeds, edges = _instance(0)
    h1 = SteinerSolver(SolverConfig(backend="single")).prepare(g)
    with pytest.raises(ValueError, match=r"\(S,\)"):
        h1.solve(np.stack([seeds, seeds]))
    hb = SteinerSolver(SolverConfig(backend="batch")).prepare(g)
    with pytest.raises(ValueError, match=r"\(B, S\)"):
        hb.solve(seeds)


# ----------------------------------------------------------------------------
# executable reuse — prepare once, solve many, re-trace zero times
# ----------------------------------------------------------------------------


def test_prepare_traces_once_across_repeated_solves():
    g, n, seeds, edges = _instance(1)
    handle = SteinerSolver(SolverConfig(backend="single", mode="bucket")).prepare(g)
    rng = np.random.default_rng(0)
    first = handle.solve(seeds)
    base = trace_count()  # first solve may or may not have traced (shared cache)
    # warm path runs under the runtime sanitizer: zero implicit host
    # transfers (jax.transfer_guard) and zero retraces (TS06 at run time)
    with sanitize.sanitizer():
        for _ in range(4):  # same |S|, different seed values
            s = rng.choice(n, size=len(seeds), replace=False).astype(np.int32)
            out = handle.solve(s)
            assert out.total_distance > 0
        assert trace_count() == base, "repeated solve() must re-trace zero times"
        assert first.total_distance == handle.solve(seeds).total_distance


def test_mesh_handle_caches_executable_per_seed_count():
    g, n, seeds, edges = _instance(2)
    handle = SteinerSolver(
        SolverConfig(backend="mesh1d", mode="bucket", mesh_shape=(1, 1))
    ).prepare(g)
    assert handle.num_executables == 0
    handle.solve(seeds)
    assert handle.num_executables == 1
    base = trace_count("mesh1d")
    with sanitize.sanitizer(key="mesh1d"):
        handle.solve(np.roll(seeds, 1))  # same |S| → cached executable
    assert trace_count("mesh1d") == base
    handle.solve(seeds[:3])  # new |S| → one new executable
    assert handle.num_executables == 2


def test_frontier_handle_caches_ell_view():
    g, n, seeds, edges = _instance(0)
    solver = SteinerSolver(SolverConfig(backend="single", mode="frontier"))
    h1 = solver.prepare(g)
    h2 = solver.prepare(g)
    assert h1.artifact("ell") is not None
    # the memo makes repeated prepare() of the same resident graph free
    assert h1.artifact("ell") is h2.artifact("ell")
    _, d_ref = ref.mehlhorn_ref(n, edges, seeds.tolist())
    assert h1.solve(seeds).total_distance == pytest.approx(d_ref, abs=1e-4)


def test_pallas_traces_once_and_shares_ell():
    g, n, seeds, edges = _instance(2)
    solver = SteinerSolver(SolverConfig(backend="single", mode="pallas"))
    h1 = solver.prepare(g)
    h2 = solver.prepare(g)
    # the memoized ELL view is shared with repeated prepare()
    assert h1.artifact("ell") is not None
    assert h1.artifact("ell") is h2.artifact("ell")
    first = h1.solve(seeds)
    base = trace_count()
    rng = np.random.default_rng(1)
    with sanitize.sanitizer():
        for _ in range(4):  # same |S|, different seed values
            s = rng.choice(n, size=len(seeds), replace=False).astype(np.int32)
            assert h1.solve(s).total_distance > 0
        assert trace_count() == base, "repeated pallas solve() must not re-trace"
        assert first.total_distance == h2.solve(seeds).total_distance


# ----------------------------------------------------------------------------
# kernel-path serving invariants (repro.serve.plan contract)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("pallas_frontier", [False, True])
def test_pallas_duplicate_seed_padding_inert(pallas_frontier):
    """Duplicate-seed padding (the serve planner's bucket fill) must not
    change the kernel path's result — mirrors the dense/bucket contract
    asserted in tests/test_serve.py."""
    g, n, seeds, edges = _instance(1)
    cfg = SolverConfig(
        backend="single",
        mode="pallas",
        pallas_frontier=pallas_frontier,
        frontier_size=48,
        block_rows=16,
    )
    handle = SteinerSolver(cfg).prepare(g)
    base = handle.solve(seeds)
    padded = np.concatenate([seeds, np.full(3, seeds[0], np.int32)])
    out = handle.solve(padded)
    assert out.total_distance == base.total_distance
    assert out.num_edges == base.num_edges
    np.testing.assert_array_equal(
        np.asarray(out.raw.state.lab), np.asarray(base.raw.state.lab)
    )
    np.testing.assert_array_equal(
        np.asarray(out.raw.state.dist), np.asarray(base.raw.state.dist)
    )


@pytest.mark.parametrize("pallas_frontier", [False, True])
def test_pallas_max_iters_honored(pallas_frontier):
    g, n, seeds, edges = _instance(1)
    cfg = SolverConfig(
        backend="single",
        mode="pallas",
        pallas_frontier=pallas_frontier,
        frontier_size=16,
        block_rows=16,
        max_iters=2,
    )
    out = SteinerSolver(cfg).prepare(g).solve(seeds)
    assert int(out.raw.stats.iterations) <= 2


def test_shim_path_memoizes_ell(monkeypatch):
    """Repeated mode="frontier" calls through the legacy steiner_tree
    front door must not pay the O(E) host-Python ELL rebuild."""
    import repro.core.graph as graphmod

    g, n, seeds, edges = _instance(1)
    calls = {"n": 0}
    real = graphmod.to_ell

    def counting(gg, k, **kw):
        calls["n"] += 1
        return real(gg, k, **kw)

    monkeypatch.setattr(graphmod, "to_ell", counting)
    r1 = steiner_tree(g, jnp.asarray(seeds), mode="frontier")
    r2 = steiner_tree(g, jnp.asarray(seeds), mode="frontier")
    assert calls["n"] <= 1  # 0 if another test already memoized this g
    assert float(r1.tree.total_distance) == float(r2.tree.total_distance)


def test_ell_view_cached_identity_and_rebuild():
    g, n, seeds, edges = _instance(2)
    a = ell_view_cached(g, 8)
    b = ell_view_cached(g, 8)
    assert a is b
    c = ell_view_cached(g, 16)  # different width → different view
    assert c is not a


# ----------------------------------------------------------------------------
# mesh frontier mode — the distributed prioritized schedule (paper §IV)
# ----------------------------------------------------------------------------


def _mesh_frontier_cfg(**kw):
    return SolverConfig(
        backend="mesh1d",
        mode="frontier",
        mesh_shape=(1, 1),
        ell_width=8,
        frontier_size=32,
        **kw,
    )


def test_mesh_frontier_traces_once_and_caches_ellpart():
    g, n, seeds, edges = _instance(1)
    _, d_ref = ref.mehlhorn_ref(n, edges, seeds.tolist())
    handle = SteinerSolver(_mesh_frontier_cfg()).prepare(g)
    assert handle.artifact("ellpart") is not None
    assert handle.artifact("part") is None  # no edge partition built
    first = handle.solve(seeds)
    assert first.total_distance == pytest.approx(d_ref, abs=1e-4)
    assert handle.num_executables == 1
    base = trace_count("mesh1d")
    rng = np.random.default_rng(0)
    with sanitize.sanitizer(key="mesh1d"):
        for _ in range(3):  # same |S|, different seed values
            s = rng.choice(n, size=len(seeds), replace=False).astype(np.int32)
            assert handle.solve(s).total_distance > 0
        assert trace_count("mesh1d") == base, "same-|S| solves must not re-trace"
    assert handle.num_executables == 1


def test_mesh_frontier_fewer_messages_than_bucket():
    """The acceptance contract: bit-identical total with strictly less
    message work than the Δ-bucket schedule (paper Fig. 5/6)."""
    g, n, seeds, edges = _instance(0)
    front = SteinerSolver(_mesh_frontier_cfg()).prepare(g).solve(seeds)
    bucket = (
        SteinerSolver(
            SolverConfig(backend="mesh1d", mode="bucket", mesh_shape=(1, 1))
        )
        .prepare(g)
        .solve(seeds)
    )
    assert front.total_distance == bucket.total_distance
    assert front.num_edges == bucket.num_edges
    assert front.raw.messages < bucket.raw.messages


def test_mesh_frontier_duplicate_seed_padding_inert():
    """The serve planner's pad-with-duplicates contract holds under the
    prioritized mesh schedule (and the min-scatter init fix)."""
    g, n, seeds, edges = _instance(2)
    handle = SteinerSolver(_mesh_frontier_cfg()).prepare(g)
    base = handle.solve(seeds)
    padded = np.concatenate([seeds, np.full(3, seeds[0], np.int32)])
    out = handle.solve(padded)
    assert out.total_distance == base.total_distance
    assert out.num_edges == base.num_edges
    np.testing.assert_array_equal(
        np.asarray(out.raw.dist), np.asarray(base.raw.dist)
    )
    assert out.raw.edge_set() == base.raw.edge_set()


def test_mesh_frontier_rejects_legacy_edge_partition():
    """run_dist_steiner's (mesh, Partition) pair has no ELL view."""
    from repro.core.dist_steiner import partition_edges, run_dist_steiner
    from repro.launch.mesh import make_mesh

    g, n, seeds, edges = _instance(0)
    part = partition_edges(
        np.asarray(g.src), np.asarray(g.dst), np.asarray(g.w), n,
        n_replica=1, n_blocks=1, symmetrize=False,
    )
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(TypeError, match="EllPartition"):
        run_dist_steiner(mesh, part, seeds, mode="frontier")


# ----------------------------------------------------------------------------
# mesh-path wire-format validation (DistSteinerConfig)
# ----------------------------------------------------------------------------


def _dcfg(**kw):
    from repro.core.dist_steiner import DistSteinerConfig

    return DistSteinerConfig(n=64, nb=16, **kw)


def test_lab_i16_accepts_full_int16_range():
    # |S| = 32767 fits int16 (labels take values in [0, S]); the old
    # traced assert rejected it off-by-one
    _dcfg(num_seeds=32767, lab_i16=True)


def test_lab_i16_rejects_s_32768():
    with pytest.raises(ValueError, match="lab_i16.*32768"):
        _dcfg(num_seeds=32768, lab_i16=True)


def test_fused_gather_label_packing_guard():
    # f32 label packing is exact below 2^24; at/above it would silently
    # corrupt cell ownership — reject at config time
    _dcfg(num_seeds=2**24 - 1, fuse_gather=True)
    with pytest.raises(ValueError, match="fuse_gather.*2\\*\\*24"):
        _dcfg(num_seeds=2**24, fuse_gather=True)
    _dcfg(num_seeds=2**24, fuse_gather=False)  # unfused i32 gather is fine


def test_dist_config_frontier_rejects_local_steps():
    with pytest.raises(ValueError, match="local_steps"):
        _dcfg(num_seeds=4, mode="frontier", local_steps=2)


# ----------------------------------------------------------------------------
# preset plumbing (configs.steiner → dryrun)
# ----------------------------------------------------------------------------


def test_paper_workload_presets_are_solver_configs():
    from repro.configs.steiner import SOLVER_PRESETS, solver_preset

    assert set(SOLVER_PRESETS) == {
        "lvj_1k",
        "ukw_1k",
        "clw_10k",
        "serve_pallas",
        "mesh_frontier",
    }
    for name in ("lvj_1k", "ukw_1k", "clw_10k", "mesh_frontier"):
        p = solver_preset(name)
        assert isinstance(p, SolverConfig)
        assert p.backend == "mesh1d"
    assert solver_preset("clw_10k").pair_chunks > 1  # §V-F chunked Allreduce
    fast = solver_preset("serve_pallas")  # the kernel fast path preset
    assert (fast.backend, fast.mode) == ("batch", "pallas")
    mf = solver_preset("mesh_frontier")  # §IV message prioritization
    assert (mf.mode, mf.local_steps) == ("frontier", 1)
    with pytest.raises(KeyError, match="no solver preset"):
        solver_preset("nope")
