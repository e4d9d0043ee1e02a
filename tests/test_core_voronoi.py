"""Voronoi cell computation vs the multi-source Dijkstra oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import from_edges, to_ell
from repro.core import ref
from repro.core.voronoi import (
    I32_MAX,
    _voronoi_cells,
    init_state,
    lex_segment_argmin,
    relax_dense,
    segmin_passes,
    voronoi_cells,
    voronoi_cells_frontier,
)
from repro.kernels.minplus.ops import (
    voronoi_cells_pallas,
    voronoi_cells_pallas_frontier,
)

from helpers import random_instance


@pytest.mark.parametrize("mode", ["dense", "bucket"])
@pytest.mark.parametrize("trial", range(6))
def test_voronoi_matches_dijkstra(mode, trial):
    src, dst, w, n, seeds, edges = random_instance(trial)
    g = from_edges(src, dst, w, n, pad_to=8)
    st_, stats = voronoi_cells(g, jnp.asarray(seeds), mode=mode)
    dist, lab, pred = ref.voronoi_ref(n, edges, seeds.tolist())
    np.testing.assert_allclose(np.asarray(st_.dist), dist)
    np.testing.assert_array_equal(np.asarray(st_.lab), lab)
    np.testing.assert_array_equal(np.asarray(st_.pred), pred)
    assert int(stats.iterations) > 0


@pytest.mark.parametrize("trial", range(3))
def test_voronoi_frontier_matches(trial):
    src, dst, w, n, seeds, edges = random_instance(trial)
    g = from_edges(src, dst, w, n, pad_to=8)
    ell = to_ell(g, k=8, pad_rows_to=32)
    st_, _ = voronoi_cells_frontier(ell, jnp.asarray(seeds), frontier_size=32)
    dist, lab, pred = ref.voronoi_ref(n, edges, seeds.tolist())
    np.testing.assert_allclose(np.asarray(st_.dist), dist)
    np.testing.assert_array_equal(np.asarray(st_.lab), lab)
    np.testing.assert_array_equal(np.asarray(st_.pred), pred)


@pytest.mark.parametrize("trial", range(3))
def test_voronoi_pallas_matches(trial):
    src, dst, w, n, seeds, edges = random_instance(trial)
    g = from_edges(src, dst, w, n, pad_to=8)
    ell = to_ell(g, k=8, pad_rows_to=64)
    st_, stats = voronoi_cells_pallas(ell, jnp.asarray(seeds), block_rows=64)
    dist, lab, pred = ref.voronoi_ref(n, edges, seeds.tolist())
    np.testing.assert_allclose(np.asarray(st_.dist), dist)
    np.testing.assert_array_equal(np.asarray(st_.lab), lab)
    np.testing.assert_array_equal(np.asarray(st_.pred), pred)
    # real convergence stats, not the old zero placeholder
    assert float(stats.relaxations) > 0
    assert float(stats.messages) > 0


@pytest.mark.parametrize("block_rows", [16, 128])
@pytest.mark.parametrize("trial", range(3))
def test_voronoi_pallas_frontier_matches(trial, block_rows):
    """Top-K compacted kernel schedule: same fixpoint as the oracle, with
    gathered tiles both smaller and larger than the frontier."""
    src, dst, w, n, seeds, edges = random_instance(trial)
    g = from_edges(src, dst, w, n, pad_to=8)
    ell = to_ell(g, k=8, pad_rows_to=64)
    st_, stats = voronoi_cells_pallas_frontier(
        ell,
        jnp.asarray(seeds),
        frontier_size=32,
        block_rows=block_rows,
    )
    dist, lab, pred = ref.voronoi_ref(n, edges, seeds.tolist())
    np.testing.assert_allclose(np.asarray(st_.dist), dist)
    np.testing.assert_array_equal(np.asarray(st_.lab), lab)
    np.testing.assert_array_equal(np.asarray(st_.pred), pred)
    assert float(stats.relaxations) > 0


def test_bucket_delta_zero_rejected():
    """delta<=0 never advances the bucket threshold — formerly a silent
    spin through the full 4n+64 round cap."""
    src, dst, w, n, seeds, edges = random_instance(0)
    g = from_edges(src, dst, w, n, pad_to=8)
    with pytest.raises(ValueError, match="delta must be positive"):
        voronoi_cells(g, jnp.asarray(seeds), mode="bucket", delta=0.0)
    with pytest.raises(ValueError, match="delta must be positive"):
        voronoi_cells(g, jnp.asarray(seeds), mode="bucket", delta=-1.5)
    # dense mode documents delta as bucket-only and ignores it — no raise
    st_, _ = voronoi_cells(g, jnp.asarray(seeds), mode="dense", delta=0.0)
    dist, _, _ = ref.voronoi_ref(n, edges, seeds.tolist())
    np.testing.assert_allclose(np.asarray(st_.dist), dist)


def test_bucket_delta_traced_rejected_loudly():
    """Δ is a static knob: a traced value can no longer bypass validation
    and stall the bucket loop (the PR-4 bug class) — it is rejected
    outright on the host path, before any trace runs."""
    import jax

    src, dst, w, n, seeds, edges = random_instance(0)
    g = from_edges(src, dst, w, n, pad_to=8)
    f = jax.jit(
        lambda d: voronoi_cells(g, jnp.asarray(seeds), mode="bucket", delta=d)
    )
    with pytest.raises(TypeError, match="host scalar"):
        f(0.0)
    # host scalars still validate eagerly, including numpy scalars
    with pytest.raises(ValueError, match="delta must be positive"):
        voronoi_cells(
            g, jnp.asarray(seeds), mode="bucket", delta=np.float32(0.0)
        )
    # and a positive numpy scalar is a valid static width
    st_, _ = voronoi_cells(
        g, jnp.asarray(seeds), mode="bucket", delta=np.float32(2.0)
    )
    dist, _, _ = ref.voronoi_ref(n, edges, seeds.tolist())
    np.testing.assert_allclose(np.asarray(st_.dist), dist)


def test_voronoi_cells_frontier_mode_redirect():
    """The COO entry point's unknown-mode error points at the dedicated
    frontier/pallas entry points instead of implying two modes exist."""
    src, dst, w, n, seeds, edges = random_instance(0)
    g = from_edges(src, dst, w, n, pad_to=8)
    with pytest.raises(ValueError, match="voronoi_cells_frontier"):
        voronoi_cells(g, jnp.asarray(seeds), mode="frontier")
    with pytest.raises(ValueError, match="voronoi_cells_pallas"):
        voronoi_cells(g, jnp.asarray(seeds), mode="pallas")


def test_bucket_fewer_messages_than_dense():
    """The paper's Fig. 5/6 effect: prioritization cuts message volume.

    A wide edge-weight range ([1, 500], paper Fig. 7) makes FIFO/dense
    propagation waste many soon-overwritten updates; Δ-bucketed priority
    suppresses them.
    """
    from repro.data.graphs import rmat_edges

    src, dst, w, n = rmat_edges(8, 8, max_weight=500, seed=12)
    rng = np.random.default_rng(12)
    seeds = rng.choice(n, size=8, replace=False).astype(np.int32)
    g = from_edges(src, dst, w, n, pad_to=8)
    _, s_dense = voronoi_cells(g, jnp.asarray(seeds), mode="dense")
    _, s_buck = voronoi_cells(g, jnp.asarray(seeds), mode="bucket")
    # strictly fewer generated messages AND fewer overwritten updates
    assert float(s_buck.messages) < float(s_dense.messages)
    assert float(s_buck.relaxations) <= float(s_dense.relaxations)



# ----------------------------------------------------------------------------
# Packed (lab, src) tie-break: two segment-min passes against three.
# ----------------------------------------------------------------------------


def _assert_equal(a, ua, b, ub):
    for x, y in ((a.dist, b.dist), (a.lab, b.lab), (a.pred, b.pred), (ua, ub)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _ties_graph(pad_to=8, dup=False, island=False):
    """Integer weights 1-3 (many equal candidates); optionally every edge
    twice more (once heavier), and a 6-vertex path no seed reaches."""
    from repro.data.graphs import er_edges

    src, dst, w, n = er_edges(40, 0.15, max_weight=3, seed=5)
    w = np.ceil(w).astype(np.float32)
    if dup:
        src = np.concatenate([src, src, src])
        dst = np.concatenate([dst, dst, dst])
        w = np.concatenate([w, w, w + 1])
    if island:
        isl = np.arange(n, n + 5, dtype=np.int32)
        src = np.concatenate([src, isl, isl + 1]).astype(np.int32)
        dst = np.concatenate([dst, isl + 1, isl]).astype(np.int32)
        w = np.concatenate([w, np.ones(10, np.float32)])
        n += 6
    return from_edges(src, dst, w, n, pad_to=pad_to)


_SEEDS = [3, 11, 17, 22, 29, 31, 37, 2]


def _relax_both(g, st, num_labels, relax=jax.jit(relax_dense)):
    """Runs the packed key (``num_labels``) and the three passes side by
    side to the fixpoint, Δ-masked rounds (as the bucket schedule's) and
    dense ones in turn, comparing every round; returns the fixpoint."""
    assert segmin_passes(g.n, num_labels) == 2
    packed = dataclasses.replace(st, num_labels=num_labels)
    plain = dataclasses.replace(st, num_labels=None)
    for theta in (1.0, 2.0, 3.0, 5.0) + (np.inf,) * 4:
        d = plain.dist[..., g.src]
        cand = jnp.where(d <= theta, d + g.w, jnp.inf)
        for args in ((cand,), ()):
            a, ua = relax(g, packed, *args)
            b, ub = relax(g, plain, *args)
            _assert_equal(a, ua, b, ub)
            packed, plain = a, b
    return plain


@pytest.mark.parametrize(
    "case", ["ties_w123", "duplicate_edges", "unreached", "inf_padding",
             "batch_duplicate_seeds", "fallback", "key_bound"]
)
def test_packed_tie_break_matches_three_passes(case):
    """The two-pass segment argmin gives the three-pass one's (dist, lab,
    pred) and ``upd`` bit for bit, round by round; past the int32 key
    bound the helper takes the three passes itself."""
    if case in ("ties_w123", "duplicate_edges", "unreached", "inf_padding"):
        g = _ties_graph(pad_to=256 if case == "inf_padding" else 8,
                        dup=case == "duplicate_edges", island=case == "unreached")
        st = _relax_both(g, init_state(g.n, jnp.asarray(_SEEDS)), len(_SEEDS))
        src, dst, w = (np.asarray(x).tolist() for x in (g.src, g.dst, g.w))
        edges = [e for e in zip(src, dst, w) if np.isfinite(e[2])]
        dist, lab, pred = ref.voronoi_ref(g.n, edges, _SEEDS)
        np.testing.assert_array_equal(np.asarray(st.dist), dist)
        np.testing.assert_array_equal(np.asarray(st.lab), lab)
        np.testing.assert_array_equal(np.asarray(st.pred), pred)
        if case == "unreached":
            assert int(np.sum(np.asarray(st.lab) == len(_SEEDS))) == 6
    elif case == "batch_duplicate_seeds":
        # the batch backend's lanes: shorter seed sets padded to 8 with
        # duplicates of their first seed
        g = _ties_graph(dup=True)
        lanes = [[3, 11, 17], [5, 9, 21, 33, 38], [7], _SEEDS]
        seeds = jnp.asarray([s + [s[0]] * (8 - len(s)) for s in lanes], jnp.int32)
        st = jax.vmap(lambda s: init_state(g.n, s))(seeds)
        dense = jax.jit(jax.vmap(relax_dense, in_axes=(None, 0)))
        masked = jax.jit(jax.vmap(relax_dense, in_axes=(None, 0, 0)))
        _relax_both(g, st, 8,
                    relax=lambda g_, s, *c: (masked if c else dense)(g_, s, *c))
    elif case == "fallback":
        # (S + 1) * n above 2**31 - 1: three passes, the same answers as
        # the packed key at the true S
        g = _ties_graph(dup=True)
        S_big = I32_MAX // g.n + 1
        assert segmin_passes(g.n, S_big) == 3
        st = init_state(g.n, jnp.asarray(_SEEDS))
        for _ in range(6):
            args = (st.dist[g.src] + g.w, st.lab[g.src], g.src, g.dst, g.n)
            for x, y in zip(lex_segment_argmin(*args, S_big),
                            lex_segment_argmin(*args, len(_SEEDS))):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            a, ua = relax_dense(g, dataclasses.replace(st, num_labels=S_big))
            st, ub = relax_dense(g, dataclasses.replace(st, num_labels=len(_SEEDS)))
            _assert_equal(a, ua, st, ub)
    else:  # key_bound: labels and sources at the top of the packed range
        n, S = 65536, 32766  # (S + 1) * n = 2**31 - 2**16
        assert segmin_passes(n, S) == 2 and segmin_passes(n, S + 1) == 3
        rng = np.random.default_rng(7)
        E = 4096
        dst = jnp.asarray(rng.integers(0, 64, E), jnp.int32)
        src = jnp.asarray(rng.integers(n - 8, n, E), jnp.int32)
        lab = jnp.asarray(rng.integers(S - 3, S + 1, E), jnp.int32)
        cand = jnp.asarray(rng.integers(1, 3, E), jnp.float32)
        packed = lex_segment_argmin(cand, lab, src, dst, n, S)
        for x, y in zip(packed, lex_segment_argmin(cand, lab, src, dst, n)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        # vertices no edge enters read (+inf, I32_MAX, I32_MAX) either way
        assert (np.asarray(packed[2])[64:] == I32_MAX).all()
        assert int(np.asarray(packed[1])[:64].max()) <= S


def _body_scatters(lowered) -> tuple[int, int]:
    """Scatter ops of the lowered entry function before its ``while``, and
    from the ``while`` on (its condition and body)."""
    main = lowered.as_text().split("func.func private")[0]
    pre, _, loop = main.partition("stablehlo.while(")
    return pre.count('"stablehlo.scatter"('), loop.count('"stablehlo.scatter"(')


@pytest.mark.parametrize("mode", ["bucket", "dense"])
def test_relaxation_round_holds_two_scatters(mode):
    """A round's segment mins: two scatters with the packed key, where the
    three-pass argmin had three; past the key bound, three again.  Before
    the loop: init_state's two scatters and the out-degree's one."""
    g = from_edges(np.array([0, 1, 2], np.int32), np.array([1, 2, 3], np.int32),
                   np.array([1.0, 2.0, 3.0], np.float32), 4)
    kw = dict(mode=mode, delta=None, max_iters=None)
    # S with (S + 1) * 4 > 2**31 - 1, lowered from its shape alone
    big = jax.ShapeDtypeStruct((I32_MAX // 4 + 1,), jnp.int32)
    for seeds, passes in ((jnp.asarray([0, 3], jnp.int32), 2), (big, 3)):
        assert _body_scatters(_voronoi_cells.lower(g, seeds, **kw)) == (3, passes)
        # the stats state the passes the body holds
        _, stats = jax.eval_shape(lambda s: _voronoi_cells(g, s, **kw), seeds)
        assert stats.segmin_passes == passes
