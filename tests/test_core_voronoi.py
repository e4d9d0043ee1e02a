"""Voronoi cell computation vs the multi-source Dijkstra oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import from_edges, to_ell
from repro.core import ref
from repro.core.voronoi import voronoi_cells, voronoi_cells_frontier
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
