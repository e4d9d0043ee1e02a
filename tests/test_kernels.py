"""Pallas kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.minplus.minplus import minplus_call
from repro.kernels.minplus.ref import minplus_ref
from repro.kernels.segmin.ops import segmin_bucketed
from repro.kernels.segmin.ref import segmin_bucketed_ref

IMAX = np.iinfo(np.int32).max


def _ell_inputs(R, K, N, dtype, seed):
    rng = np.random.default_rng(seed)
    nbr = jnp.asarray(rng.integers(0, N, (R, K)), jnp.int32)
    wgt = np.asarray(rng.uniform(1, 10, (R, K)), np.float32)
    wgt[rng.random((R, K)) < 0.25] = np.inf
    dist = np.where(rng.random(N) < 0.5, rng.uniform(0, 50, N), np.inf)
    lab = jnp.asarray(rng.integers(0, 7, N), jnp.int32)
    return nbr, jnp.asarray(wgt, dtype), jnp.asarray(dist, dtype), lab


def _triples_equal(a, b, dist_rtol=0.0):
    am, al, as_ = (np.asarray(x) for x in a)
    bm, bl, bs = (np.asarray(x) for x in b)
    if dist_rtol:
        fin = np.isfinite(am) & np.isfinite(bm)
        assert np.array_equal(np.isfinite(am), np.isfinite(bm))
        np.testing.assert_allclose(am[fin], bm[fin], rtol=dist_rtol)
    else:
        np.testing.assert_array_equal(am, bm)
    np.testing.assert_array_equal(al, bl)
    np.testing.assert_array_equal(as_, bs)


@pytest.mark.parametrize("shape", [(128, 4, 64), (256, 8, 300), (512, 16, 1024), (128, 32, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_minplus_resident_sweep(shape, dtype):
    R, K, N = shape
    nbr, wgt, dist, lab = _ell_inputs(R, K, N, dtype, seed=R + K)
    out = minplus_call(nbr, wgt, dist, lab, block_rows=min(128, R))
    ref = minplus_ref(nbr, wgt, dist, lab)
    # bf16 inputs are upcast identically in kernel and oracle → exact match
    _triples_equal(out, ref)


def _segmin_inputs(NB, EB, VB, dtype, seed):
    rng = np.random.default_rng(seed)
    cand = np.where(
        rng.random((NB, EB)) < 0.7, rng.uniform(0, 100, (NB, EB)), np.inf
    )
    ldst = jnp.asarray(rng.integers(0, VB, (NB, EB)), jnp.int32)
    lab = jnp.asarray(rng.integers(0, 9, (NB, EB)), jnp.int32)
    src = jnp.asarray(rng.integers(0, 10**6, (NB, EB)), jnp.int32)
    return jnp.asarray(cand, dtype), ldst, lab, src


@pytest.mark.parametrize("shape", [(1, 256, 32), (4, 512, 64), (2, 1000, 128), (8, 64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segmin_sweep(shape, dtype):
    NB, EB, VB = shape
    cand, ldst, lab, src = _segmin_inputs(NB, EB, VB, dtype, seed=EB)
    out = segmin_bucketed(cand, ldst, lab, src, vb=VB, edge_block=256)
    ref = segmin_bucketed_ref(cand, ldst, lab, src, VB)
    _triples_equal(out, ref)


def test_segmin_all_padding():
    """Degenerate tile: every lane inert → identity triple everywhere."""
    NB, EB, VB = 2, 128, 16
    cand = jnp.full((NB, EB), jnp.inf, jnp.float32)
    z = jnp.zeros((NB, EB), jnp.int32)
    m, ml, ms = segmin_bucketed(cand, z, z, z, vb=VB, edge_block=128)
    assert np.all(np.isinf(np.asarray(m)))
    assert np.all(np.asarray(ml) == IMAX)
    assert np.all(np.asarray(ms) == IMAX)


def test_pallas_iteration_cap_clamped():
    """Regression: the default 4n+64 round cap must clamp to int32 range —
    an overflowed (negative) cap exits the while_loop unconverged."""
    from repro.kernels.minplus.ops import _cap

    big_default = 4 * 2**30 + 64  # what 4n+64 yields for n = 2**30
    assert int(_cap(None, big_default)) == 2**31 - 2
    assert int(_cap(7, big_default)) == 7  # explicit max_iters wins
    assert int(_cap(None, 100)) == 100  # small graphs unaffected


@pytest.mark.parametrize(
    "backend,want",
    [("tpu", False), ("gpu", False), ("cpu", True), ("METAL", True)],
)
def test_default_interpret_platform_policy(monkeypatch, backend, want):
    """Compiled on TPU/GPU, interpreter fallback on anything else."""
    import jax

    from repro.kernels import default_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert default_interpret() is want


def test_minplus_empty_rows():
    """Rows whose every lane is +inf padding return the identity triple."""
    R, K, N = 128, 8, 64
    nbr = jnp.zeros((R, K), jnp.int32)
    wgt = jnp.full((R, K), jnp.inf, jnp.float32)
    dist = jnp.zeros((N,), jnp.float32)
    lab = jnp.zeros((N,), jnp.int32)
    m, ml, ms = minplus_call(nbr, wgt, dist, lab, block_rows=128)
    assert np.all(np.isinf(np.asarray(m)))
    assert np.all(np.asarray(ml) == IMAX)
    assert np.all(np.asarray(ms) == IMAX)
