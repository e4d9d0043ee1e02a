"""Pallas TPU kernel: scatter-free min-plus ELL relaxation.

The Voronoi-cell hot loop (paper Alg. 4) is, per destination vertex v,

    (dist, lab, pred)[v]  ←  lex-min over incoming edges (u, v, w) of
                             (dist[u] + w, lab[u], u)

On MPI this is an asynchronous scatter of messages; on TPU we invert it
into a *gather + row reduction* over the padded ELL adjacency (rows =
destination vertices, split at width K — the HavoqGT "vertex delegate"
analogue, see ``repro.core.graph.to_ell``). No scatter appears anywhere.

The neighbour gather ``dist[nbr]`` / ``lab[nbr]`` runs in XLA ahead of
the ``pallas_call``: Mosaic lowers no data-dependent gather from a
vector, and an XLA gather over HBM has no VMEM bound on N.  The gathered
tiles are laid out (K, R), rows on lanes, so each grid step reduces four
(K, BR) tiles — gathered distances, gathered labels, weights, neighbour
ids — over sublanes and writes lane-dense (1, BR) minima.  (A (BR,)
output block would have to match XLA's 1024-element tiling of a 1-D
array, which ties BR to 1024.)

dtypes: distances/weights f32 or bf16 (upcast to f32 in the kernel); ids
int32. Lexicographic identity: (+inf, INT32_MAX, INT32_MAX).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

IMAX = jnp.iinfo(jnp.int32).max


def _col_lexmin(cand, lab, src):
    """Per-column lexicographic argmin of (K, BR) (cand, lab, src) tiles,
    kept (1, BR)."""
    m = jnp.min(cand, axis=0, keepdims=True)
    e1 = cand == m
    ml = jnp.min(jnp.where(e1, lab, IMAX), axis=0, keepdims=True)
    e2 = e1 & (lab == ml)
    ms = jnp.min(jnp.where(e2, src, IMAX), axis=0, keepdims=True)
    return m, ml, ms


def _kernel(dn_ref, ln_ref, wgt_ref, nbr_ref, out_d, out_l, out_s):
    cand = dn_ref[...].astype(jnp.float32) + wgt_ref[...].astype(jnp.float32)
    ok = jnp.isfinite(cand)
    lab = jnp.where(ok, ln_ref[...], IMAX)
    srcm = jnp.where(ok, nbr_ref[...], IMAX)
    m, ml, ms = _col_lexmin(cand, lab, srcm)
    out_d[...] = m
    out_l[...] = ml
    out_s[...] = ms


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def minplus_call(
    nbr: jax.Array,
    wgt: jax.Array,
    dist: jax.Array,
    lab: jax.Array,
    *,
    block_rows: int = 256,
    interpret: bool | None = None,
):
    """Min-plus relaxation of (R, K) ELL tiles.

    Args:
      nbr: (R, K) int32 neighbor ids (padding → any id with wgt=+inf).
      wgt: (R, K) weights (f32/bf16; +inf padding).
      dist: (N,) distances (f32/bf16).
      lab: (N,) int32 labels.
      block_rows: rows per grid step; R must be a multiple.
      interpret: None → :func:`default_interpret` per platform.

    Returns:
      (m, ml, ms): (R,) f32 / i32 / i32 per-row lexicographic minima.
    """
    if interpret is None:
        interpret = default_interpret()
    R, K = nbr.shape
    assert R % block_rows == 0, (R, block_rows)
    tile = pl.BlockSpec((K, block_rows), lambda r: (0, r))
    row = pl.BlockSpec((1, block_rows), lambda r: (0, r))
    nbr_t = nbr.T
    m, ml, ms = pl.pallas_call(
        _kernel,
        grid=(R // block_rows,),
        in_specs=[tile, tile, tile, tile],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, R), jnp.float32),
            jax.ShapeDtypeStruct((1, R), jnp.int32),
            jax.ShapeDtypeStruct((1, R), jnp.int32),
        ],
        interpret=interpret,
    )(dist[nbr_t], lab[nbr_t], wgt.T, nbr_t)
    return m[0], ml[0], ms[0]
