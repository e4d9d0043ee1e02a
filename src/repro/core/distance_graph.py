"""Distance graph G'1 construction — Alg. 2 Step 2 / Alg. 5 of the paper.

For every pair of Voronoi cells (s, t) that a *cross-cell* data-graph edge
(u, v) bridges, compute

    d'1(s, t) = min over bridges of  d1(s, u) + d(u, v) + d1(v, t)

together with the bridging edge (u, v) that realizes the minimum. The paper
does a per-rank local reduction followed by an MPI_Allreduce(MPI_MIN) on
distances, then a second Allreduce(MPI_MIN) on endpoint vertex ids to make
the winning bridge unique (Alg. 5 EDGE_PRUNING_COLL). We mirror that with a
three-pass lexicographic segment-min on (d', u, v), where the bridge is
canonically oriented so that u lies in the lower-indexed seed's cell.

The pair tables are dense of size S² (flat key ``min*S + max``). For the
paper's largest |S| = 10K this is the same ~50M-entry buffer the paper
allreduces (§V-F); the chunked-collective option lives in the distributed
driver (:mod:`repro.core.dist_steiner`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.graph import Graph
from repro.core.voronoi import VoronoiState

INF = jnp.inf
IMAX = jnp.iinfo(jnp.int32).max


def pair_key(a: jax.Array, b: jax.Array, S: int) -> jax.Array:
    """Canonical flat key for an unordered seed-index pair (a != b)."""
    lo = jnp.minimum(a, b)
    hi = jnp.maximum(a, b)
    return lo * S + hi


def local_pair_tables(
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    dist_src: jax.Array,
    dist_dst: jax.Array,
    lab_src: jax.Array,
    lab_dst: jax.Array,
    S: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-shard pair tables over an arbitrary edge slice (the Alg. 5 local
    reduction). All inputs are (e,) arrays; gathers happen in the caller so
    this kernel works for both the single-device and shard_map paths.

    Returns (dmat, umat, vmat), each (S*S,):
      dmat — min bridge distance per pair (INF if none)
      umat — endpoint in the lower seed's cell of the winning bridge
      vmat — endpoint in the higher seed's cell
    Ties: lexicographic (d', u, v) — deterministic and mesh-shape invariant.
    """
    cross = (lab_src != lab_dst) & (lab_src < S) & (lab_dst < S) & jnp.isfinite(w)
    d = dist_src + w + dist_dst
    d = jnp.where(cross, d, INF)
    key = jnp.where(cross, pair_key(lab_src, lab_dst, S), S * S)
    lower_first = lab_src < lab_dst
    cu = jnp.where(lower_first, src, dst)
    cv = jnp.where(lower_first, dst, src)

    dmat = jax.ops.segment_min(d, key, S * S + 1)[: S * S]
    e1 = cross & (d == dmat[key])
    umat = jax.ops.segment_min(jnp.where(e1, cu, IMAX), key, S * S + 1)[: S * S]
    e2 = e1 & (cu == umat[key])
    vmat = jax.ops.segment_min(jnp.where(e2, cv, IMAX), key, S * S + 1)[: S * S]
    return dmat, umat, vmat


@jax.named_scope("distance_graph")
def distance_graph(
    g: Graph, st: VoronoiState, S: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-device G'1: gathers per-edge state then reduces pair tables."""
    return local_pair_tables(
        g.src,
        g.dst,
        g.w,
        st.dist[g.src],
        st.dist[g.dst],
        st.lab[g.src],
        st.lab[g.dst],
        S,
    )
