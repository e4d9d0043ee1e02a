"""Steiner tree edge identification — Alg. 2 Steps 4-6 / Alg. 6 of the paper.

After the MST G'2 of the distance graph is known, the paper (a) prunes every
cross-cell edge whose seed pair is not an MST edge (keeping exactly one
bridge per MST pair — Alg. 5 EDGE_PRUNING_COLL) and (b) walks predecessor
pointers from both endpoints of each surviving bridge back to the owning
seeds, collecting in-cell shortest-path edges (Alg. 6 TREE_EDGE_ASYNC).

The asynchronous pointer-walk becomes *pointer doubling* here: we mark the
bridge endpoints and propagate "marked" along ``pred`` with a scatter-or
while squaring the pointer each round — O(log depth) data-parallel rounds
instead of a depth-long sequential chase.

Two identities keep this lookup-free:
  * weight of tree edge (pred[v], v)  =  dist[v] - dist[pred[v]]
  * weight of the bridge of MST pair p =  dmat[p] - dist[u_p] - dist[v_p]
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.mst import mst_pairs
from repro.core.voronoi import VoronoiState

INF = jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SteinerTree:
    """Dense encoding of the output Steiner tree G_S.

    In-cell path edges are ``(pred[v], v)`` for every ``path_edge[v]``;
    cross-cell bridges are ``(bridge_u[i], bridge_v[i])`` for every
    ``bridge_valid[i]`` (one per MST pair, paper Alg. 5 pruning).
    """

    in_tree_vertex: jax.Array  # (N,) bool
    path_edge: jax.Array  # (N,) bool
    bridge_u: jax.Array  # (S,) i32
    bridge_v: jax.Array  # (S,) i32
    bridge_w: jax.Array  # (S,) f32
    bridge_valid: jax.Array  # (S,) bool
    total_distance: jax.Array  # f32 scalar — D(G_S)
    num_edges: jax.Array  # i32 scalar — |E_S|


def bridge_endpoints(
    dmat: jax.Array,
    umat: jax.Array,
    vmat: jax.Array,
    dist: jax.Array,
    parent: jax.Array,
    S: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Alg. 2 Step 4: the surviving bridge (u, v, w) per MST pair.

    Row i describes the bridge of MST edge (parent[i], i); the root row
    (parent[i] == i) is invalid.
    """
    keys = mst_pairs(parent, S)  # (S,) flat pair keys; S*S for root
    valid = keys < S * S
    k = jnp.minimum(keys, S * S - 1)
    bu = jnp.where(valid, umat[k], 0)
    bv = jnp.where(valid, vmat[k], 0)
    bw = jnp.where(valid, dmat[k] - dist[bu] - dist[bv], 0.0)
    return bu, bv, bw, valid


def mark_paths(st: VoronoiState, endpoints: jax.Array) -> jax.Array:
    """Marks every vertex on the pred-chain from ``endpoints`` to its seed.

    Args:
      st: converged Voronoi state.
      endpoints: (N,) bool — initially-marked vertices (bridge endpoints).

    Returns:
      (N,) bool — all vertices on any marked chain (pointer doubling).
    """
    n = st.pred.shape[0]

    def body(carry):
        marked, ptr, _ = carry
        # scatter-or marked into ptr target, then square the pointer
        # NB: empty segments yield int32.min from segment_max → compare > 0.
        hit = jax.ops.segment_max(marked.astype(jnp.int32), ptr, n) > 0
        new = marked | hit
        return new, ptr[ptr], jnp.any(new != marked)

    def cond(carry):
        return carry[2]

    marked, _, _ = jax.lax.while_loop(
        cond, body, (endpoints, st.pred, jnp.bool_(True))
    )
    return marked


@jax.named_scope("extract")
def extract_tree(
    n: int,
    st: VoronoiState,
    dmat: jax.Array,
    umat: jax.Array,
    vmat: jax.Array,
    parent: jax.Array,
    S: int,
) -> SteinerTree:
    """Alg. 2 Steps 4-7: prune bridges, walk predecessors, total distance."""
    bu, bv, bw, bvalid = bridge_endpoints(dmat, umat, vmat, st.dist, parent, S)
    endpoints = jnp.zeros((n,), jnp.bool_)
    endpoints = endpoints.at[bu].max(bvalid)
    endpoints = endpoints.at[bv].max(bvalid)
    marked = mark_paths(st, endpoints)

    # In-cell tree edges: (pred[v], v) for marked non-root vertices.
    path_edge = marked & (st.pred != jnp.arange(n, dtype=jnp.int32))
    path_w = jnp.where(path_edge, st.dist - st.dist[st.pred], 0.0)
    total = jnp.sum(path_w) + jnp.sum(bw)
    nedges = jnp.sum(path_edge) + jnp.sum(bvalid)
    return SteinerTree(
        in_tree_vertex=marked,
        path_edge=path_edge,
        bridge_u=bu,
        bridge_v=bv,
        bridge_w=bw,
        bridge_valid=bvalid,
        total_distance=total,
        num_edges=nedges.astype(jnp.int32),
    )


def tree_edge_sets(st: VoronoiState, tree: SteinerTree, n_lanes=None):
    """Host-side: the undirected edge set {(u, v)} of G_S per batch lane.

    The ONE edge-materialization implementation — the single-query
    :func:`tree_edge_list` and the serve engine's per-lane result
    assembly both delegate here.

    Args:
      st, tree: converged state + extracted tree; arrays may carry a
        leading (B,) batch axis (the "batch" backend's output) or none
        (one lane).
      n_lanes: materialize only the first ``n_lanes`` lanes (the serve
        engine's distinct-query prefix; the rest are inert padding).

    Returns:
      list of ``frozenset[(u, v)]``, one per materialized lane.
    """
    import numpy as np

    pred = np.atleast_2d(np.asarray(st.pred))
    pe = np.atleast_2d(np.asarray(tree.path_edge))
    bu = np.atleast_2d(np.asarray(tree.bridge_u))
    bv = np.atleast_2d(np.asarray(tree.bridge_v))
    bvalid = np.atleast_2d(np.asarray(tree.bridge_valid))
    lanes = pe.shape[0] if n_lanes is None else n_lanes
    out = []
    for i in range(lanes):
        es = set()
        for v in np.nonzero(pe[i])[0]:
            a, b = int(pred[i, v]), int(v)
            es.add((min(a, b), max(a, b)))
        for j in np.nonzero(bvalid[i])[0]:
            a, b = int(bu[i, j]), int(bv[i, j])
            es.add((min(a, b), max(a, b)))
        out.append(frozenset(es))
    return out


def tree_edge_list(st: VoronoiState, tree: SteinerTree):
    """Host-side: materializes the undirected edge set {(u, v)} of G_S
    (single lane; thin wrapper over :func:`tree_edge_sets`)."""
    return set(tree_edge_sets(st, tree)[0])
