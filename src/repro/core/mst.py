"""Minimum spanning tree of the distance graph G'1 — Alg. 2 Step 3.

The paper argues (§III) that because G'1 has at most C(|S|, 2) edges a
*sequential* MST (Boost Prim) replicated on every rank is the right design.
We provide the faithful analogue — :func:`prim_dense`, a fully vectorized
Prim over the dense pair matrix inside a ``fori_loop`` (O(S) steps × O(S)
vector work, replicated on every device) — plus a beyond-paper parallel
alternative, :func:`boruvka_dense` (O(log S) rounds of component-min +
pointer-jumping), which wins once |S| reaches the paper's 10K regime.

Both return a parent array over seed indices; ``parent[root] == root``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INF = jnp.inf


@jax.named_scope("mst")
def prim_dense(wmat: jax.Array) -> jax.Array:
    """Prim's MST over a dense (S, S) weight matrix (INF = non-edge).

    Returns parent: (S,) int32, parent[0] == 0 (root). Vertices in other
    components keep ``parent[v] == v`` (checked by callers via wmat).
    """
    S = wmat.shape[0]

    def body(_, carry):
        in_tree, best, best_from, parent = carry
        # next vertex: lexicographic (weight, id) argmin outside the tree
        masked = jnp.where(in_tree, INF, best)
        v = jnp.argmin(masked).astype(jnp.int32)  # jnp.argmin: first minimum
        ok = jnp.isfinite(masked[v])
        parent = parent.at[v].set(jnp.where(ok, best_from[v], parent[v]))
        in_tree = in_tree.at[v].set(in_tree[v] | ok)
        row = wmat[v]
        better = ok & (row < best) & ~in_tree
        best = jnp.where(better, row, best)
        best_from = jnp.where(better, v, best_from)
        return in_tree, best, best_from, parent

    in_tree0 = jnp.zeros((S,), jnp.bool_).at[0].set(True)
    best0 = wmat[0]
    best_from0 = jnp.zeros((S,), jnp.int32)
    parent0 = jnp.arange(S, dtype=jnp.int32)
    _, _, _, parent = jax.lax.fori_loop(
        0, S - 1, body, (in_tree0, best0, best_from0, parent0)
    )
    return parent


@jax.named_scope("mst")
def boruvka_dense(wmat: jax.Array) -> jax.Array:
    """Borůvka's MST over a dense (S, S) matrix — O(log S) parallel rounds.

    Deterministic via a *globally consistent* strict order on undirected
    edges: (weight, min(u,v), max(u,v)) — simultaneous per-component picks
    then all belong to the unique MST under that order (cut property), so
    no round can choose an unsafe edge. Returns the same parent-array
    encoding as Prim (chosen adjacency folded into a parent array rooted
    at 0).
    """
    S = wmat.shape[0]
    ids = jnp.arange(S, dtype=jnp.int32)
    lo_m = jnp.minimum(ids[:, None], ids[None, :])  # min(u, v) per entry
    hi_m = jnp.maximum(ids[:, None], ids[None, :])

    def round_body(carry):
        comp, chosen, rounds = carry
        # mask intra-component entries
        w = jnp.where(comp[:, None] == comp[None, :], INF, wmat)
        # per-component min weight
        row_min = jnp.min(w, axis=1)
        cmin = jax.ops.segment_min(row_min, comp, S)
        valid = jnp.isfinite(cmin)
        # among entries achieving cmin: min canonical (lo, hi) — two passes
        e0 = w == cmin[comp][:, None]
        rlo = jnp.min(jnp.where(e0, lo_m, S), axis=1)
        clo = jax.ops.segment_min(rlo, comp, S)
        e1 = e0 & (lo_m == clo[comp][:, None])
        rhi = jnp.min(jnp.where(e1, hi_m, S), axis=1)
        chi = jax.ops.segment_min(rhi, comp, S)
        u = jnp.where(valid, clo, 0)  # chosen undirected edge {u, v}
        v = jnp.where(valid, chi, 0)
        # record chosen edges (for valid components only)
        chosen = chosen.at[u, v].max(valid)
        chosen = chosen.at[v, u].max(valid)
        # hook: component root c adopts the component of the FOREIGN endpoint
        outside = jnp.where(comp[u] == ids, v, u)
        tgt = jnp.where(valid, comp[outside], ids)
        # break mutual (2-cycle) hooks: the smaller id becomes the root.
        # (With a strict total order on edges these are the only cycles.)
        mutual = (tgt[tgt] == ids) & (tgt != ids)
        tgt = jnp.where(mutual & (ids < tgt), ids, tgt)

        # pointer jumping to the chain root (acyclic after 2-cycle removal)
        def jump(c):
            return c[c]

        def jcond(c):
            return jnp.any(c != c[c])

        tgt = jax.lax.while_loop(jcond, jump, tgt)
        comp_new = tgt[comp]
        # canonical representative = min member id of the merged component
        comp_new = jax.ops.segment_min(ids, comp_new, S)[comp_new]
        return comp_new, chosen, rounds + 1

    def round_cond(carry):
        comp, _, rounds = carry
        w = jnp.where(comp[:, None] == comp[None, :], INF, wmat)
        return jnp.any(jnp.isfinite(w)) & (rounds < 2 * S + 2)

    comp0 = ids
    chosen0 = jnp.zeros((S, S), jnp.bool_)
    _, chosen, _ = jax.lax.while_loop(
        round_cond, round_body, (comp0, chosen0, jnp.int32(0))
    )
    return _root_parents(chosen)


def _root_parents(adj: jax.Array) -> jax.Array:
    """Folds a tree adjacency matrix into a parent array rooted at 0.

    BFS by repeated frontier expansion (at most S rounds; each round is a
    vectorized matrix step) — replicated small-matrix work, like the paper's
    replicated sequential MST.
    """
    S = adj.shape[0]
    ids = jnp.arange(S, dtype=jnp.int32)

    def body(carry):
        parent, visited, _ = carry
        # vertices adjacent to visited set and not yet visited adopt the
        # smallest visited neighbor as parent
        nbr_vis = adj & visited[None, :]
        has = jnp.any(nbr_vis, axis=1) & ~visited
        first = jnp.argmax(nbr_vis, axis=1).astype(jnp.int32)
        parent = jnp.where(has, first, parent)
        visited2 = visited | has
        return parent, visited2, jnp.any(visited2 != visited)

    def cond(carry):
        return carry[2]

    parent0 = ids
    visited0 = jnp.zeros((S,), jnp.bool_).at[0].set(True)
    parent, _, _ = jax.lax.while_loop(cond, body, (parent0, visited0, jnp.bool_(True)))
    return parent


def mst_pairs(parent: jax.Array, S: int) -> jax.Array:
    """Flat pair keys of the MST edges; S*S sentinel for the root row."""
    child = jnp.arange(S, dtype=jnp.int32)
    lo = jnp.minimum(parent, child)
    hi = jnp.maximum(parent, child)
    key = lo * S + hi
    return jnp.where(parent == child, S * S, key)
