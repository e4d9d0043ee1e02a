"""Distributed-ready graph containers for the Steiner core.

The paper partitions a symmetric, positively-weighted edge list across MPI
ranks. We mirror that with a flat COO edge list (both directions stored) that
is padded to a device-divisible length so it can be sharded with
``shard_map``/``pjit`` without ragged remainders.

Conventions
-----------
* vertices are ``int32`` ids in ``[0, n)``
* the edge list is *symmetric*: for every (u, v, w) the reverse (v, u, w) is
  also stored (matching the paper's ``2|E|`` directed-edge representation)
* padding edges are self-loops ``(0, 0, +inf)`` — they can never win a
  min-plus relaxation and contribute ``+inf`` only to masked lanes
* weights are ``float32`` in ``[1, inf)`` per the paper's distance function

Graphs larger than host RAM live on disk as ``.gstore`` directories
(:mod:`repro.graphstore`); ``GraphStore.to_graph()`` materializes this
container from the memmapped CSR, and ``GraphStore.ell(k)`` builds the
:class:`EllGraph` view straight from that CSR through the same
:func:`ell_from_csr` fill that :func:`to_ell` uses (their outputs are
asserted equal in tests/test_graphstore.py).
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

PAD_WEIGHT = jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Symmetric weighted graph in COO form (padded).

    Attributes:
      src: (E,) int32 source vertex per directed edge.
      dst: (E,) int32 destination vertex per directed edge.
      w:   (E,) float32 edge weight; ``+inf`` marks padding.
      n:   static number of vertices.
    """

    src: jax.Array
    dst: jax.Array
    w: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_edges(self) -> int:
        """Padded directed edge count (static)."""
        return self.src.shape[0]

    def degree(self) -> jax.Array:
        """Out-degree per vertex (padding excluded)."""
        real = jnp.isfinite(self.w)
        return jax.ops.segment_sum(real.astype(jnp.int32), self.src, self.n)


def from_edges(
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray,
    n: int,
    *,
    symmetrize: bool = True,
    pad_to: int = 1,
) -> Graph:
    """Builds a padded :class:`Graph` from host numpy arrays.

    Args:
      src, dst, w: directed edges (one direction if ``symmetrize``).
      n: vertex count.
      symmetrize: store both directions of every edge.
      pad_to: pad edge count up to a multiple of this (device divisibility).
    """
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    w = np.asarray(w, np.float32)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        w = np.concatenate([w, w])
    e = src.shape[0]
    padded = ((e + pad_to - 1) // pad_to) * pad_to
    if padded != e:
        pad = padded - e
        src = np.concatenate([src, np.zeros(pad, np.int32)])
        dst = np.concatenate([dst, np.zeros(pad, np.int32)])
        w = np.concatenate([w, np.full(pad, np.inf, np.float32)])
    return Graph(src=jnp.asarray(src), dst=jnp.asarray(dst), w=jnp.asarray(w), n=n)


def to_networkx(g: Graph):
    """Materializes an undirected networkx graph (tests / small graphs only)."""
    import networkx as nx

    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    gx = nx.Graph()
    gx.add_nodes_from(range(g.n))
    real = np.isfinite(w)
    for u, v, d in zip(src[real], dst[real], w[real]):
        uu, vv = int(u), int(v)
        if gx.has_edge(uu, vv):
            gx[uu][vv]["weight"] = min(gx[uu][vv]["weight"], float(d))
        else:
            gx.add_edge(uu, vv, weight=float(d))
    return gx


# ----------------------------------------------------------------------------
# ELL (padded adjacency) view — consumed by the Pallas min-plus kernel.
# ----------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EllGraph:
    """Padded row-major adjacency (ELLPACK) with high-degree row splitting.

    The paper's HavoqGT substrate splits high-degree "hub" vertices across
    ranks (vertex delegates). The TPU analogue: rows whose degree exceeds
    ``k`` are split into multiple ELL rows mapped back to the same vertex via
    ``row2v``, keeping the (rows, k) tile shape dense and MXU/VPU friendly.

    Attributes:
      nbr: (R, K) int32 neighbor ids; padding points at vertex 0.
      wgt: (R, K) float32 weights; padding is ``+inf``.
      row2v: (R,) int32 owning vertex of each ELL row.
      n: vertex count.
    """

    nbr: jax.Array
    wgt: jax.Array
    row2v: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))


def stable_argsort(keys: np.ndarray) -> np.ndarray:
    """``np.argsort(keys, kind="stable")`` for integer keys in [0, 2**32).

    Two LSD radix passes over 16-bit digits (numpy radix-sorts 16-bit
    types in O(E)), one when every key fits 16 bits: the same permutation
    as the comparison sort, several times faster at Graph500 edge counts.
    """
    keys = np.asarray(keys)
    order = np.argsort((keys & 0xFFFF).astype(np.uint16), kind="stable")
    if keys.size and int(keys.max()) >> 16:
        hi = (keys[order] >> 16).astype(np.uint16)
        order = order[np.argsort(hi, kind="stable")]
    return order


def to_ell(g: Graph, k: int, *, pad_rows_to: int = 1) -> EllGraph:
    """Converts COO → split-row ELL with row width ``k`` (host-side).

    Each vertex's neighbours keep their COO order (a stable sort by
    source), then fill its rows through :func:`ell_from_csr`.
    """
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    real = np.isfinite(w)
    src, dst, w = src[real], dst[real], w[real]
    order = stable_argsort(src)
    indptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=g.n))])
    return ell_from_csr(
        indptr, dst[order], w[order], g.n, k, pad_rows_to=pad_rows_to
    )


def ell_from_csr(
    indptr,
    indices,
    weights,
    n: int,
    k: int,
    *,
    pad_rows_to: int = 1,
    rows_per_chunk: int = 1 << 16,
) -> EllGraph:
    """Chunkwise CSR → split-row ELLPACK fill.

    A vertex of degree d gets ``max(1, ceil(d / k))`` consecutive rows;
    padding lanes point at vertex 0 with ``+inf`` weight, and padding
    rows (up to a multiple of ``pad_rows_to``) alias vertex 0.  Accepts
    memmaps or in-memory arrays; only ``indptr`` is materialized up
    front, the edge slabs are touched one vertex-chunk at a time.
    """
    indptr = np.asarray(indptr)
    counts = np.diff(indptr).astype(np.int64)
    rows_per_v = np.maximum(1, -(-counts // k))
    row_off = np.concatenate([[0], np.cumsum(rows_per_v)])
    n_rows = int(row_off[-1])
    padded_rows = -(-n_rows // pad_rows_to) * pad_rows_to
    nbr = np.zeros((padded_rows, k), np.int32)
    wgt = np.full((padded_rows, k), np.inf, np.float32)
    row2v = np.zeros(padded_rows, np.int32)
    row2v[:n_rows] = np.repeat(np.arange(n, dtype=np.int32), rows_per_v)
    flat_nbr = nbr.reshape(-1)
    flat_wgt = wgt.reshape(-1)
    for v0 in range(0, n, rows_per_chunk):
        v1 = min(v0 + rows_per_chunk, n)
        e0, e1 = int(indptr[v0]), int(indptr[v1])
        if e1 == e0:
            continue
        c = counts[v0:v1]
        edge_v = np.repeat(np.arange(v0, v1, dtype=np.int64), c)
        within = np.arange(e0, e1) - np.repeat(indptr[v0:v1], c)
        # consecutive split rows of one vertex are contiguous, so the
        # j-th edge of vertex v lands at flat slot row_off[v]*k + j
        flat = row_off[edge_v] * k + within
        flat_nbr[flat] = indices[e0:e1]
        flat_wgt[flat] = weights[e0:e1]
    return EllGraph(
        nbr=jnp.asarray(nbr),
        wgt=jnp.asarray(wgt),
        row2v=jnp.asarray(row2v),
        n=n,
    )


_ELL_MEMO_CAP = 16
_ell_memo: "dict[tuple[int, int], tuple[weakref.ref, EllGraph]]" = {}

# Per-Graph version tokens: a process-unique, never-reused integer per
# (graph object, mutation epoch).  ``id(g)`` is NOT a safe cache key — a
# garbage-collected Graph's id can be handed to a brand-new Graph, and a
# memo keyed on it would serve the dead graph's ELL view for the new one.
# Tokens are drawn from a monotonic counter and stashed on the instance,
# so they can never alias; the delta layer bumps them when it mutates a
# graph's arrays in place (see :func:`bump_graph_version`).
_token_counter = 0


def graph_token(g: Graph) -> int:
    """The graph's current version token (assigned lazily, never reused)."""
    tok = getattr(g, "_version_token", None)
    if tok is None:
        global _token_counter
        _token_counter += 1
        tok = _token_counter
        object.__setattr__(g, "_version_token", tok)
    return tok


def bump_graph_version(g: Graph) -> int:
    """Assigns a fresh token, invalidating every memoized view of ``g``.

    Callers that mutate a graph's buffers in place (the delta overlay
    layer, when it reweights a resident COO array) must bump so stale ELL
    views cannot be served; building a *new* Graph object needs no bump —
    fresh objects get fresh tokens.
    """
    global _token_counter
    _token_counter += 1
    object.__setattr__(g, "_version_token", _token_counter)
    return _token_counter


def ell_view_cached(g: Graph, k: int) -> EllGraph:
    """Memoized :func:`to_ell` keyed on ``(graph_token(g), k)``.

    ``to_ell`` is an O(E) host sort and fill — far more expensive than
    the solve it feeds when queries repeat against one resident graph.
    The key is the per-Graph version token (process-unique, never reused — unlike
    ``id()``, which the allocator recycles), so a new graph can never hit
    a dead graph's entry and a version bump drops stale views.  The memo
    holds only a weak reference to ``g`` (so retiring a graph frees its
    O(E) arrays and views); the table is bounded at ``_ELL_MEMO_CAP``
    entries (FIFO eviction).
    """
    key = (graph_token(g), int(k))
    hit = _ell_memo.get(key)
    if hit is not None and hit[0]() is g:
        return hit[1]
    ell = to_ell(g, k)
    while len(_ell_memo) >= _ELL_MEMO_CAP:
        _ell_memo.pop(next(iter(_ell_memo)))

    def _drop(ref, key=key):
        # collected graph → free its view immediately; guard against the
        # slot having been rebound (bounded-table eviction + re-insert)
        cur = _ell_memo.get(key)
        if cur is not None and cur[0] is ref:
            del _ell_memo[key]

    _ell_memo[key] = (weakref.ref(g, _drop), ell)
    return ell


# ----------------------------------------------------------------------------
# Destination-sorted COO view — consumed by the Pallas segment-min kernel and
# the frontier-compacted relaxation.
# ----------------------------------------------------------------------------


def sort_by_dst(g: Graph) -> Tuple[Graph, jax.Array]:
    """Returns a copy with edges stably sorted by destination, and the perm."""
    order = jnp.argsort(g.dst, stable=True)
    return (
        Graph(src=g.src[order], dst=g.dst[order], w=g.w[order], n=g.n),
        order,
    )
