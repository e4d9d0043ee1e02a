"""The plain reference: Mehlhorn's 2-approximate Steiner tree, on the host.

It imports nothing of the program and follows the semantics the program
states (its ``core/voronoi.py``, ``distance_graph.py``, ``mst.py`` and
``tree.py`` docstrings), written out here independently:

1. Voronoi cells.  ``dist[v]`` is the distance to the nearest seed;
   ``lab[v]`` the least seed index among the seeds at that distance
   (``S`` where unreached; a vertex listed at several indices takes the
   least); ``pred[v]`` the least neighbour ``u`` with
   ``dist[u] + w(u, v) == dist[v]`` and ``lab[u] == lab[v]`` (``v`` itself
   for seeds and unreached vertices).
2. Distance graph G'1.  For each pair of cells the cross edge minimising
   ``(dist[u] + w + dist[v], u, v)``, oriented so that ``u`` lies in the
   cell of the lower seed index.
3. MST of G'1 by Prim from seed index 0: the next vertex is the least
   ``(best weight, index)`` outside the tree, and a vertex's best edge is
   replaced only by a strictly lighter one.
4. Tree.  The bridge of every MST pair, and every edge ``(pred[v], v)`` on
   the predecessor chains from the bridges' endpoints to their seeds.  The
   total is the sum of those edges' weights.

``rnd`` rounds the result of every addition; the identity gives the exact
answer (float64 sums of integer weights), :func:`round_bf16` the control in
bfloat16.
"""

from __future__ import annotations

import dataclasses
import heapq

import numpy as np


def round_bf16(x) -> np.ndarray:
    """Rounds float32 values to the nearest bfloat16 (ties to even)."""
    u = np.array(x, dtype=np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def exact(x):
    return x


@dataclasses.dataclass
class Graph:
    """Both directions of every undirected edge, and the undirected lists."""

    n: int
    src: np.ndarray  # undirected, one direction
    dst: np.ndarray
    w: np.ndarray
    a: np.ndarray  # directed
    b: np.ndarray
    ww: np.ndarray

    @classmethod
    def from_edges(cls, src, dst, w, n):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        w = np.asarray(w, np.float64)
        return cls(n, src, dst, w, np.concatenate([src, dst]),
                   np.concatenate([dst, src]), np.concatenate([w, w]))


@dataclasses.dataclass
class Tree:
    dist: np.ndarray
    lab: np.ndarray
    pred: np.ndarray
    edges: set  # {(min, max)}
    total: float
    num_edges: int
    path_edge: np.ndarray  # (n,) bool: (pred[v], v) is a tree edge
    bridge_u: np.ndarray  # one bridge per MST pair
    bridge_v: np.ndarray
    bridge_w: np.ndarray


def _dijkstra(g: Graph, seeds: np.ndarray) -> np.ndarray:
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    # parallel edges stay separate entries, so the lightest one counts
    order = np.argsort(g.a, kind="stable")
    indptr = np.zeros(g.n + 1, np.int64)
    np.cumsum(np.bincount(g.a, minlength=g.n), out=indptr[1:])
    csr = sp.csr_matrix((g.ww[order], g.b[order], indptr), shape=(g.n, g.n))
    return dijkstra(csr, indices=np.unique(seeds), min_only=True)


def _bellman_ford(g: Graph, seeds: np.ndarray, rnd) -> np.ndarray:
    """Synchronous min-plus rounds to the fixpoint, every sum rounded."""
    order = np.argsort(g.b, kind="stable")
    a, b, w = g.a[order], g.b[order], g.ww[order].astype(np.float32)
    heads = np.flatnonzero(np.r_[True, b[1:] != b[:-1]])
    dst = b[heads]
    dist = np.full(g.n, np.inf, np.float32)
    dist[seeds] = 0.0
    while True:
        m = np.minimum.reduceat(rnd(dist[a] + w), heads)
        new = dist.copy()
        new[dst] = np.minimum(dist[dst], m)
        if np.array_equal(new, dist):
            return dist
        dist = new


def voronoi(g: Graph, seeds: np.ndarray, rnd=exact):
    """``(dist, lab, pred)`` as step 1 of the module docstring defines them."""
    seeds = np.asarray(seeds, np.int64)
    S = seeds.size
    dist = _dijkstra(g, seeds) if rnd is exact else _bellman_ford(g, seeds, rnd)
    lab = np.full(g.n, S, np.int64)
    np.minimum.at(lab, seeds, np.arange(S))
    pred = np.arange(g.n, dtype=np.int64)
    cand = rnd(dist[g.a] + g.ww.astype(dist.dtype))
    tight = np.isfinite(cand) & (cand == dist[g.b]) & (dist[g.b] > 0)
    ta, tb = g.a[tight], g.b[tight]
    # a vertex's label is the least label of its tight in-neighbours, which
    # lie nearer; settle the vertices in order of distance
    order = np.lexsort((tb, dist[tb]))
    ta, tb = ta[order], tb[order]
    level = dist[tb]
    cuts = np.flatnonzero(np.r_[True, level[1:] != level[:-1], True])
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        va, vb = ta[lo:hi], tb[lo:hi]
        heads = np.flatnonzero(np.r_[True, vb[1:] != vb[:-1]])
        lab[vb[heads]] = np.minimum.reduceat(lab[va], heads)
    same = lab[ta] == lab[tb]
    best = np.full(g.n, np.iinfo(np.int64).max)
    np.minimum.at(best, tb[same], ta[same])
    has = best < np.iinfo(np.int64).max
    pred[has] = best[has]
    return dist, lab, pred


def _prim(S: int, lo: np.ndarray, hi: np.ndarray, d: np.ndarray) -> np.ndarray:
    """Parent array over seed indices (``parent[0] == 0``; vertices not
    reached keep ``parent[i] == i``)."""
    adj = [[] for _ in range(S)]
    for i, j, x in zip(lo.tolist(), hi.tolist(), d.tolist()):
        adj[i].append((j, x))
        adj[j].append((i, x))
    best = [np.inf] * S
    best_from = [0] * S
    parent = list(range(S))
    in_tree = [False] * S
    heap: list = []

    def add(v):
        in_tree[v] = True
        for u, x in adj[v]:
            if not in_tree[u] and x < best[u]:
                best[u], best_from[u] = x, v
                heapq.heappush(heap, (x, u))

    add(0)
    while heap:
        x, u = heapq.heappop(heap)
        if in_tree[u] or x != best[u]:
            continue
        parent[u] = best_from[u]
        add(u)
    return np.asarray(parent, np.int64)


def steiner(g: Graph, seeds, rnd=exact) -> Tree:
    """The reference tree of ``seeds`` (seed order matters for ties)."""
    seeds = np.asarray(seeds, np.int64)
    S = seeds.size
    dist, lab, pred = voronoi(g, seeds, rnd)
    wt = g.ww.astype(dist.dtype)
    la, lb = lab[g.a], lab[g.b]
    cross = (la != lb) & (la < S) & (lb < S)
    a, b, la, lb = g.a[cross], g.b[cross], la[cross], lb[cross]
    d = rnd(rnd(dist[a] + wt[cross]) + dist[b])
    low_first = la < lb
    cu, cv = np.where(low_first, a, b), np.where(low_first, b, a)
    key = np.minimum(la, lb) * S + np.maximum(la, lb)
    order = np.lexsort((cv, cu, d, key))
    first = order[np.r_[True, key[order][1:] != key[order][:-1]]]
    pkey, pd, pu, pv = key[first], d[first], cu[first], cv[first]
    parent = _prim(S, pkey // S, pkey % S, pd)
    child = np.flatnonzero(parent != np.arange(S))
    want = np.minimum(parent[child], child) * S + np.maximum(parent[child], child)
    at = np.searchsorted(pkey, want)
    bu, bv = pu[at], pv[at]
    bw = rnd(rnd(pd[at] - dist[bu]) - dist[bv])
    marked = np.zeros(g.n, bool)
    front = np.unique(np.concatenate([bu, bv]))
    while front.size:
        marked[front] = True
        nxt = pred[front]
        front = np.unique(nxt[~marked[nxt]])
    path = np.flatnonzero(marked & (pred != np.arange(g.n)))
    pw = rnd(dist[path] - dist[pred[path]])
    if rnd is exact:
        total = np.sum(pw) + np.sum(bw)
    else:
        total = np.float32(0.0)
        for x in np.concatenate([pw, bw]).astype(np.float32):
            total = rnd(np.float32(total + x))
    edges = {(min(u, v), max(u, v)) for u, v in zip(pred[path].tolist(), path.tolist())}
    edges |= {(min(u, v), max(u, v)) for u, v in zip(bu.tolist(), bv.tolist())}
    path_edge = np.zeros(g.n, bool)
    path_edge[path] = True
    return Tree(dist, lab, pred, edges, float(total), int(path.size + bu.size),
                path_edge, bu, bv, bw)
