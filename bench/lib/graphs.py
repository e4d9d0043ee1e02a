"""Graph500 Kronecker (RMAT) graphs made from a seed, and their largest component.

A copy of the program's generator (``repro.graphstore.ingest.RmatEdgeSource``)
kept with the benchmark so that a change to the program cannot change the
yardstick.  Edges and weights are a function of ``(scale, edge_factor, a, b,
c, max_weight, graph_seed)`` alone, ``graph_seed`` being fixed in the
configuration: they are drawn in fixed blocks of ``BLOCK_EDGES``, block ``i``
from ``SeedSequence((graph_seed, 2 + i))``.  The run's seed draws Graph500's
permutation of the vertex ids, from ``SeedSequence((seed, 0))``.  So every
run solves the same graph under other names: the same work, other inputs.

A configuration may set ``id_blocks`` (``B``, dividing ``n``), the number
of contiguous blocks of ``n / B`` ids over which the system under test
spreads the vertices, one block a chip.  A deployment partitions its graph
once, when it loads it, so which vertices share a block is fixed with the
graph: a permutation ``base`` from ``SeedSequence((graph_seed, 1))`` puts
each vertex in its block, and the run's seed then only renames the ids
inside each block, one permutation of each block from ``SeedSequence((seed,
0))``.  Every run then gives each block the same vertices and edges.
Without it (or with 1) the run's seed permutes all ids, as Graph500 does.
Unlike the program's default, no connecting path is added: the graph is the
Graph500 one, with its many small components, and queries draw their seeds
from the largest.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_EDGES = 1 << 16


def _block(scale, a, b, c, max_weight, seed, i, m):
    rng = np.random.default_rng(np.random.SeedSequence((seed, 2 + i)))
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for lvl in range(scale):
        r = rng.random(m)
        # quadrants a | b / c | d of the Kronecker initiator
        src += (r >= a + b).astype(np.int64) << lvl
        dst += (((r >= a) & (r < a + b)) | (r >= a + b + c)).astype(np.int64) << lvl
    keep = src != dst
    src, dst = src[keep], dst[keep]
    w = rng.integers(1, max_weight + 1, size=src.shape[0])
    return src.astype(np.int32), dst.astype(np.int32), w.astype(np.float32)


def rmat(scale: int, edge_factor: int, *, a: float, b: float, c: float,
         max_weight: int, seed: int):
    """Returns ``(src, dst, w, n)`` before the permutation of the ids: one
    direction of every undirected edge, ``n = 2**scale``, self-loops
    dropped, parallel edges kept, integer weights uniform in
    ``[1, max_weight]`` as float32."""
    if not (0 < a and 0 <= b and 0 <= c and a + b + c < 1):
        raise ValueError(f"bad RMAT probabilities a={a} b={b} c={c}")
    n = 1 << scale
    m = edge_factor * n
    spans = [(i, min(BLOCK_EDGES, m - lo)) for i, lo in enumerate(range(0, m, BLOCK_EDGES))]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        parts = list(pool.map(
            lambda s: _block(scale, a, b, c, max_weight, seed, s[0], s[1]), spans))
    src = np.concatenate([p[0] for p in parts])
    dst = np.concatenate([p[1] for p in parts])
    w = np.concatenate([p[2] for p in parts])
    return src, dst, w, n


def largest_component(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Sorted vertex ids of the largest connected component."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    adj = sp.coo_matrix((np.ones(src.shape[0], np.int8), (src, dst)), shape=(n, n)).tocsr()
    _, comp = connected_components(adj, directed=True, connection="weak")
    return np.flatnonzero(comp == np.argmax(np.bincount(comp))).astype(np.int32)


def make_graph(spec: dict, seed: int):
    """``(src, dst, w, n, component)``: the configuration's graph with its ids
    permuted by ``seed`` (``id_permutation``), and its largest component, in
    the order of the ids before the permutation, so that a draw by position
    picks the same vertices under every seed's names."""
    if spec["generator"] != "graph500_rmat":
        raise ValueError(f"unknown graph generator {spec['generator']!r}")
    src, dst, w, n = rmat(spec["scale"], spec["edge_factor"], a=spec["a"], b=spec["b"],
                          c=spec["c"], max_weight=spec["max_weight"], seed=spec["graph_seed"])
    component = largest_component(n, src, dst)
    perm = id_permutation(n, int(spec.get("id_blocks", 1)), spec["graph_seed"], seed)
    return perm[src], perm[dst], w, n, perm[component]


def id_permutation(n: int, blocks: int, graph_seed: int, seed: int) -> np.ndarray:
    """The new id of each vertex: a permutation of all ``n`` ids by ``seed``
    where ``blocks`` is 1, else ``local[base]``, ``base`` a permutation of
    all ids by ``graph_seed`` and ``local`` one by ``seed`` of each block of
    ``n / blocks`` ids, so that ``perm[v] // (n / blocks)`` is fixed by
    ``graph_seed`` alone."""
    rng = np.random.default_rng(np.random.SeedSequence((seed, 0)))
    if blocks == 1:
        return rng.permutation(n).astype(np.int32)
    if blocks < 1 or n % blocks:
        raise ValueError(f"id_blocks={blocks} does not divide {n} vertices into equal blocks")
    base = np.random.default_rng(np.random.SeedSequence((graph_seed, 1))).permutation(n)
    local = rng.permuted(np.arange(n).reshape(blocks, n // blocks), axis=1).reshape(n)
    return local[base].astype(np.int32)
