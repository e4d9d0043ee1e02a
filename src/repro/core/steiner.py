"""End-to-end 2-approximation Steiner tree — the paper's Alg. 2 / Alg. 3.

Single-process (one device) pipeline; the multi-device shard_map version
lives in :mod:`repro.core.dist_steiner`. Both run the same five stages:

  1. Voronoi cells (multi-source shortest paths)      — voronoi.py
  2. distance graph G'1 (min cross-cell bridges)      — distance_graph.py
  3. MST G'2 of G'1 (replicated, Prim or Borůvka)     — mst.py
  4. bridge pruning to the MST pairs                  — tree.py
  5. predecessor walk → tree edges, total distance    — tree.py

Approximation bound: D(G_S)/D_min <= 2(1 - 1/l) by Mehlhorn's proof [17]
(every MST of G'1 is an MST of the complete seed distance graph G_1).

Every stage is batch-safe: :func:`run_pipeline` is the unjitted pipeline
body, safe to compose under ``jax.vmap`` / ``jax.jit`` — the multi-query
serving layer (:mod:`repro.serve.batch`) vmaps it over a leading query
axis against one resident graph.

The jitted executables themselves live in :mod:`repro.solver.backends`
(the unified solver registry); :func:`steiner_tree` below is a thin
delegating shim kept for source compatibility.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import distance_graph as dgmod
from repro.core import mst as mstmod
from repro.core import tree as treemod
from repro.core import voronoi as vmod
from repro.core.graph import EllGraph, Graph


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SteinerResult:
    tree: treemod.SteinerTree
    state: vmod.VoronoiState
    stats: vmod.VoronoiStats
    parent: jax.Array  # (S,) MST parent over seed indices
    dmat: jax.Array  # (S*S,) distance-graph weights


def finish_pipeline(
    g: Graph,
    st: vmod.VoronoiState,
    stats: vmod.VoronoiStats,
    S: int,
    mst_algo: str = "prim",
) -> SteinerResult:
    """Stages 2-5 (distance graph → MST → pruning → walk) from converged
    Voronoi state. Pure jnp — vmap/jit-compose freely."""
    dmat, umat, vmat = dgmod.distance_graph(g, st, S)
    with jax.named_scope("mst"):
        wmat = dmat.reshape(S, S)
        wmat = jnp.minimum(wmat, wmat.T)  # symmetrize upper-triangular table
        wmat = jnp.where(jnp.eye(S, dtype=bool), jnp.inf, wmat)
    if mst_algo == "prim":
        parent = mstmod.prim_dense(wmat)
    elif mst_algo == "boruvka":
        parent = mstmod.boruvka_dense(wmat)
    else:
        raise ValueError(f"unknown mst_algo: {mst_algo!r}")
    tree = treemod.extract_tree(g.n, st, dmat, umat, vmat, parent, S)
    return SteinerResult(tree=tree, state=st, stats=stats, parent=parent, dmat=dmat)


def run_pipeline(
    g: Graph,
    seeds: jax.Array,
    *,
    num_seeds: Optional[int] = None,
    mode: str = "bucket",
    mst_algo: str = "prim",
    delta: Optional[float] = None,
    max_iters: Optional[int] = None,
    telemetry_rounds: int = 0,
    init: Optional[vmod.VoronoiState] = None,
) -> SteinerResult:
    """Unjitted full pipeline over the COO graph (modes "dense"/"bucket").

    This is the trace-level entry point: the solver backends
    (:mod:`repro.solver.backends`) jit it for the one-query case
    (``_exec_single_coo``) and vmap it over a (B, S) seed batch
    (``_exec_batch``); :func:`steiner_tree` and
    :func:`repro.serve.batch.steiner_tree_batch` are shims over those.
    ``telemetry_rounds`` (static) sizes the per-round telemetry buffer
    returned as ``result.stats.history`` (0 → None).  ``init`` warm-starts
    the Voronoi relaxation (see ``voronoi_cells`` for the soundness
    contract — used by the delta layer's affected-cell re-solve).
    """
    S = int(num_seeds if num_seeds is not None else seeds.shape[0])
    st, stats = vmod.voronoi_cells(
        g,
        seeds,
        mode=mode,
        delta=delta,
        max_iters=max_iters,
        telemetry_rounds=telemetry_rounds,
        init=init,
    )
    return finish_pipeline(g, st, stats, S, mst_algo)


def steiner_tree(
    g: Graph,
    seeds: jax.Array,
    *,
    num_seeds: Optional[int] = None,
    mode: str = "bucket",
    mst_algo: str = "prim",
    delta: Optional[float] = None,
    max_iters: Optional[int] = None,
    ell: Optional[EllGraph] = None,
    ell_width: int = 32,
    frontier_size: int = 1024,
) -> SteinerResult:
    """Computes a 2-approximate Steiner minimal tree for (g, seeds).

    .. deprecated::
        Thin shim over the unified solver — delegates to the ``"single"``
        backend of :mod:`repro.solver` (``SolverConfig(backend="single")``
        → ``SteinerSolver.prepare(graph)`` → ``handle.solve(seeds)``).
        The compiled executable is shared with the solver path, and a
        repeated ``mode="frontier"`` call against the same ``g`` object
        reuses a memoized ELL view (:func:`repro.core.graph.ell_view_cached`)
        instead of paying the O(E) host-Python rebuild.

    Args:
      g: symmetric weighted graph (padded COO).
      seeds: (S,) int32 seed vertex ids.
      num_seeds: static |S| (defaults to seeds.shape[0]).
      mode: Voronoi relaxation schedule — "dense" | "bucket" | "frontier"
        | "pallas" (the min-plus kernel of :mod:`repro.kernels.minplus`).
      mst_algo: "prim" (paper-faithful sequential analogue) | "boruvka".
      delta: bucket width (mode="bucket").
      max_iters: safety cap on relaxation rounds.
      ell: prebuilt ELL adjacency for mode="frontier"/"pallas"; a memoized
        view keyed on ``(id(g), ell_width)`` is used when omitted.
      ell_width: ELL row width when building the view here.
      frontier_size: top-K frontier rows per round (mode="frontier").

    Returns:
      SteinerResult; ``result.tree.total_distance`` is D(G_S).
    """
    from repro.solver.config import SolverConfig
    from repro.solver.registry import get_backend

    cfg = SolverConfig(
        backend="single",
        mode=mode,
        mst_algo=mst_algo,
        delta=delta,
        max_iters=max_iters,
        ell_width=ell_width,
        frontier_size=frontier_size,
    )
    S = int(num_seeds if num_seeds is not None else seeds.shape[0])
    return get_backend("single").solve_raw(cfg, g, seeds, S, ell=ell)
