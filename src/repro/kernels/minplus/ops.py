"""Public jit'd wrappers for the min-plus kernel + Voronoi integration.

``relax_ell`` applies one kernel relaxation to a :class:`VoronoiState`;
``voronoi_cells_pallas`` iterates it to the same fixpoint as
:func:`repro.core.voronoi.voronoi_cells` (tests assert exact agreement),
and ``voronoi_cells_pallas_frontier`` is the work-compacted schedule: a
top-K priority selection of dirty ELL rows feeds the same dense-tile
kernel, so per-round work is O(K·k) like
:func:`repro.core.voronoi.voronoi_cells_frontier` but the relaxation is a
VPU row reduction instead of flat segment scatters.

Both drivers are the execution engine behind ``SolverConfig(mode="pallas")``
(:mod:`repro.solver.backends`).  The platform picks the Pallas execution
mode (:func:`repro.kernels.default_interpret`): compiled on TPU/GPU, the
interpreter on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.graph import EllGraph
from repro.core.voronoi import (
    VoronoiState,
    VoronoiStats,
    _hist_write,
    _round_row,
    hist_init,
    init_state,
    sat_add,
)
from repro.kernels.minplus.minplus import minplus_call

IMAX = jnp.iinfo(jnp.int32).max
INF = jnp.inf


def _cap(max_iters: Optional[int], default: int) -> jnp.ndarray:
    # clamp to int32 range: 4n + 64 overflows for n >= 2**29, and a
    # wrapped/negative cap makes the while_loop exit unconverged
    return jnp.int32(min(max_iters if max_iters is not None else default, 2**31 - 2))


def _pad_rows(x, mult, fill):
    r = x.shape[0]
    pad = (-r) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad, *x.shape[1:]), fill, x.dtype)])


def _rows_to_vertices(m, ml, ms, row2v, n, st):
    """Reduces per-row lexicographic minima to per-vertex state updates.

    Split high-degree rows recombine lexicographically; ``upd`` is the
    strict-improvement mask over (dist, lab, pred).
    """
    mv = jax.ops.segment_min(m, row2v, n)
    e1 = m == mv[row2v]
    mlv = jax.ops.segment_min(jnp.where(e1, ml, IMAX), row2v, n)
    e2 = e1 & (ml == mlv[row2v])
    msv = jax.ops.segment_min(jnp.where(e2, ms, IMAX), row2v, n)
    upd = jnp.isfinite(mv) & (
        (mv < st.dist)
        | ((mv == st.dist) & (mlv < st.lab))
        | ((mv == st.dist) & (mlv == st.lab) & (msv < st.pred))
    )
    new = VoronoiState(
        dist=jnp.where(upd, mv, st.dist),
        lab=jnp.where(upd, mlv, st.lab),
        pred=jnp.where(upd, msv, st.pred),
    )
    return new, upd


@functools.partial(jax.jit, static_argnames=("block_rows",))
def relax_ell(
    ell: EllGraph,
    st: VoronoiState,
    *,
    block_rows: int = 256,
) -> tuple[VoronoiState, jax.Array]:
    """One min-plus relaxation of the full ELL adjacency via the kernel.

    Returns:
      (new_state, upd) — ``upd`` is the (N,) bool mask of vertices whose
      (dist, lab, pred) strictly improved (same contract as
      :func:`repro.core.voronoi.relax_dense`).
    """
    n = ell.n
    nbr = _pad_rows(ell.nbr, block_rows, 0)
    wgt = _pad_rows(ell.wgt, block_rows, jnp.inf)
    row2v = _pad_rows(ell.row2v, block_rows, 0)
    m, ml, ms = minplus_call(nbr, wgt, st.dist, st.lab, block_rows=block_rows)
    return _rows_to_vertices(m, ml, ms, row2v, n, st)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_rows",
        "max_iters",
        "telemetry_rounds",
    ),
)
@jax.named_scope("voronoi")
def voronoi_cells_pallas(
    ell: EllGraph,
    seeds: jax.Array,
    *,
    block_rows: int = 256,
    max_iters: Optional[int] = None,
    telemetry_rounds: int = 0,
) -> tuple[VoronoiState, VoronoiStats]:
    """Bellman-Ford Voronoi cells with the Pallas relaxation kernel.

    Stats mirror ``voronoi_cells(mode="dense")``: ``relaxations`` counts
    vertices whose state strictly improved, ``messages`` charges each
    improved vertex one message per neighbor (the paper's generated-
    traffic metric, Fig. 6).
    """
    n = ell.n
    cap = _cap(max_iters, 4 * n + 64)
    st0 = init_state(n, seeds)
    # out-degree per vertex: ELL rows of one vertex sum their real lanes
    deg = jax.ops.segment_sum(
        jnp.sum(jnp.isfinite(ell.wgt), axis=1).astype(jnp.int32), ell.row2v, n
    )
    # the kernel expands every tile of the row-padded adjacency each round
    R, k = ell.nbr.shape
    scanned = (R + (-R) % block_rows) * k
    zero = jnp.int32(0)

    hist0 = hist_init(telemetry_rounds)

    def body(carry):
        st, it, rlx, msg, _, hist = carry
        new, upd = relax_ell(ell, st, block_rows=block_rows)
        ch = jnp.any(upd)
        imp = jnp.sum(upd)
        dmsg = jnp.sum(jnp.where(upd, deg, 0))
        hist = _hist_write(
            hist, it, _round_row(imp, dmsg, imp, new.dist)
        )
        return (new, it + 1, sat_add(rlx, imp), sat_add(msg, dmsg), ch, hist)

    def cond(carry):
        _, it, _, _, ch, _ = carry
        return ch & (it < cap)

    st, iters, rlx, msg, _, hist = jax.lax.while_loop(
        cond, body, (st0, zero, zero, zero, jnp.bool_(True), hist0)
    )
    return st, VoronoiStats(
        iterations=iters,
        relaxations=rlx,
        messages=msg,
        history=hist if telemetry_rounds > 0 else None,
        scan_per_round=scanned,
        segmin_passes=3,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "frontier_size",
        "block_rows",
        "max_iters",
        "telemetry_rounds",
    ),
)
@jax.named_scope("voronoi")
def voronoi_cells_pallas_frontier(
    ell: EllGraph,
    seeds: jax.Array,
    *,
    frontier_size: int = 1024,
    block_rows: int = 256,
    max_iters: Optional[int] = None,
    telemetry_rounds: int = 0,
) -> tuple[VoronoiState, VoronoiStats]:
    """Top-K compacted Voronoi cells over dense Pallas tiles.

    The same priority idea as :func:`~repro.core.voronoi.voronoi_cells_frontier`
    — each round touches only the K highest-priority *dirty* ELL rows — but
    relaxation is pull-based: the selected rows' (K, k) neighbor tiles feed
    the min-plus kernel, replacing the flat segment scatters with a dense
    VPU row reduction.  Two per-row flags drive the schedule:

    * ``pull``   — a neighbor of the row's vertex improved, so the row's
      lexicographic minimum must be recomputed; priority is the improving
      neighbor's distance (lowest first, the paper's message priority).
    * ``expand`` — the row's vertex itself improved since the row was last
      expanded, so the row's neighbor list must be re-marked as ``pull``;
      priority is the vertex's own distance.

    A selected row does both with one gathered tile.  Every improvement of
    a (possibly split) vertex flags ALL of its rows for expansion, and an
    expansion marks exactly the neighbors listed in that row, so updates
    propagate through every split row and the fixpoint equals the dense
    schedule's (asserted against the Dijkstra oracle in tests).
    """
    n = ell.n
    R, k = ell.nbr.shape
    K = min(frontier_size, R)  # gathered tiles pad K up to block_rows, not R
    cap = _cap(max_iters, 16 * n + 64)
    st0 = init_state(n, seeds)
    # seeds "improved" at init: their rows start expand-dirty
    exp0 = jnp.isin(ell.row2v, seeds)
    pull0 = jnp.zeros((R,), jnp.bool_)
    prio0 = jnp.full((R,), INF, jnp.float32)
    hist0 = hist_init(telemetry_rounds)
    # the kernel expands the K gathered tiles, padded up to block_rows
    scanned = (K + (-K) % block_rows) * k
    zero = jnp.int32(0)

    def body(carry):
        st, pull, prio, exp, it, rlx, msg, hist = carry
        # --- priority: pull at the marker's distance, expand at own dist
        p = jnp.minimum(
            jnp.where(pull, prio, INF),
            jnp.where(exp, st.dist[ell.row2v], INF),
        )
        _, rows = jax.lax.top_k(-p, K)
        sel = jnp.isfinite(p[rows])  # rows actually dirty
        do_expand = exp[rows] & sel
        # clear selected rows (re-marked below if their vertex improves)
        pull = pull.at[rows].set(pull[rows] & ~sel)
        prio = prio.at[rows].set(jnp.where(sel, INF, prio[rows]))
        exp = exp.at[rows].set(exp[rows] & ~sel)
        # --- gather the selected tiles and relax them through the kernel
        tnbr = _pad_rows(ell.nbr[rows], block_rows, 0)
        twgt = _pad_rows(
            jnp.where(sel[:, None], ell.wgt[rows], INF), block_rows, INF
        )
        v_of = _pad_rows(ell.row2v[rows], block_rows, 0)
        m, ml, ms = minplus_call(tnbr, twgt, st.dist, st.lab, block_rows=block_rows)
        new, upd = _rows_to_vertices(m, ml, ms, v_of, n, st)
        # --- expansion: mark the listed neighbors' rows for re-pull at the
        # expander's (post-update) distance
        do_expand_p = _pad_rows(do_expand, block_rows, False)
        mark = do_expand_p[:, None] & jnp.isfinite(twgt)
        flat = tnbr.reshape(-1)
        mark_prio = jnp.where(
            mark, new.dist[v_of][:, None], INF
        ).reshape(-1)
        dirty_v = (
            jnp.zeros((n,), jnp.int32)
            .at[flat]
            .max(mark.reshape(-1).astype(jnp.int32))
            > 0
        )
        prio_v = jnp.full((n,), INF, jnp.float32).at[flat].min(mark_prio)
        pull = pull | dirty_v[ell.row2v]
        prio = jnp.minimum(prio, prio_v[ell.row2v])
        # --- every row of an improved vertex needs (re-)expansion
        exp = exp | upd[ell.row2v]
        imp = jnp.sum(upd)
        dmsg = jnp.sum(jnp.isfinite(twgt))
        # frontier = dirty rows actually popped this round
        hist = _hist_write(
            hist, it, _round_row(jnp.sum(sel), dmsg, imp, new.dist)
        )
        return (
            new, pull, prio, exp, it + 1, sat_add(rlx, imp),
            sat_add(msg, dmsg), hist,
        )

    def cond(carry):
        _, pull, _, exp, it, _, _, _ = carry
        return (jnp.any(pull) | jnp.any(exp)) & (it < cap)

    st, _, _, _, iters, rlx, msg, hist = jax.lax.while_loop(
        cond, body, (st0, pull0, prio0, exp0, zero, zero, zero, hist0)
    )
    return st, VoronoiStats(
        iterations=iters,
        relaxations=rlx,
        messages=msg,
        history=hist if telemetry_rounds > 0 else None,
        scan_per_round=scanned,
        segmin_passes=3,
    )
