"""Voronoi cell computation — Alg. 2 Step 1 / Alg. 4 of the paper.

The paper computes all |S| Voronoi cells at once with an *asynchronous*
Bellman-Ford over MPI, accelerated by a best-effort priority message queue
(§IV). XLA's SPMD model has no asynchronous point-to-point messages, so we
adapt the insight rather than emulate the mechanism (see DESIGN.md):

* ``mode="dense"``    — bulk-synchronous Bellman-Ford: every edge relaxes
  every round. This is the FIFO-queue baseline of the paper's §V-C.
* ``mode="bucket"``   — Δ-bucketed relaxation: only edges whose source
  distance is below the current threshold may relax, mimicking the paper's
  priority queue (low-distance messages first). Wasteful long-distance
  over-estimates are never propagated, cutting total *useful work* exactly
  like the paper's message-count reduction (Fig. 5/6).
* ``mode="frontier"`` — top-K compacted frontier over the ELL view: each
  round gathers the K lowest-distance *changed* vertices and relaxes only
  their adjacency rows. Work-proportional (the true TPU analogue of a
  priority queue); used by the perf-optimized configuration.

All modes converge to the same unique fixpoint because updates use a strict
lexicographic order on ``(dist, lab, pred)`` — identical to the numpy
Dijkstra oracle in :mod:`repro.core.ref`.

Per-vertex state (paper Table II):
  dist[v] = d1(src(v), v)    lab[v] = index of owning seed    pred[v]
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import EllGraph, Graph
from repro.knobs import solver_jit
from repro.obs import ROUND_CHANNELS

INF = jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VoronoiState:
    """Per-vertex Voronoi state: (dist, lab, pred)."""

    dist: jax.Array  # (N,) f32
    lab: jax.Array  # (N,) i32; == S for unreached
    pred: jax.Array  # (N,) i32; == v for seeds / unreached
    # static label count S, set inside the dense/bucket loop: every label
    # is at most S, so relax_dense may pack its (lab, src) tie-break into
    # one int32 key (segmin_passes).  None: the three-pass tie-break.
    num_labels: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VoronoiStats:
    """Convergence statistics (the paper's Fig. 5/6 message metrics)."""

    iterations: jax.Array  # i32 — number of global rounds
    relaxations: jax.Array  # i32 — vertex improvements (one winning edge each)
    # i32 — "messages": dense, bucket and pallas charge each improved
    # vertex its out-degree (generated traffic); frontier and
    # pallas_frontier count the finite candidates of the expanded lanes
    messages: jax.Array
    # (H+1, 4) i32 per-round telemetry ring — rows 0..H-1 hold rounds
    # 0..H-1 in obs.ROUND_CHANNELS order (frontier, messages, relaxations,
    # unreached); row H accumulates rounds >= H (_hist_write).
    # None when the loop ran with telemetry_rounds=0 (the default for
    # direct callers).
    history: Optional[jax.Array] = None
    # static: edges the schedule's kernel reads each round (the whole edge
    # array, or the rows it expands); × iterations = the solve's scan
    scan_per_round: int = dataclasses.field(default=0, metadata=dict(static=True))
    # static: segment-min passes a round makes (2 with the packed (lab, src)
    # key, else 3); × iterations = SolveTelemetry.segmin_scatters
    segmin_passes: int = dataclasses.field(default=0, metadata=dict(static=True))


# Telemetry rows: obs.ROUND_CHANNELS order, int32 counts.  On one device a
# round's count is at most the number of directed edges; sums over rounds
# (the loop totals, the spill slot) saturate at I32_MAX instead of wrapping.
CHANNELS = len(ROUND_CHANNELS)
UNREACHED = ROUND_CHANNELS.index("unreached")
I32_MAX = 2**31 - 1


def sat_add(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a + b`` for non-negative int32 counts, held at 2**31 - 1 instead
    of wrapping."""
    return a + jnp.minimum(b, I32_MAX - a)


def hist_init(telemetry_rounds: int, *lead: int) -> jax.Array:
    """The zeroed (H+1, *lead, 4) telemetry ring."""
    return jnp.zeros((telemetry_rounds + 1, *lead, CHANNELS), jnp.int32)


def _round_row(
    frontier: jax.Array,
    messages: jax.Array,
    relaxations: jax.Array,
    dist: jax.Array,
) -> jax.Array:
    """One telemetry row in obs.ROUND_CHANNELS order."""
    unreached = jnp.sum(~jnp.isfinite(dist))
    return jnp.stack(
        [jnp.asarray(x).astype(jnp.int32)
         for x in (frontier, messages, relaxations, unreached)]
    )


def _hist_write(hist: jax.Array, it: jax.Array, row: jax.Array) -> jax.Array:
    """Writes ``row`` (shape ``hist.shape[1:]``) at round ``it``.  Rounds
    >= H land in the spill slot H: the counts add up there (saturating),
    ``unreached`` keeps the latest round's value."""
    H = hist.shape[0] - 1
    last = jnp.arange(CHANNELS) == UNREACHED
    spill = jnp.where(last, row, sat_add(hist[H], row))
    row = jnp.where(it >= H, spill, row)
    return jax.lax.dynamic_update_slice(
        hist, row[None], (jnp.minimum(it, H),) + (0,) * row.ndim
    )


def init_state(n: int, seeds: jax.Array) -> VoronoiState:
    """Paper Alg. 3 INITIALIZATION: seeds at distance 0 owning themselves.

    Duplicate seed entries are safe: the label scatter is a ``min`` so a
    vertex listed at several seed indices is owned by the lowest index —
    consistent with the lexicographic (dist, lab, pred) update order. The
    higher duplicate indices then label empty cells, which makes
    pad-with-duplicates inert through the whole pipeline (the serving
    layer's shape-bucketing relies on this; see :mod:`repro.serve.plan`).
    """
    S = seeds.shape[0]
    dist = jnp.full((n,), INF, jnp.float32).at[seeds].set(0.0)
    lab = jnp.full((n,), S, jnp.int32).at[seeds].min(jnp.arange(S, dtype=jnp.int32))
    pred = jnp.arange(n, dtype=jnp.int32)
    return VoronoiState(dist=dist, lab=lab, pred=pred)


def segmin_passes(n: int, num_labels: Optional[int]) -> int:
    """Segment-min passes :func:`lex_segment_argmin` makes (static): 2
    where its packed key fits, ``(S + 1) * n <= 2**31 - 1``; else 3."""
    return 2 if num_labels is not None and (num_labels + 1) * n <= I32_MAX else 3


def lex_segment_argmin(
    cand: jax.Array,
    lab_src: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    n: int,
    num_labels: Optional[int] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-vertex lexicographic minimum of ``(cand, lab_src, src)`` over
    the edges into it: ``(m, minlab, minsrc)``, each (n,).  A vertex no
    edge enters gets ``(+inf, I32_MAX, I32_MAX)``.

    With ``num_labels`` S stated and ``(S + 1) * n <= 2**31 - 1``, the
    (lab, src) tie-break is one int32 key ``lab * n + src`` (src < n, so
    its order is the lexicographic one) and one segment-min finds it; else
    a second pass finds the least label and a third the least source.
    """
    m = jax.ops.segment_min(cand, dst, n)
    elig = cand == m[dst]
    # every key lab * n + src < (S + 1) * n stays below the I32_MAX sentinel
    if num_labels is not None and (num_labels + 1) * n <= I32_MAX:
        key = jax.ops.segment_min(
            jnp.where(elig, lab_src * n + src, I32_MAX), dst, n
        )
        none = key == I32_MAX
        minlab = jnp.where(none, I32_MAX, key // n)
        minsrc = jnp.where(none, I32_MAX, key % n)
        return m, minlab, minsrc
    minlab = jax.ops.segment_min(jnp.where(elig, lab_src, I32_MAX), dst, n)
    elig2 = elig & (lab_src == minlab[dst])
    minsrc = jax.ops.segment_min(jnp.where(elig2, src, I32_MAX), dst, n)
    return m, minlab, minsrc


def relax_dense(
    g: Graph,
    st: VoronoiState,
    active_cand: Optional[jax.Array] = None,
) -> tuple[VoronoiState, jax.Array]:
    """One synchronous relaxation over the (masked) edge list.

    Args:
      g: COO graph (padded edges carry +inf weight).
      st: current state; its static ``num_labels`` decides whether the
        tie-break takes two segment-min passes or three.
      active_cand: optional (E,) f32 candidate override; default
        ``dist[src] + w``. Callers mask inactive edges with +inf.

    Returns:
      (new_state, upd) — ``upd`` is the (N,) bool mask of vertices whose
      (dist, lab, pred) strictly improved this round (callers derive the
      improved/attempted counts from it).
    """
    cand = st.dist[g.src] + g.w if active_cand is None else active_cand
    m, minlab, minsrc = lex_segment_argmin(
        cand, st.lab[g.src], g.src, g.dst, g.n, st.num_labels
    )

    # Strict lexicographic improvement on (dist, lab, pred); finite only.
    upd = jnp.isfinite(m) & (
        (m < st.dist)
        | ((m == st.dist) & (minlab < st.lab))
        | ((m == st.dist) & (minlab == st.lab) & (minsrc < st.pred))
    )
    new = dataclasses.replace(
        st,
        dist=jnp.where(upd, m, st.dist),
        lab=jnp.where(upd, minlab, st.lab),
        pred=jnp.where(upd, minsrc, st.pred),
    )
    return new, upd


def _changed(a: VoronoiState, b: VoronoiState) -> jax.Array:
    return (
        jnp.any(a.dist != b.dist) | jnp.any(a.lab != b.lab) | jnp.any(a.pred != b.pred)
    )


def voronoi_cells(
    g: Graph,
    seeds: jax.Array,
    *,
    mode: str = "bucket",
    delta: Optional[float] = None,
    max_iters: Optional[int] = None,
    telemetry_rounds: int = 0,
    init: Optional[VoronoiState] = None,
) -> tuple[VoronoiState, VoronoiStats]:
    """Computes all Voronoi cells (paper Alg. 2 Step 1).

    Args:
      g: symmetric weighted graph.
      seeds: (S,) int32 seed vertex ids.
      mode: "dense" (FIFO analogue) or "bucket" (priority analogue).
      delta: bucket width for mode="bucket"; a STATIC knob — must be a
        host scalar > 0 (a zero/negative width never advances the bucket
        threshold; a traced width is rejected outright); default mean
        finite weight.
      max_iters: safety cap on rounds (default 4n + 64).
      telemetry_rounds: static H — carry a (H+1, 4) per-round telemetry
        buffer through the loop and return it as ``stats.history``.
        0 (default) returns ``history=None``.  H is part of the compiled
        executable, so host-side observers toggling on/off never retrace.
      init: optional warm-start state replacing ``init_state(n, seeds)``.
        Sound whenever every vertex entry is either already AT the new
        fixpoint or reset to its initialization row — e.g. a previous
        epoch's converged state with every vertex of a delta-affected
        Voronoi cell reset (:func:`repro.delta.resolve.reset_affected`):
        the relaxation then re-derives exactly the reset region and
        converges to the same fixpoint as a cold solve, usually in far
        fewer rounds.  A state with *stale-low* entries (e.g. kept across
        an edge deletion without resetting its cell) is NOT sound —
        Bellman-Ford never raises a distance.

    Returns:
      (VoronoiState, VoronoiStats)
    """
    # Δ is a STATIC knob: validation happens on the host path, always.
    # (It used to ride the trace as an operand, where a traced Δ could
    # bypass an isinstance check and, at Δ <= 0, stall the bucket loop —
    # the PR-4 bug class.  A traced Δ is now rejected here outright.)
    if mode == "bucket" and delta is not None:
        if not isinstance(delta, (int, float, np.integer, np.floating)):
            raise TypeError(
                f"delta must be a host scalar (it is a static knob of the "
                f"bucket schedule), got {type(delta).__name__} — traced "
                f"delta values are not supported"
            )
        if not delta > 0:
            raise ValueError(f"delta must be positive, got {delta}")
    if telemetry_rounds < 0:
        raise ValueError(f"telemetry_rounds must be >= 0, got {telemetry_rounds}")
    return _voronoi_cells(
        g,
        seeds,
        mode=mode,
        delta=delta,
        max_iters=max_iters,
        telemetry_rounds=telemetry_rounds,
        init=init,
    )


@solver_jit
@jax.named_scope("voronoi")
def _voronoi_cells(
    g: Graph,
    seeds: jax.Array,
    *,
    mode: str,
    delta: Optional[float],
    max_iters: Optional[int],
    telemetry_rounds: int = 0,
    init: Optional[VoronoiState] = None,
) -> tuple[VoronoiState, VoronoiStats]:
    n = g.n
    S = seeds.shape[0]
    cap = jnp.int32(min(max_iters if max_iters is not None else 4 * n + 64, 2**31 - 2))
    # a warm init has a different pytree structure than None, so the warm
    # path compiles its own executable and the cold path never retraces
    st0 = init_state(n, seeds) if init is None else init
    # labels are at most S (the unreached sentinel): relax_dense may pack
    # its tie-break; the returned state states no label count, as before
    st0 = dataclasses.replace(st0, num_labels=S)
    passes = segmin_passes(n, S)
    hist0 = hist_init(telemetry_rounds)
    # out-degree: an improved vertex "sends a message" to every neighbor
    # (the paper's generated-message-traffic metric, Fig. 6)
    deg = jax.ops.segment_sum(jnp.isfinite(g.w).astype(jnp.int32), g.src, n)
    # both schedules read the whole edge array every round
    scanned = g.src.shape[0]
    zero = jnp.int32(0)

    if mode == "dense":

        def body(carry):
            st, it, rlx, msg, _, hist = carry
            new, upd = relax_dense(g, st)
            imp = jnp.sum(upd)
            dmsg = jnp.sum(jnp.where(upd, deg, 0))
            # dense has no explicit frontier; its active set IS the
            # improved-vertex set
            hist = _hist_write(
                hist, it, _round_row(imp, dmsg, imp, new.dist)
            )
            return (
                new, it + 1, sat_add(rlx, imp), sat_add(msg, dmsg),
                _changed(st, new), hist,
            )

        def cond(carry):
            _, it, _, _, changed, _ = carry
            return changed & (it < cap)

        st, iters, rlx, msg, _, hist = jax.lax.while_loop(
            cond, body, (st0, zero, zero, zero, jnp.bool_(True), hist0)
        )
        return dataclasses.replace(st, num_labels=None), VoronoiStats(
            iterations=iters,
            relaxations=rlx,
            messages=msg,
            history=hist if telemetry_rounds > 0 else None,
            scan_per_round=scanned,
            segmin_passes=passes,
        )

    if mode == "bucket":
        finite_w = jnp.where(jnp.isfinite(g.w), g.w, 0.0)
        n_real = jnp.maximum(jnp.sum(jnp.isfinite(g.w)), 1)
        d = (
            jnp.float32(delta)
            if delta is not None
            else jnp.maximum(jnp.sum(finite_w) / n_real, 1e-6)
        )

        def body(carry):
            st, theta, it, rlx, msg, _, hist = carry
            active = st.dist[g.src] <= theta
            cand = jnp.where(active, st.dist[g.src] + g.w, INF)
            new, upd = relax_dense(g, st, active_cand=cand)
            changed = _changed(st, new)
            # Terminate only when a no-change round had EVERY source active
            # (such a round is equivalent to a dense fixpoint check);
            # otherwise advance the bucket threshold by Δ and keep going.
            # Stall guard (defense in depth): Δ is a static knob now, so
            # a non-positive value cannot reach this loop — but if one
            # ever did, it would never advance theta; exit at the first
            # quiescent round instead of silently burning the round cap.
            max_fin = jnp.max(jnp.where(jnp.isfinite(new.dist), new.dist, -INF))
            done = ~changed & ((theta >= max_fin) | (d <= 0))
            imp = jnp.sum(upd)
            dmsg = jnp.sum(jnp.where(upd, deg, 0))
            # frontier = vertices under the bucket threshold (the paper's
            # eligible-to-send set this round)
            front = jnp.sum(jnp.isfinite(new.dist) & (new.dist <= theta))
            hist = _hist_write(
                hist, it, _round_row(front, dmsg, imp, new.dist)
            )
            theta = jnp.where(changed, theta, theta + d)
            return (
                new, theta, it + 1, sat_add(rlx, imp), sat_add(msg, dmsg),
                ~done, hist,
            )

        def cond(carry):
            _, _, it, _, _, work, _ = carry
            return work & (it < cap)

        st, _, iters, rlx, msg, _, hist = jax.lax.while_loop(
            cond,
            body,
            (
                st0,
                jnp.float32(0.0),
                zero,
                zero,
                zero,
                jnp.bool_(True),
                hist0,
            ),
        )
        return dataclasses.replace(st, num_labels=None), VoronoiStats(
            iterations=iters,
            relaxations=rlx,
            messages=msg,
            history=hist if telemetry_rounds > 0 else None,
            scan_per_round=scanned,
            segmin_passes=passes,
        )

    raise ValueError(
        f"unknown mode: {mode!r} — this entry point runs 'dense' | 'bucket'; "
        f"mode='frontier' runs via voronoi_cells_frontier over the ELL "
        f"view, and mode='pallas' via "
        f"repro.kernels.minplus.ops.voronoi_cells_pallas"
    )


# ----------------------------------------------------------------------------
# Frontier-compacted relaxation over the ELL view (perf-optimized path).
# ----------------------------------------------------------------------------


@solver_jit
@jax.named_scope("voronoi")
def voronoi_cells_frontier(
    ell: EllGraph,
    seeds: jax.Array,
    *,
    frontier_size: int = 1024,
    max_rounds: Optional[int] = None,
    telemetry_rounds: int = 0,
    init: Optional[VoronoiState] = None,
) -> tuple[VoronoiState, VoronoiStats]:
    """Top-K compacted-frontier Voronoi cells over the ELL adjacency.

    The TPU-native priority queue: each round selects (up to) the K ELL rows
    whose owning vertex (a) changed since it was last expanded and (b) has
    the smallest tentative distance, then relaxes only those rows' edges.
    Work per round is O(K · k) instead of O(E) — the paper's message
    prioritization made work-proportional.

    ``init`` warm-starts the loop from a partially-converged state (the
    delta layer's affected-cell re-solve): one violated-edge sweep seeds
    the dirty set with exactly the rows whose expansion would improve a
    neighbor — for a state converged everywhere outside a reset region
    that is the repair boundary plus the region's own seed rows — so
    total work is proportional to the region, not the graph.
    """
    n = ell.n
    R, k = ell.nbr.shape
    frontier_size = min(frontier_size, R)  # top_k cap on small graphs
    S = seeds.shape[0]
    S_sent = jnp.int32(jnp.iinfo(jnp.int32).max)
    cap = jnp.int32(min(max_rounds if max_rounds is not None else 16 * n + 64, 2**31 - 2))

    hist0 = hist_init(telemetry_rounds)
    # each round reads the K selected rows' k lanes
    scanned = frontier_size * k
    zero = jnp.int32(0)
    if init is None:
        st0 = init_state(n, seeds)
        dirty0 = jnp.zeros((R,), jnp.bool_).at[:].set(
            jnp.isin(ell.row2v, seeds)
        )  # rows of seed vertices start dirty
    else:
        st0 = init
        # ELL padding carries +inf weight, so padded lanes never mark a
        # row dirty; the lexicographic tie-breaks mirror the loop's own
        # update predicate, so a fully-converged init yields an all-clean
        # dirty set and the loop exits without a round.
        v_of = ell.row2v
        cand = st0.dist[v_of][:, None] + ell.wgt  # (R, k)
        nd = st0.dist[ell.nbr]
        nl = st0.lab[ell.nbr]
        np_ = st0.pred[ell.nbr]
        lab_u = st0.lab[v_of][:, None]
        src_u = v_of[:, None]
        better = jnp.isfinite(cand) & (
            (cand < nd)
            | ((cand == nd) & (lab_u < nl))
            | ((cand == nd) & (lab_u == nl) & (src_u < np_))
        )
        dirty0 = jnp.any(better, axis=1)

    def body(carry):
        st, dirty, it, rlx, msg, hist = carry
        # --- select top-K lowest-distance dirty rows (the "priority queue")
        rowdist = jnp.where(dirty, st.dist[ell.row2v], INF)
        neg = -rowdist  # top_k selects largest
        _, rows = jax.lax.top_k(neg, frontier_size)
        sel_ok = jnp.isfinite(rowdist[rows])
        # mark selected rows clean
        dirty = dirty.at[rows].set(dirty[rows] & ~sel_ok)
        # --- gather + relax the selected rows' edges
        nbr = ell.nbr[rows]  # (K, k)
        wgt = jnp.where(sel_ok[:, None], ell.wgt[rows], INF)
        v_of = ell.row2v[rows]  # (K,)
        cand = st.dist[v_of][:, None] + wgt  # (K, k)
        labc = jnp.where(sel_ok, st.lab[v_of], S_sent)
        srcc = jnp.where(sel_ok, v_of, S_sent)
        flat_dst = nbr.reshape(-1)
        flat_cand = cand.reshape(-1)
        flat_lab = jnp.broadcast_to(labc[:, None], cand.shape).reshape(-1)
        flat_src = jnp.broadcast_to(srcc[:, None], cand.shape).reshape(-1)

        m = jax.ops.segment_min(flat_cand, flat_dst, n)
        e1 = flat_cand == m[flat_dst]
        ml = jax.ops.segment_min(jnp.where(e1, flat_lab, S_sent), flat_dst, n)
        e2 = e1 & (flat_lab == ml[flat_dst])
        ms = jax.ops.segment_min(jnp.where(e2, flat_src, S_sent), flat_dst, n)
        upd = jnp.isfinite(m) & (
            (m < st.dist)
            | ((m == st.dist) & (ml < st.lab))
            | ((m == st.dist) & (ml == st.lab) & (ms < st.pred))
        )
        new = VoronoiState(
            dist=jnp.where(upd, m, st.dist),
            lab=jnp.where(upd, ml, st.lab),
            pred=jnp.where(upd, ms, st.pred),
        )
        # rows of updated vertices become dirty again
        dirty = dirty | upd[ell.row2v]
        imp = jnp.sum(upd)
        dmsg = jnp.sum(jnp.isfinite(flat_cand))
        # frontier = ELL rows actually expanded this round (the top-K pop)
        hist = _hist_write(
            hist, it, _round_row(jnp.sum(sel_ok), dmsg, imp, new.dist)
        )
        return (
            new, dirty, it + 1, sat_add(rlx, imp), sat_add(msg, dmsg), hist
        )

    def cond(carry):
        _, dirty, it, _, _, _ = carry
        return jnp.any(dirty) & (it < cap)

    st, _, iters, rlx, msg, hist = jax.lax.while_loop(
        cond, body, (st0, dirty0, zero, zero, zero, hist0)
    )
    return st, VoronoiStats(
        iterations=iters,
        relaxations=rlx,
        messages=msg,
        history=hist if telemetry_rounds > 0 else None,
        scan_per_round=scanned,
        segmin_passes=3,
    )
