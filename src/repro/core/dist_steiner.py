"""Distributed Steiner tree — the paper's Alg. 3 on a JAX device mesh.

Mapping the paper's MPI design onto XLA SPMD (see DESIGN.md §Adaptation):

  paper (HavoqGT / MPI)                     this module (shard_map)
  ----------------------------------------  --------------------------------
  graph partitions, ~equal vertices/rank    1D partition: vertex blocks over
                                            the "model" axis; edges bucketed
                                            by dst-block and spread over the
                                            replica axes ("pod", "data")
  async vertex-centric visitors             bulk-synchronous relaxation with
                                            an optional *local-steps* mode: T
                                            collective-free local rounds per
                                            global exchange (stale reads are
                                            safe — distances only decrease)
  priority message queue                    Δ-bucketed thresholding (only
                                            low-distance sources may send),
                                            or mode="frontier": per-block
                                            top-K dirty-row selection over a
                                            sharded ELL view (work per round
                                            O(K·k)/device instead of O(Eb))
  MPI_Allreduce(MPI_MIN) on E_N distances   lax.pmin on the S² pair table
  Allreduce(MIN) on endpoint vertex ids     two more lexicographic pmin passes
  replicated sequential MST (Boost Prim)    replicated dense Prim / Borůvka
  TREE_EDGE_ASYNC pred-walk                 pointer-doubling with a gathered
                                            pred vector
  chunked collectives for |S|=10K (§V-F)    ``pair_chunks`` option

State layout per device: its vertex block (nb,) of (dist, lab, pred),
replicated across the replica axes; its edge shard (Eb,). One relaxation
round costs one all-gather of (dist, lab) over "model" plus three pmins of
(nb,) over the replica axes — these collectives ARE the roofline terms the
perf loop iterates on.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distance_graph import local_pair_tables
from repro.core.graph import stable_argsort
from repro.core.mst import boruvka_dense, prim_dense
from repro.core.tree import bridge_endpoints
from repro.core.voronoi import I32_MAX, _hist_write, hist_init, sat_add

INF = jnp.inf
IMAX = jnp.iinfo(jnp.int32).max


@dataclasses.dataclass(frozen=True)
class Partition:
    """Host-side partitioning result (numpy; device placement by caller).

    Flat edge arrays have length ``n_replica * n_blocks * eb`` laid out
    replica-major so that ``P((*replica_axes, vert_axis))`` puts bucket
    ``(r, b)`` on replica r / vertex-column b.
    """

    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray
    n: int  # true vertex count
    nb: int  # vertex block size (padded)
    eb: int  # edges per device (padded)
    n_blocks: int
    n_replica: int

    @property
    def npad(self) -> int:
        return self.nb * self.n_blocks


def partition_edges(
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray,
    n: int,
    *,
    n_replica: int,
    n_blocks: int,
    symmetrize: bool = True,
    block_multiple: int = 8,
) -> Partition:
    """1D dst-block edge partition (paper §IV scale-out design).

    Every directed edge goes to the vertex column owning its destination
    block; edges within a block are dealt round-robin across replicas.
    Padding edges are ``(0, block_base, +inf)`` — inert under min-plus.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float32)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        w = np.concatenate([w, w])
    nb = -(-n // n_blocks)
    nb = -(-nb // block_multiple) * block_multiple
    blk = dst // nb
    order = stable_argsort(blk)
    src, dst, w, blk = src[order], dst[order], w[order], blk[order]
    counts = np.bincount(blk, minlength=n_blocks)
    # round-robin replica assignment within each block
    within = np.arange(len(src)) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)[:-1]]), counts
    )
    rep = within % n_replica
    per_bucket = np.zeros((n_replica, n_blocks), np.int64)
    for b in range(n_blocks):
        c = counts[b]
        per_bucket[:, b] = c // n_replica + (np.arange(n_replica) < c % n_replica)
    eb = max(1, int(per_bucket.max()))
    eb = -(-eb // block_multiple) * block_multiple
    osrc = np.zeros((n_replica, n_blocks, eb), np.int32)
    odst = np.zeros((n_replica, n_blocks, eb), np.int32)
    ow = np.full((n_replica, n_blocks, eb), np.inf, np.float32)
    for b in range(n_blocks):
        odst[:, b, :] = b * nb  # padding dst = block base (local id 0)
    # stable fill
    pos = np.zeros((n_replica, n_blocks), np.int64)
    bucket_key = rep * n_blocks + blk
    korder = stable_argsort(bucket_key)
    ks, kd, kw, kk = src[korder], dst[korder], w[korder], bucket_key[korder]
    uniq, starts = np.unique(kk, return_index=True)
    ends = np.r_[starts[1:], len(kk)]
    for u, s0, s1 in zip(uniq, starts, ends):
        r, b = divmod(int(u), n_blocks)
        c = s1 - s0
        osrc[r, b, :c] = ks[s0:s1]
        odst[r, b, :c] = kd[s0:s1]
        ow[r, b, :c] = kw[s0:s1]
        pos[r, b] = c
    return Partition(
        src=osrc.reshape(-1),
        dst=odst.reshape(-1),
        w=ow.reshape(-1),
        n=n,
        nb=nb,
        eb=eb,
        n_blocks=n_blocks,
        n_replica=n_replica,
    )


@dataclasses.dataclass(frozen=True)
class EllPartition:
    """Host-side 1D-sharded ELL view (numpy; device placement by caller).

    ELL rows (source-major padded adjacency, see
    :class:`repro.core.graph.EllGraph`) are bucketed by the vertex block
    owning their *source* vertex and dealt round-robin across replicas
    within the block, mirroring :class:`Partition`'s edge layout.  Flat
    arrays have leading length ``n_replica * n_blocks * rb`` laid out
    replica-major so ``P((*replica_axes, vert_axis))`` puts bucket
    ``(r, b)`` on replica r / vertex-column b.  Padding rows alias the
    block base vertex (``b * nb``) with all-``+inf`` weights — they can
    never be selected into a frontier (no finite edges).
    """

    nbr: np.ndarray  # (n_replica * n_blocks * rb, k) int32 neighbor ids
    wgt: np.ndarray  # (n_replica * n_blocks * rb, k) f32; +inf padding
    row2v: np.ndarray  # (n_replica * n_blocks * rb,) int32 owning vertex
    n: int  # true vertex count
    nb: int  # vertex block size (padded)
    rb: int  # ELL rows per device (padded)
    k: int  # ELL row width
    n_blocks: int
    n_replica: int

    @property
    def npad(self) -> int:
        return self.nb * self.n_blocks

    @classmethod
    def from_buckets(cls, nbr, wgt, row2v, *, n: int, nb: int):
        """Flattens filled (R, B, rb[, k]) bucket arrays (see
        :func:`ell_bucket_arrays`) into the device layout."""
        R, B, rb, k = nbr.shape
        return cls(
            nbr=nbr.reshape(-1, k),
            wgt=wgt.reshape(-1, k),
            row2v=row2v.reshape(-1),
            n=n,
            nb=nb,
            rb=rb,
            k=k,
            n_blocks=B,
            n_replica=R,
        )


def ell_bucket_arrays(counts: np.ndarray, k: int, nb: int, block_multiple: int = 8):
    """Allocates the padded per-bucket ELL arrays, plus ``rb``.

    The single source of the shard geometry — ``rb`` rounding, ``+inf``
    weight padding, padding rows aliasing the block base vertex — shared
    by :func:`partition_ell` and the disk loader
    (:func:`repro.graphstore.partition.load_partition_ell`), whose
    outputs must agree bit for bit.
    """
    R, B = counts.shape
    rb = max(1, int(counts.max()))
    rb = -(-rb // block_multiple) * block_multiple
    nbr = np.zeros((R, B, rb, k), np.int32)
    wgt = np.full((R, B, rb, k), np.inf, np.float32)
    row2v = np.zeros((R, B, rb), np.int32)
    for b in range(B):
        row2v[:, b, :] = b * nb  # padding rows alias the block base
    return nbr, wgt, row2v, rb


def partition_ell(
    ell,
    *,
    n_replica: int,
    n_blocks: int,
    block_multiple: int = 8,
) -> EllPartition:
    """Shards a global ELL view by source vertex block (1D layout).

    Every ELL row goes to the vertex column owning its source block
    (``row2v // nb``); rows within a block are dealt round-robin across
    replicas in global row order, so the shard contents are identical to
    what :func:`repro.graphstore.partition.partition_ell_store` streams
    to disk from the same CSR (bit-for-bit, asserted in tests).
    """
    nbr = np.asarray(ell.nbr)
    wgt = np.asarray(ell.wgt)
    row2v = np.asarray(ell.row2v, np.int64)
    n = ell.n
    k = nbr.shape[1]
    nb = -(-n // n_blocks)
    nb = -(-nb // block_multiple) * block_multiple
    blk = row2v // nb
    # within-block rank in global row order → round-robin replica
    order = stable_argsort(blk)
    bs = blk[order]
    run_start = np.r_[0, np.flatnonzero(bs[1:] != bs[:-1]) + 1]
    run_len = np.diff(np.r_[run_start, bs.shape[0]])
    within = np.empty(blk.shape[0], np.int64)
    within[order] = np.arange(bs.shape[0]) - np.repeat(run_start, run_len)
    rep = within % n_replica
    counts = np.zeros((n_replica, n_blocks), np.int64)
    np.add.at(counts, (rep, blk), 1)
    onbr, owgt, orow, _ = ell_bucket_arrays(counts, k, nb, block_multiple)
    bucket_key = rep * n_blocks + blk
    korder = stable_argsort(bucket_key)  # ascending row order
    kk = bucket_key[korder]
    uniq, starts = np.unique(kk, return_index=True)
    ends = np.r_[starts[1:], len(kk)]
    for u, s0, s1 in zip(uniq, starts, ends):
        r, b = divmod(int(u), n_blocks)
        rows = korder[s0:s1]
        c = len(rows)
        onbr[r, b, :c] = nbr[rows]
        owgt[r, b, :c] = wgt[rows]
        orow[r, b, :c] = row2v[rows]
    return EllPartition.from_buckets(onbr, owgt, orow, n=n, nb=nb)


# ----------------------------------------------------------------------------
# shard_map pipeline
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DistSteinerConfig:
    """Static configuration of the distributed pipeline.

    Wire-format knobs are validated here, eagerly, instead of inside the
    traced pipeline: ``lab_i16`` gathers labels as int16, which holds
    every label value in [0, S] only while ``S < 32768``; ``fuse_gather``
    rides labels on an f32 all-gather, exact only while ``S < 2**24`` —
    beyond that the packing would *silently* corrupt cell ownership.
    """

    n: int
    nb: int
    num_seeds: int
    mode: str = "bucket"  # "dense" | "bucket" | "frontier"
    mst_algo: str = "prim"  # "prim" | "boruvka"
    local_steps: int = 1  # >1: async-style collective amortization
    pair_chunks: int = 1  # paper §V-F chunked Allreduce on the S² table
    max_iters: Optional[int] = None
    delta: Optional[float] = None
    fuse_gather: bool = True  # single fused (dist, lab) all-gather
    lab_i16: bool = False  # gather labels as int16 (S < 32768): 6B/vertex
    frontier_size: int = 1024  # top-K dirty rows per device (mode="frontier")
    # static H: carry a replicated (H+1, 4) per-round telemetry buffer
    # (obs.ROUND_CHANNELS rows; global — psum'd — counts) through the
    # fixpoint loop. 0 keeps the raw engine lean; the solver passes its
    # SolverConfig.telemetry_rounds explicitly.
    telemetry_rounds: int = 0
    # static flag: additionally carry a replicated (H+1, n_ranks, 4)
    # per-rank buffer (all_gather of the per-device channel rows) — the
    # flight recorder behind repro.obs.flight.  Disabled, the buffer has
    # zero rank slots and the per-rank collectives are never traced.
    telemetry_per_rank: bool = False

    def __post_init__(self) -> None:
        if self.mode not in ("dense", "bucket", "frontier"):
            raise ValueError(
                f"unknown mode: {self.mode!r} "
                f"(use 'dense' | 'bucket' | 'frontier')"
            )
        if self.lab_i16 and self.num_seeds >= 32768:
            raise ValueError(
                f"lab_i16 gathers labels as int16, which requires "
                f"|S| < 32768; got num_seeds={self.num_seeds}"
            )
        if self.fuse_gather and not self.lab_i16 and self.num_seeds >= 2**24:
            raise ValueError(
                f"fuse_gather packs labels into an f32 all-gather, exact "
                f"only for |S| < 2**24; got num_seeds={self.num_seeds} — "
                f"use fuse_gather=False (or lab_i16 for |S| < 32768)"
            )
        if self.mode == "frontier" and self.local_steps != 1:
            raise ValueError(
                f"local_steps > 1 is not supported with mode='frontier' "
                f"(the top-K candidates must cross devices every round); "
                f"got local_steps={self.local_steps}"
            )
        if self.frontier_size < 1:
            raise ValueError(
                f"frontier_size must be >= 1, got {self.frontier_size}"
            )
        if self.telemetry_rounds < 0:
            raise ValueError(
                f"telemetry_rounds must be >= 0, got {self.telemetry_rounds}"
            )
        if self.telemetry_per_rank and self.telemetry_rounds < 1:
            raise ValueError(
                "telemetry_per_rank requires telemetry_rounds >= 1 "
                "(the per-rank flight recorder rides the round buffer)"
            )


def _spec(*names):
    from jax.sharding import PartitionSpec as P

    return P(*names)


def sat_psum(x: jax.Array, axes) -> jax.Array:
    """``psum`` of a non-negative int32 count per device, held at 2**31 - 1
    instead of wrapping: the 16-bit halves are summed apart (exact for
    fewer than 2**15 devices) and joined only where the total fits."""
    hi, lo = jax.lax.psum(jnp.stack([x >> 16, x & 0xFFFF]), axes)
    fits = hi <= (I32_MAX - lo) >> 16
    return jnp.where(fits, (hi << 16) + lo, I32_MAX)


def make_dist_steiner(
    mesh,
    cfg: DistSteinerConfig,
    *,
    vert_axis: str = "model",
    replica_axes: Sequence[str] = ("data",),
):
    """Builds the jitted distributed Steiner pipeline for ``mesh``.

    For ``mode="dense"``/``"bucket"`` returns ``fn(src, dst, w, seeds) ->
    (dist, lab, pred, marked, path_edge, bridge (bu, bv, bw, bvalid),
    total, num_edges, stats)`` where the edge arrays follow the
    :class:`Partition` layout.  For ``mode="frontier"`` the signature is
    ``fn(nbr, wgt, row2v, seeds)`` over the :class:`EllPartition` layout
    (same 9-part output).
    """
    from jax.sharding import NamedSharding

    replica_axes = tuple(replica_axes)
    all_axes = replica_axes + (vert_axis,)
    S = cfg.num_seeds
    nb = cfg.nb
    n_blocks = mesh.shape[vert_axis]
    npad = nb * n_blocks
    # frontier advances ≤ K rows/device/round: allow proportionally more
    # rounds before the safety cap (matching voronoi_cells_frontier)
    default_cap = (16 if cfg.mode == "frontier" else 4) * cfg.n + 64
    cap = cfg.max_iters if cfg.max_iters is not None else default_cap
    cap = min(cap, 2**31 - 2)  # int32 loop counter at billion-vertex scale

    def gather_state(dist_l, lab_l):
        """All-gather the vertex state along the vertex axis.

        ``fuse_gather`` packs (dist, lab) into one f32 collective — labels
        are exact in f32 for S < 2^24 (paper max |S| = 10K).
        ``lab_i16`` instead gathers labels as int16 (valid for S < 32768):
        6 instead of 8 wire bytes per vertex per round.  Both bounds are
        enforced eagerly by :class:`DistSteinerConfig` validation.
        """
        with jax.named_scope("exchange"):
            if cfg.lab_i16:
                distf = jax.lax.all_gather(dist_l, vert_axis, tiled=True)
                lab16 = jax.lax.all_gather(
                    lab_l.astype(jnp.int16), vert_axis, tiled=True
                )
                return distf, lab16.astype(jnp.int32)
            if cfg.fuse_gather:
                packed = jnp.stack([dist_l, lab_l.astype(jnp.float32)], axis=0)
                full = jax.lax.all_gather(packed, vert_axis, axis=1, tiled=True)
                return full[0], full[1].astype(jnp.int32)
            distf = jax.lax.all_gather(dist_l, vert_axis, tiled=True)
            labf = jax.lax.all_gather(lab_l, vert_axis, tiled=True)
            return distf, labf

    def init_block(seeds, off):
        """Paper Alg. 3 INITIALIZATION for my (nb,) block slice.

        Scatters use ``min`` so duplicate seed entries are inert: a
        vertex listed at several seed indices is owned by the lowest
        index, matching :func:`repro.core.voronoi.init_state` (the serve
        planner's pad-with-duplicates contract).
        """
        sidx = jnp.arange(S, dtype=jnp.int32)
        inblk = (seeds >= off) & (seeds < off + nb)
        tgt = jnp.where(inblk, seeds - off, nb)
        dist_l = jnp.full((nb + 1,), INF, jnp.float32).at[tgt].min(0.0)[:nb]
        lab_l = jnp.full((nb + 1,), S, jnp.int32).at[tgt].min(sidx)[:nb]
        return dist_l, lab_l

    def chunk_pmin(x, fill):
        if cfg.pair_chunks <= 1:
            return jax.lax.pmin(x, all_axes)
        csz = -(-(S * S) // cfg.pair_chunks)
        pad = csz * cfg.pair_chunks - S * S
        xp = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
        xp = xp.reshape(cfg.pair_chunks, csz)

        def cbody(i, acc):
            return acc.at[i].set(jax.lax.pmin(xp[i], all_axes))

        out = jax.lax.fori_loop(0, cfg.pair_chunks, cbody, jnp.zeros_like(xp))
        return out.reshape(-1)[: S * S]

    def finish(
        dist_l, lab_l, pred_l, esrc, edst, ew, off, gids, iters, rlx, msg,
        hist, histr,
    ):
        """Stages 2-6 after Voronoi convergence (shared by every mode):
        pair tables → Allreduce(MIN) → replicated MST → bridge pruning →
        pred-walk marking.  ``(esrc, edst, ew)`` is my shard's directed
        edge slice in GLOBAL ids (+inf weights are inert)."""
        # ---- MIN distance edges → G'1 (paper Alg. 5) + Allreduce(MIN)
        with jax.named_scope("distance_graph"):
            distf, labf = gather_state(dist_l, lab_l)
            dm_l, um_l, vm_l = local_pair_tables(
                esrc, edst, ew, distf[esrc], distf[edst], labf[esrc],
                labf[edst], S,
            )
            dmat = chunk_pmin(dm_l, INF)
            um_c = jnp.where(dm_l == dmat, um_l, IMAX)
            umat = chunk_pmin(um_c, IMAX)
            vm_c = jnp.where((dm_l == dmat) & (um_l == umat), vm_l, IMAX)
            vmat = chunk_pmin(vm_c, IMAX)

        # ---- replicated MST (paper Alg. 3 line 17)
        with jax.named_scope("mst"):
            wmat = dmat.reshape(S, S)
            wmat = jnp.minimum(wmat, wmat.T)
            wmat = jnp.where(jnp.eye(S, dtype=bool), INF, wmat)
            parent = (
                prim_dense(wmat) if cfg.mst_algo == "prim"
                else boruvka_dense(wmat)
            )
        with jax.named_scope("extract"):
            # ---- bridge pruning + TREE_EDGE (paper Alg. 6), pointer doubling
            bu, bv, bw, bvalid = bridge_endpoints(dmat, umat, vmat, distf, parent, S)
            predf = jax.lax.all_gather(pred_l, vert_axis, tiled=True)  # (npad,)
            ep_tgt_u = jnp.where(bvalid & (bu >= off) & (bu < off + nb), bu - off, nb)
            ep_tgt_v = jnp.where(bvalid & (bv >= off) & (bv < off + nb), bv - off, nb)
            marked_l = (
                jnp.zeros((nb + 1,), jnp.bool_)
                .at[ep_tgt_u]
                .set(True)
                .at[ep_tgt_v]
                .set(True)[:nb]
            )

            def mbody(carry):
                marked_l, ptr, _ = carry
                markedf = jax.lax.all_gather(marked_l, vert_axis, tiled=True)
                t = ptr - off
                inb = (t >= 0) & (t < nb)
                hit = (
                    jax.ops.segment_max(
                        jnp.where(inb, markedf.astype(jnp.int32), 0),
                        jnp.clip(t, 0, nb - 1),
                        nb,
                    )
                    > 0
                )
                new = marked_l | hit
                ch = jax.lax.pmax(
                    jnp.any(new != marked_l).astype(jnp.int32), all_axes
                )
                return new, ptr[ptr], ch > 0

            marked_l, _, _ = jax.lax.while_loop(
                lambda c: c[2], mbody, (marked_l, predf, jnp.bool_(True))
            )

            path_edge_l = marked_l & (pred_l != gids)
            path_w = jnp.where(path_edge_l, dist_l - distf[pred_l], 0.0)
            total = jax.lax.psum(jnp.sum(path_w), (vert_axis,)) + jnp.sum(bw)
            nedges = jax.lax.psum(
                jnp.sum(path_edge_l).astype(jnp.int32), (vert_axis,)
            ) + jnp.sum(bvalid).astype(jnp.int32)

            stats = jnp.stack([iters, rlx, msg])
            return (
                dist_l,
                lab_l,
                pred_l,
                marked_l,
                path_edge_l,
                bu,
                bv,
                bw,
                bvalid,
                total,
                nedges,
                stats,
                hist,
                histr,
            )

    # per-round telemetry row (obs.ROUND_CHANNELS): all channels are
    # global (psum'd) int32 counts, so the carried history is
    # replica-uniform and rides a replicated out_spec.  Phantom padding
    # vertices (gids >= n) never settle; subtract them from the unreached
    # residual.
    n_ghost = npad - cfg.n
    hist0 = hist_init(cfg.telemetry_rounds)

    def round_row(front, dmsg, imp, dl):
        unr = (
            jax.lax.psum(jnp.sum(~jnp.isfinite(dl)), (vert_axis,)) - n_ghost
        )
        return jnp.stack([front, dmsg, imp, unr])

    # ---- per-rank flight recorder (cfg.telemetry_per_rank) ----
    # Rank = linear device index in (replica..., vert) axis order, so the
    # all_gather'd rows land at rank r*n_blocks + b.  Disabled, the buffer
    # carries zero rank slots and no per-rank collective is ever traced —
    # the round loop is textually identical to the global-only path.
    per_rank = cfg.telemetry_per_rank
    n_rep_total = 1
    for _a in replica_axes:
        n_rep_total *= mesh.shape[_a]
    n_ranks = n_rep_total * n_blocks if per_rank else 0
    histr0 = hist_init(cfg.telemetry_rounds, n_ranks)

    def rank_rows(front_l, msg_l, imp_l, unr_l):
        """All-gather this device's channel row → replica-uniform
        (n_ranks, 4).  Callers pre-gate replica-uniform block channels to
        the replica-0 rank so the per-rank rows sum exactly (integer
        counts) to the global channels."""
        row = jnp.stack([front_l, msg_l, imp_l, unr_l])
        return jax.lax.all_gather(row, all_axes, tiled=False)

    def body(src, dst, w, seeds):
        my_blk = jax.lax.axis_index(vert_axis)
        off = my_blk * nb
        gids = jnp.arange(nb, dtype=jnp.int32) + off
        ldst = dst - off  # partitioner guarantees dst ∈ my block

        # ---- INITIALIZATION (paper Alg. 3 lines 1-9)
        dist_l, lab_l = init_block(seeds, off)
        pred_l = gids

        if cfg.mode == "bucket":
            wfin = jnp.where(jnp.isfinite(w), w, 0.0)
            wsum = jax.lax.psum(jnp.sum(wfin), all_axes)
            wcnt = jax.lax.psum(
                jnp.sum(jnp.isfinite(w).astype(jnp.float32)), all_axes
            )
            delta = (
                jnp.float32(cfg.delta)
                if cfg.delta is not None
                else jnp.maximum(wsum / jnp.maximum(wcnt, 1.0), 1e-6)
            )
        else:
            delta = jnp.float32(0.0)

        def local_relax(dist_l, lab_l, pred_l, distf, labf, theta):
            """One relaxation against (possibly stale) gathered state.

            Sources in our own block read the *fresh* local copy — the
            paper's asynchronous in-rank progress.
            """
            sin = (src >= off) & (src < off + nb)
            lsrc = jnp.clip(src - off, 0, nb - 1)
            dsrc = jnp.where(sin, dist_l[lsrc], distf[src])
            lsrc_lab = jnp.where(sin, lab_l[lsrc], labf[src])
            cand = dsrc + w
            if cfg.mode == "bucket":
                cand = jnp.where(dsrc <= theta, cand, INF)
            m = jax.ops.segment_min(cand, ldst, nb)
            e1 = cand == m[ldst]
            ml = jax.ops.segment_min(jnp.where(e1, lsrc_lab, IMAX), ldst, nb)
            e2 = e1 & (lsrc_lab == ml[ldst])
            ms = jax.ops.segment_min(jnp.where(e2, src, IMAX), ldst, nb)
            upd = jnp.isfinite(m) & (
                (m < dist_l)
                | ((m == dist_l) & (ml < lab_l))
                | ((m == dist_l) & (ml == lab_l) & (ms < pred_l))
            )
            new = (
                jnp.where(upd, m, dist_l),
                jnp.where(upd, ml, lab_l),
                jnp.where(upd, ms, pred_l),
            )
            att = jnp.sum(jnp.isfinite(cand))
            return new, upd, att

        def merge_replicas(dist_l, lab_l, pred_l):
            """Lexicographic pmin of diverged replica states (local-steps)."""
            d = jax.lax.pmin(dist_l, replica_axes)
            lc = jnp.where(dist_l == d, lab_l, IMAX)
            l = jax.lax.pmin(lc, replica_axes)
            pc = jnp.where((dist_l == d) & (lab_l == l), pred_l, IMAX)
            p = jax.lax.pmin(pc, replica_axes)
            return d, l, p

        if per_rank:
            # block-state channels (frontier/relaxations/unreached) are
            # replica-uniform; attribute them to each block's replica-0
            # rank so per-rank rows sum exactly to the global channels.
            is_r0 = sum(jax.lax.axis_index(a) for a in replica_axes) == 0
            my_ghost = jnp.sum(gids >= cfg.n)

        # ---- VORONOI_CELL_ASYNC (paper Alg. 4)
        def vbody(carry):
            dist_l, lab_l, pred_l, theta, it, rlx, msg, _, hist, histr = carry
            distf, labf = gather_state(dist_l, lab_l)

            def inner(i, c):
                dl, ll, pl, msg_i = c
                (dl, ll, pl), _, att = local_relax(dl, ll, pl, distf, labf, theta)
                return dl, ll, pl, sat_add(msg_i, att)

            dl, ll, pl, msg_i = jax.lax.fori_loop(
                0, cfg.local_steps, inner, (dist_l, lab_l, pred_l, jnp.int32(0))
            )
            with jax.named_scope("exchange"):
                dl, ll, pl = merge_replicas(dl, ll, pl)
            changed_l = (
                jnp.any(dl != dist_l) | jnp.any(ll != lab_l) | jnp.any(pl != pred_l)
            )
            changed = jax.lax.pmax(changed_l.astype(jnp.int32), all_axes) > 0
            imp_l = jnp.sum((dl != dist_l) | (ll != lab_l) | (pl != pred_l))
            imp = jax.lax.psum(imp_l, (vert_axis,))
            msg_g = sat_psum(msg_i, all_axes)
            if cfg.mode == "bucket":
                # frontier = vertices under the bucket threshold this round
                front_l = jnp.sum(jnp.isfinite(dl) & (dl <= theta))
                front = jax.lax.psum(front_l, (vert_axis,))
            else:
                # dense has no explicit frontier; its active set IS the
                # improved-vertex set
                front_l = imp_l
                front = imp
            hist = _hist_write(
                hist, it, round_row(front, msg_g, imp, dl)
            )
            if per_rank:
                z = jnp.int32(0)
                unr_l = jnp.sum(~jnp.isfinite(dl)) - my_ghost
                histr = _hist_write(histr, it, rank_rows(
                    jnp.where(is_r0, front_l, z),
                    msg_i,
                    jnp.where(is_r0, imp_l, z),
                    jnp.where(is_r0, unr_l, z),
                ))
            if cfg.mode == "bucket":
                # terminate only on a no-change round with every source active
                mx_l = jnp.max(jnp.where(jnp.isfinite(dl), dl, -INF))
                max_fin = jax.lax.pmax(mx_l, all_axes)
                done = ~changed & (theta >= max_fin)
                theta = jnp.where(changed, theta, theta + delta)
                work = ~done
            else:
                work = changed
            return (
                dl, ll, pl, theta, it + 1, sat_add(rlx, imp),
                sat_add(msg, msg_g), work, hist, histr,
            )

        def vcond(carry):
            _, _, _, _, it, _, _, work, _, _ = carry
            return work & (it < cap)

        zero = jnp.int32(0)
        with jax.named_scope("voronoi"):
            (
                dist_l, lab_l, pred_l, _, iters, rlx, msg, _, hist, histr
            ) = jax.lax.while_loop(
                vcond,
                vbody,
                (
                    dist_l,
                    lab_l,
                    pred_l,
                    jnp.float32(0.0),
                    zero,
                    zero,
                    zero,
                    jnp.bool_(True),
                    hist0,
                    histr0,
                ),
            )

        return finish(
            dist_l, lab_l, pred_l, src, dst, w, off, gids, iters, rlx, msg,
            hist, histr,
        )

    def frontier_body(nbr, wgt, row2v, seeds):
        """Paper §IV message prioritization over the sharded ELL view.

        Each device keeps a per-row *dirty* flag and, every round, selects
        its top-K lowest-distance dirty rows — the distributed analogue of
        the paper's priority message queue (one best-effort queue per
        rank) — relaxing only those rows' O(K·k) edges instead of the full
        O(Eb) shard.  Candidates are delivered to their (possibly remote)
        destination block by the same lexicographic pmin merge the
        dense/bucket paths use for replica divergence, here extended over
        the vertex axis; convergence lands on the identical (dist, lab,
        pred) fixpoint, so the tree is bit-identical to dense/bucket.
        """
        my_blk = jax.lax.axis_index(vert_axis)
        off = my_blk * nb
        gids = jnp.arange(nb, dtype=jnp.int32) + off
        dist_l, lab_l = init_block(seeds, off)
        pred_l = gids

        rb = nbr.shape[0]
        K = min(cfg.frontier_size, rb)  # top_k cap on small shards
        # local vertex of each of my rows (row sources live in my block;
        # padding rows alias the block base → local 0)
        lrow = jnp.clip(row2v - off, 0, nb - 1)
        # rows with no finite edge (ELL padding, degree-0 vertices) can
        # never produce a message: permanently ineligible for the queue
        has_edges = jnp.any(jnp.isfinite(wgt), axis=1)
        dirty0 = jnp.isin(row2v, seeds) & has_edges

        if per_rank:
            # frontier pops and message attempts are genuinely per-device
            # here; only the block-state channels (relaxations/unreached)
            # need replica-0 attribution.
            is_r0 = sum(jax.lax.axis_index(a) for a in replica_axes) == 0
            my_ghost = jnp.sum(gids >= cfg.n)

        def vbody(carry):
            dist_l, lab_l, pred_l, dirty, it, rlx, msg, _, hist, histr = carry
            # --- the priority queue: top-K lowest-distance dirty rows
            rowdist = jnp.where(dirty, dist_l[lrow], INF)
            _, rows = jax.lax.top_k(-rowdist, K)
            sel_ok = jnp.isfinite(rowdist[rows])
            dirty = dirty.at[rows].set(dirty[rows] & ~sel_ok)
            # --- relax only the selected rows' edges
            lsel = lrow[rows]
            rwgt = jnp.where(sel_ok[:, None], wgt[rows], INF)
            cand = dist_l[lsel][:, None] + rwgt  # (K, k)
            labc = jnp.where(sel_ok, lab_l[lsel], IMAX)
            srcc = jnp.where(sel_ok, row2v[rows], IMAX)
            flat_dst = nbr[rows].reshape(-1)  # GLOBAL destination ids
            flat_cand = cand.reshape(-1)
            flat_lab = jnp.broadcast_to(labc[:, None], cand.shape).reshape(-1)
            flat_src = jnp.broadcast_to(srcc[:, None], cand.shape).reshape(-1)
            # local 3-pass lexicographic segmin over the FULL vertex range
            m = jax.ops.segment_min(flat_cand, flat_dst, npad)
            e1 = flat_cand == m[flat_dst]
            ml = jax.ops.segment_min(
                jnp.where(e1, flat_lab, IMAX), flat_dst, npad
            )
            e2 = e1 & (flat_lab == ml[flat_dst])
            ms = jax.ops.segment_min(
                jnp.where(e2, flat_src, IMAX), flat_dst, npad
            )
            # --- deliver to the owning blocks: lexicographic pmin over
            # replicas AND blocks, then my (nb,) slice of the result
            with jax.named_scope("exchange"):
                m_g = jax.lax.pmin(m, all_axes)
                ml_g = jax.lax.pmin(jnp.where(m == m_g, ml, IMAX), all_axes)
                ms_g = jax.lax.pmin(
                    jnp.where((m == m_g) & (ml == ml_g), ms, IMAX), all_axes
                )
            m_s = jax.lax.dynamic_slice_in_dim(m_g, off, nb)
            ml_s = jax.lax.dynamic_slice_in_dim(ml_g, off, nb)
            ms_s = jax.lax.dynamic_slice_in_dim(ms_g, off, nb)
            upd = jnp.isfinite(m_s) & (
                (m_s < dist_l)
                | ((m_s == dist_l) & (ml_s < lab_l))
                | ((m_s == dist_l) & (ml_s == lab_l) & (ms_s < pred_l))
            )
            dist_l = jnp.where(upd, m_s, dist_l)
            lab_l = jnp.where(upd, ml_s, lab_l)
            pred_l = jnp.where(upd, ms_s, pred_l)
            # rows of updated vertices become dirty again (their replicas
            # compute the same upd, so every shard of v's rows agrees)
            dirty = dirty | (upd[lrow] & has_edges)
            imp_l = jnp.sum(upd)
            imp = jax.lax.psum(imp_l, (vert_axis,))
            att = jnp.sum(jnp.isfinite(flat_cand))
            msg_g = sat_psum(att, all_axes)
            # frontier = rows actually popped across every per-device queue
            front_l = jnp.sum(sel_ok)
            front = jax.lax.psum(front_l, all_axes)
            hist = _hist_write(
                hist, it,
                round_row(front, msg_g, imp, dist_l),
            )
            if per_rank:
                z = jnp.int32(0)
                unr_l = jnp.sum(~jnp.isfinite(dist_l)) - my_ghost
                histr = _hist_write(histr, it, rank_rows(
                    front_l,
                    att,
                    jnp.where(is_r0, imp_l, z),
                    jnp.where(is_r0, unr_l, z),
                ))
            work = jax.lax.pmax(jnp.any(dirty).astype(jnp.int32), all_axes) > 0
            return (
                dist_l, lab_l, pred_l, dirty, it + 1, sat_add(rlx, imp),
                sat_add(msg, msg_g), work, hist, histr,
            )

        def vcond(carry):
            _, _, _, _, it, _, _, work, _, _ = carry
            return work & (it < cap)

        zero = jnp.int32(0)
        with jax.named_scope("voronoi"):
            (
                dist_l, lab_l, pred_l, _, iters, rlx, msg, _, hist, histr
            ) = jax.lax.while_loop(
                vcond,
                vbody,
                (
                    dist_l,
                    lab_l,
                    pred_l,
                    dirty0,
                    zero,
                    zero,
                    zero,
                    jnp.bool_(True),
                    hist0,
                    histr0,
                ),
            )
        # my shard's directed edges, flattened from the ELL rows (padding
        # lanes carry +inf weight — inert through the pair tables)
        esrc = jnp.broadcast_to(row2v[:, None], nbr.shape).reshape(-1)
        return finish(
            dist_l, lab_l, pred_l, esrc, nbr.reshape(-1), wgt.reshape(-1),
            off, gids, iters, rlx, msg, hist, histr,
        )

    if cfg.mode == "frontier":
        body = frontier_body

    P = _spec
    edge_spec = P((*replica_axes, vert_axis))
    state_spec = P(vert_axis)
    rep = P()
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(edge_spec, edge_spec, edge_spec, rep),
        out_specs=(
            state_spec,
            state_spec,
            state_spec,
            state_spec,
            state_spec,
            rep,
            rep,
            rep,
            rep,
            rep,
            rep,
            rep,
            rep,  # hist — global counts, replica-uniform
            rep,  # histr — all-gathered per-rank rows, replica-uniform
        ),
        check_vma=False,
    )
    in_sh = tuple(
        NamedSharding(mesh, s) for s in (edge_spec, edge_spec, edge_spec, rep)
    )
    return jax.jit(fn, in_shardings=in_sh)


@dataclasses.dataclass(frozen=True)
class DistSteinerResult:
    """Host-friendly view of the distributed pipeline output."""

    dist: np.ndarray
    lab: np.ndarray
    pred: np.ndarray
    marked: np.ndarray
    path_edge: np.ndarray
    bridge_u: np.ndarray
    bridge_v: np.ndarray
    bridge_w: np.ndarray
    bridge_valid: np.ndarray
    total_distance: float
    num_edges: int
    iterations: int
    relaxations: int
    messages: int
    # (H+1, 4) per-round telemetry (obs.ROUND_CHANNELS rows); None when
    # the pipeline ran with telemetry_rounds=0
    history: Optional[np.ndarray] = None
    # (H+1, n_ranks, 4) per-rank flight-recorder buffer; None unless the
    # pipeline ran with telemetry_per_rank=True
    per_rank: Optional[np.ndarray] = None
    # edges the relaxation reads per round over all devices (static)
    scan_per_round: int = 0

    def edge_set(self):
        out = set()
        for v in np.nonzero(self.path_edge)[0]:
            a, b = int(self.pred[v]), int(v)
            out.add((min(a, b), max(a, b)))
        for i in np.nonzero(self.bridge_valid)[0]:
            a, b = int(self.bridge_u[i]), int(self.bridge_v[i])
            out.add((min(a, b), max(a, b)))
        return out


def result_from_device(out, n: int, scan_per_round: int = 0) -> DistSteinerResult:
    """Converts the raw 14-tuple pipeline output to a host-side result."""
    (
        dist,
        lab,
        pred,
        marked,
        path_edge,
        bu,
        bv,
        bw,
        bvalid,
        total,
        ne,
        stats,
        hist,
        histr,
    ) = [np.asarray(x) for x in out]
    return DistSteinerResult(
        dist=dist[:n],
        lab=lab[:n],
        pred=pred[:n],
        marked=marked[:n],
        path_edge=path_edge[:n],
        bridge_u=bu,
        bridge_v=bv,
        bridge_w=bw,
        bridge_valid=bvalid,
        total_distance=float(total),
        num_edges=int(ne),
        iterations=int(stats[0]),
        relaxations=int(stats[1]),
        messages=int(stats[2]),
        history=hist if hist.shape[0] > 1 else None,
        per_rank=histr if histr.shape[1] > 0 else None,
        scan_per_round=scan_per_round,
    )


def run_dist_steiner(
    mesh,
    part: Partition,
    seeds: np.ndarray,
    *,
    vert_axis: str = "model",
    replica_axes: Sequence[str] = ("data",),
    **cfg_kw,
) -> DistSteinerResult:
    """Convenience wrapper: partition → device_put → jitted pipeline → host.

    .. deprecated::
        Thin shim over the unified solver — delegates to the ``"mesh1d"``
        backend of :mod:`repro.solver` (``SolverConfig(backend="mesh1d")``
        → ``SteinerSolver.prepare(graph)`` → ``handle.solve(seeds)``),
        which additionally reuses the device-placed partition and compiled
        executable across queries.  Kept for callers that already hold a
        ``(mesh, Partition)`` pair; each call re-places the edge arrays
        and re-traces.
    """
    from repro.solver.config import SolverConfig
    from repro.solver.registry import get_backend

    cfg = SolverConfig(backend="mesh1d", **cfg_kw)
    return get_backend("mesh1d").solve_prepared(
        cfg,
        mesh,
        part,
        np.asarray(seeds, np.int32),
        vert_axis=vert_axis,
        replica_axes=tuple(replica_axes),
    )
