"""2D (src-block × dst-block) partitioned Voronoi engine — beyond-paper.

The paper's (and our baseline's) 1D partition all-gathers the FULL
(dist, lab) vector every round: wire ≈ n·8 bytes/device/round. The classic
2D SpMV decomposition assigns edge (u, v) to device (row(u), col(v)):

  * vertices live in R·C fine blocks of ``nf``; device (r, c) owns fine
    block f = r·C + c (state spec P(("data", "model")));
  * the round's gather is only along the row (``all_gather`` over "model"
    → the n/R-sized source range of row r);
  * the lexicographic pmin runs down the column (over "data") on the
    n/C-sized destination range.

Per-round wire: n/R (gather) + ~6·n/C (three pmin passes) vs the 1D
n + 6·n/16 — a ~3× analytic cut at R=C=16, confirmed by the dry-run
collective parse (see EXPERIMENTS §4.1).

Voronoi relaxation only; the pair-table/MST/extraction phases reuse the
same logic as the 1D engine with one-time global gathers (they are <5% of
round traffic — paper §V-A). Converged output is bit-identical to the 1D
engine and the numpy Dijkstra oracle (tested).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dist_steiner import sat_psum
from repro.core.distance_graph import local_pair_tables
from repro.core.mst import boruvka_dense, prim_dense
from repro.core.tree import bridge_endpoints
from repro.core.voronoi import _hist_write, hist_init, sat_add

INF = jnp.inf
IMAX = jnp.iinfo(jnp.int32).max


@dataclasses.dataclass(frozen=True)
class Partition2D:
    """Device-major flat edge arrays for the (row × col) layout.

    For device (r, c): ``src_row`` is LOCAL to row r's vertex range
    [r·C·nf, (r+1)·C·nf); ``dst_col`` is local to column c's interleaved
    range (fine block i·C+c ↦ [i·nf, (i+1)·nf)).
    """

    src_row: np.ndarray
    dst_col: np.ndarray
    w: np.ndarray
    n: int
    nf: int
    R: int
    C: int
    eb: int

    @property
    def npad(self) -> int:
        return self.nf * self.R * self.C


def partition_edges_2d(
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray,
    n: int,
    *,
    R: int,
    C: int,
    symmetrize: bool = True,
    block_multiple: int = 8,
) -> Partition2D:
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float32)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        w = np.concatenate([w, w])
    nf = -(-n // (R * C))
    nf = -(-nf // block_multiple) * block_multiple
    fine_s = src // nf
    fine_d = dst // nf
    r = np.minimum(fine_s // C, R - 1)
    c = fine_d % C
    dev = r * C + c
    order = np.argsort(dev, kind="stable")
    src, dst, w, dev = src[order], dst[order], w[order], dev[order]
    counts = np.bincount(dev, minlength=R * C)
    eb = -(-int(counts.max()) // block_multiple) * block_multiple
    osrc = np.zeros((R * C, eb), np.int32)
    odst = np.zeros((R * C, eb), np.int32)
    ow = np.full((R * C, eb), np.inf, np.float32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for d in range(R * C):
        s0, cnt = starts[d], counts[d]
        sl = slice(s0, s0 + cnt)
        rr = d // C
        # local src within row rr
        osrc[d, :cnt] = src[sl] - rr * C * nf
        # local dst within column c: fine i = dst//nf (i % C == c)
        fi = dst[sl] // nf
        odst[d, :cnt] = (fi // C) * nf + (dst[sl] % nf)
        ow[d, :cnt] = w[sl]
    return Partition2D(
        src_row=osrc.reshape(-1),
        dst_col=odst.reshape(-1),
        w=ow.reshape(-1),
        n=n,
        nf=nf,
        R=R,
        C=C,
        eb=eb,
    )


def make_dist_steiner_2d(
    mesh,
    *,
    n: int,
    nf: int,
    num_seeds: int,
    mode: str = "bucket",
    mst_algo: str = "prim",
    max_iters=None,
    delta=None,
    row_axis: str = "data",
    col_axis: str = "model",
    telemetry_rounds: int = 0,
    telemetry_per_rank: bool = False,
):
    """Jitted 2D pipeline: fn(src_row, dst_col, w, seeds) → same outputs as
    the 1D engine (state in fine-block order = plain vertex order)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if telemetry_rounds < 0:
        raise ValueError(
            f"telemetry_rounds must be >= 0, got {telemetry_rounds}"
        )
    if telemetry_per_rank and telemetry_rounds < 1:
        raise ValueError(
            "telemetry_per_rank requires telemetry_rounds >= 1 "
            "(the per-rank flight recorder rides the round buffer)"
        )
    R = mesh.shape[row_axis]
    C = mesh.shape[col_axis]
    S = num_seeds
    npad = nf * R * C
    row_n = C * nf  # vertices per row block
    col_n = R * nf  # vertices per column block
    cap = min(max_iters if max_iters is not None else 4 * n + 64, 2**31 - 2)
    both = (row_axis, col_axis)
    n_ghost = npad - n  # phantom padding vertices, never reached

    def body(src_l, dst_l, w, seeds):
        r_idx = jax.lax.axis_index(row_axis)
        c_idx = jax.lax.axis_index(col_axis)
        fine = r_idx * C + c_idx
        off = fine * nf  # global base of my state slice
        gids = jnp.arange(nf, dtype=jnp.int32) + off

        # ---- init my (nf,) state slice
        sidx = jnp.arange(S, dtype=jnp.int32)
        inblk = (seeds >= off) & (seeds < off + nf)
        tgt = jnp.where(inblk, seeds - off, nf)
        dist_l = jnp.full((nf + 1,), INF, jnp.float32).at[tgt].set(0.0)[:nf]
        lab_l = jnp.full((nf + 1,), S, jnp.int32).at[tgt].set(sidx)[:nf]
        pred_l = gids

        if mode == "bucket":
            wfin = jnp.where(jnp.isfinite(w), w, 0.0)
            wsum = jax.lax.psum(jnp.sum(wfin), both)
            wcnt = jax.lax.psum(jnp.sum(jnp.isfinite(w).astype(jnp.float32)), both)
            dlt = (
                jnp.float32(delta)
                if delta is not None
                else jnp.maximum(wsum / jnp.maximum(wcnt, 1.0), 1e-6)
            )
        else:
            dlt = jnp.float32(0.0)

        # my slice's position inside the row gather / the column range
        row_pos = c_idx * nf  # slice offset within the gathered row block
        col_pos = r_idx * nf  # slice offset within the column range

        hist0 = hist_init(telemetry_rounds)
        # per-rank flight recorder: every channel is genuinely per-device
        # on the 2D mesh (state slices are disjoint), so the rank row is
        # just this device's local counts; rank = r*C + c via the
        # (row, col) all_gather order.  Disabled → zero rank slots.
        n_ranks = R * C if telemetry_per_rank else 0
        histr0 = hist_init(telemetry_rounds, n_ranks)
        if telemetry_per_rank:
            my_ghost = jnp.sum(gids >= n)

        def vbody(carry):
            dist_l, lab_l, pred_l, theta, it, rlx, msg, _, hist, histr = carry
            # gather (dist, lab) of MY ROW's vertex range — n/R wire
            with jax.named_scope("exchange"):
                packed = jnp.stack([dist_l, lab_l.astype(jnp.float32)], axis=0)
                rowst = jax.lax.all_gather(packed, col_axis, axis=1, tiled=True)
            dist_row, lab_row = rowst[0], rowst[1].astype(jnp.int32)

            dsrc = dist_row[src_l]
            lsrc = lab_row[src_l]
            cand = dsrc + w
            if mode == "bucket":
                cand = jnp.where(dsrc <= theta, cand, INF)
            gsrc = src_l + r_idx * row_n  # back to global ids for tie-break
            # local 3-pass lex segmin into my COLUMN's range (col_n,)
            loc_m = jax.ops.segment_min(cand, dst_l, col_n)
            e1 = cand == loc_m[dst_l]
            loc_ml = jax.ops.segment_min(jnp.where(e1, lsrc, IMAX), dst_l, col_n)
            e2 = e1 & (lsrc == loc_ml[dst_l])
            loc_ms = jax.ops.segment_min(jnp.where(e2, gsrc, IMAX), dst_l, col_n)
            # column-wide lexicographic merge — three n/C pmins (same
            # conditioned-contribution pattern as the Alg. 5 pair merge)
            with jax.named_scope("exchange"):
                m = jax.lax.pmin(loc_m, row_axis)
                ml = jax.lax.pmin(
                    jnp.where(loc_m == m, loc_ml, IMAX), row_axis
                )
                ms = jax.lax.pmin(
                    jnp.where((loc_m == m) & (loc_ml == ml), loc_ms, IMAX),
                    row_axis,
                )

            # my slice of the column result
            m_s = jax.lax.dynamic_slice_in_dim(m, col_pos, nf)
            ml_s = jax.lax.dynamic_slice_in_dim(ml, col_pos, nf)
            ms_s = jax.lax.dynamic_slice_in_dim(ms, col_pos, nf)
            upd = jnp.isfinite(m_s) & (
                (m_s < dist_l)
                | ((m_s == dist_l) & (ml_s < lab_l))
                | ((m_s == dist_l) & (ml_s == lab_l) & (ms_s < pred_l))
            )
            nd = jnp.where(upd, m_s, dist_l)
            nl = jnp.where(upd, ml_s, lab_l)
            npd = jnp.where(upd, ms_s, pred_l)
            ch_l = jnp.any(upd)
            changed = jax.lax.pmax(ch_l.astype(jnp.int32), both) > 0
            # state slices are disjoint across the 2D mesh (each device
            # owns one fine block), so a psum over both axes is the
            # global count — the paper's per-round work metrics
            imp_l = jnp.sum(upd)
            imp = jax.lax.psum(imp_l, both)
            att = jnp.sum(jnp.isfinite(cand))
            msg_g = sat_psum(att, both)
            if mode == "bucket":
                front_l = jnp.sum(jnp.isfinite(nd) & (nd <= theta))
                front = jax.lax.psum(front_l, both)
            else:
                front_l = imp_l
                front = imp
            unr = jax.lax.psum(jnp.sum(~jnp.isfinite(nd)), both) - n_ghost
            hist = _hist_write(hist, it, jnp.stack([front, msg_g, imp, unr]))
            if telemetry_per_rank:
                unr_l = jnp.sum(~jnp.isfinite(nd)) - my_ghost
                row = jnp.stack([front_l, att, imp_l, unr_l])
                rows = jax.lax.all_gather(row, both, tiled=False)
                histr = _hist_write(histr, it, rows)
            if mode == "bucket":
                mx = jnp.max(jnp.where(jnp.isfinite(nd), nd, -INF))
                max_fin = jax.lax.pmax(mx, both)
                done = ~changed & (theta >= max_fin)
                theta = jnp.where(changed, theta, theta + dlt)
                work = ~done
            else:
                work = changed
            return (
                nd, nl, npd, theta, it + 1, sat_add(rlx, imp),
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

        # ---- stages 2-6: one-time global gathers (cheap phases)
        with jax.named_scope("distance_graph"):
            packed = jnp.stack([dist_l, lab_l.astype(jnp.float32)], axis=0)
            fullst = jax.lax.all_gather(packed, both, axis=1, tiled=True)
            distf, labf = fullst[0], fullst[1].astype(jnp.int32)
            gsrc = src_l + r_idx * row_n
            gdst_fine = dst_l // nf
            gdst = (gdst_fine * C + c_idx) * nf + (dst_l % nf)
            dm_l, um_l, vm_l = local_pair_tables(
                gsrc, gdst, w, distf[gsrc], distf[gdst], labf[gsrc],
                labf[gdst], S,
            )
            dmat = jax.lax.pmin(dm_l, both)
            umat = jax.lax.pmin(jnp.where(dm_l == dmat, um_l, IMAX), both)
            vmat = jax.lax.pmin(
                jnp.where((dm_l == dmat) & (um_l == umat), vm_l, IMAX), both
            )
        with jax.named_scope("mst"):
            wmat = dmat.reshape(S, S)
            wmat = jnp.minimum(wmat, wmat.T)
            wmat = jnp.where(jnp.eye(S, dtype=bool), INF, wmat)
            parent = (
                prim_dense(wmat) if mst_algo == "prim" else boruvka_dense(wmat)
            )
        with jax.named_scope("extract"):
            bu, bv, bw, bvalid = bridge_endpoints(dmat, umat, vmat, distf, parent, S)

            predf = jax.lax.all_gather(pred_l, both, tiled=True)
            tu = jnp.where(bvalid & (bu >= off) & (bu < off + nf), bu - off, nf)
            tv = jnp.where(bvalid & (bv >= off) & (bv < off + nf), bv - off, nf)
            marked_l = (
                jnp.zeros((nf + 1,), jnp.bool_).at[tu].set(True).at[tv].set(True)[:nf]
            )

            def mbody(carry):
                marked_l, ptr, _ = carry
                markedf = jax.lax.all_gather(marked_l, both, tiled=True)
                t = ptr - off
                inb = (t >= 0) & (t < nf)
                hit = (
                    jax.ops.segment_max(
                        jnp.where(inb, markedf.astype(jnp.int32), 0),
                        jnp.clip(t, 0, nf - 1),
                        nf,
                    )
                    > 0
                )
                new = marked_l | hit
                ch = jax.lax.pmax(jnp.any(new != marked_l).astype(jnp.int32), both)
                return new, ptr[ptr], ch > 0

            marked_l, _, _ = jax.lax.while_loop(
                lambda cr: cr[2], mbody, (marked_l, predf, jnp.bool_(True))
            )
            path_edge_l = marked_l & (pred_l != gids)
            path_w = jnp.where(path_edge_l, dist_l - distf[pred_l], 0.0)
            total = jax.lax.psum(jnp.sum(path_w), both) + jnp.sum(bw)
            nedges = jax.lax.psum(
                jnp.sum(path_edge_l).astype(jnp.int32), both
            ) + jnp.sum(bvalid).astype(jnp.int32)
            stats = jnp.stack([iters, rlx, msg])
            return (dist_l, lab_l, pred_l, marked_l, path_edge_l,
                    bu, bv, bw, bvalid, total, nedges, stats, hist, histr)

    espec = P((row_axis, col_axis))
    st = P((row_axis, col_axis))
    rep = P()
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(espec, espec, espec, rep),
        out_specs=(
            st, st, st, st, st, rep, rep, rep, rep, rep, rep, rep,
            rep,  # hist — global counts, uniform across the mesh
            rep,  # histr — all-gathered per-rank rows, uniform
        ),
        check_vma=False,
    )
    in_sh = tuple(NamedSharding(mesh, s) for s in (espec, espec, espec, rep))
    return jax.jit(fn, in_shardings=in_sh)


def run_dist_steiner_2d(mesh, part: Partition2D, seeds, **kw):
    """Host wrapper mirroring run_dist_steiner (1D).

    .. deprecated::
        Thin shim over the unified solver — delegates to the ``"mesh2d"``
        backend of :mod:`repro.solver` (``SolverConfig(backend="mesh2d")``
        → ``SteinerSolver.prepare(graph)`` → ``handle.solve(seeds)``),
        which additionally reuses the device-placed partition and compiled
        executable across queries.
    """
    from repro.solver.config import SolverConfig
    from repro.solver.registry import get_backend

    row_axis = kw.pop("row_axis", "data")
    col_axis = kw.pop("col_axis", "model")
    cfg = SolverConfig(backend="mesh2d", **kw)
    return get_backend("mesh2d").solve_prepared(
        cfg,
        mesh,
        part,
        np.asarray(seeds, np.int32),
        row_axis=row_axis,
        col_axis=col_axis,
    )
