import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step, in_shardings=…).lower(**ShapeDtypeStructs)``
followed by ``.compile()`` must succeed on the single-pod (16×16) and
multi-pod (2×16×16) production meshes for every cell, and
``memory_analysis()`` must fit 16GB/chip. Results (memory, cost, parsed
collective bytes → roofline terms) are cached as JSON under
``benchmarks/results/dryrun/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-32b \
      --shape decode_32k --mesh single
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_IDS, get_arch
from repro.configs.base import ShapeSpec
from repro.launch.mesh import make_production_mesh
from repro.launch import roofline as rl
from repro.models import gnn as gnn_mod
from repro.models import recsys as rec_mod
from repro.models import transformer as tf_mod
from repro.optim import OptConfig, opt_state_specs

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


# ----------------------------------------------------------------------------
# Cell builders → (lowered, model_flops_total)
# ----------------------------------------------------------------------------



def _specs_gb(*trees) -> float:
    """Exact per-device bytes of ShapeDtypeStructs (shard shapes)."""
    total = 0
    for t in trees:
        for leaf in jax.tree.leaves(t):
            if not hasattr(leaf, "shape"):
                continue
            shard = leaf.shape
            sh = getattr(leaf, "sharding", None)
            if sh is not None:
                shard = sh.shard_shape(leaf.shape)
            n = 1
            for d in shard:
                n *= d
            total += n * leaf.dtype.itemsize
    return total / 2**30


def _lm_analytic_gb(cfg, shape, mesh, dp_axes, accum, state_gb) -> dict:
    """Per-device TPU memory model for LM cells.

    The CPU backend's memory_analysis inflates bf16 models (bf16 dots are
    emulated by hoisted f32 weight copies that real TPUs never make), so
    the fits verdict uses: exact sharded state (params/opt/cache/inputs,
    from the specs) + an activation working-set model (remat stack with
    sequence-parallel boundaries, bwd live set, f32 logits slice).
    """
    msz = mesh.shape["model"]
    dsz = 1
    for ax in dp_axes:
        dsz *= mesh.shape[ax]
    d, L = cfg.d_model, cfg.n_layers
    Vp = cfg.vocab_padded
    work = 0.0
    if shape.kind in ("train", "prefill"):
        chunks = max(accum, 1)  # grad-accum (train) or batch chunking (prefill)
        if shape.kind == "prefill":
            tok_dev = shape.global_batch * shape.seq_len // dsz
            chunks = max(1, min(shape.global_batch // dsz, tok_dev // 8192))
            chunks = 1 << (chunks.bit_length() - 1)
        tokm = shape.global_batch * shape.seq_len // dsz // chunks
        ff_shard = max(cfg.d_ff, cfg.n_shared * cfg.moe_d_ff if cfg.moe else 0)
        ff_shard = max(ff_shard // msz, d)
        # remat boundaries persist only when there is a backward pass
        stack = (L * tokm * d * 2 / msz) if shape.kind == "train" else 0.0
        live = 10 * tokm * max(d, ff_shard) * 2  # working set
        if cfg.moe:
            # dispatched slots: experts are model-sharded, so each device
            # holds cap/msz slots of width d
            cap = 1.25 * tokm * cfg.top_k / msz
            live += 6 * cap * max(d, cfg.moe_d_ff) * 2
        logits = tokm * (Vp // msz) * 4 * (3 if shape.kind == "train" else 0)
        if shape.kind == "prefill":
            logits = (shape.global_batch // dsz) * (Vp // msz) * 4
        work = (stack + live + logits) / 2**30
        if shape.kind == "train":
            # transient grads of one layer during update (rest is in state)
            work += 2 * state_gb / max(L, 1)
    else:  # decode: per-chunk attention buffers only
        bd = max(shape.global_batch // dsz, 1)
        work = (bd * cfg.n_heads * 4096 * 8.0) / 2**30 + 0.25
    return {"analytic_state_gb": state_gb, "analytic_work_gb": work,
            "analytic_peak_gb": state_gb + work}


def _lm_lower(cfg, shape: ShapeSpec, mesh, dp_axes, kv_chunk: int,
              grad_accum: int = 1, seq_shard: bool = True,
              unroll: bool = False):
    pspecs = tf_mod.param_specs(cfg, mesh)
    ispecs = tf_mod.input_specs(cfg, shape, mesh, dp_axes)
    if shape.kind == "train":
        ocfg = OptConfig(quantized=cfg.params_count() > 1e11)
        ospecs = opt_state_specs(pspecs, ocfg, mesh)
        psh = jax.tree.map(lambda x: x.sharding, pspecs)
        step = tf_mod.make_train_step(
            cfg, ocfg, dp_axes, kv_chunk=kv_chunk, grad_accum=grad_accum,
            seq_shard=seq_shard, param_shardings=psh, unroll=unroll,
        )
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            pspecs, ospecs, ispecs["tokens"]
        )
    if shape.kind == "prefill":
        # chunk the prefill batch so tokens-in-flight/device ≈ 8K
        dsz = 1
        for ax in dp_axes:
            dsz *= mesh.shape[ax]
        tok_dev = shape.global_batch * shape.seq_len // max(dsz, 1)
        bc = max(1, min(shape.global_batch // dsz, tok_dev // 8192))
        bc = 1 << (bc.bit_length() - 1)
        step = tf_mod.make_prefill_step(cfg, dp_axes, kv_chunk=kv_chunk,
                                        seq_shard=seq_shard, batch_chunks=bc,
                                        unroll=unroll)
        return jax.jit(step).lower(pspecs, ispecs["tokens"])
    if shape.kind == "decode":
        step = tf_mod.make_decode_step(cfg, dp_axes, unroll=unroll)
        return jax.jit(step, donate_argnums=(1,)).lower(
            pspecs, ispecs["caches"], ispecs["tokens"], ispecs["cache_len"]
        )
    raise ValueError(shape.kind)


def _cost_triple(compiled):
    ca = compiled.cost_analysis() or {}
    coll = rl.collective_bytes(compiled.as_text())
    return (
        float(ca.get("flops", 0.0)),
        float(ca.get("bytes accessed", 0.0)),
        coll,
    )


def _combine(consts, layer_terms):
    """const + Σ L_i × layer_i for (flops, bytes, coll-dict) triples."""
    f, b, c = consts
    f = max(f, 0.0)
    b = max(b, 0.0)
    c = {k: max(v, 0.0) for k, v in c.items()}
    for mult, (lf, lb, lc) in layer_terms:
        f += mult * max(lf, 0.0)
        b += mult * max(lb, 0.0)
        for k in c:
            c[k] += mult * max(lc.get(k, 0.0), 0.0)
    return f, b, c


def _lm_calibrated_cost(cfg, shape, mesh, dp_axes):
    """Layer-count-calibrated HLO cost.

    XLA's HLO cost analysis counts while/scan bodies ONCE, so a scanned
    L-layer model under-reports FLOPs/bytes/collective-bytes by ~L×. We
    compile tiny layer-count variants (one/two blocks per type) with
    single-chunk attention (trip-count-1 inner scan) and combine:

        total = const + Ld·(dense block) + Lm·(moe block)
    """
    import dataclasses as dc

    kv_chunk = max(shape.seq_len, 1024)  # one chunk → counted exactly

    def costs(ld, lm):
        if cfg.moe:
            v = dc.replace(cfg, n_layers=ld + lm, first_dense_layers=ld)
        else:
            v = dc.replace(cfg, n_layers=ld)
        # grad_accum=1 in cost compiles: identical total FLOPs, and the
        # accumulation scan body would otherwise be counted once.
        # unroll=True: XLA cost analysis never multiplies while trip counts
        # — the 1/2-layer calibration variants must be fully unrolled.
        lowered = _lm_lower(v, shape, mesh, dp_axes, kv_chunk, grad_accum=1,
                            unroll=True)
        return _cost_triple(lowered.compile())

    Ld = cfg.first_dense_layers if cfg.moe else cfg.n_layers
    Lm = cfg.n_layers - Ld if cfg.moe else 0
    if cfg.moe and Ld > 0:
        c11 = costs(1, 1)
        c21 = costs(2, 1)
        c12 = costs(1, 2)
        dense_l = tuple_sub(c21, c11)
        moe_l = tuple_sub(c12, c11)
        const = tuple_sub(tuple_sub(c11, dense_l), moe_l)
        return _combine(const, [(Ld, dense_l), (Lm, moe_l)])
    if cfg.moe:
        c1 = costs(0, 1)
        c2 = costs(0, 2)
        layer = tuple_sub(c2, c1)
        return _combine(tuple_sub(c1, layer), [(Lm, layer)])
    c1 = costs(1, 0)
    c2 = costs(2, 0)
    layer = tuple_sub(c2, c1)
    return _combine(tuple_sub(c1, layer), [(Ld, layer)])


def tuple_sub(a, b):
    return (
        a[0] - b[0],
        a[1] - b[1],
        {k: a[2].get(k, 0.0) - b[2].get(k, 0.0) for k in a[2]},
    )


def _lm_cell(arch, shape: ShapeSpec, mesh, dp_axes):
    cfg = arch.model
    act = cfg.active_params_count()
    if shape.kind == "train":
        mf = 6.0 * act * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        mf = 2.0 * act * shape.global_batch * shape.seq_len
    else:
        mf = 2.0 * act * shape.global_batch
    # memory compile: the production (scanned, chunked-attention) program.
    # grad_accum keeps microbatch tokens/device ≈ 16K (activation memory).
    accum = 1
    if shape.kind == "train":
        dsz = 1
        for ax in dp_axes:
            dsz *= mesh.shape[ax]
        tok_dev = shape.global_batch * shape.seq_len // max(dsz, 1)
        accum = max(1, min(shape.global_batch // dsz, tok_dev // 16384))
        accum = 1 << (accum.bit_length() - 1)  # power of two
    lowered = _lm_lower(cfg, shape, mesh, dp_axes, kv_chunk=1024,
                        grad_accum=accum)
    cost = _lm_calibrated_cost(cfg, shape, mesh, dp_axes)
    # analytic memory: exact sharded state + activation model
    pspecs = tf_mod.param_specs(cfg, mesh)
    ispecs = tf_mod.input_specs(cfg, shape, mesh, dp_axes)
    state = _specs_gb(pspecs, ispecs)
    if shape.kind == "train":
        ocfg = OptConfig(quantized=cfg.params_count() > 1e11)
        state += _specs_gb(opt_state_specs(pspecs, ocfg, mesh))
        state += _specs_gb(pspecs)  # accumulated-gradient buffer
    analytic = _lm_analytic_gb(cfg, shape, mesh, dp_axes, accum, state)
    return lowered, mf, cost, analytic


def _gnn_model_flops(cfg, shape) -> float:
    n, e, f = gnn_mod.effective_graph(shape)
    h = cfg.d_hidden
    if cfg.kind == "sage":
        fwd = 2 * n * (f * h + h * h) * cfg.n_layers + 2 * e * h
    elif cfg.kind == "gatedgcn":
        fwd = 2 * n * f * h + cfg.n_layers * (6 * 2 * max(n, e) * h * h + 4 * e * h)
    elif cfg.kind == "schnet":
        fwd = 2 * n * f * h + cfg.n_interactions * (
            2 * e * (cfg.rbf * h + h * h) + 4 * n * h * h
        )
    else:  # graphcast
        nm = n // 4 + 1
        fwd = (
            2 * n * f * h
            + cfg.n_layers * (2 * 8 * nm * (3 * h * h + 2 * h * h))
            + 2 * e * (3 * h * h + 2 * h * h) * 2
            + 2 * n * h * cfg.n_vars
        )
    return 3.0 * fwd  # fwd + bwd ≈ 3×


def _gnn_cell(arch, shape: ShapeSpec, mesh, dp_axes):
    cfg = arch.model
    _, _, f = gnn_mod.effective_graph(shape)
    pspecs = gnn_mod.param_specs(cfg, f, mesh)
    ispecs = gnn_mod.input_specs(cfg, shape, mesh, dp_axes)
    ocfg = OptConfig()
    ospecs = opt_state_specs(pspecs, ocfg, mesh)
    step = gnn_mod.make_train_step(cfg, shape, ocfg, dp_axes=dp_axes)
    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(pspecs, ospecs, ispecs)
    return lowered, _gnn_model_flops(cfg, shape)


def _recsys_cell(arch, shape: ShapeSpec, mesh, dp_axes):
    cfg = arch.model
    pspecs = rec_mod.param_specs(cfg, mesh)
    ispecs = rec_mod.input_specs(cfg, shape, mesh, dp_axes)
    d, K, Lh = cfg.embed_dim, cfg.n_interests, cfg.hist_len
    route = cfg.capsule_iters * 2 * shape.batch * Lh * K * d * 2
    if shape.kind == "recsys_train":
        ocfg = OptConfig()
        ospecs = opt_state_specs(pspecs, ocfg, mesh)
        step = rec_mod.make_step(cfg, shape, ocfg)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(pspecs, ospecs, ispecs)
        mf = 3.0 * (route + 2 * shape.batch * shape.batch * d)
    else:
        step = rec_mod.make_step(cfg, shape)
        lowered = jax.jit(step).lower(pspecs, ispecs)
        ncand = shape.n_candidates or 256 * shape.batch
        mf = route + 2.0 * max(1, shape.batch) * ncand * K * d
    return lowered, mf


def _steiner_cell(arch, shape: ShapeSpec, mesh, dp_axes, multi_pod):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.steiner import solver_preset
    from repro.core.dist_steiner import DistSteinerConfig, make_dist_steiner

    # canonical per-workload SolverConfig preset — knobs come from ONE
    # place (configs.steiner.SOLVER_PRESETS); only the mesh is ours
    scfg = solver_preset(shape.name)
    n_blocks = mesh.shape["model"]
    n_rep = 1
    for ax in dp_axes:
        n_rep *= mesh.shape[ax]
    n, e, S = shape.n_nodes, shape.n_edges, shape.batch
    nb = -(-(-(-n // n_blocks)) // 8) * 8
    eb = -(-e // (n_rep * n_blocks) // 8 + 1) * 8
    total_e = n_rep * n_blocks * eb
    cfg = DistSteinerConfig(
        n=n,
        nb=nb,
        num_seeds=S,
        mode=scfg.mode,
        mst_algo=scfg.mst_algo,
        local_steps=scfg.local_steps,
        pair_chunks=scfg.pair_chunks,
        fuse_gather=scfg.fuse_gather,
        lab_i16=scfg.lab_i16,
        max_iters=scfg.max_iters,
    )
    fn = make_dist_steiner(mesh, cfg, replica_axes=dp_axes)
    espec = NamedSharding(mesh, P((*dp_axes, "model")))
    rep = NamedSharding(mesh, P())
    args = (
        jax.ShapeDtypeStruct((total_e,), jnp.int32, sharding=espec),
        jax.ShapeDtypeStruct((total_e,), jnp.int32, sharding=espec),
        jax.ShapeDtypeStruct((total_e,), jnp.float32, sharding=espec),
        jax.ShapeDtypeStruct((S,), jnp.int32, sharding=rep),
    )
    lowered = fn.lower(*args)
    # "useful" work per relaxation round: one add + compare chain per edge
    mf = 5.0 * e
    return lowered, mf


def build_cell(arch_id: str, shape: ShapeSpec, mesh, multi_pod: bool):
    """→ (lowered, model_flops_total, calibrated_cost|None, analytic|None)."""
    arch = get_arch(arch_id)
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    if arch.family == "lm":
        return _lm_cell(arch, shape, mesh, dp_axes)
    if arch.family == "gnn":
        lowered, mf = _gnn_cell(arch, shape, mesh, dp_axes)
        return lowered, mf, None, None
    if arch.family == "recsys":
        lowered, mf = _recsys_cell(arch, shape, mesh, dp_axes)
        return lowered, mf, None, None
    if arch.family == "steiner":
        lowered, mf = _steiner_cell(arch, shape, mesh, dp_axes, multi_pod)
        return lowered, mf, None, None
    raise ValueError(arch.family)


def run_cell(arch_id: str, shape: ShapeSpec, multi_pod: bool, out_dir: Path,
             force: bool = False) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out = out_dir / f"{arch_id}__{shape.name}__{mesh_name}.json"
    if out.exists() and not force:
        return json.loads(out.read_text())
    rec = {
        "arch": arch_id,
        "shape": shape.name,
        "mesh": mesh_name,
        "kind": shape.kind,
    }
    if not shape.applicable:
        rec.update(status="skipped", note=shape.note)
        out.write_text(json.dumps(rec, indent=1))
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = 512 if multi_pod else 256
        with jax.set_mesh(mesh):
            lowered, mf, cost, analytic = build_cell(arch_id, shape, mesh, multi_pod)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        if cost is None:
            cost = _cost_triple(compiled)
        roof = rl.analyze_terms(*cost, model_flops_total=mf, n_chips=n_chips)
        mem = rl.memory_report(compiled)
        if analytic is not None:
            # bf16 models: CPU backend emulates bf16 dots with hoisted f32
            # weight copies — the analytic TPU model decides the verdict.
            mem.update(analytic)
            mem["fits_16gb"] = analytic["analytic_peak_gb"] < 16.0
            mem["note"] = "fits verdict from analytic TPU model (bf16 CPU emulation inflates measured peak)"
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=mem,
            roofline=roof.row(),
        )
    except Exception as exc:  # record the failure — these are bugs to fix
        rec.update(
            status="error",
            error=f"{type(exc).__name__}: {exc}",
            trace=traceback.format_exc()[-4000:],
        )
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()
    out_dir = Path(args.out)
    archs = list(ALL_IDS) if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    n_ok = n_skip = n_err = 0
    for arch_id in archs:
        spec = get_arch(arch_id)
        for shape in spec.shapes:
            if args.shape != "all" and shape.name != args.shape:
                continue
            for mp in meshes:
                rec = run_cell(arch_id, shape, mp, out_dir, force=args.force)
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_err += st == "error"
                msg = f"[{st:7s}] {arch_id:22s} {shape.name:14s} {rec['mesh']}"
                if st == "ok":
                    r = rec["roofline"]
                    m = rec["memory"]
                    peak = m.get("analytic_peak_gb", m["peak_est_gb"])
                    msg += (
                        f" dominant={r['dominant']:10s}"
                        f" t=(c {r['t_compute_s']:.2e}, m {r['t_memory_s']:.2e},"
                        f" x {r['t_collective_s']:.2e})s"
                        f" peak={peak:.1f}GB"
                        f" fits={m['fits_16gb']}"
                    )
                elif st == "error":
                    msg += " " + rec["error"][:120]
                print(msg, flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors", flush=True)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
