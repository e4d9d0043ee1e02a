"""Substrate tests: checkpointing (incl. crash/restart), compression, data."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager, load_pytree, save_pytree
from repro.data.recsys import BehaviorStream
from repro.data.tokens import TokenStream
from repro.distributed.compression import compress_tree, init_error, _dequant


def test_save_load_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": {"c": jnp.ones((5,), jnp.int32), "d": jnp.bfloat16(2.5)},
    }
    save_pytree(tree, tmp_path / "x.npz")
    back = load_pytree(tree, tmp_path / "x.npz")
    for l0, l1 in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


def test_manager_rolling_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (10, 20, 30):
        mgr.save(s, {"w": jnp.full((4,), s, jnp.float32)}, blocking=True)
    assert mgr.latest_step() == 30
    assert sorted(mgr.steps()) == [20, 30]  # rolled
    step, st = mgr.restore({"w": jnp.zeros((4,), jnp.float32)})
    assert step == 30 and float(st["w"][0]) == 30


def test_crash_restart_resumes_to_same_loss(tmp_path):
    """Paper-grade fault tolerance: killed job resumes bit-comparable."""
    from repro.launch.train import TrainConfig, train

    base = dict(
        arch="starcoder2-3b",
        steps=24,
        batch=2,
        seq_len=32,
        ckpt_every=8,
        lr=1e-3,
    )
    # uninterrupted reference
    cfg_ref = TrainConfig(ckpt_dir=str(tmp_path / "ref"), **base)
    _, _, losses_ref = train(cfg_ref, log=lambda *_: None)
    # crash at step 17, then relaunch
    cfg_crash = TrainConfig(
        ckpt_dir=str(tmp_path / "crash"), failure_at_step=17, **base
    )
    with pytest.raises(RuntimeError, match="injected failure"):
        train(cfg_crash, log=lambda *_: None)
    cfg_resume = TrainConfig(ckpt_dir=str(tmp_path / "crash"), **base)
    _, _, losses_resumed = train(cfg_resume, log=lambda *_: None)
    # the resumed tail must match the reference tail (same data, same state)
    np.testing.assert_allclose(losses_resumed[-1], losses_ref[-1], rtol=1e-4)


def test_compression_error_feedback_converges():
    """Mean of compressed grads over steps ≈ mean of true grads."""
    rng = np.random.default_rng(0)
    params = {"w": jnp.zeros((300,), jnp.float32)}
    err = init_error(params)
    acc_true = np.zeros(300)
    acc_q = np.zeros(300)
    for i in range(50):
        g = {"w": jnp.asarray(rng.normal(size=300) * (1 + np.arange(300) / 50), jnp.float32)}
        qtree, err = compress_tree(g, err)
        q, s = qtree["w"]
        deq = _dequant(q, s, (300,), jnp.float32)
        acc_true += np.asarray(g["w"])
        acc_q += np.asarray(deq)
    # error feedback keeps the ACCUMULATED signal nearly unbiased
    denom = np.abs(acc_true).mean()
    assert np.abs(acc_q - acc_true).mean() < 0.02 * denom


def test_token_stream_deterministic_and_seekable():
    s1 = TokenStream(1000, 4, 16, seed=3)
    s2 = TokenStream(1000, 4, 16, seed=3)
    np.testing.assert_array_equal(s1.batch_at(7), s2.batch_at(7))
    assert not np.array_equal(s1.batch_at(7), s1.batch_at(8))
    assert s1.batch_at(7).shape == (4, 16)
    assert s1.batch_at(7).max() < 1000


def test_behavior_stream_targets_share_cluster():
    bs = BehaviorStream(10_000, 12, 8, seed=1)
    b = bs.batch_at(0)
    assert b["hist_ids"].shape == (8, 12)
    assert b["target_id"].shape == (8,)
    assert (b["hist_mask"].sum(1) >= 6).all()


def test_elastic_restore_changes_sharding(tmp_path):
    """Restore onto an explicit sharding (mesh relayout path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    tree = {"w": jnp.arange(16, dtype=jnp.float32)}
    save_pytree(tree, tmp_path / "e.npz")
    mesh = make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data"))}
    back = load_pytree(tree, tmp_path / "e.npz", shardings=sh)
    assert back["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(back["w"]), np.arange(16))
