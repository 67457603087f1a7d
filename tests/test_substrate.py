"""Substrate tests: checkpoint manager (fault tolerance), data pipeline,
optimizer, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.synthetic import SyntheticLM
from repro.train import grad_compress as gc
from repro.train import optimizer as opt_lib


# --------------------------- checkpointing -----------------------------------
def _tree(seed=0):
    k = jax.random.key(seed)
    return {"a": jax.random.normal(k, (4, 8)), "b": {"c": jnp.arange(5)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree()
    mgr.save(10, t, meta={"note": "x"})
    got, meta = mgr.restore_latest(jax.tree_util.tree_map(np.zeros_like, t))
    assert meta["step"] == 10 and meta["note"] == "x"
    for a, b in zip(jax.tree_util.tree_leaves(t), jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.steps() == [3, 4]  # retention dropped 1, 2
    _, meta = mgr.restore_latest(_tree())
    assert meta["step"] == 4


def test_checkpoint_corruption_fallback(tmp_path):
    """Fault tolerance: a truncated newest checkpoint falls back to older."""
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, _tree(1))
    mgr.save(2, _tree(2))
    # corrupt step 2's array file
    bad = os.path.join(str(tmp_path), "step_0000000002", "arrays_p0.npz")
    with open(bad, "wb") as f:
        f.write(b"not a zip")
    got, meta = mgr.restore_latest(_tree())
    assert meta["step"] == 1


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(7, _tree(7), blocking=False)
    mgr.wait()
    assert mgr.steps() == [7]


def test_checkpoint_registered_dataclass_roundtrip(tmp_path):
    from repro.train.train_step import TrainState

    params = {"w": jnp.ones((3, 3))}
    st = TrainState(params=params, opt=opt_lib.init(params), step=jnp.int32(5))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, st)
    got, _ = mgr.restore(5, jax.tree_util.tree_map(np.zeros_like, st))
    assert int(got.step) == 5
    np.testing.assert_array_equal(np.asarray(got.params["w"]), np.ones((3, 3)))


# --------------------------- data pipeline ------------------------------------
def test_data_deterministic_restart():
    ds = SyntheticLM(vocab=128, seq_len=16, global_batch=8)
    b1 = ds.batch(5)
    b2 = ds.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(ds.batch(6)["tokens"], b1["tokens"])


def test_data_host_sharding_partitions_global_batch():
    full = SyntheticLM(vocab=128, seq_len=8, global_batch=8)
    shards = [
        SyntheticLM(vocab=128, seq_len=8, global_batch=8, host_index=i, host_count=2)
        for i in range(2)
    ]
    assert all(s.local_batch == 4 for s in shards)
    # each host's stream is independent of the other's existence
    a0 = shards[0].batch(3)["tokens"]
    a1 = shards[1].batch(3)["tokens"]
    assert a0.shape == (4, 8) and a1.shape == (4, 8)
    assert not np.array_equal(a0, a1)


def test_data_labels_are_shifted_tokens():
    ds = SyntheticLM(vocab=64, seq_len=12, global_batch=2)
    b = ds.batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# --------------------------- optimizer -----------------------------------------
def test_adamw_converges_quadratic():
    cfg = opt_lib.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)
    params = {"x": jnp.asarray([5.0, -3.0])}
    state = opt_lib.init(params)

    def loss(p):
        return jnp.sum((p["x"] - jnp.asarray([1.0, 2.0])) ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = opt_lib.apply(cfg, params, g, state)
    np.testing.assert_allclose(np.asarray(params["x"]), [1.0, 2.0], atol=1e-2)


def test_adamw_grad_clip_and_schedule():
    cfg = opt_lib.AdamWConfig(lr=1e-2, grad_clip=1.0, warmup_steps=10, total_steps=100)
    params = {"x": jnp.zeros((3,))}
    state = opt_lib.init(params)
    g = {"x": jnp.full((3,), 100.0)}
    params, state, m = opt_lib.apply(cfg, params, g, state)
    assert float(m["grad_norm"]) > 100.0
    assert float(m["lr"]) == pytest.approx(1e-2 / 10, rel=1e-4)  # warmup step 1
    # clipped step magnitude bounded by lr * (1 + eps-ish)
    assert np.all(np.abs(np.asarray(params["x"])) < 2e-2)


# --------------------------- grad compression -----------------------------------
def test_quantize_roundtrip_error_bounded():
    x = np.random.default_rng(0).normal(size=(256,)).astype(np.float32)
    q, s = gc.quantize_int8(jnp.asarray(x))
    err = np.asarray(gc.dequantize(q, s)) - x
    assert np.abs(err).max() <= float(s) / 2 + 1e-7


def test_error_feedback_reduces_bias():
    """With feedback, the *accumulated* quantization error stays bounded
    (doesn't grow with steps) and the running sum converges to the truth."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.normal(size=(64,)).astype(np.float32) * 1e-3)
    err = jnp.zeros_like(g_true)
    acc_q = np.zeros_like(np.asarray(g_true))
    for step in range(50):
        q, s, err = gc.compress_with_feedback(g_true, err)
        acc_q += np.asarray(gc.dequantize(q, s))
    # mean dequantized gradient ≈ true gradient (error feedback kills bias)
    np.testing.assert_allclose(acc_q / 50, np.asarray(g_true), atol=2e-5)


def test_compressed_psum_matches_full_precision():
    from jax.sharding import Mesh, PartitionSpec as P

    n = jax.device_count()  # 1 on CPU: still exercises the code path
    mesh = Mesh(np.array(jax.devices()), ("d",))
    g = jnp.linspace(-1, 1, 32)
    err = jnp.zeros_like(g)

    def f(g, err):
        return gc.compressed_psum(g, err, "d")

    out, new_err = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P())
    )(g, err)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g) * n, atol=2e-2)
