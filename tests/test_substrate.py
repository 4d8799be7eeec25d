"""Substrate tests: optimizer, data, checkpointing, fault tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager, restore_pytree, save_pytree
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import DataPipeline
from repro.launch.steps import init_train_state, make_train_step
from repro.models.common import ExecConfig
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         compress_int8, cosine_schedule, decompress_int8,
                         ef_compress_update)
from repro.runtime import FaultTolerantLoop

EX = ExecConfig(ssd_chunk=8, attn_block=16)
SHAPE = ShapeConfig("t", "train", seq_len=32, global_batch=4)
CFG = get_config("tinyllama_1_1b").reduced()


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------
def test_adamw_decreases_quadratic():
    params = {"w": jnp.array([3.0, -2.0])}
    state = adamw_init(params)
    lr = cosine_schedule(0.1, warmup=1, total=100)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(params, grads, state, lr,
                                        weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(20.0)
    got = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
    assert got == pytest.approx(1.0, rel=1e-5)


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_cosine_schedule_bounds(step):
    lr = cosine_schedule(1e-3, warmup=100, total=10_000)(jnp.int32(step))
    assert 0.0 <= float(lr) <= 1e-3 + 1e-9


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------
@given(st.integers(1, 8), st.integers(1, 64))
@settings(max_examples=20, deadline=None)
def test_int8_roundtrip_bounded_error(rows, cols):
    x = jax.random.normal(jax.random.PRNGKey(rows * 100 + cols),
                          (rows, cols))
    q, s = compress_int8(x)
    back = decompress_int8(q, s, x.shape)
    scale = jnp.max(jnp.abs(x.reshape(rows, -1)), -1, keepdims=True)
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale.max()) / 127 + 1e-6


def test_error_feedback_accumulates():
    g = {"w": jnp.array([[0.001, 1.0, -1.0, 0.0004]])}
    deq1, err1 = ef_compress_update(g, None)
    # the residual carries what quantisation dropped
    total = jnp.abs(deq1["w"] + err1["w"] - g["w"]).max()
    assert float(total) < 1e-6


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------
def test_pipeline_deterministic_and_resumable():
    p1 = DataPipeline(CFG, SHAPE, seed=7)
    batches = [next(p1) for _ in range(3)]
    p2 = DataPipeline(CFG, SHAPE, seed=7)
    p2.restore({"seed": 7, "step": 2})
    b2 = next(p2)
    np.testing.assert_array_equal(batches[2]["tokens"], b2["tokens"])
    assert batches[0]["tokens"].max() < CFG.vocab


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------
def test_pytree_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
            "b": {"c": jnp.ones((4,), jnp.float32)}}
    save_pytree(tree, tmp_path / "ck")
    back = restore_pytree(tree, tmp_path / "ck")
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
        assert x.dtype == y.dtype


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_write=False)
    tree = {"x": jnp.zeros((2,))}
    for s in (10, 20, 30):
        mgr.save(s, tree)
    assert mgr.all_steps() == [20, 30]
    assert mgr.latest_step() == 30


# ---------------------------------------------------------------------------
# End-to-end: train -> crash -> restore -> bitwise continuation
# ---------------------------------------------------------------------------
def _fresh(seed=0):
    return init_train_state(CFG, EX, seed=seed)


def test_loss_decreases_over_training():
    step = jax.jit(make_train_step(CFG, EX, base_lr=5e-3, warmup=5,
                                   total=120))
    state = _fresh()
    pipe = DataPipeline(CFG, SHAPE, seed=1)
    losses = []
    for i in range(60):
        state, m = step(state, pipe.batch_at(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses


def test_crash_restore_bitwise_identical(tmp_path):
    step = jax.jit(make_train_step(CFG, EX, base_lr=1e-4))
    pipe = DataPipeline(CFG, SHAPE, seed=3)
    mgr = CheckpointManager(tmp_path / "ck", async_write=False)
    loop = FaultTolerantLoop(step, mgr, pipe, checkpoint_every=4)
    state, last = loop.run(_fresh(), 6)   # ckpt at 4, stop at 6

    # uninterrupted reference: 10 steps straight
    pipe_ref = DataPipeline(CFG, SHAPE, seed=3)
    ref = _fresh()
    for i in range(10):
        ref, _ = step(ref, pipe_ref.batch_at(i))

    # "crash": new process state, resume from step 4 and run to 10
    pipe2 = DataPipeline(CFG, SHAPE, seed=3)
    loop2 = FaultTolerantLoop(step, mgr, pipe2, checkpoint_every=100)
    restored, start = loop2.resume_or_init(_fresh(seed=9))
    assert start == 4
    state2, last2 = loop2.run(restored, 10, start_step=start)
    assert last2 == 10
    for a, b in zip(jax.tree.leaves(ref.params),
                    jax.tree.leaves(state2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loop_records_the_steps_declared_metrics(tmp_path):
    """Declared counters add up over the steps, declared gauges keep the
    last step's value; the step's other metrics are not recorded."""
    from repro.obs import metrics as obs_metrics
    values = iter([1.5, 2.5])

    def step(state, batch):
        return state, {"loss": jnp.float32(2.0),
                       "moe.routed_rows": jnp.float32(3.0),
                       "moe.load_max_over_mean": jnp.float32(next(values))}

    mgr = CheckpointManager(tmp_path / "ck", async_write=False)
    loop = FaultTolerantLoop(step, mgr, DataPipeline(CFG, SHAPE, seed=1),
                             checkpoint_every=100)
    with obs_metrics.scope() as m:
        loop.run(_fresh(), 2)
    assert m.counters == {"moe.routed_rows": 6.0}
    assert m.gauges == {"moe.load_max_over_mean": 2.5}


def test_grad_accumulation_matches_large_batch():
    """accum=2 over batch 4 == one step over the same 4 sequences."""
    step1 = jax.jit(make_train_step(CFG, EX, base_lr=1e-4))
    step2 = jax.jit(make_train_step(CFG, EX, base_lr=1e-4, accum=2))
    pipe = DataPipeline(CFG, SHAPE, seed=5)
    batch = pipe.batch_at(0)
    s1, m1 = step1(_fresh(), batch)
    s2, m2 = step2(_fresh(), batch)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
