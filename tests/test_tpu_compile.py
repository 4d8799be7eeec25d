"""Compile the main path's device programs for a TPU v5e that is described,
not attached: what the chip's compiler refuses fails here at no chip time.

Nothing runs, so nothing about results or times is checked: only that
each program compiles and, where a Pallas kernel is expected, that the
compiled program holds one (``tpu_custom_call``).  The topology is
described inside a module-scoped fixture (never at import), and the
tests skip where it cannot be described.  Keep every such test in this
one file: only the worker that runs it loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding,
                          PartitionSpec as P, SingleDeviceSharding)

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back without one
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", ["tinyllama_1_1b", "qwen3_moe_235b_a22b",
                                  "deepseek_v2_lite"])
def test_flash_attention_fwd_compiles(one_chip, arch):
    a = get_config(arch).attn
    q = _spec(one_chip, (1, a.n_heads, 2048, a.head_dim))
    k = _spec(one_chip, (1, a.n_kv_heads, 2048, a.head_dim))
    v = _spec(one_chip, (1, a.n_kv_heads, 2048, a.v_dim))
    text = _compile_text(lambda q_, k_, v_: flash_attention_fwd(q_, k_, v_),
                         q, k, v)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b,hq,hkv,s,d,dv", [
    (2, 16, 8, 4096, 128, 128),      # InternLM2-1.8B, 2 x 4,096 tokens
    (4, 16, 16, 4096, 192, 128),     # DeepSeek-V2-Lite MLA, 4 x 4,096
])
def test_flash_attention_fwd_compiles_at_train_shapes(one_chip, b, hq, hkv,
                                                      s, d, dv):
    """The forward with the plan's tiles in bfloat16 at the train cells'
    shapes: its VMEM fits, and the kernel keeps its name."""
    bf = jnp.bfloat16
    text = _compile_text(lambda q_, k_, v_: flash_attention_fwd(q_, k_, v_),
                         _spec(one_chip, (b, hq, s, d), bf),
                         _spec(one_chip, (b, hkv, s, d), bf),
                         _spec(one_chip, (b, hkv, s, dv), bf))
    assert "%flash_attention_fwd" in text


def test_moe_gmm_share_compiles_with_its_vjp(one_chip):
    """One chip's share of DeepSeek-V2-Lite's experts (8 of 64) at a
    micro-batch of 16,384 tokens, rows sized for the worst case: the
    Pallas forward and XLA's grouped matmuls for the backward."""
    import dataclasses

    from repro.models.moe import share_rows
    cfg = get_config("deepseek_v2_lite")
    m = dataclasses.replace(cfg.moe, experts_held=8)
    rows = share_rows(16384, m)

    def f(x, w, g):
        return jax.value_and_grad(lambda x_, w_: jnp.sum(ops.moe_gmm(
            x_, w_, g, backend="pallas").astype(jnp.float32)), (0, 1))(x, w)

    text = _compile_text(
        f, _spec(one_chip, (rows, cfg.d_model), jnp.bfloat16),
        _spec(one_chip, (8, cfg.d_model, m.d_ff_expert), jnp.bfloat16),
        _spec(one_chip, (8,), jnp.int32))
    assert "%moe_gmm" in text and "ragged-dot" in text


def test_ssd_scan_compiles_mamba2(one_chip):
    cfg = get_config("mamba2_780m")
    s = cfg.ssm
    h, b, seq = s.n_heads(cfg.d_model), 1, 2048
    args = (_spec(one_chip, (b, seq, h, s.head_dim)),
            _spec(one_chip, (b, seq, h)), _spec(one_chip, (h,)),
            _spec(one_chip, (b, seq, s.n_groups, s.d_state)),
            _spec(one_chip, (b, seq, s.n_groups, s.d_state)))
    text = _compile_text(lambda *t: ssd_scan(*t, chunk=s.chunk), *args)
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles(one_chip):
    d = get_config("tinyllama_1_1b").d_model
    text = _compile_text(lambda x, w: rmsnorm(x, w),
                         _spec(one_chip, (4096, d)), _spec(one_chip, (d,)))
    assert "tpu_custom_call" in text


def test_moe_gmm_compiles_qwen3_experts(one_chip):
    cfg = get_config("qwen3_moe_235b_a22b")
    e, t = cfg.moe.top_k, 2048
    text = _compile_text(
        lambda x, w, g: moe_gmm(x, w, g),
        _spec(one_chip, (t, cfg.d_model)),
        _spec(one_chip, (e, cfg.d_model, cfg.moe.d_ff_expert)),
        _spec(one_chip, (t // 128,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_runs_per_shard_on_2x2_mesh(topo):
    """Under a multi-device mesh the Pallas forward runs inside a
    shard_map: GSPMD refuses to partition a Mosaic kernel."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    a = get_config("tinyllama_1_1b").attn
    sh = NamedSharding(mesh, P("data", "model", None, None))
    q = _spec(sh, (2, a.n_heads, 2048, a.head_dim))
    kv = _spec(sh, (2, a.n_kv_heads, 2048, a.head_dim))
    with jax.set_mesh(mesh):
        text = _compile_text(
            lambda q_, k_, v_: ops.flash_attention(q_, k_, v_,
                                                   backend="pallas"),
            q, kv, kv)
    assert "tpu_custom_call" in text


def _captured_terms(monkeypatch):
    """The (a, fabric, hw) of the first batched-terms call of the
    paper_qwen3 study, run on the host."""
    from repro.api import Scenario, Study
    from repro.dse import batched_sim
    seen = []
    real = batched_sim._run_terms

    def spy(a, fabric, hw, backend):
        seen.append((a, fabric, hw))
        return real(a, fabric, hw, backend)

    monkeypatch.setattr(batched_sim, "_run_terms", spy)
    sc = Scenario.load(os.path.join(os.path.dirname(__file__), "..",
                                    "scenarios", "paper_qwen3.json"))
    Study(sc.replace(backend="numpy", validate_top=0)).run()
    assert seen
    return seen[0]


def test_dse_terms_program_compiles_x64(one_chip, monkeypatch):
    from repro.dse.batched_sim import _TERM_KEYS, _jax_terms_fn
    a, fabric, hw = _captured_terms(monkeypatch)
    with jax.enable_x64(True):
        fn = _jax_terms_fn(fabric, hw, a["w_scalars"])
        args = [_spec(one_chip, np.shape(a[k]), np.asarray(a[k]).dtype)
                for k in _TERM_KEYS]
        text = fn.lower(*args).compile().as_text()
    assert "f64" in text


def test_event_wavefront_program_compiles_x64(one_chip):
    from repro.events.batch import _jax_shape_fn
    with jax.enable_x64(True):
        fn = _jax_shape_fn("1f1b", 4, 1, 8)
        text = fn.lower(_spec(one_chip, (6, 64), jnp.float64)
                        ).compile().as_text()
    assert "f64" in text
