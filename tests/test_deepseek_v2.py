"""DeepSeek-V2's mechanisms in the trainer, at a tiny size on the CPU with
seeded random weights, against the plain float32 reference of the
benchmark (``chipbench/reference/deepseek_v2.py``): latent attention, the
attention kernels with a value head size of their own, the grouped
matmul and its VJP, dropless routing, the expert share, the dense-then-
scanned stack, and one gradient-accumulating train step.  And the study
side: the new config fields leave every preset's counts and a
``qwen3_235b_paper`` study's records as they were."""
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import deepseek_v2 as ref
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import AttnConfig, ModelConfig, MoEConfig
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.launch.steps import TrainState, make_train_step
from repro.models import attention, moe, transformer
from repro.models.common import ExecConfig
from repro.optim import adamw_init

Z = {"d": 64, "h": 4, "nope": 16, "rope": 8, "v": 16, "r": 32, "ff": 96,
     "ffe": 32, "experts": 16, "held": 16, "top_k": 4, "shared": 2,
     "layers": 3, "dense": 1, "vocab": 256, "theta": 10000.0, "eps": 1e-6,
     "alpha": 1e-3, "norm_topk": False, "scale": 1.0}
EX = ExecConfig(attn_block=16)           # float32 compute, xla kernels
HIGHEST = jax.default_matmul_precision("highest")


def program(z=Z, **moe_kw) -> ModelConfig:
    """The trainer's config for the reference's sizes ``z``."""
    m = dict(n_experts=z["experts"], top_k=z["top_k"], d_ff_expert=z["ffe"],
             capacity_factor=None, n_shared=z["shared"],
             norm_topk=z["norm_topk"], seq_aux=True, aux_alpha=z["alpha"],
             experts_held=z["held"])
    m.update(moe_kw)
    return ModelConfig(
        name="dsv2-tiny", family="moe", n_layers=z["layers"], d_model=z["d"],
        d_ff=z["ff"], vocab=z["vocab"],
        attn=AttnConfig(n_heads=z["h"], n_kv_heads=z["h"],
                        head_dim=z["nope"] + z["rope"], rope_theta=z["theta"],
                        kv_lora_rank=z["r"], qk_nope_head_dim=z["nope"],
                        qk_rope_head_dim=z["rope"], v_head_dim=z["v"]),
        moe=MoEConfig(**m), first_k_dense=z["dense"], norm_eps=z["eps"],
        tie_embeddings=False)


def _params(z=Z, seed=0):
    return ref.init_params(jax.random.PRNGKey(seed), z)


def _layer(tree, i=0):
    return jax.tree.map(lambda t: t[i], tree)


def _close(a, b, tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=tol,
                                   atol=tol)


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------
def test_mla_forward_and_grads_match_reference():
    cfg = program()
    p = _layer(_params()["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, Z["d"]))
    ct = jax.random.normal(jax.random.PRNGKey(2), (2, 32, Z["d"]))

    def prog(p_, x_):
        return attention.attn_train(p_, x_, cfg.attn, norm_eps=Z["eps"],
                                    ex=EX)[0]

    def want(p_, x_):
        return ref.mla(x_, p_, Z, block=16)

    with HIGHEST:
        got, g = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(prog(*a) * ct), (0, 1)))(p, x)
        exp, g_ref = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(want(*a) * ct), (0, 1)))(p, x)
        _close(jax.jit(prog)(p, x), jax.jit(want)(p, x), 2e-5)
    _close(got, exp, 2e-5)
    _close(g, g_ref, 5e-5)


@pytest.mark.parametrize("backend", ["pallas", "xla", "xla_blocked"])
def test_attention_with_its_own_value_head_size(backend):
    """q/k of 24 and v of 16 per head, no padding of v: the Pallas
    forward (interpreted) and the XLA forwards and backwards against the
    dense oracle."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (1, 2, 64, 24))
    k = jax.random.normal(ks[1], (1, 2, 64, 24))
    v = jax.random.normal(ks[2], (1, 2, 64, 16))
    do = jax.random.normal(ks[3], (1, 2, 64, 16))
    want = kref.attention_ref(q, k, v)
    if backend == "pallas":
        out, _ = flash_attention_fwd(q, k, v, block_q=32, block_k=32,
                                     interpret=True)
        _close(out, want, 2e-5)
        return

    def f(*a):
        return ops.flash_attention(*a, block=16, backend=backend)

    out, vjp = jax.vjp(f, q, k, v)
    _close(out, want, 2e-5)
    _, vjp_ref = jax.vjp(kref.attention_ref, q, k, v)
    _close(vjp(do), vjp_ref(do), 5e-5)


# ---------------------------------------------------------------------------
# Grouped matmul
# ---------------------------------------------------------------------------
def _segment_matmul(x, w, sizes):
    out, start = jnp.zeros((x.shape[0], w.shape[-1])), 0
    for e, n in enumerate(sizes):
        out = out.at[start:start + n].set(x[start:start + n] @ w[e])
        start += n
    return out


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_moe_gmm_and_its_vjp_match_segment_matmul(backend):
    """Rows sized for the worst case (48), 24 live in three groups of
    whole 8-row blocks, one group empty; group sizes traced."""
    sizes = [16, 0, 8]
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (48, 32))
    w = jax.random.normal(ks[1], (3, 32, 24))
    dy = jax.random.normal(ks[2], (48, 24)).at[24:].set(0.0)

    @jax.jit
    def f(x_, w_, g_):
        return ops.moe_gmm(x_, w_, g_, backend=backend, block_t=8)

    gs = jnp.asarray(sizes, jnp.int32)
    out, vjp = jax.vjp(lambda a, b: f(a, b, gs), x, w)
    with HIGHEST:
        want, vjp_ref = jax.vjp(lambda a, b: _segment_matmul(a, b, sizes),
                                x, w)
        _close(out, want, 1e-5)
        assert not np.asarray(out[24:]).any()
        _close(vjp(dy), vjp_ref(dy), 1e-4)


# ---------------------------------------------------------------------------
# The dropless expert share
# ---------------------------------------------------------------------------
def _moe_inputs(skew=0.0, seed=5):
    p = _layer(_params(seed=seed)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 32, Z["d"]))
    if skew:          # every token's router logit for expert 0 far ahead
        p = dict(p, router=p["router"].at[:, 0].set(skew * jnp.ones(Z["d"])))
        x = x + 1.0
    return p, x


def test_dropless_routing_computes_every_routed_row():
    """Under a skew that sends every token to expert 0, the capacity
    path (factor 1.25) drops most of them; the dropless layer computes
    every routed row and matches the reference layer."""
    p, x = _moe_inputs(skew=4.0)
    with HIGHEST:
        want, aux_ref = ref.moe(x, p, Z)
        got, aux, stats = moe.moe_apply(p, x, program().moe, EX)
        capped, _, _ = moe.moe_apply(p, x, program(capacity_factor=1.25).moe,
                                     EX)
    _close(got, want, 2e-5)
    _close(aux, aux_ref, 1e-6)
    assert float(stats["moe.routed_rows"]) == 64 * Z["top_k"]
    assert float(jnp.max(jnp.abs(capped - want))) > 0.1


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight shares of two experts each, every one routing over all 16:
    their outputs, with the shared experts counted once, add up to the
    reference layer that holds all 16."""
    p, x = _moe_inputs()
    held = 2
    share = program(experts_held=held).moe
    total = 0.0
    with HIGHEST:
        for i in range(Z["experts"] // held):
            part = {**p, **{k: p[k][i * held:(i + 1) * held]
                            for k in ("w1", "w3", "w2")}}
            y, _, _ = moe.moe_apply(part, x, share, EX, first=i * held)
            total = total + y
        shared = ref.swiglu(x, p["shared"])
        want, _ = ref.moe(x, p, Z)
    _close(total - (Z["experts"] // held - 1) * shared, want, 5e-5)


# ---------------------------------------------------------------------------
# The stack and the train step
# ---------------------------------------------------------------------------
def _batch(rows=4, seq=32, step=0):
    return ref.tokens(jax.random.PRNGKey(7), step, rows, seq, Z["vocab"])


def test_dense_then_scanned_stack_matches_reference():
    cfg = program()
    params = _params()
    built = transformer.lm_init(jax.random.PRNGKey(0), cfg, EX)
    assert jax.tree.structure(built) == jax.tree.structure(params)
    assert jax.tree.map(jnp.shape, built) == jax.tree.map(jnp.shape, params)
    b = _batch()
    with HIGHEST:
        got, m = jax.jit(lambda p_: transformer.lm_loss(p_, b, cfg, EX))(
            params)
        want = jax.jit(lambda p_: ref.loss(p_, b, Z, block=16))(params)
    _close(got, want, 1e-5)
    assert float(m["moe.routed_rows"]) == 2 * 4 * 32 * Z["top_k"]


def test_accum_train_step_matches_reference():
    """One step of two micro-batches: the loss, the gradient (read back
    from the first moment) and the updated weights."""
    o = {"lr": 3e-4, "warmup_steps": 100, "total_steps": 10000, "b1": 0.9,
         "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}
    cfg = program()
    params = _params()
    b = _batch(rows=4)
    step = jax.jit(make_train_step(cfg, EX, accum=2))
    with HIGHEST:
        state, metrics = step(TrainState(params, adamw_init(params)), b)
        grad = jax.jit(jax.value_and_grad(
            lambda p_, b_: ref.loss(p_, b_, Z, block=16)))
        halves = [grad(params, {k: v[i * 2:(i + 1) * 2]
                                for k, v in b.items()}) for i in range(2)]
        loss = np.mean([float(lval) for lval, _ in halves])
        g = jax.tree.map(lambda a, c: (a + c) / 2,
                         *[g_ for _, g_ in halves])
        zeros = jax.tree.map(jnp.zeros_like, params)
        new, _, _, clipped = ref.adamw(params, g, zeros, zeros, 1, o)
    np.testing.assert_allclose(float(metrics["loss"]), loss, rtol=1e-5)
    _close(jax.tree.map(lambda m: m / 0.1, state.opt.m), clipped, 1e-4)
    _close(state.params, new, 1e-6)
    assert float(metrics["moe.routed_rows"]) == 2 * 2 * 2 * 32 * Z["top_k"]


def test_reduced_preset_trains_through_the_sharded_step():
    from repro.launch import train
    state = train.main(["--arch", "deepseek_v2_lite", "--reduced",
                        "--steps", "2", "--batch", "2", "--seq", "32",
                        "--accum", "2", "--ckpt-every", "1000"])
    assert "dense_layers" in state.params


# ---------------------------------------------------------------------------
# The study side
# ---------------------------------------------------------------------------
# Counts of every preset at the parent commit of the MLA / shared-expert
# / leading-dense fields: param_count, active_param_count, layer_params,
# fwd_flops_per_token(4096).
BEFORE = {
    "whisper_medium": [757876736, 757876736, 12584960, 1918406656.0],
    "mamba2_780m": [779841792, 779841792, 14637712, 1559683584.0],
    "llava_next_34b": [34388917248, 34388917248, 557856768, 72301049856.0],
    "qwen3_moe_235b_a22b": [235093610496, 21568409600, 2487754752,
                            49445052416.0],
    "mixtral_8x7b": [46702792704, 12748853248, 1451270144, 26571448320.0],
    "zamba2_7b": [6635271248, 6635271248, 77963600, 13652224160.0],
    "gemma2_2b": [2614222080, 2614222080, 77861376, 5664651776.0],
    "gemma3_27b": [27008319744, 27008319744, 412887552, 54796780032.0],
    "tinyllama_1_1b": [1100048384, 1100048384, 44044288, 2569195520.0],
    "internlm2_20b": [19861149696, 19861149696, 390082560, 42138218496.0],
}
# sha256 of the records (fabric, source, strategy, throughput, step time,
# cost, power, MFU) of the study below, at that parent commit
STUDY_SHA = "f4393d0d76cb9408fee35272523782db28a02288f6dae065c63eee4d2218caaf"


def test_existing_presets_count_as_before():
    for arch, want in BEFORE.items():
        c = get_config(arch)
        assert [c.param_count(), c.active_param_count(), c.layer_params(),
                c.fwd_flops_per_token(4096)] == want, arch
    assert set(ARCH_IDS) == set(BEFORE) | {"deepseek_v2_lite"}


def test_qwen3_study_records_as_before():
    from repro.api import Scenario, Study
    sc = Scenario(model="qwen3_moe_235b_a22b", total_tflops=4e6,
                  seq_len=10240, global_batch=512, dies_per_mcm=(8, 16),
                  m=(2, 6), cpo_ratio=(0.6,), fabrics=("oi", "ib"),
                  refine_top=2, keep_top=8, backend="numpy")
    res = Study(sc).run()
    rows = [[r.fabric, r.source, r.strategy,
             {k: r.metrics[k] for k in ("throughput", "step_time", "cost",
                                        "power", "mfu") if k in r.metrics}]
            for r in res.records]
    got = hashlib.sha256(json.dumps(rows, sort_keys=True).encode())
    assert got.hexdigest() == STUDY_SHA


def test_cost_model_refuses_what_it_cannot_cost():
    from repro.core.workload import Workload
    cfg = get_config("deepseek_v2_lite")
    with pytest.raises(ValueError, match="latent attention, shared experts"
                       ", leading dense layers"):
        Workload(model=cfg, seq_len=4096, global_batch=256)
    plain = dataclasses.replace(
        cfg, first_k_dense=0, attn=dataclasses.replace(
            cfg.attn, kv_lora_rank=None),
        moe=dataclasses.replace(cfg.moe, n_shared=0))
    assert Workload(model=plain, seq_len=4096, global_batch=256).total_params
