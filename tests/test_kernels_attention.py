"""Flash-attention kernel sweeps: pallas(interpret) and xla-blockwise vs
the dense oracle, across shapes, dtypes, GQA ratios, windows, softcaps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (_VMEM_BUDGET, flash_attention_fwd,
                                          fwd_plan)


def _mk(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    return q, k, v


SWEEP = [
    # b, hq, hkv, s, d, window, softcap, causal
    (1, 2, 2, 128, 32, None, 0.0, True),
    (2, 4, 2, 128, 16, None, 0.0, True),
    (1, 8, 1, 256, 32, None, 0.0, True),     # MQA
    (2, 4, 4, 128, 64, 32, 0.0, True),       # SWA
    (1, 2, 2, 128, 32, None, 50.0, True),    # softcap (gemma2)
    (1, 2, 2, 128, 32, 64, 30.0, True),      # SWA + softcap
    (1, 4, 2, 128, 32, None, 0.0, False),    # encoder (non-causal)
]


@pytest.mark.parametrize("b,hq,hkv,s,d,win,cap,causal", SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_interpret_vs_ref(b, hq, hkv, s, d, win, cap, causal, dtype):
    q, k, v = _mk(b, hq, hkv, s, d, dtype)
    o, lse = flash_attention_fwd(q, k, v, win, causal=causal, softcap=cap,
                                 block_q=64, block_k=64, interpret=True)
    r = ref.attention_ref(q, k, v, causal=causal, window=win, softcap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)
    assert bool(jnp.isfinite(lse).all())


PLAN_SWEEP = [
    # b, hq, hkv, s, d, dv, window, dynamic, softcap, causal
    (1, 4, 2, 1024, 64, 64, None, False, 0.0, True),     # GQA 2:1
    (1, 2, 2, 1024, 192, 128, None, False, 0.0, True),   # MLA: D 192, Dv 128
    (1, 2, 1, 1024, 64, 64, 700, False, 0.0, True),      # static window
    (1, 2, 1, 1024, 64, 64, 700, True, 0.0, True),       # dynamic window
    (1, 2, 2, 512, 64, 64, None, False, 30.0, True),     # softcap
    (1, 4, 2, 1024, 64, 64, None, False, 0.0, False),    # non-causal
]


@pytest.mark.parametrize("b,hq,hkv,s,d,dv,win,dynamic,cap,causal",
                         PLAN_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_plan_tiles_vs_ref(b, hq, hkv, s, d, dv, win, dynamic, cap,
                                  causal, dtype):
    """The forward with the plan's own tiles (interpreted), against the
    dense oracle."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, dv), dtype)

    def fwd(w):
        return flash_attention_fwd(q, k, v, w, causal=causal, softcap=cap,
                                   interpret=True)

    o, lse = jax.jit(fwd)(jnp.int32(win)) if dynamic else fwd(win)
    r = ref.attention_ref(q, k, v, causal=causal, window=win, softcap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert o.dtype == dtype and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)
    assert bool(jnp.isfinite(lse).all())


@pytest.mark.parametrize("dynamic", [False, True])
def test_pallas_window_skips_whole_blocks(dynamic):
    """A window of 100 over 64-row tiles leaves whole blocks out: the
    static table drops them, the dynamic window skips them in the
    kernel; both equal the dense oracle."""
    q, k, v = _mk(1, 2, 1, 512, 32, jnp.float32)

    def fwd(w):
        return flash_attention_fwd(q, k, v, w, block_q=64, block_k=64,
                                   interpret=True)[0]

    o = jax.jit(fwd)(jnp.int32(100)) if dynamic else fwd(100)
    assert fwd_plan(512, 32, 32, jnp.float32, None if dynamic else 100,
                    block_q=64, block_k=64).n_live == (36 if dynamic else 21)
    r = ref.attention_ref(q, k, v, window=100)
    np.testing.assert_allclose(o, r, rtol=2e-5, atol=2e-5)


def _blocks_touched(s, bq, bk, causal, window):
    """Blocks holding at least one (query, key) pair the mask keeps,
    counted pair by pair."""
    rows = np.arange(s)[:, None]
    cols = np.arange(s)[None, :]
    keep = np.ones((s, s), bool)
    if causal:
        keep &= cols <= rows
    if window is not None:
        keep &= rows - cols < window
    return int(keep.reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("s,d,dv,dtype,win,causal", [
    (512, 64, 64, jnp.float32, None, True),
    (1024, 128, 128, jnp.bfloat16, None, True),
    (1024, 192, 128, jnp.bfloat16, 700, True),
    (4096, 128, 128, jnp.bfloat16, None, True),
    (4096, 192, 128, jnp.bfloat16, 1000, True),
    (4096, 128, 128, jnp.float32, None, False),
    (1536, 256, 256, jnp.float32, None, True),
])
def test_fwd_plan(s, d, dv, dtype, win, causal):
    plan = fwd_plan(s, d, dv, dtype, win, causal=causal)
    bq, bk = plan.block_q, plan.block_k
    assert s % bq == 0 and s % bk == 0
    assert plan.vmem_bytes <= _VMEM_BUDGET
    assert plan.n_live == _blocks_touched(s, bq, bk, causal, win)
    qi, ki = plan.steps[:, 0], plan.steps[:, 1]
    # a q tile's steps are consecutive, k ascending within it
    assert (np.diff(qi) >= 0).all()
    assert (np.diff(ki)[np.diff(qi) == 0] > 0).all()
    if s == 4096 and causal and win is None:
        # the causal triangle, n (n + 1) / 2 of n^2 steps: 10 of 16 at
        # 1024-row tiles (62.5%), 36 of 64 (56%) at 512
        n = s // bq
        assert bq == bk and plan.n_live == n * (n + 1) // 2
        assert fwd_plan(s, d, dv, dtype, block_q=512,
                        block_k=512).n_live < 0.6 * 64


@pytest.mark.parametrize("b,hq,hkv,s,d,win,cap,causal", SWEEP)
def test_xla_blockwise_vs_ref(b, hq, hkv, s, d, win, cap, causal):
    q, k, v = _mk(b, hq, hkv, s, d, jnp.float32)
    o = ops.flash_attention(q, k, v, window=win, causal=causal, softcap=cap,
                            block=32, backend="xla")
    r = ref.attention_ref(q, k, v, causal=causal, window=win, softcap=cap)
    np.testing.assert_allclose(o, r, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("win,cap", [(None, 0.0), (32, 0.0), (None, 20.0)])
def test_gradients_vs_dense(win, cap):
    q, k, v = _mk(1, 4, 2, 64, 16, jnp.float32)
    gb = jax.grad(lambda q_, k_, v_: (ops.flash_attention(
        q_, k_, v_, window=win, softcap=cap, block=16,
        backend="xla") ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q_, k_, v_: (ref.attention_ref(
        q_, k_, v_, window=win, softcap=cap) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_, nm in zip(gb, gr, "qkv"):
        np.testing.assert_allclose(a, b_, rtol=3e-4, atol=3e-4,
                                   err_msg=f"d{nm}")


def test_dynamic_window_matches_static():
    q, k, v = _mk(1, 2, 2, 128, 16, jnp.float32)
    stat = ops.flash_attention(q, k, v, window=48, block=32, backend="xla")
    dyn = jax.jit(lambda w: ops.flash_attention(q, k, v, window=w, block=32,
                                                backend="xla"))(
                                                    jnp.int32(48))
    np.testing.assert_allclose(stat, dyn, rtol=1e-6, atol=1e-6)


def test_decode_matches_prefill_row():
    """decode_attention(pos) == last row of full attention over pos+1 keys."""
    q, k, v = _mk(2, 4, 2, 64, 16, jnp.float32)
    pos = 37
    full = ref.attention_ref(q[:, :, :pos + 1], k[:, :, :pos + 1],
                             v[:, :, :pos + 1], causal=True)
    dec = ops.decode_attention(q[:, :, pos:pos + 1], k, v, jnp.int32(pos))
    np.testing.assert_allclose(dec[:, :, 0], full[:, :, -1], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("b,hq,hkv,s,d,win,cap,causal", SWEEP)
def test_xla_blocked_vs_ref(b, hq, hkv, s, d, win, cap, causal):
    """Statically-skipped 2D-block path == dense oracle."""
    q, k, v = _mk(b, hq, hkv, s, d, jnp.float32)
    o = ops.flash_attention(q, k, v, window=win, causal=causal, softcap=cap,
                            block=32, backend="xla_blocked")
    r = ref.attention_ref(q, k, v, causal=causal, window=win, softcap=cap)
    np.testing.assert_allclose(o, r, rtol=2e-5, atol=2e-5)


def test_xla_blocked_grads_match_scan():
    q, k, v = _mk(1, 4, 2, 64, 16, jnp.float32)
    for win in (None, 32):
        gb = jax.grad(lambda q_: (ops.flash_attention(
            q_, k, v, window=win, block=16,
            backend="xla_blocked") ** 2).sum())(q)
        gr = jax.grad(lambda q_: (ref.attention_ref(
            q_, k, v, window=win) ** 2).sum())(q)
        np.testing.assert_allclose(gb, gr, rtol=3e-4, atol=3e-4)


def test_blocked_cross_attention_mismatched_lengths():
    """sq != sk (whisper cross-attn): independent block sizes."""
    q, _, _ = _mk(1, 4, 2, 64, 16, jnp.float32)
    _, k, v = _mk(1, 4, 2, 96, 16, jnp.float32)
    o = ops.flash_attention(q, k, v, causal=False, block=32,
                            backend="xla_blocked")
    r = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(o, r, rtol=2e-5, atol=2e-5)


def test_nondivisible_seq_block():
    """S=1500-style non-power-of-two lengths pick a divisor block."""
    q, k, v = _mk(1, 2, 2, 100, 16, jnp.float32)
    for backend in ("xla", "xla_blocked"):
        o = ops.flash_attention(q, k, v, causal=False, block=32,
                                backend=backend)
        r = ref.attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(o, r, rtol=2e-5, atol=2e-5)
