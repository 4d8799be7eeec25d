"""Attention block: init / train / prefill / decode with KV cache.

Local/global alternation (gemma) is expressed as a *dynamic* per-layer
window scalar so a single scanned layer stack serves both layer kinds.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.configs.base import AttnConfig
from repro.kernels import ops
from repro.models import common


def attn_init(key, d_model, a: AttnConfig, dtype):
    if a.mla:
        return _mla_init(key, d_model, a, dtype)
    ks = jax.random.split(key, 6)
    p = {
        "wq": common.dense_init(ks[0], d_model, a.n_heads * a.head_dim,
                                dtype),
        "wk": common.dense_init(ks[1], d_model, a.n_kv_heads * a.head_dim,
                                dtype),
        "wv": common.dense_init(ks[2], d_model, a.n_kv_heads * a.head_dim,
                                dtype),
        "wo": common.dense_init(ks[3], a.n_heads * a.head_dim, d_model,
                                dtype),
    }
    if a.qk_norm:
        p["q_norm"] = jnp.ones((a.head_dim,), dtype)
        p["k_norm"] = jnp.ones((a.head_dim,), dtype)
    return p


def _mla_init(key, d_model, a: AttnConfig, dtype):
    ks = jax.random.split(key, 4)
    h, r = a.n_heads, a.kv_lora_rank
    return {
        "wq": common.dense_init(ks[0], d_model, h * a.head_dim, dtype),
        "wkv_a": common.dense_init(ks[1], d_model, r + a.qk_rope_head_dim,
                                   dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": common.dense_init(
            ks[2], r, h * (a.qk_nope_head_dim + a.v_head_dim), dtype),
        "wo": common.dense_init(ks[3], h * a.v_head_dim, d_model, dtype),
    }


def _project_mla(params, x, a: AttnConfig, positions, norm_eps, backend):
    """Latent attention's q, k, v (arXiv:2405.04434 eqs. 9-19, no q LoRA):
    q = W_q h split into nope and rope parts per head; [c_kv, k_rope] =
    W_kva h with RMSNorm on c_kv; [k_nope, v] = W_kvb c_kv per head;
    RoPE on q_rope and on the one k_rope, which every head shares.
    Returns (B,H,S,nope+rope) q and k, and (B,H,S,v_head_dim) v."""
    b, s, _ = x.shape
    h, r = a.n_heads, a.kv_lora_rank
    nope, rd = a.qk_nope_head_dim, a.qk_rope_head_dim
    q = jnp.moveaxis((x @ params["wq"]).reshape(b, s, h, nope + rd), 1, 2)
    kv = x @ params["wkv_a"]
    c_kv = common.norm(kv[..., :r], params["kv_norm"], norm_eps, backend)
    k_rope = common.apply_rope(kv[:, None, :, r:], positions, a.rope_theta)
    kvb = jnp.moveaxis((c_kv @ params["wkv_b"]).reshape(
        b, s, h, nope + a.v_head_dim), 1, 2)
    q = jnp.concatenate(
        [q[..., :nope], common.apply_rope(q[..., nope:], positions,
                                          a.rope_theta)], -1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_rope, (b, h, s, rd))], -1)
    return q, k, kvb[..., nope:]


def _project_qkv(params, x, a: AttnConfig, positions, norm_eps, backend,
                 rope=True):
    if a.mla:
        return _project_mla(params, x, a, positions, norm_eps, backend)
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, a.n_heads, a.head_dim)
    k = (x @ params["wk"]).reshape(b, s, a.n_kv_heads, a.head_dim)
    v = (x @ params["wv"]).reshape(b, s, a.n_kv_heads, a.head_dim)
    if a.qk_norm:
        q = common.norm(q, params["q_norm"], norm_eps, backend)
        k = common.norm(k, params["k_norm"], norm_eps, backend)
    q = jnp.moveaxis(q, 1, 2)   # (B,H,S,D)
    k = jnp.moveaxis(k, 1, 2)
    v = jnp.moveaxis(v, 1, 2)
    if rope:
        q = common.apply_rope(q, positions, a.rope_theta)
        k = common.apply_rope(k, positions, a.rope_theta)
    return q, k, v


def layer_window(a: AttnConfig, is_global, seq_len):
    """Window for a (possibly alternating) layer.

    Uniform archs get a STATIC python int (enables statically-skipped
    block attention); alternating archs (gemma) get a traced scalar from
    the per-layer flag — unless the caller uses the period-grouped layer
    scan, which passes static windows itself (see transformer.py).
    """
    if a.window is None:
        return None
    if a.local_global_period == 0:
        return int(a.window)
    if isinstance(is_global, (bool, int)):
        return None if is_global else int(a.window)
    big = jnp.int32(seq_len + 1)
    return jnp.where(is_global, big, jnp.int32(a.window))


def attn_train(params, x, a: AttnConfig, *, window=None, norm_eps, ex,
               causal=True, kv_source=None):
    """Full-sequence attention (train / prefill / encoder / cross).

    kv_source: if given, keys/values come from this tensor (cross-attn;
    no causal mask, no rope on kv source positions mismatch is the
    caller's concern).  Returns (out, (k, v)) with k/v pre-rope-cache
    layout (B,Hkv,S,D) for prefill cache building.
    """
    with (jax.named_scope("mla") if a.mla else contextlib.nullcontext()):
        return _attn_train(params, x, a, window, norm_eps, ex, causal,
                           kv_source)


def _attn_train(params, x, a, window, norm_eps, ex, causal, kv_source):
    b, s, _ = x.shape
    positions = jnp.arange(s)
    if kv_source is None:
        q, k, v = _project_qkv(params, x, a, positions, norm_eps,
                               ex.backend, rope=True)
        o = ops.flash_attention(q, k, v, window=window, causal=causal,
                                softcap=a.attn_softcap, block=ex.attn_block,
                                backend=ex.backend)
    else:
        # cross attention: q from x, k/v from source (no rope, whisper-style)
        sk = kv_source.shape[1]
        q = (x @ params["wq"]).reshape(b, s, a.n_heads, a.head_dim)
        q = jnp.moveaxis(q, 1, 2)
        k = (kv_source @ params["wk"]).reshape(b, sk, a.n_kv_heads,
                                               a.head_dim)
        v = (kv_source @ params["wv"]).reshape(b, sk, a.n_kv_heads,
                                               a.head_dim)
        k = jnp.moveaxis(k, 1, 2)
        v = jnp.moveaxis(v, 1, 2)
        o = ops.flash_attention(q, k, v, window=None, causal=False,
                                softcap=a.attn_softcap, block=ex.attn_block,
                                backend=ex.backend)
    out = jnp.moveaxis(o, 1, 2).reshape(b, s, a.n_heads * a.v_dim)
    return out @ params["wo"], (k, v)


def attn_decode(params, x, cache_k, cache_v, pos, a: AttnConfig, *,
                is_global, norm_eps, ex, rolling_window=None):
    """One-token decode.  x: (B,1,D_model); caches: (B,Hkv,Smax,hd).

    pos: int32 scalar — index of the new token.  rolling_window: if the
    cache is a rolling buffer of this size, positions wrap (mixtral SWA).
    Returns (out, new_cache_k, new_cache_v).
    """
    b = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _project_qkv(params, x, a, positions, norm_eps, ex.backend,
                           rope=True)
    smax = cache_k.shape[2]
    slot = pos % smax if rolling_window is not None else pos
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, 0, slot, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, 0, slot, 0))
    if rolling_window is not None:
        # every slot in the rolling buffer is within the window; only mask
        # unfilled slots during warm-up.
        eff_pos = jnp.minimum(pos, smax - 1)
        o = ops.decode_attention(q, cache_k, cache_v, eff_pos,
                                 window=None, softcap=a.attn_softcap)
    else:
        window = layer_window(a, is_global, smax)
        o = ops.decode_attention(q, cache_k, cache_v, pos, window=window,
                                 softcap=a.attn_softcap)
    out = jnp.moveaxis(o, 1, 2).reshape(b, 1, a.n_heads * a.v_dim)
    return out @ params["wo"], cache_k, cache_v


def cross_decode(params, x, ck, cv, a: AttnConfig):
    """Decode-time cross attention against precomputed enc K/V."""
    b = x.shape[0]
    q = (x @ params["wq"]).reshape(b, 1, a.n_heads, a.head_dim)
    q = jnp.moveaxis(q, 1, 2)
    o = ops.decode_attention(q, ck, cv, jnp.int32(ck.shape[2] - 1),
                             softcap=a.attn_softcap)
    out = jnp.moveaxis(o, 1, 2).reshape(b, 1, a.n_heads * a.head_dim)
    return out @ params["wo"]
