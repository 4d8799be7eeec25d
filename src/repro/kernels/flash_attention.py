"""Pallas TPU flash-attention forward kernel.

Design (TPU-native, not a CUDA port):
  * grid = (B, Hq, nQ, nK); the k dimension is innermost/'arbitrary' so the
    fp32 accumulator lives in VMEM scratch across k steps (MXU-friendly
    128-aligned blocks, no HBM round-trips for the softmax state).
  * GQA is expressed in the k/v BlockSpec index_map (kv head = hq*Hkv//Hq)
    so no repeated K/V materialisation ever happens in HBM.
  * sliding-window size is a *dynamic* SMEM scalar: one compiled kernel
    serves local and global layers (gemma-style alternation inside a
    scanned layer stack); fully-masked k-blocks are skipped via pl.when.
  * optional logit soft-capping (gemma2) fused into the score computation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
               l_ref, *, scale, softcap, causal, block_q, block_k, n_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    window = win_ref[0]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # any key in this block can be attended by any query in the q block?
    live = jnp.logical_and(
        jnp.logical_or(not causal, k_start <= q_start + block_q - 1),
        k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = (rows - cols) < window
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                 # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])  # masked rows -> 0
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_fwd(q, k, v, window=None, *, causal=True, softcap=0.0,
                        scale=None, block_q=128, block_k=128,
                        interpret=False):
    """q, k: (B, Hq|Hkv, S, D); v: (B, Hkv, S, Dv); returns (B, Hq, Sq, Dv)
    and the log-sum-exp rows.  Dv may differ from D (latent attention).

    ``window``: None (full), python int, or int32 scalar array (dynamic).
    Assumes Sq == Sk (training / prefill self-attention).
    """
    b, hq, sq, d = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    assert sq == sk, "fwd kernel is for self-attention (train/prefill)"
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    n_q, n_k = sq // block_q, sk // block_k

    if window is None:
        window = sk + block_k  # never limits
    win = jnp.asarray(window, jnp.int32).reshape(1)

    kernel = functools.partial(
        _fa_kernel, scale=scale, softcap=softcap, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k)

    kv_map = lambda b_, h_, qi, ki: (b_, (h_ * hkv) // hq, ki, 0)
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            # lse as a (block_q, 1) column: a block's last two dims must
            # tile (8, 128) or span the array, and a trailing 1 spans it
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(win, q, k, v)
    o, lse = out
    return o, lse[..., 0]
