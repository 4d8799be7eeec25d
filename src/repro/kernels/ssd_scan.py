"""Pallas TPU kernel for the Mamba2 SSD (state-space duality) operator.

TPU adaptation of the SSD chunked algorithm (Dao & Gu, 2024): the GPU
version leans on warp-level scans; on TPU we recast everything as
MXU matmuls inside a chunk plus a *sequential grid dimension* that carries
the (P x N) inter-chunk state in VMEM scratch — the TPU-idiomatic
replacement for a cross-block carry.

grid = (B, H, nChunks): chunks innermost ('arbitrary'), state scratch
persists across chunk steps for a fixed (batch, head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref,
                *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (Q, 1)
    bmat = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    cmat = c_ref[0, 0].astype(jnp.float32)         # (Q, N)
    a = a_ref[pl.program_id(1)]                    # scalar decay rate (<0)

    # Inclusive prefix sum of the per-step log decay, as a column and as
    # a row, from masked (Q, Q) reductions: Mosaic lowers neither cumsum
    # nor a (Q, 1) -> (1, Q) transpose.
    la = dt * a                                    # (Q, 1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = idx >= jdx
    la_rows = jnp.broadcast_to(la, (chunk, chunk))          # [k, j] = la_k
    cum_row = jnp.sum(jnp.where(idx <= jdx, la_rows, 0.0), axis=0,
                      keepdims=True)                        # (1, Q)
    la_cols = jnp.broadcast_to(
        jnp.sum(jnp.where(idx == jdx, la_rows, 0.0), axis=0, keepdims=True),
        (chunk, chunk))                                     # [i, k] = la_k
    cum = jnp.sum(jnp.where(causal, la_cols, 0.0), axis=1,
                  keepdims=True)                            # (Q, 1) L_i

    # intra-chunk (matmul form): M[i,j] = (C_i.B_j) dt_j exp(L_i - L_j), j<=i
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dec = jnp.where(causal, cum - cum_row, 0.0)  # clamp before exp
    m = cb * jnp.where(causal, jnp.exp(dec), 0.0)
    y = jax.lax.dot(m, x * dt, preferred_element_type=jnp.float32)

    # inter-chunk: y_i += (C_i exp(L_i)) @ state^T   (state: (P, N))
    y += jax.lax.dot_general(cmat * jnp.exp(cum), state_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: h' = exp(L_Q) h + sum_j exp(L_Q - L_j) dt_j x_j B_j^T
    tot = jnp.sum(la, axis=0, keepdims=True)       # (1, 1) = L_Q
    w = jnp.exp(tot - cum) * dt                    # (Q, 1)
    upd = jax.lax.dot_general(x * w, bmat, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = state_ref[...] * jnp.exp(tot) + upd

    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=128, interpret=False):
    """x: (Bb,S,H,P); dt: (Bb,S,H); A: (H,); B,C: (Bb,S,G,N).

    Returns y: (Bb,S,H,P).  (D-skip and gating applied by the caller.)
    The kernel runs head-major, (Bb,H,S,P) with dt as (Bb,H,S,1): a
    block's last two dims must tile (8, 128) or span the array, so the
    sequence chunk has to be the second-to-last dim.
    """
    bb, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    kv_map = lambda b_, h_, ci: (b_, (h_ * g) // h, ci, 0)
    seq_map = lambda b_, h_, ci: (b_, h_, ci, 0)
    out = pl.pallas_call(
        kernel,
        grid=(bb, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # A (H,)
            pl.BlockSpec((1, 1, chunk, p), seq_map),           # x
            pl.BlockSpec((1, 1, chunk, 1), seq_map),           # dt
            pl.BlockSpec((1, 1, chunk, n), kv_map),            # B
            pl.BlockSpec((1, 1, chunk, n), kv_map),            # C
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), seq_map),
        out_shape=jax.ShapeDtypeStruct((bb, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), x.transpose(0, 2, 1, 3),
      dt.transpose(0, 2, 1)[..., None], B.transpose(0, 2, 1, 3),
      C.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
