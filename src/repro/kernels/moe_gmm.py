"""Pallas TPU grouped matmul for MoE expert FFNs (megablocks-style).

Tokens arrive sorted by expert and padded so every token block of size
``block_t`` belongs to exactly ONE expert; ``block_group_ids[t]`` names it.
The expert weight block is selected by a scalar-prefetch index_map, so the
kernel streams only the weights of experts that actually own tokens on this
core — the TPU-native analogue of megablocks' block-sparse matmul (no
(T, E, capacity) one-hot dispatch tensors ever touch HBM).

grid = (nT, nN, nK): fp32 accumulation over the K dimension in VMEM scratch.
The token array may be sized for the worst case: only the first ``n_live``
blocks are computed.  The blocks past them map onto the last live block's
tiles, so they move no data, and their rows of the output are left
unwritten (callers mask them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the scoped VMEM a kernel may use unless it asks for more
_DEFAULT_VMEM = 16 * 1024 * 1024


def _gmm_kernel(gid_ref, live_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k):
    ti = pl.program_id(0)
    ki = pl.program_id(2)
    live = ti < live_ref[0]

    @pl.when(jnp.logical_and(live, ki == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _acc():
        acc_ref[...] += jax.lax.dot(x_ref[...], w_ref[0],
                                    preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, ki == n_k - 1))
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_n", "block_k",
                                             "interpret"))
def moe_gmm(x, w, block_group_ids, n_live=None, *, block_t=128, block_n=128,
            block_k=128, interpret=False):
    """x: (T, K) sorted+padded tokens; w: (E, K, N);
    block_group_ids: (T//block_t,) int32 expert id per token block;
    n_live: int32 count of leading blocks to compute (None = all).
    Returns (T, N); rows past the live blocks are left unwritten.
    """
    t, kdim = x.shape
    e, _, n = w.shape
    block_t = min(block_t, t)
    block_n = min(block_n, n)
    block_k = min(block_k, kdim)
    assert t % block_t == 0 and n % block_n == 0 and kdim % block_k == 0
    n_t, n_n, n_k = t // block_t, n // block_n, kdim // block_k
    assert block_group_ids.shape == (n_t,)
    if n_live is None:
        n_live = n_t
    live = jnp.asarray(n_live, jnp.int32).reshape(1)

    def tile(ti, ni, ki, live_ref):
        """Block indices of grid step (ti, ni, ki); a step past the live
        blocks takes the last live step's, so nothing is fetched."""
        last = jnp.maximum(live_ref[0], 1) - 1
        dead = ti > last
        return (jnp.where(dead, last, ti), jnp.where(dead, n_n - 1, ni),
                jnp.where(dead, n_k - 1, ki))

    def x_map(ti, ni, ki, gid, live_ref):
        t_, _, k_ = tile(ti, ni, ki, live_ref)
        return t_, k_

    def w_map(ti, ni, ki, gid, live_ref):
        t_, n_, k_ = tile(ti, ni, ki, live_ref)
        return gid[t_], k_, n_

    def o_map(ti, ni, ki, gid, live_ref):
        t_, n_, _ = tile(ti, ni, ki, live_ref)
        return t_, n_

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_t, n_n, n_k),
        in_specs=[pl.BlockSpec((block_t, block_k), x_map),
                  pl.BlockSpec((1, block_k, block_n), w_map)],
        out_specs=pl.BlockSpec((block_t, block_n), o_map),
        scratch_shapes=[pltpu.VMEM((block_t, block_n), jnp.float32)],
    )
    # double-buffered operand and output tiles, and the accumulator
    vmem = (2 * (block_t * block_k * x.dtype.itemsize
                 + block_k * block_n * w.dtype.itemsize
                 + block_t * block_n * x.dtype.itemsize)
            + 4 * block_t * block_n)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            # sequential: a step past the live blocks rewrites the last
            # live output tile from the buffer it shares with that step
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=(None if vmem <= _DEFAULT_VMEM * 3 // 4
                              else vmem + _DEFAULT_VMEM)),
        interpret=interpret,
        name="moe_gmm",
    )(block_group_ids.astype(jnp.int32), live, x, w)
    return out
