"""Pallas TPU grouped matmul for MoE expert FFNs (megablocks-style).

Tokens arrive sorted by expert and padded so every token block of size
``block_t`` belongs to exactly ONE expert; ``block_group_ids[t]`` names it.
The expert weight block is selected by a scalar-prefetch index_map, so the
kernel streams only the weights of experts that actually own tokens on this
core — the TPU-native analogue of megablocks' block-sparse matmul (no
(T, E, capacity) one-hot dispatch tensors ever touch HBM).

grid = (nT, nN, nK): fp32 accumulation over the K dimension in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gmm_kernel(gid_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_n", "block_k",
                                             "interpret"))
def moe_gmm(x, w, block_group_ids, *, block_t=128, block_n=128, block_k=128,
            interpret=False):
    """x: (T, K) sorted+padded tokens; w: (E, K, N);
    block_group_ids: (T//block_t,) int32 expert id per token block.
    Returns (T, N).
    """
    t, kdim = x.shape
    e, _, n = w.shape
    block_t = min(block_t, t)
    block_n = min(block_n, n)
    block_k = min(block_k, kdim)
    assert t % block_t == 0 and n % block_n == 0 and kdim % block_k == 0
    n_t, n_n, n_k = t // block_t, n // block_n, kdim // block_k
    assert block_group_ids.shape == (n_t,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_t, n_n, n_k),
        in_specs=[
            pl.BlockSpec((block_t, block_k),
                         lambda ti, ni, ki, gid: (ti, ki)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda ti, ni, ki, gid: (gid[ti], ki, ni)),
        ],
        out_specs=pl.BlockSpec((block_t, block_n),
                               lambda ti, ni, ki, gid: (ti, ni)),
        scratch_shapes=[pltpu.VMEM((block_t, block_n), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_group_ids.astype(jnp.int32), x, w)
    return out
