"""Pallas TPU fused RMSNorm kernel (row-blocked, fp32 statistics)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps, weight_offset):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    w = weight_offset + w_ref[...].astype(jnp.float32)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "weight_offset",
                                             "block_rows", "interpret"))
def rmsnorm(x, w, *, eps=1e-6, weight_offset=0.0, block_rows=256,
            interpret=False):
    """x: (..., D); w: (D,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    # pad rows to a block multiple
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n_r = x2.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps,
                          weight_offset=weight_offset),
        grid=(n_r,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
            pl.BlockSpec((d,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, w)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
