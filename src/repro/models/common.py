"""Shared model-execution config + small building blocks."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops


@dataclass(frozen=True)
class ExecConfig:
    """Runtime execution knobs (orthogonal to the architecture config)."""
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    # activation checkpointing policy applied to each scanned layer:
    #   'none' | 'full' | 'dots'
    remat: str = "none"
    # tile of the XLA attention paths (forward and backward); the Pallas
    # forward sizes its own tiles from the shapes (``fwd_plan``)
    attn_block: int = 128
    ssd_chunk: int = 128
    backend: Optional[str] = None      # kernel backend override
    # MoE dispatch implementation: 'dense' (padded-bucket einsum, pjit
    # partitions it) — 'shard_map' A2A lives in parallel/moe_a2a.py.
    moe_impl: str = "dense"
    # mesh axis names carrying the batch dim of activations; a
    # with_sharding_constraint is seeded after every embedding gather
    # (GSPMD cannot infer batch sharding through a 2D-sharded table
    # gather, so without this the whole model runs batch-replicated).
    batch_axes: Optional[tuple] = None
    # mesh axis for Megatron-style sequence parallelism: the (B, S, D)
    # layer carry is kept sequence-sharded on this axis between blocks
    # (16x smaller remat residuals; GSPMD inserts the AG/RS pair around
    # attention/FFN exactly like Megatron-SP).
    seq_axis: Optional[str] = None
    # Period-grouped layer scan for alternating local/global archs: the
    # scan iterates over pattern periods and unrolls within, so every
    # sub-layer's window is STATIC (enables statically-skipped block
    # attention + correct AOT flop accounting).
    static_layer_pattern: bool = False
    # Fully unroll the layer scan (used by the dry-run depth variants so
    # XLA cost analysis sees every layer; scan bodies are counted once).
    layer_unroll: bool = False
    # MoE bucket sharding: scatter outputs have no inferable sharding, so
    # the (E, cap, D) dispatch buckets are constrained explicitly —
    # expert dim on ``moe_expert_axis`` when n_experts divides it (EP),
    # else the capacity dim on the batch axes.
    moe_expert_axis: Optional[str] = None
    # Additionally shard the CAPACITY dim of the buckets over these axes
    # (None = paper-faithful baseline, where each data-parallel rank
    # redundantly computes every expert's full capacity; setting this to
    # the batch axes is the §Perf hillclimb fix — 16x less expert compute
    # at the cost of a real all-to-all).
    moe_cap_axes: Optional[tuple] = None
    # concrete jax Mesh, required when moe_impl == "a2a" (shard_map path)
    mesh: Any = None

    def wrap_remat(self, fn):
        if self.remat == "none":
            return fn
        if self.remat == "full":
            return jax.checkpoint(fn)
        if self.remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots)
        raise ValueError(self.remat)


def layer_scan(ex: "ExecConfig", body, init, xs):
    """lax.scan for layer stacks, honouring ex.layer_unroll."""
    return jax.lax.scan(body, init, xs,
                        unroll=True if ex.layer_unroll else 1)


def shard_batch(x, ex: "ExecConfig"):
    """Constrain the leading (batch) dim of an activation to ex.batch_axes."""
    if ex.batch_axes is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(ex.batch_axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def shard_acts(x, ex: "ExecConfig"):
    """Constrain a (B, S, D) layer carry: batch over batch_axes and, when
    sequence parallelism is on, S over seq_axis."""
    if ex.batch_axes is None and ex.seq_axis is None:
        return x
    if x.ndim != 3 or x.shape[1] == 1 or ex.seq_axis is None:
        return shard_batch(x, ex)
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, P(ex.batch_axes, ex.seq_axis, None))


def initializer(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype, scale=None):
    if scale is None:
        scale = d_in ** -0.5
    return initializer(key, (d_in, d_out), scale, dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                     / head_dim)


def apply_rope(x, positions, theta: float):
    """x: (B, H, S, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (D/2,)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
        ang = ang[None, None]                          # (1,1,S,D/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs
        ang = ang[:, None]                             # (B,1,S,D/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Norms / MLP
# ---------------------------------------------------------------------------
def norm(x, w, eps, backend=None):
    return ops.rmsnorm(x, w, eps=eps, backend=backend)


def mlp_apply(params, x, gated: bool):
    if gated:
        h = jax.nn.silu(x @ params["w1"]) * (x @ params["w3"])
    else:
        h = jax.nn.gelu(x @ params["w1"])
    return h @ params["w2"]


def mlp_init(key, d_model, d_ff, gated: bool, dtype):
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], d_model, d_ff, dtype),
         "w2": dense_init(ks[1], d_ff, d_model, dtype)}
    if gated:
        p["w3"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def cross_entropy(logits, labels, *, logit_softcap=0.0, mask=None):
    """logits: (B,S,V) fp32-safe CE; labels: (B,S) int32.  mask: (B,S).

    The gold logit is extracted with a masked reduction rather than
    take_along_axis so a vocab-sharded logits tensor never gets
    all-gathered under pjit (the reduction stays local + one psum).
    """
    logits = logits.astype(jnp.float32)
    if logit_softcap:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab_iota = jnp.arange(logits.shape[-1], dtype=labels.dtype)
    onehot = (labels[..., None] == vocab_iota)
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = lse - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
