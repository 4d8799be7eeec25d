"""Mixture-of-Experts layer: top-k router, then one of two dispatches.

* Capacity-padded (``capacity_factor`` set): sort-free rank-based
  scatter into (E, capacity, D) buckets and one batched einsum over the
  expert dim -- the form XLA SPMD can partition over an expert-sharded
  mesh axis (EP); tokens past an expert's capacity are dropped.  An
  explicit shard_map all-to-all variant lives in parallel/moe_a2a.py.
* Dropless expert share (``capacity_factor`` None): the layer holds
  ``experts_held`` of the ``n_experts`` the router scores (one chip's
  expert-parallel share), and computes, for every token routed to them,
  those experts' part of the result: rows sorted by expert, each expert's
  rows padded to whole kernel blocks, three grouped matmuls
  (``kernels.ops.moe_gmm``).  Buffers are sized for the worst case (every
  token sending all its choices here); the kernel computes only the
  blocks that hold rows.

Shared experts (DeepSeekMoE) are one SwiGLU MLP applied to every token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels import ops
from repro.models import common

# rows per grouped-matmul block; each expert's rows are padded to it
GMM_BLOCK = 128


def moe_init(key, d_model, m: MoEConfig, dtype):
    ks = jax.random.split(key, 4)
    e, f = m.held, m.d_ff_expert
    scale = d_model ** -0.5
    p = {
        "router": common.dense_init(ks[0], d_model, m.n_experts, dtype,
                                    scale=d_model ** -0.5),
        "w1": common.initializer(ks[1], (e, d_model, f), scale, dtype),
        "w3": common.initializer(ks[2], (e, d_model, f), scale, dtype),
        "w2": common.initializer(ks[3], (e, f, d_model), f ** -0.5, dtype),
    }
    if m.n_shared:
        p["shared"] = common.mlp_init(jax.random.fold_in(key, 4), d_model,
                                      m.d_ff_shared, True, dtype)
    return p


def _capacity(n_tokens: int, m: MoEConfig) -> int:
    cap = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-cap // 8) * 8)   # round up to multiple of 8


def router_topk(logits, m: MoEConfig, n_seq: int = 1):
    """logits: (T, E) fp32 -> (weights (T,k), ids (T,k), aux_loss).

    Softmax scores, greedy top-k.  The balance loss is sequence-wise
    (arXiv:2405.04434 eq. 23, per sequence of the ``n_seq`` the T rows
    hold, then averaged) when ``m.seq_aux``, else Switch-style."""
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    e = logits.shape[-1]
    if m.seq_aux:
        t = logits.shape[0]
        s = t // n_seq
        # f_i: expert i's share of the sequence's choices, times E / k
        counts = jnp.zeros((n_seq, e), jnp.float32).at[
            jnp.arange(n_seq)[:, None], ids.reshape(n_seq, s * m.top_k)
        ].add(1.0)
        f = counts * (e / (s * m.top_k))
        p_mean = probs.reshape(n_seq, s, e).mean(1)
        return weights, ids, jnp.mean(jnp.sum(f * p_mean, -1))
    # Switch-style load-balance loss
    me = probs.mean(0)                                    # mean router prob
    one_hot = jax.nn.one_hot(ids[:, 0], e)                # primary expert
    ce = one_hot.mean(0)                                  # fraction routed
    aux = e * jnp.sum(me * ce)
    return weights, ids, aux


def _shard_buckets(t, ex):
    """Constrain an (E, cap, ...) tensor per ex.moe_expert_axis /
    ex.moe_cap_axes."""
    if ex.moe_expert_axis is None and ex.batch_axes is None \
            and ex.moe_cap_axes is None:
        return t
    from jax.sharding import PartitionSpec as P
    cap = ex.moe_cap_axes
    if ex.moe_expert_axis is not None:
        spec = P(ex.moe_expert_axis, cap, *([None] * (t.ndim - 2)))
    else:
        spec = P(None, cap if cap is not None else ex.batch_axes,
                 *([None] * (t.ndim - 2)))
    return jax.lax.with_sharding_constraint(t, spec)


def moe_apply(params, x, m: MoEConfig, ex, first: int = 0):
    """x: (B, S, D) -> (y, aux_loss, stats).  ``first``: the global id of
    the first expert held (dropless share).  ``stats`` are the dropless
    dispatch's per-call counts (``moe.*``), empty for the capacity path."""
    b, s, d = x.shape
    with jax.named_scope("moe"):
        xf = x.reshape(b * s, d)
        logits = jnp.dot(xf, params["router"],
                         preferred_element_type=jnp.float32)
        weights, ids, aux = router_topk(logits, m, n_seq=b)
        if m.capacity_factor is None:
            y, stats = _dropless(params, xf, weights, ids, m, ex, first)
        else:
            if m.held != m.n_experts:
                raise ValueError("an expert share needs dropless routing "
                                 "(capacity_factor=None)")
            y, stats = _capacity_dispatch(params, xf, weights, ids, m,
                                          ex), {}
        if m.n_shared:
            y = y + common.mlp_apply(params["shared"], xf, True)
    return y.reshape(b, s, d), aux, stats


def _capacity_dispatch(params, xf, weights, ids, m: MoEConfig, ex):
    t, d = xf.shape
    cap = _capacity(t, m)
    e = m.n_experts
    flat_e = ids.reshape(-1)                               # (T*k,)
    tok_of = jnp.repeat(jnp.arange(t), m.top_k)            # (T*k,)

    # rank of each (token, choice) within its expert, in token order
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    group_start = jnp.searchsorted(sorted_e, jnp.arange(e))
    rank_sorted = jnp.arange(t * m.top_k) - group_start[sorted_e]
    ranks = jnp.zeros((t * m.top_k,), jnp.int32).at[sort_idx].set(
        rank_sorted.astype(jnp.int32))

    keep = ranks < cap
    slot = jnp.where(keep, ranks, cap)                     # cap = drop slot

    # dispatch: buckets (E, cap, D) — the scatter IS the A2A under an
    # expert-sharded constraint
    buckets = jnp.zeros((e, cap + 1, d), xf.dtype)
    buckets = buckets.at[flat_e, slot].add(xf[tok_of], mode="drop")
    buckets = _shard_buckets(buckets[:, :cap], ex)

    # expert FFN: batched gated MLP over the expert dim
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buckets, params["w1"]))
         * jnp.einsum("ecd,edf->ecf", buckets, params["w3"]))
    h = _shard_buckets(h, ex)
    out_b = _shard_buckets(jnp.einsum("ecf,efd->ecd", h, params["w2"]), ex)

    # combine
    out_b = jnp.concatenate(
        [out_b, jnp.zeros((e, 1, d), out_b.dtype)], axis=1)
    gathered = out_b[flat_e, slot]                         # (T*k, D)
    gathered = gathered * (weights.reshape(-1, 1)
                           * keep[:, None]).astype(gathered.dtype)
    return gathered.reshape(t, m.top_k, d).sum(1)


def share_rows(t: int, m: MoEConfig) -> int:
    """Static row count of the dropless dispatch for ``t`` tokens: every
    token sending min(top_k, held) choices here, plus each held expert's
    padding to whole blocks."""
    worst = t * min(m.top_k, m.held) + m.held * (GMM_BLOCK - 1)
    return -(-worst // GMM_BLOCK) * GMM_BLOCK


def _dropless(params, xf, weights, ids, m: MoEConfig, ex, first: int):
    t, d = xf.shape
    h, k = m.held, m.top_k
    cap = share_rows(t, m)
    local = ids.reshape(-1) - first                        # (T*k,)
    mine = (local >= 0) & (local < h)
    onehot = (local[:, None] == jnp.arange(h)) & mine[:, None]
    counts = onehot.sum(0, dtype=jnp.int32)                # rows per expert
    sizes = -(-counts // GMM_BLOCK) * GMM_BLOCK            # whole blocks
    offsets = jnp.cumsum(sizes) - sizes
    # each row's place: its expert's offset plus its rank, in token order
    rank = jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1
    e_of = jnp.where(mine, local, 0)
    pos = jnp.where(mine, offsets[e_of]
                    + jnp.take_along_axis(rank, e_of[:, None], 1)[:, 0], cap)
    row_tok = jnp.full((cap,), t, jnp.int32).at[pos].set(
        jnp.repeat(jnp.arange(t, dtype=jnp.int32), k), mode="drop")
    xs = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])[row_tok]

    gmm = lambda a, w: ops.moe_gmm(a, w, sizes, backend=ex.backend,
                                   block_t=GMM_BLOCK)
    hh = jax.nn.silu(gmm(xs, params["w1"])) * gmm(xs, params["w3"])
    out = gmm(hh, params["w2"])                            # (cap, D)

    got = jnp.where(mine[:, None], out[jnp.minimum(pos, cap - 1)], 0)
    y = jnp.sum(got.reshape(t, k, d) * weights.astype(xf.dtype)[..., None],
                axis=1, dtype=jnp.float32).astype(xf.dtype)
    routed = counts.sum()
    stats = {"moe.routed_rows": routed.astype(jnp.float32),
             "moe.gmm_pad_rows": (sizes.sum() - routed).astype(jnp.float32),
             "moe.load_max_over_mean": counts.max() / jnp.maximum(
                 counts.mean(), 1e-9)}
    return y, stats
