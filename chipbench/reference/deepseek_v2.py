"""Plain float32 reference of DeepSeek-V2 training on one expert-parallel
chip's share, in ``jax.numpy`` alone.

The block stack follows arXiv:2405.04434 and the published
``deepseek-ai/DeepSeek-V2-Lite`` configuration: token embedding; per layer
RMSNorm, multi-head latent attention, residual, RMSNorm, an MLP or an MoE,
residual; a final RMSNorm and an untied output head; mean next-token
cross-entropy over the vocabulary slice, plus the sequence-wise expert
balance loss (§2.2 eq. 23, weight ``aux_loss_alpha``) of every MoE layer.
No bias anywhere.

* Latent attention (§2.1, no q LoRA): q = W_q h, split per head into
  ``qk_nope_head_dim`` and ``qk_rope_head_dim``; [c_kv, k_rope] = W_kva h
  with RMSNorm on c_kv; [k_nope, v] = W_kvb c_kv per head; RoPE
  (rotate-half form; the published code permutes the rope dims to the
  same form first) on q_rope and on the one k_rope every head shares;
  softmax scale 1/sqrt(nope + rope), no YaRN.
* The first ``first_k_dense_replace`` layers have a SwiGLU MLP of
  ``intermediate_size``.
* An MoE layer: the router scores all ``n_routed_experts * ep_chips``
  experts (softmax in float32, greedy top-k, weights not renormalised,
  times ``routed_scaling_factor``); this chip holds the first
  ``n_routed_experts`` of them and adds, for each token, only those
  experts' weighted outputs.  Each held expert is computed here on every
  token and weighted by its gate (zero where the token did not choose
  it): no sorting, padding or capacity.  ``n_shared_experts`` shared
  experts are one SwiGLU MLP of ``n_shared_experts *
  moe_intermediate_size`` applied to every token.

Matrix products run at ``highest`` precision; attention is computed in
blocks of query rows and each layer and each expert is rematerialised,
so the reference fits on one chip beside nothing else.  AdamW, the feed,
the control's operand rounding and the slice norms are those of
``chipbench.reference.internlm2``.

The parameters are made here in the tree the trainer takes (``embed``,
``dense_layers`` and ``layers`` stacked over depth, ``final_norm``,
``lm_head``).

The operation and byte counts of the cell's readers are at the end.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench.reference.internlm2 import (adamw, rmsnorm, rope, rounder,
                                           same, slice_norms, tokens)

__all__ = ["sizes", "init_params", "loss", "train", "tokens", "slice_norms",
           "dense_params", "expert_params", "train_flops",
           "mla_attention_fwd", "gmm_fwd"]


def sizes(cfg: Dict) -> Dict:
    """The shapes a run uses, from the configuration file."""
    held = cfg["n_routed_experts"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "ff": cfg["intermediate_size"],
            "ffe": cfg["moe_intermediate_size"],
            "experts": held * cfg["ep_chips"], "held": held,
            "top_k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"],
            "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"], "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "alpha": float(cfg["aux_loss_alpha"]),
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "scale": float(cfg["routed_scaling_factor"])}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def init_params(key, z: Dict, dtype=jnp.float32) -> Dict:
    """Seeded weights: normal(0, 1/sqrt(fan_in)) matrices, 0.02-scaled
    embedding, unit norm gains."""
    d, h, v = z["d"], z["h"], z["vocab"]
    qk = z["nope"] + z["rope"]
    n_dense, n_moe = z["dense"], z["layers"] - z["dense"]
    e, f, fs = z["held"], z["ffe"], z["shared"] * z["ffe"]
    ks = iter(jax.random.split(key, 32))

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def attn(n):
        return {"wq": mat((n, d, h * qk), d),
                "wkv_a": mat((n, d, z["r"] + z["rope"]), d),
                "kv_norm": jnp.ones((n, z["r"]), dtype),
                "wkv_b": mat((n, z["r"], h * (z["nope"] + z["v"])), z["r"]),
                "wo": mat((n, h * z["v"], d), h * z["v"])}

    def mlp(lead, width):
        return {"w1": mat(lead + (d, width), d),
                "w3": mat(lead + (d, width), d),
                "w2": mat(lead + (width, d), width)}

    def norms(n):
        return {"ln1": jnp.ones((n, d), dtype), "ln2": jnp.ones((n, d), dtype)}

    layers = {**norms(n_moe), "attn": attn(n_moe),
              "moe": {"router": mat((n_moe, d, z["experts"]), d),
                      **mlp((n_moe, e), f), "shared": mlp((n_moe,), fs)}}
    params = {"embed": (0.02 * jax.random.normal(next(ks), (v, d),
                                                 jnp.float32)).astype(dtype),
              "layers": layers, "final_norm": jnp.ones((d,), dtype),
              "lm_head": mat((d, v), d)}
    if n_dense:
        params["dense_layers"] = {**norms(n_dense), "attn": attn(n_dense),
                                  "mlp": mlp((n_dense,), z["ff"])}
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def attention(q, k, v, block: int, r=same):
    """Causal attention, queries in blocks.  q, k: (B, H, S, Dqk);
    v: (B, H, S, Dv); ``r`` rounds matrix-product operands."""
    b, h, s, d = q.shape
    block = min(block, s)
    k, v = r(k), r(v)
    nb = s // block
    qb = q.reshape(b, h, nb, block, d).transpose(2, 0, 1, 3, 4)
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        i, qi = args
        sc = jnp.einsum("bhqd,bhkd->bhqk", r(qi), k) * d ** -0.5
        rows = i * block + jnp.arange(block)
        sc = jnp.where(cols[None, :] <= rows[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", r(p), v)

    out = jax.lax.map(one, (jnp.arange(nb), qb))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, s, v.shape[-1])


def mla(x, p, z, block, r=same):
    b, s, _ = x.shape
    h, nope, rd, rank = z["h"], z["nope"], z["rope"], z["r"]
    w = jax.tree.map(r, p)
    q = (x @ w["wq"]).reshape(b, s, h, nope + rd).transpose(0, 2, 1, 3)
    kv = x @ w["wkv_a"]
    c = r(rmsnorm(kv[..., :rank], p["kv_norm"], z["eps"]))
    k_rope = rope(kv[:, None, :, rank:], z["theta"])          # (B,1,S,rd)
    kvb = (c @ w["wkv_b"]).reshape(b, s, h, nope + z["v"]).transpose(
        0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], z["theta"])],
                        -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope, (b, h, s, rd))], -1)
    o = attention(q, k, kvb[..., nope:], block, r)
    return r(o.transpose(0, 2, 1, 3).reshape(b, s, h * z["v"])) @ w["wo"]


def swiglu(x, p, r=same):
    w = jax.tree.map(r, p)
    return r(jax.nn.silu(x @ w["w1"]) * (x @ w["w3"])) @ w["w2"]


def router(x, w_router, z, n_seq: int):
    """x: (T, D) -> (gate (T, E): each token's weight on each expert, zero
    off its top-k; balance loss).  Routing in float32."""
    logits = x @ w_router
    probs = jax.nn.softmax(logits, axis=-1)
    top, ids = jax.lax.top_k(probs, z["top_k"])
    if z["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * z["scale"]
    chosen = jax.nn.one_hot(ids, z["experts"], dtype=jnp.float32)  # T,k,E
    gate = jnp.einsum("tk,tke->te", top, chosen)
    t, e = probs.shape
    s = t // n_seq
    f = chosen.reshape(n_seq, s * z["top_k"], e).sum(1) \
        * (e / (s * z["top_k"]))
    aux = jnp.mean(jnp.sum(f * probs.reshape(n_seq, s, e).mean(1), -1))
    return gate, aux


def moe(x, p, z, r=same):
    """The held experts' part of the routed output plus the shared
    experts; x: (B, S, D)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    gate, aux = router(xf, p["router"], z, b)

    @functools.partial(jax.checkpoint, static_argnums=1)
    def expert(y, e):
        w = {k: p[k][e] for k in ("w1", "w3", "w2")}
        return y + gate[:, e:e + 1] * swiglu(r(xf), w, r)

    y = jnp.zeros_like(xf)
    for e in range(z["held"]):
        y = expert(y, e)
    y = y + swiglu(r(xf), p["shared"], r)
    return y.reshape(b, s, d), aux


def layer(x, p, z, block, r=same):
    x = x + mla(r(rmsnorm(x, p["ln1"], z["eps"])), p["attn"], z, block, r)
    m = rmsnorm(x, p["ln2"], z["eps"])
    if "moe" in p:
        y, aux = moe(m, p["moe"], z, r)
        return x + y, aux
    return x + swiglu(r(m), p["mlp"], r), 0.0


def loss(params, batch, z, block: int = 512, r=same):
    x = params["embed"][batch["tokens"]]
    step = jax.checkpoint(lambda x_, p_: layer(x_, p_, z, block, r))
    aux = 0.0
    for name, n in (("dense_layers", z["dense"]),
                    ("layers", z["layers"] - z["dense"])):
        for i in range(n):
            x, a = step(x, jax.tree.map(lambda t: t[i], params[name]))
            aux = aux + a
    x = rmsnorm(x, params["final_norm"], z["eps"])
    logits = r(x) @ r(params["lm_head"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                               axis=-1)[..., 0]
    return jnp.mean(lse - gold) + z["alpha"] * aux


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------
def train(key_w, key_d, z: Dict, o: Dict, batch: int, seq: int,
          n_steps: int, operands=None) -> Tuple[list, Dict, Dict]:
    """``n_steps`` reference steps from the seeded weights and feed, each
    the mean of the gradients (and losses) of the ``batch`` rows, then
    one AdamW update.  The program sums micro-batches of several rows;
    its loss is a mean over rows of equal length, the balance loss is
    per sequence and the routing per token, dropless, so each row's
    gradient here is taken alone: the same mean, in the least memory.
    Returns (losses, per-slice norms of the first clipped gradient,
    per-slice norms of the parameters' change after the last step).
    ``operands`` names a dtype to round matrix-product operands to (the
    control); ``None`` keeps them in float32."""
    r = same if operands is None else rounder(operands)
    with jax.default_matmul_precision("highest"):
        init = jax.jit(lambda k: init_params(k, z))
        grad = jax.value_and_grad(lambda p, b: loss(p, b, z, r=r))

        def add(p, gsum, b):
            lval, g = grad(p, b)
            return lval, jax.tree.map(jnp.add, gsum, g)

        add = jax.jit(add, donate_argnums=1)
        upd = jax.jit(lambda p, g, m, v, s: adamw(p, g, m, v, s, o),
                      static_argnums=4, donate_argnums=(0, 1, 2, 3))
        params = init(key_w)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, g1 = [], None
        for step in range(n_steps):
            b = tokens(key_d, step, batch, seq, z["vocab"])
            g = jax.tree.map(jnp.zeros_like, params)
            lsum = 0.0
            for i in range(batch):
                lval, g = add(params, g, {k: x[i:i + 1]
                                          for k, x in b.items()})
                lsum += float(lval)
            losses.append(lsum / batch)
            g = jax.tree.map(lambda x: x / batch, g)
            params, m, v, g = upd(params, g, m, v, step + 1)
            if step == 0:
                g1 = slice_norms(g)
            del g
        del m, v
        delta = slice_norms(jax.tree.map(jnp.subtract, params,
                                         init(key_w)))
    return losses, g1, delta


# ---------------------------------------------------------------------------
# Operations and bytes (a model FLOP is a multiply-add counted as two)
# ---------------------------------------------------------------------------
def _mla_params(z: Dict) -> int:
    d, h = z["d"], z["h"]
    return (d * h * (z["nope"] + z["rope"]) + d * (z["r"] + z["rope"])
            + z["r"] * h * (z["nope"] + z["v"]) + h * z["v"] * d)


def dense_params(z: Dict) -> int:
    """Matrix weights every token meets: each layer's attention, the
    dense layers' MLPs, each MoE layer's router and shared experts, the
    output head (the embedding is a gather)."""
    d, n_moe = z["d"], z["layers"] - z["dense"]
    shared = 3 * d * z["shared"] * z["ffe"]
    return (z["layers"] * _mla_params(z) + z["dense"] * 3 * d * z["ff"]
            + n_moe * (d * z["experts"] + shared) + d * z["vocab"])


def expert_params(z: Dict) -> int:
    """Matrix weights one routed row meets: one expert's SwiGLU."""
    return 3 * z["d"] * z["ffe"]


def train_flops(z: Dict, seq: int, tokens_: float, routed_rows: float
                ) -> float:
    """Forward and backward operations of ``tokens_`` trained tokens whose
    MoE layers sent ``routed_rows`` token-expert rows to the held
    experts: 6 per matrix weight a token or a row meets, and causal
    attention's two products (query i meets i + 1 keys; scores over the
    q/k head size, values over the v head size), three times over."""
    attn = 3.0 * z["layers"] * z["h"] * (z["nope"] + z["rope"] + z["v"]) \
        * (seq + 1)
    return (6.0 * dense_params(z) + attn) * tokens_ \
        + 6.0 * expert_params(z) * routed_rows


def mla_attention_fwd(b: int, z: Dict, s: int, itemsize: int
                      ) -> Dict[str, float]:
    """One causal forward call of the attention kernel at latent
    attention's head sizes: scores 2 * (nope + rope) and values 2 * v
    operations per (query, visible key) pair per head; reads q, k, v and
    writes the output and the float32 log-sum-exp row."""
    h, qk, dv = z["h"], z["nope"] + z["rope"], z["v"]
    flops = float(b * h * s * (s + 1) * (qk + dv))
    nbytes = itemsize * b * h * s * (2 * qk + 2 * dv) + 4 * b * h * s
    return {"flops": flops, "bytes": float(nbytes)}


def gmm_fwd(z: Dict, rows: float, calls: float, itemsize: int
            ) -> Dict[str, float]:
    """The grouped-matmul forward calls of the routed experts over
    ``rows`` computed rows in all (routed and padding): the gate, up and
    down products, 2 * d * ffe operations a row each; each call reads its
    rows and its held experts' weights and writes its rows."""
    d, f = z["d"], z["ffe"]
    flops = 3.0 * 2.0 * d * f * rows
    nbytes = itemsize * (3.0 * (d + f) * rows
                         + calls * z["held"] * d * f)
    return {"flops": flops, "bytes": nbytes}
