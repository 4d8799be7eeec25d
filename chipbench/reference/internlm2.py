"""Plain float32 reference of InternLM2 training, in ``jax.numpy`` alone.

The block stack follows arXiv:2403.17297 and the published
``internlm/internlm2-*`` configurations: token embedding; per layer
RMSNorm, grouped-query causal self-attention with rotary position
embeddings (rotate-half form, theta from the configuration), residual,
RMSNorm, SwiGLU MLP, residual; a final RMSNorm and an untied output
head; mean next-token cross-entropy.  No bias anywhere.

Matrix products run at ``highest`` precision.  Attention is computed in
blocks of query rows and each layer is rematerialised, so the reference
fits on one chip beside nothing else; neither changes the result.

``train(..., operands="float8_e4m3fn")`` is the comparison's control:
the same reference with both operands of every forward matrix product
rounded to that 8-bit float, each tensor scaled by its largest magnitude
(the per-tensor scaling of fp8 training).

The optimizer is AdamW as the configuration's ``optimizer`` block
states it: gradients clipped to a global norm, bias-corrected moments,
decoupled weight decay, a linear warm-up into a cosine schedule.

The parameters are made here too, from a key, in the tree the trainer
takes (``embed``, ``layers`` stacked over depth, ``final_norm``,
``lm_head``), so that the program and the reference start from the same
weights without either taking them from the other.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def sizes(cfg: Dict) -> Dict[str, int]:
    """The shapes a run uses, from the configuration file."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": d // h, "ff": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def init_params(key, z: Dict[str, int], dtype=jnp.float32) -> Dict:
    """Seeded weights: normal(0, 1/sqrt(fan_in)) matrices, 0.02-scaled
    embedding, unit norm gains."""
    d, h, kv, hd, ff, v, n = (z["d"], z["h"], z["kv"], z["hd"], z["ff"],
                              z["vocab"], z["layers"])
    ks = jax.random.split(key, 9)

    def mat(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    layers = {
        "ln1": jnp.ones((n, d), dtype), "ln2": jnp.ones((n, d), dtype),
        "attn": {"wq": mat(ks[0], (n, d, h * hd), d),
                 "wk": mat(ks[1], (n, d, kv * hd), d),
                 "wv": mat(ks[2], (n, d, kv * hd), d),
                 "wo": mat(ks[3], (n, h * hd, d), h * hd)},
        "mlp": {"w1": mat(ks[4], (n, d, ff), d),
                "w2": mat(ks[5], (n, ff, d), ff),
                "w3": mat(ks[6], (n, d, ff), d)},
    }
    return {"embed": (0.02 * jax.random.normal(ks[7], (v, d), jnp.float32)
                      ).astype(dtype),
            "layers": layers, "final_norm": jnp.ones((d,), dtype),
            "lm_head": mat(ks[8], (d, v), d)}


def tokens(key, step: int, batch: int, seq: int, vocab: int) -> Dict:
    """One batch of the feed: ``batch`` rows of ``seq + 1`` token ids with
    a Zipf-like marginal (a squared uniform), every row drawn apart;
    inputs are the first ``seq``, labels the last ``seq``."""
    u = jax.random.uniform(jax.random.fold_in(key, step), (batch, seq + 1))
    ids = (u * u * (vocab - 1)).astype(jnp.int32)
    return {"tokens": ids[:, :seq], "labels": ids[:, 1:]}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def same(x):
    return x


def rounder(dtype):
    """Operand rounding to ``dtype`` with a per-tensor scale; the rounding
    passes gradients through unchanged."""
    dtype = jnp.dtype(dtype)
    top = float(jnp.finfo(dtype).max)

    def q(x):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return x + jax.lax.stop_gradient(
            (x / s).astype(dtype).astype(x.dtype) * s - x)
    return q


def rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x: (B, H, S, D); rotate-half rotary embedding."""
    s, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, block: int, r=same):
    """Causal GQA attention, queries in blocks.  q: (B, H, S, D);
    k, v: (B, KV, S, D); ``r`` rounds matrix-product operands."""
    b, h, s, d = q.shape
    group = h // k.shape[1]
    block = min(block, s)
    k = r(jnp.repeat(k, group, axis=1))
    v = r(jnp.repeat(v, group, axis=1))
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
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d)


def layer(x, p, z, block, r=same):
    b, s, _ = x.shape
    h, kv, hd = z["h"], z["kv"], z["hd"]
    w = jax.tree.map(r, {**p["attn"], **p["mlp"]})
    a = r(rmsnorm(x, p["ln1"], z["eps"]))
    q = (a @ w["wq"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = (a @ w["wk"]).reshape(b, s, kv, hd).transpose(0, 2, 1, 3)
    v = (a @ w["wv"]).reshape(b, s, kv, hd).transpose(0, 2, 1, 3)
    o = attention(rope(q, z["theta"]), rope(k, z["theta"]), v, block, r)
    x = x + r(o.transpose(0, 2, 1, 3).reshape(b, s, h * hd)) @ w["wo"]
    m = r(rmsnorm(x, p["ln2"], z["eps"]))
    return x + r(jax.nn.silu(m @ w["w1"]) * (m @ w["w3"])) @ w["w2"]


def loss(params, batch, z, block: int = 512, r=same):
    x = params["embed"][batch["tokens"]]
    step = jax.checkpoint(lambda x_, p_: layer(x_, p_, z, block, r))
    for i in range(z["layers"]):
        x = step(x, jax.tree.map(lambda t: t[i], params["layers"]))
    x = rmsnorm(x, params["final_norm"], z["eps"])
    logits = r(x) @ r(params["lm_head"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                               axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------
def lr_at(step: int, o: Dict) -> float:
    """Learning rate of optimizer step ``step`` (1-based)."""
    base, warm, total = o["lr"], o["warmup_steps"], o["total_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return 0.5 * base * (1.0 + math.cos(math.pi * frac))


def adamw(params, grads, m, v, step: int, o: Dict):
    """One AdamW step; returns (params, m, v, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps, wd = o["b1"], o["b2"], o["eps"], o["weight_decay"]
    lr = lr_at(step, o)
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                    + wd * p), params, m, v)
    return params, m, v, grads


def train(key_w, key_d, z: Dict, o: Dict, batch: int, seq: int,
          n_steps: int, operands=None, accum: int = 1
          ) -> Tuple[list, Dict, Dict]:
    """``n_steps`` reference steps from the seeded weights and feed, each
    the mean of the gradients (and losses) of ``accum`` micro-batches of
    ``batch // accum`` consecutive rows, then one AdamW update.
    Returns (losses, per-slice norms of the first clipped gradient,
    per-slice norms of the parameters' change after the last step).
    ``operands`` names a dtype to round matrix-product operands to (the
    control); ``None`` keeps them in float32."""
    r = same if operands is None else rounder(operands)
    rows = batch // accum
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
            for i in range(accum):
                lval, g = add(params, g, {k: x[i * rows:(i + 1) * rows]
                                          for k, x in b.items()})
                lsum += float(lval)
            losses.append(lsum / accum)
            g = jax.tree.map(lambda x: x / accum, g)
            params, m, v, g = upd(params, g, m, v, step + 1)
            if step == 0:
                g1 = slice_norms(g)
            del g
        del m, v
        # the starting weights again, made anew rather than kept
        delta = slice_norms(jax.tree.map(jnp.subtract, params,
                                         init(key_w)))
    return losses, g1, delta


def slice_norms(tree) -> Dict[str, float]:
    """L2 norm of every matrix, and of each layer's slice of the stacked
    layer weights, keyed by path."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        x = leaf.astype(jnp.float32)
        if name.startswith("['layers']"):
            axes = tuple(range(1, x.ndim))
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=axes))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    host = jax.device_get(out)
    flat = {}
    for name, v in host.items():
        if getattr(v, "ndim", 0):
            for i, x in enumerate(v.tolist()):
                flat[f"{name}[{i}]"] = float(x)
        else:
            flat[name] = float(v)
    return flat
