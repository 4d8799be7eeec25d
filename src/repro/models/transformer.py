"""Decoder-only transformer LM (dense / MoE / VLM-backbone).

One scanned, homogeneous layer stack serves every layer: local/global
attention alternation is a per-layer dynamic window scalar (gemma), MoE vs
dense is static per-arch.  Compile time and HLO size are O(1) in depth.
The ``first_k_dense`` leading dense layers of a MoE model (DeepSeek) run
unscanned before it, from their own stacked params (``dense_layers``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common, moe
from repro.obs import metrics as obs_metrics


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def layer_init(key, cfg: ModelConfig, dtype, dense: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attention.attn_init(ks[0], cfg.d_model, cfg.attn, dtype),
    }
    if cfg.moe is not None and not dense:
        p["moe"] = moe.moe_init(ks[1], cfg.d_model, cfg.moe, dtype)
    else:
        p["mlp"] = common.mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                   cfg.gated_mlp, dtype)
    return p


def lm_init(key, cfg: ModelConfig, ex: common.ExecConfig):
    dtype = ex.param_dtype
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    n_dense = cfg.first_k_dense
    layers = jax.vmap(lambda k: layer_init(k, cfg, dtype))(
        layer_keys[n_dense:])
    params = {
        "embed": common.initializer(k_embed, (cfg.vocab, cfg.d_model),
                                    0.02, dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if n_dense:
        params["dense_layers"] = jax.vmap(
            lambda k: layer_init(k, cfg, dtype, dense=True))(
                layer_keys[:n_dense])
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(k_head, cfg.d_model,
                                              cfg.vocab, dtype)
    return params


def layer_flags(cfg: ModelConfig):
    """(L,) int32 — 1 where the scanned layer uses GLOBAL (full)
    attention."""
    l = cfg.n_layers - cfg.first_k_dense
    if cfg.attn is None or cfg.attn.local_global_period == 0:
        return jnp.ones((l,), jnp.int32)   # uniform (window handled statically)
    p = cfg.attn.local_global_period
    idx = jnp.arange(l)
    return (idx % p == p - 1).astype(jnp.int32)


def _embed(params, tokens, cfg, ex, prefix_embeds=None):
    x = common.shard_batch(
        params["embed"][tokens].astype(ex.compute_dtype), ex)
    if prefix_embeds is not None:
        p = prefix_embeds.shape[1]
        x = jnp.concatenate(
            [prefix_embeds.astype(ex.compute_dtype), x[:, p:]], axis=1)
    return x


def _unembed(params, x, cfg):
    table = params.get("lm_head")
    if table is None:
        return x @ params["embed"].T
    return x @ table


def _cache_len(cfg: ModelConfig, seq_len: int):
    a = cfg.attn
    if a is not None and a.window and a.local_global_period == 0:
        return min(seq_len, a.window), a.window   # rolling
    return seq_len, None


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------
def _ffn(lp, h, cfg, ex):
    """The layer's MLP or MoE -> (out, aux_loss, stats); which one is
    decided by the layer's params (``mlp`` or ``moe``)."""
    if "moe" not in lp:
        return common.mlp_apply(lp["mlp"], h, cfg.gated_mlp), 0.0, {}
    if ex.moe_impl == "a2a" and ex.mesh is not None:
        if cfg.moe.capacity_factor is None:
            raise ValueError("the a2a dispatch is capacity-padded; "
                             f"{cfg.name} routes dropless")
        from repro.parallel.moe_a2a import moe_apply_a2a
        m, aux = moe_apply_a2a(lp["moe"], h, cfg.moe, ex, ex.mesh)
        return m, aux, {}
    return moe.moe_apply(lp["moe"], h, cfg.moe, ex)


def _layer_train(x, lp, window, cfg, ex, collect_kv=False):
    h = common.norm(x, lp["ln1"], cfg.norm_eps, ex.backend)
    a, kv = attention.attn_train(lp["attn"], h, cfg.attn, window=window,
                                 norm_eps=cfg.norm_eps, ex=ex)
    x = x + a
    h = common.norm(x, lp["ln2"], cfg.norm_eps, ex.backend)
    m, aux, stats = _ffn(lp, h, cfg, ex)
    x = common.shard_acts(x + m, ex)
    return x, aux, (kv if collect_kv else None), stats


def _dense_layers(params, cfg: ModelConfig):
    """The leading dense layers' params, one dict per layer."""
    return [jax.tree.map(lambda t: t[i], params["dense_layers"])
            for i in range(cfg.first_k_dense)]


def _use_period_path(cfg: ModelConfig, ex) -> bool:
    a = cfg.attn
    return (ex.static_layer_pattern and a is not None
            and a.local_global_period > 1)


def _split_periods(tree, period, n_layers):
    n_full = n_layers // period
    main = jax.tree.map(
        lambda t: t[:n_full * period].reshape(n_full, period,
                                              *t.shape[1:]), tree)
    rest = jax.tree.map(lambda t: t[n_full * period:], tree)
    return main, rest, n_full, n_layers - n_full * period


def _period_window(cfg: ModelConfig, j: int):
    """Static window for position j inside a pattern period."""
    a = cfg.attn
    return None if j == a.local_global_period - 1 else int(a.window)


def lm_hidden(params, tokens, cfg: ModelConfig, ex, prefix_embeds=None):
    """Full-sequence forward -> (hidden (B,S,D), aux_loss, stats): the MoE
    layers' dispatch counts summed over the layers."""
    x = _embed(params, tokens, cfg, ex, prefix_embeds)
    s = x.shape[1]
    aux = 0.0
    if cfg.first_k_dense:
        window = attention.layer_window(cfg.attn, True, s)
        dense = ex.wrap_remat(
            lambda x_, lp_: _layer_train(x_, lp_, window, cfg, ex)[0])
        for lp in _dense_layers(params, cfg):
            x = dense(x, lp)

    if _use_period_path(cfg, ex):
        p = cfg.attn.local_global_period
        main, rest, n_full, n_rest = _split_periods(params["layers"], p,
                                                    cfg.n_layers)

        def pbody(carry, lp_grp):
            x, aux = carry
            for j in range(p):
                lp = jax.tree.map(lambda t: t[j], lp_grp)
                x, a_, _, _ = _layer_train(x, lp, _period_window(cfg, j),
                                           cfg, ex)
                aux = aux + a_
            return (x, aux), None

        pbody = ex.wrap_remat(pbody)
        (x, aux), _ = common.layer_scan(ex, pbody, (x, aux), main)
        for j in range(n_rest):
            lp = jax.tree.map(lambda t: t[j], rest)
            x, a_, _, _ = _layer_train(x, lp, _period_window(cfg, j), cfg,
                                       ex)
            aux = aux + a_
        stats = {}
    else:
        flags = layer_flags(cfg)

        def body(carry, inp):
            x, aux = carry
            lp, flag = inp
            window = attention.layer_window(cfg.attn, flag, s) \
                if cfg.attn else None
            x, a_, _, st = _layer_train(x, lp, window, cfg, ex)
            return (x, aux + a_), st

        body = ex.wrap_remat(body)
        (x, aux), per_layer = common.layer_scan(ex, body, (x, aux),
                                                (params["layers"], flags))
        stats = obs_metrics.reduce_stacked(per_layer)
    x = common.norm(x, params["final_norm"], cfg.norm_eps, ex.backend)
    return x, aux, stats


def lm_loss(params, batch, cfg: ModelConfig, ex):
    x, aux, stats = lm_hidden(params, batch["tokens"], cfg, ex,
                              batch.get("prefix_embeds"))
    logits = _unembed(params, x, cfg)
    ce = common.cross_entropy(logits, batch["labels"],
                              logit_softcap=cfg.logit_softcap,
                              mask=batch.get("loss_mask"))
    loss = ce + (cfg.moe.aux_alpha if cfg.moe else 0.01) * aux
    return loss, {"ce": ce, "aux": aux, **stats}


def lm_prefill(params, tokens, cfg: ModelConfig, ex, prefix_embeds=None):
    """-> (last-position logits (B,V), cache dict)."""
    x = _embed(params, tokens, cfg, ex, prefix_embeds)
    s = tokens.shape[1]
    clen, rolling = _cache_len(cfg, s)

    def trim(kv):
        k, v = kv
        if rolling is not None and s > clen:
            k = k[:, :, -clen:]
            v = v[:, :, -clen:]
        return k, v

    dense_kv = []
    for lp in _dense_layers(params, cfg):
        x, _, kv, _ = _layer_train(x, lp, attention.layer_window(
            cfg.attn, True, s), cfg, ex, collect_kv=True)
        dense_kv.append(trim(kv))

    if _use_period_path(cfg, ex):
        p = cfg.attn.local_global_period
        main, rest, n_full, n_rest = _split_periods(params["layers"], p,
                                                    cfg.n_layers)

        def pbody(x, lp_grp):
            ks, vs = [], []
            for j in range(p):
                lp = jax.tree.map(lambda t: t[j], lp_grp)
                x, _, kv, _ = _layer_train(x, lp, _period_window(cfg, j),
                                           cfg, ex, collect_kv=True)
                k, v = trim(kv)
                ks.append(k)
                vs.append(v)
            return x, (jnp.stack(ks), jnp.stack(vs))

        x, (ck, cv) = common.layer_scan(ex, pbody, x, main)
        ck = ck.reshape(n_full * p, *ck.shape[2:])
        cv = cv.reshape(n_full * p, *cv.shape[2:])
        for j in range(n_rest):
            lp = jax.tree.map(lambda t: t[j], rest)
            x, _, kv, _ = _layer_train(x, lp, _period_window(cfg, j), cfg,
                                       ex, collect_kv=True)
            k, v = trim(kv)
            ck = jnp.concatenate([ck, k[None]], 0)
            cv = jnp.concatenate([cv, v[None]], 0)
    else:
        flags = layer_flags(cfg)

        def body(carry, inp):
            x, aux = carry
            lp, flag = inp
            window = attention.layer_window(cfg.attn, flag, s) \
                if cfg.attn else None
            x, a, kv, _ = _layer_train(x, lp, window, cfg, ex,
                                       collect_kv=True)
            k, v = trim(kv)
            return (x, aux + a), (k, v)

        (x, _), (ck, cv) = common.layer_scan(ex, body, (x, 0.0),
                                        (params["layers"], flags))
    if dense_kv:
        ck = jnp.concatenate([jnp.stack([k for k, _ in dense_kv]), ck])
        cv = jnp.concatenate([jnp.stack([v for _, v in dense_kv]), cv])
    x = common.norm(x, params["final_norm"], cfg.norm_eps, ex.backend)
    logits = _unembed(params, x[:, -1:], cfg)[:, 0]
    return logits, {"k": ck, "v": cv}


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    """Zeroed KV cache sized for ``seq_len`` total positions."""
    a = cfg.attn
    clen, _ = _cache_len(cfg, seq_len)
    shape = (cfg.n_layers, batch, a.n_kv_heads, clen)
    return {"k": jnp.zeros(shape + (a.head_dim,), dtype),
            "v": jnp.zeros(shape + (a.v_dim,), dtype)}


def lm_decode_step(params, cache, tokens, pos, cfg: ModelConfig, ex):
    """tokens: (B,) int32; pos: () int32.  -> (logits (B,V), new cache)."""
    x = common.shard_batch(
        params["embed"][tokens][:, None, :].astype(ex.compute_dtype), ex)
    flags = layer_flags(cfg)
    a_cfg = cfg.attn
    # rolling=None -> absolute positions; else rolling buffer of that size
    rolling = a_cfg.window if (a_cfg.window and
                               a_cfg.local_global_period == 0) else None

    def layer(x, lp, flag, ck, cv):
        h = common.norm(x, lp["ln1"], cfg.norm_eps, ex.backend)
        att, ck, cv = attention.attn_decode(
            lp["attn"], h, ck, cv, pos, a_cfg, is_global=flag,
            norm_eps=cfg.norm_eps, ex=ex, rolling_window=rolling)
        x = x + att
        h = common.norm(x, lp["ln2"], cfg.norm_eps, ex.backend)
        if "moe" in lp:
            m, _, _ = moe.moe_apply(lp["moe"], h, cfg.moe, ex)
        else:
            m = common.mlp_apply(lp["mlp"], h, cfg.gated_mlp)
        return x + m, (ck, cv)

    n_dense = cfg.first_k_dense
    ck, cv = cache["k"], cache["v"]
    dense_kv = []
    for i, lp in enumerate(_dense_layers(params, cfg)):
        x, kv = layer(x, lp, True, ck[i], cv[i])
        dense_kv.append(kv)
    if n_dense:
        ck, cv = ck[n_dense:], cv[n_dense:]

    def body(x, inp):
        return layer(x, *inp)

    x, (ck, cv) = common.layer_scan(ex, body, x,
                                    (params["layers"], flags, ck, cv))
    if dense_kv:
        ck = jnp.concatenate([jnp.stack([k for k, _ in dense_kv]), ck])
        cv = jnp.concatenate([jnp.stack([v for _, v in dense_kv]), cv])
    x = common.norm(x, params["final_norm"], cfg.norm_eps, ex.backend)
    logits = _unembed(params, x[:, 0], cfg)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits, {"k": ck, "v": cv}
