"""Sharding rules: pytree path -> PartitionSpec for every arch/step.

Scheme (MaxText-style 2.5D):
  * ``model`` axis — tensor parallelism: attention heads / FFN width /
    vocab / expert dim (EP when the expert count divides the axis).
  * ``data`` (+ ``pod``) axes — FSDP: batch for activations, the
    non-TP dim of every weight (ZeRO-3; XLA inserts the all-gathers).

GSPMD tolerates non-divisible dims (it pads), so the rules only pick WHICH
dims shard; uneven vocab (e.g. 51865) is fine.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def _axes(mesh):
    fsdp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    fsdp = fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None)
    return fsdp, ("model" if "model" in mesh.axis_names else None)


def _ep_on_model(cfg: ModelConfig, mesh) -> bool:
    if cfg.moe is None:
        return False
    msize = mesh.shape.get("model", 1)
    return cfg.moe.n_experts % msize == 0


def _sanitize(spec: P, shape, mesh) -> P:
    """Null out spec entries whose dim is not divisible by the axis size
    (jit in_shardings require exact divisibility, unlike constraints)."""
    out = []
    for d, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(entry if shape[d] % size == 0 else None)
    # pad to rank
    out += [None] * (len(shape) - len(out))
    return P(*out)


def param_specs(cfg: ModelConfig, params_shape: Dict[str, Any], mesh):
    """PartitionSpec tree matching the params pytree (by leaf path)."""
    fsdp, tp = _axes(mesh)
    ep_model = _ep_on_model(cfg, mesh)

    def rule(path, leaf):
        return _sanitize(_rule(path, leaf), leaf.shape, mesh)

    def _rule(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1]
        stacked = "layers" in names or "enc_layers" in names \
            or "dec_layers" in names or "dense_layers" in names
        pre = (None,) if stacked else ()
        nd = len(leaf.shape)

        if name in ("embed", "lm_head"):
            # (V, D) / (D, V): shard the big vocab dim by model, other by fsdp
            big = int(np.argmax(leaf.shape))
            spec = [None, None]
            spec[big] = tp
            spec[1 - big] = fsdp
            return P(*spec)
        if name == "pos_embed":
            return P()
        if name in ("wq", "wk", "wv", "in_proj", "wkv_b"):
            return P(*pre, fsdp, tp)
        if name in ("wo", "out_proj"):
            return P(*pre, tp, fsdp)
        if name in ("w1", "w3"):
            if nd - len(pre) == 3:      # MoE experts (E, D, F)
                if ep_model:
                    return P(*pre, tp, fsdp, None)
                return P(*pre, None, fsdp, tp)
            return P(*pre, fsdp, tp)
        if name == "w2":
            if nd - len(pre) == 3:      # (E, F, D)
                if ep_model:
                    return P(*pre, tp, None, fsdp)
                return P(*pre, None, tp, fsdp)
            return P(*pre, tp, fsdp)
        if name == "router":
            return P(*pre, fsdp, None)
        if name == "conv_w":
            return P(*pre, None, tp)
        if name == "conv_b":
            return P(*pre, tp)
        # norms, biases, per-head scalars: replicate
        return P()

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, kind=None):
    """PartitionSpecs for a train/prefill batch dict."""
    fsdp, _ = _axes(mesh)
    kind = kind or shape.kind

    def spec_for(key):
        if key in ("tokens", "labels", "loss_mask"):
            return P(fsdp, None) if kind != "decode" else P(fsdp)
        if key in ("prefix_embeds", "encoder_embeds"):
            return P(fsdp, None, None)
        if key == "pos":
            return P()
        raise KeyError(key)

    return spec_for


def cache_specs(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """PartitionSpec tree for a decode cache pytree.

    decode_32k (B=128): batch over fsdp, kv-heads over model.
    long_500k (B=1): the KV-cache SEQUENCE dim shards over the fsdp axes
    (flash-decode style distributed KV) and heads over model.
    """
    fsdp, tp = _axes(mesh)
    fsdp_size = 1
    for a in (fsdp if isinstance(fsdp, tuple) else (fsdp,)):
        if a:
            fsdp_size *= mesh.shape[a]
    tp_size = mesh.shape.get("model", 1)
    batch_sharded = shape.global_batch % fsdp_size == 0 \
        and shape.global_batch >= fsdp_size

    def _tp_if(dim_size):
        # jit in_shardings require divisibility (unlike constraints)
        return tp if (tp and dim_size % tp_size == 0) else None

    def _fsdp_if(dim_size):
        return fsdp if dim_size % fsdp_size == 0 else None

    def rule(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1]
        nd = len(leaf.shape)
        if name in ("k", "v", "xk", "xv"):
            # (L|napps, B, Hkv, S, hd)
            if batch_sharded:
                return P(None, _fsdp_if(leaf.shape[1]),
                         _tp_if(leaf.shape[2]), None, None)
            return P(None, None, _tp_if(leaf.shape[2]),
                     _fsdp_if(leaf.shape[3]), None)
        if name == "conv":              # (L, B, W, C)
            return P(None, _fsdp_if(leaf.shape[1]) if batch_sharded
                     else None, None, _tp_if(leaf.shape[3]))
        if name == "ssm":               # (L, B, H, P, N)
            return P(None, _fsdp_if(leaf.shape[1]) if batch_sharded
                     else None, _tp_if(leaf.shape[2]), None, None)
        return P(*([None] * nd))

    return rule


def named_sharding_tree(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
