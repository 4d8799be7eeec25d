"""jit-able train / prefill / serve step builders shared by the trainer,
the server and the AOT dry-run."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.models.common import ExecConfig
from repro.obs.metrics import reduce_stacked
from repro.optim import AdamWState, adamw_init, adamw_update, \
    cosine_schedule


class TrainState(NamedTuple):
    params: dict
    opt: AdamWState


def init_train_state(cfg: ModelConfig, ex: ExecConfig, seed: int = 0
                     ) -> TrainState:
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed), ex)
    return TrainState(params=params, opt=adamw_init(params))


def make_train_step(cfg: ModelConfig, ex: ExecConfig, *, base_lr=3e-4,
                    warmup=100, total=10000, accum: int = 1):
    """Returns train_step(state, batch) -> (state, metrics).

    accum > 1 folds gradient accumulation (microbatching) into the step:
    the batch's leading dim is split into ``accum`` microbatches scanned
    sequentially — the jax-native analogue of PP-style microbatching for
    memory, and the knob ChipLight's n_micro maps to on a 2D mesh.
    """
    model = build_model(cfg)
    lr_fn = cosine_schedule(base_lr, warmup, total)

    def loss_fn(params, batch):
        cast = jax.tree.map(lambda p: p.astype(ex.compute_dtype)
                            if jnp.issubdtype(p.dtype, jnp.floating)
                            else p, params)
        return model.loss(cast, batch, ex)

    def train_step(state: TrainState, batch):
        if accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
        else:
            def micro(carry, mb):
                gsum, lsum = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, mb)
                # the loss's own stats (ce, aux aside), per micro-batch
                stats = {k: v for k, v in m.items() if k not in ("ce", "aux")}
                return (jax.tree.map(jnp.add, gsum, g), lsum + l), stats

            mbs = jax.tree.map(
                lambda x: x.reshape(accum, x.shape[0] // accum,
                                    *x.shape[1:]), batch)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (gsum, lsum), stats = jax.lax.scan(micro, (zero, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
            metrics = {"ce": loss, "aux": 0.0, **reduce_stacked(stats)}
        new_params, new_opt, om = adamw_update(state.params, grads,
                                               state.opt, lr_fn)
        metrics = dict(metrics, loss=loss, **om)
        return TrainState(params=new_params, opt=new_opt), metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, ex: ExecConfig):
    model = build_model(cfg)

    def prefill_step(params, batch):
        cast = jax.tree.map(lambda p: p.astype(ex.compute_dtype)
                            if jnp.issubdtype(p.dtype, jnp.floating)
                            else p, params)
        return model.prefill(cast, batch, ex)

    return prefill_step


def make_serve_step(cfg: ModelConfig, ex: ExecConfig):
    """One decode step: (params, cache, tokens, pos) -> (logits, cache)."""
    model = build_model(cfg)

    def serve_step(params, cache, tokens, pos):
        cast = jax.tree.map(lambda p: p.astype(ex.compute_dtype)
                            if jnp.issubdtype(p.dtype, jnp.floating)
                            else p, params)
        return model.decode_step(cast, cache, tokens, pos, ex)

    return serve_step
