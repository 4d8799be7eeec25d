"""Train cells: ``launch/train.py``'s step, back to back.

Set-up builds the one object the window drives: the sharded train step
of ``build_sharded_train`` (the gradients of the traffic's
``micro_batches`` summed before each AdamW update), its state (weights
made on the device in one jitted call from the seed, AdamW moments at
zero) and a ``FaultTolerantLoop`` over the benchmark's own token feed.
It then drives that loop through the first three steps, which compile
every program the window uses and are the steps the reference follows:

* the loss of each of the three steps;
* the first gradient as the optimizer got it (clipped), read back from
  the first moment after one step (m = (1 - b1) g);
* the parameters' change over the three steps, read before step 4
  takes them.

Norms are taken per slice: each matrix, and each layer's slice of the
stacked layer weights.  Once the window has closed and the program's
state is freed, ``chipbench.reference.internlm2`` trains the same three
steps from the same weights and feed in float32 at ``highest``
precision.
"""
from __future__ import annotations

import gc
import math
from typing import Dict, List, Tuple

from chipbench import harness
from chipbench.reference import internlm2 as ref

N_CHECKED = 3          # steps the reference follows


class Feed:
    """The loop's data pipeline: ``batch_at(step)`` is a pure function of
    (seed, step), made on the device."""

    def __init__(self, key, batch: int, seq: int, vocab: int):
        import jax
        from repro.data.pipeline import PipelineState
        self.key = key
        self.state = PipelineState(seed=0, step=0)
        self._make = jax.jit(lambda k, s: ref.tokens(k, s, batch, seq,
                                                     vocab))

    def batch_at(self, step: int) -> Dict:
        return self._make(self.key, step)

    def checkpoint(self) -> Dict:
        return {"seed": self.state.seed, "step": self.state.step}


def program_config(cfg: Dict):
    """The trainer's ``ModelConfig`` for the configuration file."""
    from repro.configs.base import AttnConfig, ModelConfig
    z = ref.sizes(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=z["layers"],
        d_model=z["d"], d_ff=z["ff"], vocab=z["vocab"],
        attn=AttnConfig(n_heads=z["h"], n_kv_heads=z["kv"], head_dim=z["hd"],
                        rope_theta=z["theta"]),
        norm_eps=z["eps"], tie_embeddings=cfg["tie_word_embeddings"],
        gated_mlp=True, source=cfg["source"])


class Cell:
    def __init__(self, name: str, config: Dict, traffic: Dict, seed: int,
                 devices):
        import jax
        import jax.numpy as jnp
        self.name, self.config, self.traffic = name, config, traffic
        self.devices = devices
        self.z = ref.sizes(config)
        self.opt = config["optimizer"]
        self.itemsize = jnp.dtype(config["compute_dtype"]).itemsize
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq_len"])
        self.accum = int(traffic["micro_batches"])
        self.key_w = jax.random.PRNGKey(harness.key31(seed, 10))
        self.key_d = jax.random.PRNGKey(harness.key31(seed, 11))
        self.losses: List[float] = []

    # -- set-up --------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh_from_plan
        from repro.launch.steps import TrainState
        from repro.launch.train import build_sharded_train
        from repro.models.common import ExecConfig
        from repro.optim import adamw_init
        from repro.runtime import FaultTolerantLoop

        cfg = program_config(self.config)
        o = self.opt
        ex = ExecConfig(param_dtype=jnp.dtype(self.config["param_dtype"]),
                        compute_dtype=jnp.dtype(self.config["compute_dtype"]),
                        remat=self.config["remat"],
                        attn_block=int(self.config["attn_block"]))
        self.mesh = make_mesh_from_plan(tp=1, dp=1,
                                        devices=list(self.devices[:1]))
        self.ctx = jax.set_mesh(self.mesh)
        self.ctx.__enter__()
        step_fn, state_sh = build_sharded_train(cfg, ex, self.mesh,
                                                accum=self.accum,
                                                base_lr=o["lr"])
        z = self.z
        self.init = jax.jit(lambda k: ref.init_params(k, z),
                            out_shardings=state_sh.params)
        def make(k):
            p = ref.init_params(k, z)
            return TrainState(params=p, opt=adamw_init(p))

        make = jax.jit(make, out_shardings=state_sh)
        self.state = make(self.key_w)
        feed = Feed(self.key_d, self.batch, self.seq, z["vocab"])
        ckpt = CheckpointManager(harness.HERE / "out" / "ckpt")
        self.loop = FaultTolerantLoop(step_fn, ckpt, feed,
                                      checkpoint_every=1 << 40)
        self.step = 0

        # steps 1..3 through the window's own call: warm-up and readings
        self.unit()
        b1 = o["b1"]
        self.g1 = ref.slice_norms(jax.tree.map(lambda m: m / (1.0 - b1),
                                           self.state.opt.m))
        while self.step < N_CHECKED:
            self.unit()
        p0 = self.init(self.key_w)
        self.delta = ref.slice_norms(jax.tree.map(jnp.subtract,
                                              self.state.params, p0))
        del p0
        self.checked_losses = list(self.losses[:N_CHECKED])

    def unit(self) -> bool:
        import jax
        got: List[float] = []
        with jax.profiler.TraceAnnotation("chipbench.train_step"):
            self.state, self.step = self.loop.run(
                self.state, self.step + 1, start_step=self.step,
                on_metrics=lambda s, m, dt: got.append(float(m["loss"])))
            jax.block_until_ready(self.state)
        self.losses += got
        return bool(got) and math.isfinite(got[0])

    # -- the window ----------------------------------------------------
    def window(self, seconds: float) -> Dict:
        window_s, done, failed = harness.window_loop(self.unit, seconds)
        n = done + failed
        tokens = done * self.batch * self.seq      # of completed steps
        return {"window_s": window_s, "units": n, "attempted": n,
                "failed": failed, "counters": {},
                "e2e": {"train_tokens_per_s": tokens / window_s},
                # ``batch``: the rows of one micro-batch, one kernel call
                "extra": {"tokens": tokens,
                          "batch": self.batch // self.accum,
                          "seq": self.seq, "sizes": dict(self.z),
                          "itemsize": self.itemsize}}

    def free(self) -> None:
        import jax
        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = self.loop = None
        self.ctx.__exit__(None, None, None)
        gc.collect()

    # -- correctness ---------------------------------------------------
    def check(self) -> List[Tuple[str, float, float]]:
        losses, g1, delta = ref.train(self.key_w, self.key_d, self.z,
                                      self.opt, self.batch, self.seq,
                                      N_CHECKED, accum=self.accum)
        got = compare(self.checked_losses, self.g1, self.delta,
                      losses, g1, delta)
        harness.say("check losses program=" + ",".join(
            repr(x) for x in self.checked_losses) + " reference=" + ",".join(
            repr(x) for x in losses))
        lim = self.traffic["limits"]
        return [(k, float(v), float(lim[k])) for k, v in got.items()]


def _median(xs) -> float:
    xs = sorted(xs)
    n = len(xs)
    return 0.5 * (xs[(n - 1) // 2] + xs[n // 2])


def gaps(got: Dict[str, float], want: Dict[str, float], keep=None) -> float:
    """Worst slice: |norm(program) - norm(reference)| over the larger of
    the reference slice's norm and the median slice's."""
    names = [k for k in want if keep is None or k in keep]
    med = _median([want[k] for k in names])
    return max(abs(got.get(k, math.inf) - want[k]) / max(want[k], med)
               for k in names)


def compare(loss_p, g1_p, delta_p, loss_r, g1_r, delta_r) -> Dict:
    """loss_rel     largest relative gap of the three losses;
    grad_gap     worst slice of the first clipped gradient;
    update_gap   worst slice of the change over three steps, over the
                 slices whose reference gradient is at least a
                 thousandth of the median slice's (the rest move under
                 Adam by round-off alone)."""
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(loss_p, loss_r)) \
        if len(loss_p) == len(loss_r) else math.inf
    med = _median(list(g1_r.values()))
    moving = {k for k, v in g1_r.items() if v >= 1e-3 * med}
    return {"loss_rel": loss_rel, "grad_gap": gaps(g1_p, g1_r),
            "update_gap": gaps(delta_p, delta_r, moving)}
