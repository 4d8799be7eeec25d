"""Train cells of an MoE model on one expert-parallel chip's share:
``launch/train.py``'s step, back to back.

The run is ``chipbench.runners.train``'s (the sharded step of
``build_sharded_train`` with the traffic's micro-batches summed before
each AdamW update, ``FaultTolerantLoop`` over the benchmark's own feed,
steps 1-3 as warm-up and as the readings the reference follows) with:

* the program built from the configuration's latent-attention, expert
  and leading-dense fields (a program without them fails at once);
* the weights and the reference of ``chipbench.reference.deepseek_v2``;
* the window's dispatch counters (``moe.routed_rows``,
  ``moe.gmm_pad_rows``, ``moe.load_max_over_mean``), as the loop
  records them in ``repro.obs.metrics``, returned as its ``counters``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

from chipbench import harness
from chipbench.reference import deepseek_v2 as ref
from chipbench.runners import train
from chipbench.runners.train import N_CHECKED, Feed, compare

# what the program implements of the configuration's routing and blocks
SUPPORTED = {"scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
             "q_lora_rank": None, "routed_scaling_factor": 1,
             "hidden_act": "silu",
             "attention_bias": False, "seq_aux": True}

# the program's fields this runner builds on
FIELDS = {"AttnConfig": ("kv_lora_rank", "qk_nope_head_dim",
                         "qk_rope_head_dim", "v_head_dim"),
          "MoEConfig": ("n_shared", "norm_topk", "seq_aux",
                        "aux_alpha", "experts_held", "capacity_factor"),
          "ModelConfig": ("first_k_dense",)}


def program_config(cfg: Dict):
    """The trainer's ``ModelConfig`` for the configuration file."""
    from repro.configs import base
    missing = [f"{cls}.{f}" for cls, names in FIELDS.items()
               for f in names
               if f not in {x.name for x in
                            dataclasses.fields(getattr(base, cls))}]
    if missing:
        raise harness.BenchError(
            "the program has no latent attention, shared experts or "
            f"expert share (missing {', '.join(missing)})")
    odd = {k: cfg[k] for k, v in SUPPORTED.items() if cfg[k] != v}
    if odd:
        raise harness.BenchError(f"the program does not implement {odd}")
    z = ref.sizes(cfg)
    if z["experts"] != cfg["published"]["n_routed_experts"]:
        raise harness.BenchError("n_routed_experts * ep_chips is not the "
                                 "published expert count")
    return base.ModelConfig(
        name=cfg["name"], family="moe", n_layers=z["layers"], d_model=z["d"],
        d_ff=z["ff"], vocab=z["vocab"],
        attn=base.AttnConfig(
            n_heads=z["h"], n_kv_heads=z["h"],
            head_dim=z["nope"] + z["rope"], rope_theta=z["theta"],
            kv_lora_rank=z["r"], qk_nope_head_dim=z["nope"],
            qk_rope_head_dim=z["rope"], v_head_dim=z["v"]),
        moe=base.MoEConfig(
            n_experts=z["experts"], top_k=z["top_k"], d_ff_expert=z["ffe"],
            capacity_factor=None, n_shared=z["shared"],
            norm_topk=z["norm_topk"], seq_aux=True,
            aux_alpha=z["alpha"], experts_held=z["held"]),
        first_k_dense=z["dense"], norm_eps=z["eps"],
        tie_embeddings=cfg["tie_word_embeddings"], gated_mlp=True,
        source=cfg["source"])


class Cell(train.Cell):
    def __init__(self, name: str, config: Dict, traffic: Dict, seed: int,
                 devices):
        self.program = program_config(config)      # fails before any work
        super().__init__(name, config, traffic, seed, devices)
        self.z = ref.sizes(config)

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

        o = self.opt
        ex = ExecConfig(param_dtype=jnp.dtype(self.config["param_dtype"]),
                        compute_dtype=jnp.dtype(self.config["compute_dtype"]),
                        remat=self.config["remat"],
                        attn_block=int(self.config["attn_block"]))
        self.mesh = make_mesh_from_plan(tp=1, dp=1,
                                        devices=list(self.devices[:1]))
        self.ctx = jax.set_mesh(self.mesh)
        self.ctx.__enter__()
        step_fn, state_sh = build_sharded_train(self.program, ex, self.mesh,
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

    # -- the window ----------------------------------------------------
    def window(self, seconds: float) -> Dict:
        from repro.obs import metrics
        with metrics.scope() as m:
            win = super().window(seconds)
        win["counters"] = {**m.counters, **m.gauges}
        win["extra"]["moe_layers"] = self.z["layers"] - self.z["dense"]
        win["extra"]["micro_batches"] = self.accum
        return win

    # -- correctness ---------------------------------------------------
    def check(self) -> List[Tuple[str, float, float]]:
        losses, g1, delta = ref.train(self.key_w, self.key_d, self.z,
                                      self.opt, self.batch, self.seq,
                                      N_CHECKED)
        got = compare(self.checked_losses, self.g1, self.delta,
                      losses, g1, delta)
        harness.say("check losses program=" + ",".join(
            repr(x) for x in self.checked_losses) + " reference=" + ",".join(
            repr(x) for x in losses))
        lim = self.traffic["limits"]
        return [(k, float(v), float(lim[k])) for k, v in got.items()]
