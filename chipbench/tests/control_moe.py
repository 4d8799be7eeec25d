"""The comparison's control and the faults it has to catch, for train
cells of an MoE share (``runner: train_moe``).

Each variant runs a cell the way a benchmark run does (set-up, window,
free, reference, comparison) with the timed path changed underneath:

  fp8             the control: the plain reference in the program's
                  place, its forward matrix products on float8_e4m3fn
                  operands (the precision below the configuration's
                  bfloat16 compute)
  state_unchanged, half_batch, token_altered
                  as ``control.py`` has them for dense train cells
  expert_dropped  the first held expert's rows dropped: its down
                  projection zeroed in every MoE layer's dispatch, so
                  the tokens routed to it get nothing from it

On the chip, at the cell's own size, several seeds in one process:

    python3 chipbench/tests/control_moe.py --workload dsv2lite_train \\
        --variant fp8,expert_dropped --seeds 1,2,3 --seconds 5

prints one JSON line per run with every number compared and its limit.
``tiny`` sizes (the CPU tests) shrink the model and the window only.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path
from typing import Dict, List
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]

VARIANTS = ("none", "fp8", "state_unchanged", "half_batch",
            "token_altered", "expert_dropped")


def _patch(variant: str):
    from chipbench.tests import control
    if variant != "expert_dropped":
        return control._train_patch(variant)
    from repro.models import moe
    real = moe._dropless

    def dropped(params, *args, **kw):
        params = dict(params, w2=params["w2"].at[0].set(0))
        return real(params, *args, **kw)
    return mock.patch.object(moe, "_dropless", dropped)


def fp8_in_place(cell) -> None:
    """The control: the readings a run takes from the program, taken from
    the reference on float8 operands instead."""
    from chipbench.reference import deepseek_v2 as ref
    from chipbench.runners.train_moe import N_CHECKED
    cell.checked_losses, cell.g1, cell.delta = ref.train(
        cell.key_w, cell.key_d, cell.z, cell.opt, cell.batch, cell.seq,
        N_CHECKED, operands="float8_e4m3fn")


def shrink(spec) -> None:
    """CPU-test sizes: the same cell with a tiny model."""
    spec.config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=4, ep_chips=2, num_experts_per_tok=3,
        num_hidden_layers=3, vocab_size=512)
    spec.config["published"] = dict(spec.config["published"],
                                    n_routed_experts=8)
    spec.traffic.update(seq_len=128, batch=4, micro_batches=2)
    # 256 tokens a micro-batch: sound runs read up to loss_rel 2.8e-4,
    # grad_gap 9.4e-3, update_gap 3.8e-3 on the CPU at this size, the
    # control at least 1.7e-3, 2.9e-2, 1.3e-2 (six and three seeds)
    spec.traffic["limits"] = {"loss_rel": 6e-4, "grad_gap": 1.6e-2,
                              "update_gap": 7e-3}


def run(workload: str, variant: str, seeds: List[int], seconds: float,
        tiny: bool = False, devices=None) -> List[Dict]:
    from chipbench import harness
    out = []
    for seed in seeds:
        spec = harness.find_cell(workload)
        if tiny:
            shrink(spec)
        runner = harness.load_runner(spec.config)
        cell = runner.Cell(spec.name, spec.config, spec.traffic, seed,
                           devices)
        t = time.time()
        if variant == "fp8":
            win = {"units": 0, "counters": {}}
            fp8_in_place(cell)
        else:
            patch = contextlib.nullcontext() if variant == "none" \
                else _patch(variant)
            with patch:
                cell.setup()
                win = cell.window(seconds)
            cell.free()
        checks = cell.check()
        out.append({"workload": workload, "variant": variant, "seed": seed,
                    "correct": all(v <= lim for _, v, lim in checks),
                    "units": win["units"], "counters": win["counters"],
                    "seconds": time.time() - t,
                    "checks": {n: {"value": v, "limit": lim}
                               for n, v, lim in checks}})
    return out


def main(argv=None) -> int:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from chipbench import harness
    harness.use_cache_dir()
    spec = harness.find_cell(args.workload)
    devices = harness.chips(int(spec.workload["chips"]))
    for variant in args.variant.split(","):
        for row in run(args.workload, variant,
                       [int(s) for s in args.seeds.split(",")],
                       args.seconds, devices=devices):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
