"""The comparison's control and the faults it has to catch.

Each variant runs a cell the way a benchmark run does (set-up, window,
free, reference, comparison) with the timed path changed underneath:

study cells
  f32            the control: the device path's own lower precision,
                 ``_terms_core`` jitted in float32 instead of float64
  half_rows      half of every scan's rows dropped as infeasible
  altered_row    the best row's step time altered by one part in 1e6
                 where the scan produces it
train cells
  fp8            the control: the plain reference in the program's
                 place, its forward matrix products on float8_e4m3fn
                 operands (the precision below the configuration's
                 bfloat16 compute; the trainer has no such path)
  state_unchanged  the step hands back the state it was given
  half_batch     the loss and gradients taken over half of the rows
  token_altered  one input token of every batch altered inside the step

On the chip, at the cell's own size, several seeds in one process:

    python3 chipbench/tests/control.py --workload qwen3_sweep \\
        --variant f32 --seeds 1,2,3 --seconds 5

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

STUDY_VARIANTS = ("none", "f32", "half_rows", "altered_row")
TRAIN_VARIANTS = ("none", "fp8", "state_unchanged", "half_batch",
                  "token_altered")


def _study_patch(variant: str):
    import numpy as np

    from repro.dse import search
    if variant == "f32":
        import jax
        return mock.patch.object(jax, "enable_x64",
                                 lambda *a, **k: contextlib.nullcontext())
    real = search.batched_simulate

    def broken(*args, **kw):
        res = real(*args, **kw)
        if variant == "half_rows":
            res.feasible[1::2] = False
            res.step_time[1::2] = np.inf
            res.throughput[1::2] = 0.0
        elif variant == "altered_row" and res.feasible.any():
            i = int(np.argmax(res.throughput))
            res.step_time[i] *= 1.0 + 1e-6
        return res
    return mock.patch.object(search, "batched_simulate", broken)


def _train_patch(variant: str):
    from repro.launch import train as trainer
    real = trainer.make_train_step

    def make(cfg, ex, **kw):
        step = real(cfg, ex, **kw)

        def broken(state, batch):
            if variant == "state_unchanged":
                return state, step(state, batch)[1]
            if variant == "half_batch":
                half = batch["tokens"].shape[0] // 2
                batch = {k: v[:half] for k, v in batch.items()}
            elif variant == "token_altered":
                t = batch["tokens"]
                batch = dict(batch, tokens=t.at[0, 0].set(
                    (t[0, 0] + 1) % cfg.vocab))
            return step(state, batch)
        return broken
    return mock.patch.object(trainer, "make_train_step", make)


def fp8_in_place(cell) -> None:
    """The train control: the readings a run takes from the program
    (three losses, the first gradient's and the change's slice norms),
    taken from the reference on float8 operands instead."""
    from chipbench.reference import internlm2 as ref
    from chipbench.runners.train import N_CHECKED
    cell.checked_losses, cell.g1, cell.delta = ref.train(
        cell.key_w, cell.key_d, cell.z, cell.opt, cell.batch, cell.seq,
        N_CHECKED, operands="float8_e4m3fn", accum=cell.accum)


def shrink(spec) -> None:
    """CPU-test sizes: the same cell with a tiny model and grid."""
    if spec.config["runner"] == "train":
        spec.config.update(hidden_size=64, intermediate_size=128,
                           num_attention_heads=4, num_key_value_heads=2,
                           num_hidden_layers=2, vocab_size=512)
        spec.traffic.update(seq_len=256, batch=4, micro_batches=2)
        # 512 tokens a micro-batch average bfloat16's rounding less than
        # the cell's 8192: sound runs read up to loss_rel 1.3e-4,
        # grad_gap 2.9e-3, update_gap 1.9e-3 on the CPU at this size, the
        # control at least 4.3e-4, 1.9e-2, 7.0e-3 (six and three seeds)
        spec.traffic["limits"] = {"loss_rel": 5e-4, "grad_gap": 7e-3,
                                  "update_gap": 4e-3}
    else:
        spec.traffic.update(warmup=2)
        spec.traffic["C"] = {"low": 4e6, "high": 8e6}


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
            win = {"units": 0}
            fp8_in_place(cell)
        else:
            if variant == "none":
                patch = contextlib.nullcontext()
            elif spec.config["runner"] == "train":
                patch = _train_patch(variant)
            else:
                patch = _study_patch(variant)
            with patch:
                cell.setup()
                win = cell.window(seconds)
            cell.free()
        checks = cell.check()
        out.append({"workload": workload, "variant": variant, "seed": seed,
                    "correct": all(v <= lim for _, v, lim in checks),
                    "units": win["units"], "seconds": time.time() - t,
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
