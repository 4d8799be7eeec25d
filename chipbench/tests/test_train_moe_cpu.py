"""The MoE share's train cell on the CPU at a tiny size, through the
harness's own run (set-up, window, free, reference, comparison), the
same run with the timed path broken underneath (each fault and the
control must come out not correct), and its per-layer readers on a
hand-made ``harness.Run``."""
import dataclasses
import json
from unittest import mock

import jax
import pytest

from chipbench import harness
from chipbench.reference import deepseek_v2 as ref
from chipbench.tests import control_moe

CELL = "dsv2lite_train"


def test_run_cell_prints_a_correct_result(capsys):
    spec = harness.find_cell(CELL)
    control_moe.shrink(spec)
    rc = harness.run_cell(spec, 2 ** 31 + 5, 1.0, False, jax.devices()[:1],
                          t0=0.0)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("variant", ["fp8", "half_batch", "token_altered",
                                     "expert_dropped"])
def test_broken_path_is_not_correct(variant):
    row, = control_moe.run(CELL, variant, [2 ** 32 + 11], 1.0, tiny=True,
                           devices=jax.devices()[:1])
    assert row["correct"] is False, row["checks"]


def test_window_reports_the_dispatch_counters():
    row, = control_moe.run(CELL, "none", [2 ** 33 + 1], 1.0, tiny=True,
                           devices=jax.devices()[:1])
    c = row["counters"]
    assert c["moe.routed_rows"] > 0 and c["moe.gmm_pad_rows"] >= 0
    assert c["moe.load_max_over_mean"] >= 1.0


def test_a_program_without_the_fields_fails_at_once():
    from repro.configs import base

    @dataclasses.dataclass(frozen=True)
    class OldAttn:                       # a parent's AttnConfig
        n_heads: int
        n_kv_heads: int
        head_dim: int

    spec = harness.find_cell(CELL)
    with mock.patch.object(base, "AttnConfig", OldAttn):
        with pytest.raises(harness.BenchError, match="kv_lora_rank"):
            harness.load_runner(spec.config).Cell(
                spec.name, spec.config, spec.traffic, 1, None)


# ---------------------------------------------------------------------------
# Readers
# ---------------------------------------------------------------------------
Z = ref.sizes(harness.find_cell(CELL).config)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
EXTRA = {"tokens": 65536 * 2, "batch": 4, "seq": 4096, "sizes": Z,
         "itemsize": 2, "moe_layers": 4, "micro_batches": 4}


def _run(counters=None, device=None, extra=EXTRA, window_s=10.0, units=2):
    return harness.Run(cell=CELL, config={}, traffic={}, window_s=window_s,
                       units=units, counters=dict(counters or {}),
                       device=device, peaks=PEAKS, extra=dict(extra))


def _device(kernel, count, seconds):
    return {"ops": {f"{kernel}.3": [count, seconds, True],
                    "fusion.1": [9, 1.0, False]}}


def test_gmm_pad_share():
    read = harness.load_reader("gmm_pad_share")
    c = {"moe.routed_rows": 300.0, "moe.gmm_pad_rows": 100.0}
    assert read(_run(c)) == pytest.approx(25.0)
    assert read(_run({"moe.routed_rows": 512.0})) == 0.0
    assert read(_run()) is None              # a program without counters


def test_moe_train_mfu():
    read = harness.load_reader("moe_train_mfu")
    rows = 2 * 4 * 4 * 12288.0               # steps x layers x micro-batches
    got = read(_run({"moe.routed_rows": rows}))
    want = 100.0 * ref.train_flops(Z, 4096, EXTRA["tokens"], rows) \
        / 10.0 / 197e12
    assert got == pytest.approx(want)
    # 1.86 GFLOP a token at these sizes, as the cell's deployment
    assert ref.train_flops(Z, 4096, 1, 6 * 4 / 8) == pytest.approx(
        1.86e9, rel=0.02)
    assert read(_run()) is None


def test_moe_gmm_roofline():
    read = harness.load_reader("moe_gmm_roofline")
    c = {"moe.routed_rows": 2 * 16 * 12288.0, "moe.gmm_pad_rows": 0.0}
    # forward and recompute: 2 passes x 3 calls x 4 layers x 4 x 2 steps
    work = ref.gmm_fwd(Z, 2 * c["moe.routed_rows"], 192, 2)
    least = work["flops"] / 197e12
    assert work["flops"] / work["bytes"] > 197e12 / 819e9  # compute-bound
    got = read(_run(c, _device("moe_gmm", 192, 2 * least)))
    assert got == pytest.approx(50.0)
    assert read(_run(c, _device("ragged-dot-none", 192, 1.0))) is None
    assert read(_run({}, _device("moe_gmm", 192, 1.0))) is None


def test_mla_attention_fwd_roofline():
    read = harness.load_reader("mla_attention_fwd_roofline")
    work = ref.mla_attention_fwd(4, Z, 4096, 2)
    assert work["flops"] == 4 * 16 * 4096 * 4097 * (192 + 128)
    least = work["flops"] / 197e12
    got = read(_run(device=_device("flash_attention_fwd", 40, 40 * least
                                   * 4)))
    assert got == pytest.approx(25.0)
    assert read(_run(device=None)) is None
    # a dense train cell's run (no MoE facts) is not read
    dense = {k: v for k, v in EXTRA.items() if k != "moe_layers"}
    assert read(_run(extra=dense, device=_device(
        "flash_attention_fwd", 1, 1.0))) is None
