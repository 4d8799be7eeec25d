"""Each runner end to end on the CPU at a tiny size, through the
harness's own run (set-up, window, free, reference, comparison), and the
same run with the timed path broken underneath: every fault a cell can
have, and the control, must come out not correct.  The cells are read
from BENCHMARK.json as it stands."""
import json

import jax
import pytest

from chipbench import harness
from chipbench.tests import control

STUDY = "qwen3_sweep"
TRAIN = "internlm2_train"


@pytest.mark.parametrize("cell", [STUDY, TRAIN])
def test_run_cell_prints_a_correct_result(cell, capsys):
    spec = harness.find_cell(cell)
    control.shrink(spec)
    rc = harness.run_cell(spec, 2 ** 31 + 3, 1.0, False, jax.devices()[:1],
                          t0=0.0)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in spec.end_to_end}
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("workload,variant", [
    (STUDY, "f32"), (STUDY, "half_rows"), (STUDY, "altered_row"),
    (TRAIN, "fp8"), (TRAIN, "state_unchanged"), (TRAIN, "half_batch"),
    (TRAIN, "token_altered"),
])
def test_broken_path_is_not_correct(workload, variant):
    rows = control.run(workload, variant, [2 ** 32 + 11], 1.0, tiny=True,
                       devices=jax.devices()[:1])
    assert rows[0]["correct"] is False, rows[0]["checks"]
