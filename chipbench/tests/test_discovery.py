"""Cells, configurations, traffic mixes and metric readers are found by
the names in BENCHMARK.json, and the file keeps the contract's shape."""
import json
import re
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    spec = harness.find_cell(cell)
    assert spec.config["name"] == spec.workload["config"]
    assert spec.config["runner"] in ("study", "train")
    harness.load_runner(spec.config).Cell
    e2e = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer, "every cell reports a per-layer metric"
    for m in spec.per_layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.load_reader(metric))


def test_a_new_cell_needs_no_edit(tmp_path):
    """A cell added to BENCHMARK.json alone, over files already there, is
    found with its config, traffic and metrics."""
    bench = json.loads(json.dumps(BENCH))
    w = dict(bench["workloads"][0], name="extra_cell")
    bench["workloads"].append(w)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and bench["workloads"][0]["name"] in \
                m["workloads"]:
            m["workloads"].append("extra_cell")
    spec = harness.find_cell("extra_cell", bench)
    assert spec.traffic == harness.find_cell(CELLS[0]).traffic
    assert [m["name"] for m in spec.per_layer] == [
        m["name"] for m in harness.find_cell(CELLS[0]).per_layer]


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    cells = len(CELLS)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= cells // 2
    # a full check of 24 cells fits its 43200 s
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(BENCH)) <= 64 * 1024
