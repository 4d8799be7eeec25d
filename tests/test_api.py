"""Tests for the unified repro.api Scenario/Study layer + repro.cli.

Contracts under test: Scenario dict/JSON round-trips exactly (incl. hw
and workload overrides, and every preset shipped under ``scenarios/``);
registry lookups fail with clear errors; ``Study.run()`` reproduces the
engine-level ``sweep_design_space`` + ``refine_top_points`` best point
exactly; the ``repro.dse.run`` shim emits DeprecationWarning while
returning identical results; the CLI rejects malformed comma lists and
exits non-zero when every sweep cell is infeasible.
"""
import json
from pathlib import Path

import pytest

from repro import cli
from repro.api import (DRIVERS, OBJECTIVES, DesignRecord, Registry,
                       Scenario, Study, StudyResult)

REPO = Path(__file__).resolve().parents[1]

TINY = dict(model="tinyllama_1_1b", total_tflops=1e6, seq_len=4096,
            global_batch=256, dies_per_mcm=(16,), m=(2, 6),
            cpo_ratio=(0.3, 0.9), refine_top=2, keep_top=8)


# ---------------------------------------------------------------------------
# Scenario round-trips + validation
# ---------------------------------------------------------------------------
def test_scenario_roundtrip_all_presets():
    presets = sorted((REPO / "scenarios").glob("*.json"))
    assert len(presets) >= 6
    for path in presets:
        sc = Scenario.load(path)
        assert Scenario.from_dict(sc.to_dict()) == sc, path.name
        assert Scenario.from_json(sc.to_json()) == sc, path.name
        assert sc.scenario_hash() == \
            Scenario.from_dict(sc.to_dict()).scenario_hash()


def test_scenario_roundtrip_hw_and_workload_overrides():
    sc = Scenario(model="qwen3-moe-235b-a22b",      # alias canonicalizes
                  total_tflops=2e6, seq_len=8192, global_batch=128,
                  workload={"bytes_grad": 2, "bytes_act": 4},
                  hw={"ocs_reuse_mode": "paper", "mfu_ceiling": 0.5,
                      "ib_bw": 1e11},
                  driver="prf", driver_kw={"budget": 64, "kappa": 2.0},
                  objectives=("throughput", "step_time"))
    assert sc.model == "qwen3_moe_235b_a22b"
    rt = Scenario.from_dict(json.loads(sc.to_json()))
    assert rt == sc
    hw = rt.build_hw()
    assert hw.ocs_reuse_mode == "paper" and hw.mfu_ceiling == 0.5
    w = rt.build_workload()
    assert w.bytes_grad == 2 and w.bytes_act == 4 and w.seq_len == 8192


@pytest.mark.parametrize("kw,msg", [
    (dict(m=(2, 2)), "duplicate"),
    (dict(dies_per_mcm=()), "empty"),
    (dict(fabrics=("oi", "pcie")), "unknown fabrics"),
    (dict(cpo_ratio=(0.0,)), "cpo_ratio"),
    (dict(hw={"warp_speed": 9}), "unknown hw overrides"),
    (dict(workload={"seq": 1}), "unknown workload overrides"),
    (dict(total_tflops=-1.0), "total_tflops"),
    (dict(backend="torch"), "backend"),
])
def test_scenario_validation_errors(kw, msg):
    base = dict(model="tinyllama_1_1b", total_tflops=1e6)
    with pytest.raises(ValueError, match=msg):
        Scenario(**{**base, **kw})


def test_scenario_from_dict_rejects_unknown_keys_and_schema():
    d = Scenario(model="tinyllama_1_1b", total_tflops=1e6).to_dict()
    with pytest.raises(ValueError, match="unknown scenario keys"):
        Scenario.from_dict({**d, "budget": 3})
    with pytest.raises(ValueError, match="schema"):
        Scenario.from_dict({**d, "schema": 99})


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------
def test_registry_lookup_errors_name_alternatives():
    with pytest.raises(KeyError, match="exhaustive"):
        DRIVERS.get("gradient-descent")
    with pytest.raises(KeyError, match="throughput"):
        OBJECTIVES.get("carbon")
    with pytest.raises(KeyError, match="unknown driver 'nope'"):
        Scenario(model="tinyllama_1_1b", total_tflops=1e6, driver="nope")
    with pytest.raises(KeyError, match="objective"):
        Scenario(model="tinyllama_1_1b", total_tflops=1e6,
                 objectives=("throughput", "carbon"))


def test_registry_rejects_duplicate_registration():
    reg = Registry("widget")
    reg.register("a")(1)
    with pytest.raises(ValueError, match="already registered"):
        reg.register("a")
    assert reg.names() == ["a"] and "a" in reg


# ---------------------------------------------------------------------------
# Study.run() parity with the engine-level flow
# ---------------------------------------------------------------------------
def test_study_reproduces_sweep_plus_refine_exactly():
    from repro.dse.search import refine_top_points, sweep_design_space
    sc = Scenario(model="qwen3_moe_235b_a22b", total_tflops=4e6,
                  seq_len=10240, global_batch=512, dies_per_mcm=(16,),
                  m=(4, 6), cpo_ratio=(0.6,), refine_top=4, keep_top=16)
    res = Study(sc).run()

    sweep = sweep_design_space(sc.design_space(), driver="exhaustive",
                               backend="numpy", seed=0)
    pts = refine_top_points(sweep, top_k=4)
    assert pts and res.best is not None
    best = res.best_record
    assert best.source == "refined"
    assert best.metrics["throughput"] == pts[0].throughput
    assert best.metrics["cost"] == pts[0].cost
    assert res.best_point.strategy == pts[0].strategy
    # the top batched record mirrors the sweep's own best row
    top = res.records[0]
    d = sweep.describe(sweep.best)
    assert top.metrics["throughput"] == d["throughput_tok_s"]
    assert top.metrics["cost"] == d["cost_usd"]
    assert top.strategy == d["strategy"]


def test_scalar_drivers_deterministic_from_scenario_seed():
    sc = Scenario(model="tinyllama_1_1b", total_tflops=3e4, seq_len=4096,
                  global_batch=256, dies_per_mcm=(4,), m=(6,),
                  cpo_ratio=(0.6,), driver="chiplight-outer",
                  driver_kw={"method": "scalar", "outer_iters": 2,
                             "inner_budget": 8},
                  keep_top=8, seed=7)
    r1, r2 = Study(sc).run(), Study(sc).run()
    assert len(r1.traces) == 3          # outer_iters + 1 (final proposal)
    assert r1.traces == r2.traces
    assert [r.to_dict() for r in r1.records] == \
        [r.to_dict() for r in r2.records]
    assert all(r.source == "scalar" for r in r1.records)
    assert r1.best == 0
    assert r1.records[0].throughput == \
        max(r.throughput for r in r1.records)


def test_chiplight_outer_trace_includes_final_proposed_mcm():
    from repro.core.optimizer import chiplight_optimize
    from repro.core.workload import Workload
    from repro.configs import get_config
    w = Workload(model=get_config("tinyllama_1_1b"), seq_len=4096,
                 global_batch=256)
    res = chiplight_optimize(w, 3e4, dies_per_mcm=4, m0=6, outer_iters=2,
                             inner_budget=8, seed=1)
    assert len(res.outer_trace) == 3
    # the last entry is an EVALUATION of the final planner proposal
    assert res.outer_trace[-1]["best_thpt"] >= 0.0
    assert "mcm" in res.outer_trace[-1]
    res2 = chiplight_optimize(w, 3e4, dies_per_mcm=4, m0=6, outer_iters=2,
                              inner_budget=8, seed=1)
    assert res.outer_trace == res2.outer_trace


# ---------------------------------------------------------------------------
# StudyResult artifact round-trip
# ---------------------------------------------------------------------------
def test_studyresult_roundtrip(tmp_path):
    res = Study(Scenario(**TINY)).run()
    path = res.save(tmp_path / "study.json")
    loaded = StudyResult.load(path)
    assert loaded.scenario == res.scenario
    assert loaded.best == res.best and loaded.pareto == res.pareto
    assert [r.to_dict() for r in loaded.records] == \
        [r.to_dict() for r in res.records]
    assert loaded.provenance["scenario_hash"] == \
        res.scenario.scenario_hash()
    assert json.loads(path.read_text())["schema"] == 1
    with pytest.raises(ValueError, match="schema"):
        StudyResult.from_dict({**res.to_dict(), "schema": 42})


def test_record_sources_and_pareto():
    res = Study(Scenario(**TINY)).run()
    sources = {r.source for r in res.records}
    assert sources == {"batched", "refined"}
    refined = [r for r in res.records if r.source == "refined"]
    assert len(refined) == 2 and len(res.points) == 2
    assert refined[0].topo is not None          # OI topology captured
    assert refined[0].metrics["cost"] > 0       # OCS-inclusive
    par = res.pareto_indices(("throughput", "cost"))
    assert all(res.records[i].feasible for i in par)
    # no record outside the 3-objective set dominates a member on it
    assert set(res.pareto) == set(res.pareto_indices())


def test_records_from_sweep_columnar_matches_single_row_adapter():
    from repro.api import record_from_sweep, records_from_sweep
    from repro.dse.search import sweep_design_space
    sc = Scenario(**TINY)
    sweep = sweep_design_space(sc.design_space())
    idx = list(range(0, len(sweep), max(len(sweep) // 50, 1)))
    recs = records_from_sweep(sweep, idx)
    assert [r.to_dict() for r in recs] == \
        [record_from_sweep(sweep, i).to_dict() for i in idx]
    assert records_from_sweep(sweep, []) == []


def test_sweep_keep_indices_unique_and_pareto_complete():
    import numpy as np
    from repro.api.study import _sweep_keep_indices
    from repro.dse.search import sweep_design_space
    sc = Scenario(**{**TINY, "keep_top": 4})
    sweep = sweep_design_space(sc.design_space())
    kept = _sweep_keep_indices(sweep, sc)
    assert len(set(int(i) for i in kept)) == len(kept)   # no duplicates
    pareto = set(int(i) for i in sweep.pareto_indices())
    assert pareto <= set(int(i) for i in kept)           # front retained
    order = np.argsort(-sweep.metrics["throughput"][kept[:4]])
    assert np.array_equal(order, np.arange(4))           # top-N first


def test_sweep_keep_indices_match_bruteforce_front():
    import numpy as np
    from repro.api.study import _sweep_keep_indices
    from repro.dse.search import sweep_design_space
    sc = Scenario(**{**TINY, "keep_top": 4})
    sweep = sweep_design_space(sc.design_space())
    kept = _sweep_keep_indices(sweep, sc)
    met = sweep.metrics
    feas = np.nonzero(met["feasible"])[0]
    top = feas[np.argsort(-met["throughput"][feas], kind="stable")][:4]
    M = np.stack([met["throughput"], -met["cost"], -met["power"]], 1)[feas]
    front = [int(i) for j, i in enumerate(feas)
             if not ((M >= M[j]).all(1) & (M > M[j]).any(1)).any()]
    assert len(front) > 4
    want = list(top) + [i for i in front if i not in set(top.tolist())]
    assert kept.tolist() == want


def test_record_from_search_adapter_matches_cell():
    from repro.api import record_from_search
    from repro.dse.search import BatchedEvaluator, search_exhaustive
    from repro.core.mcm import mcm_from_compute
    sc = Scenario(**TINY)
    w = sc.build_workload()
    mcm = mcm_from_compute(1e6, dies_per_mcm=16, m=6)
    res = search_exhaustive(BatchedEvaluator(w, mcm, "oi"))
    recs = [record_from_search(res, mcm, "oi", i) for i in range(len(res.batch))]
    assert len(recs) == res.grid_size
    i = res.best
    assert recs[i].metrics["throughput"] == res.metrics["throughput"][i]
    assert recs[i].mcm["m"] == 6 and recs[i].source == "batched"


def test_scenario_hashable_by_content():
    a, b = Scenario(**TINY), Scenario(**TINY)
    assert hash(a) == hash(b) and len({a, b}) == 1
    assert hash(a) != hash(a.replace(seed=99))


def test_single_cell_drivers_reject_multi_cell_grid():
    sc = Scenario(**{**TINY, "driver": "chiplight-outer", "m": (2, 6)})
    with pytest.raises(ValueError, match="single MCM cell"):
        Study(sc).run()
    # the scalar railx loop is single-cell too; the batched railx sweep
    # (default) accepts the full grid
    sc = Scenario(**{**TINY, "driver": "railx", "m": (2, 6),
                     "driver_kw": {"method": "scalar"}})
    with pytest.raises(ValueError, match="single MCM cell"):
        Study(sc).run()
    res = Study(Scenario(**{**TINY, "driver": "railx",
                            "m": (2, 6)})).run()
    assert res.best is not None
    assert res.provenance["engine"] == "dse.sweep[railx]+refine"


def test_batched_driver_kw_translated_and_validated(tmp_path, capsys):
    # legacy --budget under nsga2 maps to pop_size instead of crashing
    rc = cli.main(["--model", "tinyllama_1_1b", "--C", "1e5",
                   "--driver", "nsga2", "--budget", "8",
                   "--generations", "2", "--dies", "16", "--m", "6",
                   "--cpo", "0.6", "--refine-top", "0",
                   "--out", str(tmp_path / "n.json")])
    capsys.readouterr()
    assert rc == 0
    # unknown driver_kw fails with one clear line, not a TypeError
    sc = Scenario(**{**TINY, "driver": "prf",
                     "driver_kw": {"budget": 8, "warp": 1}})
    with pytest.raises(ValueError, match="does not accept driver_kw"):
        Study(sc).run()
    with pytest.raises(SystemExit) as e:
        cli.main([str(sc.save(tmp_path / "bad.json")),
                  "--out", str(tmp_path / "b.json")])
    assert e.value.code == 2
    assert "does not accept driver_kw" in capsys.readouterr().err


def test_cli_legacy_refine_flag_maps_to_top(tmp_path, capsys):
    rc = cli.main(["--model", "tinyllama_1_1b", "--C", "1e6", "--dies",
                   "16", "--m", "6", "--cpo", "0.6", "--refine",
                   "--top", "3", "--out", str(tmp_path / "r.json")])
    capsys.readouterr()
    assert rc == 0
    assert StudyResult.load(tmp_path / "r.json").scenario.refine_top == 3


def test_design_record_roundtrip_handles_inf():
    rec = DesignRecord(strategy={"TP": 1}, mcm={"m": 2}, fabric="oi",
                       metrics={"feasible": False, "step_time": float("inf"),
                                "throughput": 0.0},
                       source="batched")
    rt = DesignRecord.from_dict(json.loads(json.dumps(rec.to_dict())))
    assert rt.metrics["step_time"] == float("inf")
    assert rt.to_dict() == rec.to_dict()


# ---------------------------------------------------------------------------
# Deprecation shim + CLI
# ---------------------------------------------------------------------------
_CLI_ARGS = ["--model", "tinyllama_1_1b", "--C", "1e6", "--dies", "16",
             "--m", "2,6", "--cpo", "0.3,0.9", "--refine-top", "2",
             "--keep-top", "8"]


def test_dse_run_shim_warns_and_matches_cli(tmp_path, capsys):
    from repro.dse import run as dse_run
    rc_new = cli.main(_CLI_ARGS + ["--out", str(tmp_path / "new.json")])
    with pytest.warns(DeprecationWarning, match="repro.cli"):
        rc_old = dse_run.main(_CLI_ARGS + ["--out",
                                           str(tmp_path / "old.json")])
    capsys.readouterr()
    assert rc_new == rc_old == 0
    new = json.loads((tmp_path / "new.json").read_text())
    old = json.loads((tmp_path / "old.json").read_text())
    assert old["records"] == new["records"]
    assert old["best"] == new["best"] and old["pareto"] == new["pareto"]
    assert old["scenario"] == new["scenario"]


@pytest.mark.parametrize("bad", [
    ["--dies", "8,,16"], ["--dies", "8,8"], ["--m", ""],
    ["--cpo", "0.3,x"], ["--fabrics", "oi,oi"],
])
def test_cli_rejects_malformed_lists(bad, capsys):
    with pytest.raises(SystemExit) as e:
        cli.main(["--model", "tinyllama_1_1b", "--C", "1e6"] + bad)
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "list" in err and "Traceback" not in err


def test_cli_infeasible_sweep_exits_nonzero(tmp_path, capsys):
    # m=16 HBM stacks consume the whole beachfront: no feasible MCM cell
    rc = cli.main(["--model", "tinyllama_1_1b", "--C", "1e6",
                   "--dies", "4", "--m", "16", "--cpo", "0.9",
                   "--out", str(tmp_path / "inf.json")])
    out = capsys.readouterr().out
    assert rc == 3 and "no feasible design point" in out
    assert json.loads((tmp_path / "inf.json").read_text())["best"] is None


def test_cli_scenario_file_with_flag_overrides(tmp_path, capsys):
    sc = Scenario(**TINY)
    path = sc.save(tmp_path / "tiny.json")
    rc = cli.main([str(path), "--driver", "random", "--budget", "16",
                   "--seed", "3", "--out", str(tmp_path / "res.json")])
    capsys.readouterr()
    assert rc == 0
    res = StudyResult.load(tmp_path / "res.json")
    assert res.scenario.driver == "random"
    assert res.scenario.driver_kw["budget"] == 16
    assert res.scenario.seed == 3
    assert res.scenario.model == "tinyllama_1_1b"   # file field kept


def test_cli_quick_mode_shrinks_grid(tmp_path, capsys):
    path = Scenario(**{**TINY, "m": (2, 4, 6), "fabrics": ("oi", "ib")}
                    ).save(tmp_path / "s.json")
    rc = cli.main([str(path), "--quick",
                   "--out", str(tmp_path / "q.json")])
    capsys.readouterr()
    assert rc == 0
    res = StudyResult.load(tmp_path / "q.json")
    assert res.scenario.m == (2,) and res.scenario.fabrics == ("oi",)


# ---------------------------------------------------------------------------
# Legacy result types only ever come from adapters (acceptance criterion)
# ---------------------------------------------------------------------------
def test_no_direct_legacy_result_construction_outside_core_dse():
    import re
    legacy = re.compile(
        r"\b(DesignPoint|DSEResult|SweepResult|SearchResult)\s*\(")
    offenders = []
    for path in (*REPO.glob("examples/*.py"), *REPO.glob("benchmarks/*.py"),
                 *(REPO / "src" / "repro").rglob("*.py")):
        rel = path.relative_to(REPO).as_posix()
        if rel.startswith(("src/repro/core/", "src/repro/dse/")):
            continue
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if legacy.search(line):
                offenders.append(f"{rel}:{i}")
    assert not offenders, offenders
