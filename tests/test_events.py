"""Tests for the event-driven timeline validator (repro.events) plus the
satellite work that rode along: vectorized traffic matrices and the
reuse-decision provenance in simulate() logs."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.mcm import mcm_from_compute
from repro.core.optimizer import enumerate_strategies
from repro.core.simulator import map_intra, simulate
from repro.core.traffic import (PARALLELISMS, Strategy, _traffic_matrix_loop,
                                traffic_matrix, traffic_volumes)
from repro.core.workload import Workload
from repro.events import compile_step, replay, replay_batch
from repro.events.dag import SCHEDULES, device_op_order

TINY = Workload(model=get_config("tinyllama_1_1b"), seq_len=4096,
                global_batch=256)
MOE = Workload(model=get_config("qwen3_moe_235b_a22b"), seq_len=10240,
               global_batch=512)
HYBRID = Workload(model=get_config("zamba2_7b"), seq_len=4096,
                  global_batch=256)

MCM_TINY = mcm_from_compute(1e6, 16, 6)
MCM_MOE = mcm_from_compute(4e6, 16, 6)
MCM_HYB = mcm_from_compute(1e6, 16, 6)

_CASES = [("tiny", TINY, MCM_TINY), ("moe", MOE, MCM_MOE),
          ("hybrid", HYBRID, MCM_HYB)]
_GRIDS = {}


def _feasible(name, w, mcm):
    if name not in _GRIDS:
        out = []
        for s in enumerate_strategies(w, mcm):
            r = simulate(w, s, mcm)
            if r.feasible:
                out.append((s, r))
        out.sort(key=lambda t: -t[1].throughput)
        _GRIDS[name] = out
    return _GRIDS[name]


# ---------------------------------------------------------------------------
# Satellite: vectorized traffic_matrix parity vs the loop reference
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(st.sampled_from([TINY, MOE]), st.integers(0, 10 ** 6),
       st.booleans())
def test_traffic_matrix_parity(w, pick, ep_fc):
    name, mcm = ("tiny", MCM_TINY) if w is TINY else ("moe", MCM_MOE)
    grid = _feasible(name, w, mcm)
    s = grid[pick % len(grid)][0]
    if s.n_devices > 2048:          # keep the O(n^2) reference cheap
        s = Strategy(tp=s.tp, dp=max(s.dp // 4, 1), pp=s.pp, cp=s.cp,
                     ep=s.ep, n_micro=s.n_micro)
    got = traffic_matrix(w, s, ep_fc=ep_fc)
    want = _traffic_matrix_loop(w, s, ep_fc=ep_fc)
    assert np.allclose(got, want, rtol=1e-12, atol=0.0)


def test_traffic_matrix_row_conservation():
    s = Strategy(tp=4, dp=4, pp=2, cp=2, ep=4, n_micro=8)
    vols = traffic_volumes(MOE, s)
    total = sum(v for p, v in vols.items() if s.degree(p) > 1)
    for ep_fc in (False, True):
        mat = traffic_matrix(MOE, s, ep_fc=ep_fc)
        assert np.allclose(mat.sum(1), total, rtol=1e-9)


# ---------------------------------------------------------------------------
# Satellite: reuse-decision provenance in simulate() logs
# ---------------------------------------------------------------------------
REUSE_S = Strategy(tp=1, dp=128, pp=2, cp=2, ep=8, n_micro=4)


def test_simulate_logs_reuse_gated():
    r = simulate(MOE, REUSE_S, MCM_MOE)
    logs = r.logs
    assert logs["reuse_cand_a"] >= 0 and logs["reuse_cand_b"] >= 0
    assert logs["reuse_gated"] == 1.0          # banked MEMS gate fired
    assert logs["reuse_active"] == 0.0
    assert logs["reuse_pair_a"] == -1.0 and logs["reuse_pair_b"] == -1.0
    assert logs["reuse_paper_mode"] == 0.0


def test_simulate_logs_reuse_paper_mode():
    hw = dataclasses.replace(MCM_MOE.hw, ocs_reuse_mode="paper")
    r = simulate(MOE, REUSE_S, MCM_MOE, hw=hw)
    logs = r.logs
    assert logs["reuse_paper_mode"] == 1.0
    assert logs["reuse_active"] == 1.0
    assert logs["reuse_gated"] == 0.0
    assert (logs["reuse_pair_a"], logs["reuse_pair_b"]) == \
           (logs["reuse_cand_a"], logs["reuse_cand_b"])
    a, b = int(logs["reuse_pair_a"]), int(logs["reuse_pair_b"])
    assert PARALLELISMS[a] != PARALLELISMS[b]


def test_simulate_logs_no_candidate():
    s, _ = _feasible("tiny", TINY, MCM_TINY)[0]
    r = simulate(TINY, s, MCM_TINY, fabric="ib")
    assert r.logs["reuse_cand_a"] == -1.0
    assert r.logs["reuse_gated"] == 0.0


# ---------------------------------------------------------------------------
# Tentpole: byte conservation (hypothesis) — dense, MoE, hybrid
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(st.sampled_from(_CASES), st.integers(0, 10 ** 6))
def test_event_byte_conservation(case, pick):
    name, w, mcm = case
    grid = _feasible(name, w, mcm)
    s = grid[pick % len(grid)][0]
    prog = compile_step(w, s, mcm, schedule="gpipe")
    r = replay(prog)
    intra, inter = map_intra(w, s, mcm)
    vols = traffic_volumes(w, s)
    for p in PARALLELISMS:
        segs = (1 if intra.get(p, 1) > 1 else 0) \
            + (1 if inter.get(p, 1) > 1 else 0)
        want = vols[p] * segs
        got = r.bytes_moved.get(p, 0.0)
        if want == 0.0:
            assert got == 0.0
        else:
            assert got == pytest.approx(want, rel=1e-6), p
            assert prog.bytes_expected[p] == pytest.approx(want, rel=1e-12)


def test_event_replay_deterministic():
    s = next(s for s, _ in _feasible("tiny", TINY, MCM_TINY) if s.pp > 1)
    a = replay(compile_step(TINY, s, MCM_TINY, schedule="1f1b"),
               record_timeline=True)
    b = replay(compile_step(TINY, s, MCM_TINY, schedule="1f1b"),
               record_timeline=True)
    assert a.step_time == b.step_time
    assert a.n_events == b.n_events
    assert a.timeline == b.timeline
    assert a.bytes_moved == b.bytes_moved


# ---------------------------------------------------------------------------
# Tentpole: fidelity vs the analytic model (gpipe / 1f1b asserted)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", _CASES, ids=[c[0] for c in _CASES])
def test_event_fidelity_top_points(case):
    name, w, mcm = case
    picks = _feasible(name, w, mcm)[:3]
    picks += [t for t in _feasible(name, w, mcm) if t[0].pp > 1][:2]
    for s, sim in picks:
        for sched in ("gpipe", "1f1b"):
            r = replay(compile_step(w, s, mcm, schedule=sched))
            assert r.analytic_step_time == pytest.approx(sim.step_time,
                                                         rel=1e-9)
            assert abs(r.err) <= 0.15, (name, s, sched, r.err)


def test_event_fidelity_with_derived_topology():
    from repro.core.optimizer import evaluate_point
    found = 0
    for s, _ in _feasible("moe", MOE, MCM_MOE)[:20]:
        pt = evaluate_point(MOE, s, MCM_MOE)
        if pt is None or pt.topo is None or not pt.topo.dims:
            continue
        r = replay(compile_step(MOE, s, MCM_MOE, topo=pt.topo,
                                schedule="gpipe"))
        assert r.analytic_step_time == pytest.approx(pt.sim.step_time,
                                                     rel=1e-9)
        assert abs(r.err) <= 0.15
        found += 1
        if found >= 3:
            break
    assert found > 0


# ---------------------------------------------------------------------------
# Tentpole: schedules — bubble ordering and memory behaviour
# ---------------------------------------------------------------------------
def _pipelined(name, w, mcm, min_nm=8):
    for s, _ in _feasible(name, w, mcm):
        if s.pp > 1 and s.n_micro >= max(min_nm, s.pp):
            return s
    pytest.skip("no pipelined strategy in grid")


def test_schedule_bubble_ordering():
    s = _pipelined("tiny", TINY, MCM_TINY)
    res = {sched: replay(compile_step(TINY, s, MCM_TINY, schedule=sched))
           for sched in SCHEDULES}
    # gpipe and (non-interleaved) 1f1b share the same bubble ratio;
    # interleaving over v chunks divides it
    assert res["1f1b"].bubble == pytest.approx(res["gpipe"].bubble,
                                               rel=0.05, abs=0.01)
    assert res["interleaved"].bubble < 0.75 * res["gpipe"].bubble
    assert res["interleaved"].step_time < res["gpipe"].step_time
    # the analytic model assumes a gpipe-style bubble
    an_bubble = simulate(TINY, s, MCM_TINY).logs["bubble"]
    assert res["gpipe"].bubble == pytest.approx(an_bubble, rel=0.05,
                                                abs=0.01)
    # 1F1B's win is activation residency, not the bubble
    assert res["1f1b"].peak_inflight <= res["gpipe"].peak_inflight
    assert res["1f1b"].peak_inflight <= s.pp
    assert res["gpipe"].peak_inflight == s.n_micro


def test_schedule_op_orders_complete():
    for sched in SCHEDULES:
        for pp, v, nm in ((1, 1, 1), (2, 1, 8), (4, 2, 8), (8, 2, 16)):
            if sched != "interleaved":
                v = 1
            for s in range(pp):
                ops = device_op_order(sched, pp, v, nm, s)
                assert len(ops) == 2 * nm * v
                assert len(set(ops)) == 2 * nm * v    # each op exactly once


# ---------------------------------------------------------------------------
# Tentpole: batch replay parity vs the scalar engine
# ---------------------------------------------------------------------------
def test_batch_replay_matches_scalar():
    progs = []
    for name, w, mcm in _CASES:
        picks = _feasible(name, w, mcm)[:2]
        picks += [t for t in _feasible(name, w, mcm) if t[0].pp > 1][:1]
        for s, _ in picks:
            for sched in ("gpipe", "1f1b"):
                progs.append(compile_step(w, s, mcm, schedule=sched))
    out = replay_batch(progs)
    for j, p in enumerate(progs):
        r = replay(p)
        assert out["step_time"][j] == pytest.approx(r.step_time, rel=0.05)
        assert out["analytic_step_time"][j] == \
            pytest.approx(r.analytic_step_time, rel=1e-12)


def test_batch_replay_interleaved_vectorized():
    """Interleaved runs through the SAME vectorized wavefront as
    gpipe/1f1b — the level-table recurrence resolves its chunk-wrap
    dependencies, so there is no scalar fallback to hide behind."""
    s = _pipelined("tiny", TINY, MCM_TINY)
    prog = compile_step(TINY, s, MCM_TINY, schedule="interleaved")
    out = replay_batch([prog] * 3)
    assert not out["scalar_fallback"].any()
    r = replay(prog)
    assert out["step_time"][0] == pytest.approx(r.step_time, rel=0.05)
    assert out["bubble"][0] == pytest.approx(r.bubble, rel=0.05, abs=0.01)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([c[0] for c in _CASES]), st.integers(0, 10 ** 6))
def test_batch_replay_interleaved_parity(name, pick):
    """Batch-vs-scalar parity for interleaved schedules across the
    feasible pipelined grid — previously vacuous (the fallback WAS the
    scalar engine), now a real recurrence-parity pin."""
    _, w, mcm = next(c for c in _CASES if c[0] == name)
    grid = [t for t in _feasible(name, w, mcm) if t[0].pp > 1]
    if not grid:
        return
    s = grid[pick % len(grid)][0]
    prog = compile_step(w, s, mcm, schedule="interleaved")
    out = replay_batch([prog])
    assert not out["scalar_fallback"].any()
    r = replay(prog)
    assert out["step_time"][0] == pytest.approx(r.step_time, rel=0.05)


# ---------------------------------------------------------------------------
# Tentpole: jax wavefront backend — parity, bucketing, auto resolution
# ---------------------------------------------------------------------------
def test_batch_replay_jax_matches_numpy():
    progs = []
    s = _pipelined("tiny", TINY, MCM_TINY)
    for sched in SCHEDULES:
        progs.append(compile_step(TINY, s, MCM_TINY, schedule=sched))
    progs += [compile_step(TINY, t[0], MCM_TINY, schedule="gpipe")
              for t in _feasible("tiny", TINY, MCM_TINY)[:3]]
    rn = replay_batch(progs, backend="numpy")
    rj = replay_batch(progs, backend="jax")
    for k in ("step_time", "makespan_body", "bubble", "dp_exposed"):
        np.testing.assert_allclose(rj[k], rn[k], rtol=1e-6, atol=0.0,
                                   err_msg=k)
    np.testing.assert_allclose(rj["err"], rn["err"], rtol=1e-6)


def test_batch_replay_jax_same_bucket_no_retrace():
    from repro.events import batch as eb
    s = _pipelined("tiny", TINY, MCM_TINY)
    progs = [compile_step(TINY, s, MCM_TINY, schedule="1f1b")] * 40
    replay_batch(progs, backend="jax")
    before = eb._JAX_TRACES["count"]
    for n in range(33, 41):           # same power-of-two bucket (64)
        replay_batch(progs[:n], backend="jax")
    assert eb._JAX_TRACES["count"] == before


def test_batch_replay_backend_resolution():
    from repro.events.batch import JAX_AUTO_MIN_RECORDS, resolve_backend
    assert resolve_backend("numpy", 10 ** 9) == "numpy"
    assert resolve_backend("jax", 1) == "jax"
    assert resolve_backend("auto", JAX_AUTO_MIN_RECORDS - 1) == "numpy"
    assert resolve_backend("auto", JAX_AUTO_MIN_RECORDS) == "jax"
    with pytest.raises(ValueError, match="backend"):
        resolve_backend("zigzag", 4)


# ---------------------------------------------------------------------------
# Wiring: Study.run(validate_top=K), Scenario fields, CLI subcommand
# ---------------------------------------------------------------------------
def _tiny_scenario(**kw):
    from repro.api import Scenario
    return Scenario(model="tinyllama_1_1b", total_tflops=1e6, seq_len=4096,
                    global_batch=256, dies_per_mcm=(16,), m=(6,),
                    cpo_ratio=(0.6,), fabrics=("oi",), refine_top=3,
                    keep_top=16, **kw)


def test_study_validate_top_stamps_records():
    from repro.api import Study
    sc = _tiny_scenario(validate_top=3, schedule="1f1b")
    res = Study(sc).run()
    stamped = [r for r in res.records
               if "validated_step_time" in r.metrics]
    assert len(stamped) == 3
    for r in stamped:
        assert r.metrics["validated_step_time"] > 0
        assert abs(r.metrics["fidelity_err"]) <= 0.15
    val = res.provenance["validate"]
    assert val["n_validated"] == 3 and val["schedule"] == "1f1b"
    assert val["backend"] == sc.backend
    assert res.timings["validate_s"] > 0
    # argument overrides the scenario field
    res2 = Study(_tiny_scenario()).run(validate_top=2)
    assert sum("validated_step_time" in r.metrics
               for r in res2.records) == 2


def test_outer_event_replay_hook():
    from repro.api import Study
    sc = _tiny_scenario(driver="chiplight-outer",
                        driver_kw={"rounds": 1, "walkers": 2,
                                   "event_replay": 2})
    res = Study(sc).run()
    assert res.provenance["n_event_replayed"] > 0
    assert res.provenance["metrics"]["counters"][
        "outer.event_replayed"] == res.provenance["n_event_replayed"]
    w = res.traces[-1]["walkers"][0]
    assert w["event_thpt"] > 0 and w["event_step_time"] > 0
    # default off: legacy trace schema, no replays
    r0 = Study(sc.replace(driver_kw={"rounds": 1, "walkers": 2})).run()
    assert "event_thpt" not in r0.traces[-1]["walkers"][0]
    assert r0.provenance["n_event_replayed"] == 0


def test_outer_event_replay_rejects_scalar():
    from repro.dse.outer import outer_search
    with pytest.raises(ValueError, match="event_replay"):
        outer_search(TINY, 1e6, method="scalar", walkers=1,
                     event_replay=2)
    with pytest.raises(ValueError, match="event_schedule"):
        outer_search(TINY, 1e6, event_replay=2, event_schedule="zigzag")


def test_study_validate_roundtrips_artifact(tmp_path):
    from repro.api import Study, StudyResult
    res = Study(_tiny_scenario(validate_top=2)).run()
    path = res.save(tmp_path / "res.json")
    loaded = StudyResult.load(path)
    assert loaded.scenario.validate_top == 2
    stamped = [r for r in loaded.records
               if "validated_step_time" in r.metrics]
    assert len(stamped) == 2


def test_scenario_rejects_bad_schedule():
    with pytest.raises(ValueError, match="schedule"):
        _tiny_scenario(schedule="zigzag")
    with pytest.raises(ValueError, match="validate_top"):
        _tiny_scenario(validate_top=-1)


def test_validate_scenario_harness():
    from repro.events.validate import validate_scenario
    block = validate_scenario(_tiny_scenario(), top=2,
                              schedules=("gpipe", "1f1b"))
    assert block["n_points"] == 2
    assert len(block["rows"]) == 4
    assert all(r["ok"] for r in block["rows"])
    for r in block["rows"]:
        assert abs(r["err"]) <= 0.15


def test_cli_validate_smoke(tmp_path):
    from repro.cli import main
    out = tmp_path / "fidelity.json"
    rc = main(["validate", "scenarios/tinyllama_quick.json", "--quick",
               "--out", str(out)])
    assert rc == 0
    import json
    report = json.loads(out.read_text())
    assert report["schema"] == 1
    assert report["n_violations"] == 0
    assert report["n_asserted"] > 0


def test_cli_validate_top_flag(capsys):
    from repro.cli import main
    rc = main(["scenarios/tinyllama_quick.json", "--validate-top", "2",
               "--quick", "--out", "artifacts/studies"])
    assert rc == 0
    assert "event-validated 2 records" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Tentpole: vectorized record->program compilation (events.compile_batch)
# ---------------------------------------------------------------------------
def _program_row(p):
    """The (6,) _ROW_KEYS row the per-record path derives from one
    compiled StepProgram — the reference compile_batch is pinned to."""
    return np.array(p.spans() + (p.n_micro * p.v,
                                 p.analytic.step_time))


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([c[0] for c in _CASES]),
       st.sampled_from(SCHEDULES), st.integers(0, 10 ** 6))
def test_compile_batch_parity(name, sched, pick):
    """Batched compilation == K compile_step walks at 1e-9: spans,
    DP cost, overlap credit, nmv and the embedded analytic step."""
    from repro.events.compile_batch import compile_batch
    _, w, mcm = next(c for c in _CASES if c[0] == name)
    grid = _feasible(name, w, mcm)
    ss = [grid[(pick + i) % len(grid)][0] for i in range(5)]
    cb = compile_batch(w, ss, mcm, schedule=sched)
    assert cb.feasible.all()
    for j, s in enumerate(ss):
        p = compile_step(w, s, mcm, schedule=sched)
        np.testing.assert_allclose(cb.rows[:, j], _program_row(p),
                                   rtol=1e-9, err_msg=f"{sched} {s}")
        assert int(cb.v[j]) == p.v
        assert cb.shape_keys[cb.key_rows[j]] == \
            (sched, p.n_stages, p.v, p.n_micro)


def test_compile_batch_topo_rows_parity():
    """Per-row derived OITopology overrides the allocation exactly like
    compile_step's topo branch (mixed with derive-it-yourself rows)."""
    from repro.core.optimizer import evaluate_point
    from repro.events.compile_batch import compile_batch
    rows = []
    for s, _ in _feasible("moe", MOE, MCM_MOE)[:20]:
        pt = evaluate_point(MOE, s, MCM_MOE)
        if pt is None or pt.topo is None or not pt.topo.dims:
            continue
        rows.append((s, pt.topo))
        if len(rows) >= 3:
            break
    assert rows
    rows.append((_feasible("moe", MOE, MCM_MOE)[0][0], None))
    ss = [s for s, _ in rows]
    topos = [t for _, t in rows]
    cb = compile_batch(MOE, ss, MCM_MOE, topos=topos, schedule="1f1b")
    assert cb.feasible.all()
    for j, (s, topo) in enumerate(rows):
        p = compile_step(MOE, s, MCM_MOE, topo=topo, schedule="1f1b")
        np.testing.assert_allclose(cb.rows[:, j], _program_row(p),
                                   rtol=1e-9)


def test_compile_batch_marks_infeasible():
    """compile_step raises on an infeasible point; the batch marks the
    row and replay() scatters inf back instead."""
    from repro.events.compile_batch import compile_batch
    good = _feasible("tiny", TINY, MCM_TINY)[0][0]
    bad = Strategy(tp=3, dp=1, pp=1, cp=1, ep=1, n_micro=1)
    cb = compile_batch(TINY, [good, bad], MCM_TINY)
    assert cb.feasible.tolist() == [True, False]
    assert np.isnan(cb.rows[:, 1]).all()
    assert cb.key_rows[1] == -1
    out = cb.replay(backend="numpy")
    assert np.isfinite(out["step_time"][0])
    assert out["step_time"][1] == np.inf
    with pytest.raises(ValueError, match="schedule"):
        compile_batch(TINY, [good], MCM_TINY, schedule="zigzag")


@pytest.mark.parametrize("case", _CASES, ids=[c[0] for c in _CASES])
def test_compile_batch_ranking_matches_per_record(case):
    """Fixed-schedule event ranking through the fused path == the
    per-record compile_step + replay_batch ranking."""
    from repro.events.compile_batch import compile_batch
    name, w, mcm = case
    grid = _feasible(name, w, mcm)
    ss = [t[0] for t in grid[:8]]
    ss += [t[0] for t in grid if t[0].pp > 1][:4]
    cb = compile_batch(w, ss, mcm, schedule="1f1b")
    got = cb.replay(backend="numpy")["step_time"]
    progs = [compile_step(w, s, mcm, schedule="1f1b") for s in ss]
    want = replay_batch(progs, backend="numpy")["step_time"]
    np.testing.assert_allclose(got, want, rtol=1e-9)
    assert np.array_equal(np.argsort(got, kind="stable"),
                          np.argsort(want, kind="stable"))


# ---------------------------------------------------------------------------
# Tentpole: schedule search — scenario axis, study re-rank, outer hook
# ---------------------------------------------------------------------------
def test_scenario_schedule_list():
    assert _tiny_scenario().schedule_list() == ("gpipe",)
    assert _tiny_scenario(schedule="search").schedule_list() == \
        tuple(SCHEDULES)
    assert _tiny_scenario(schedule="1f1b,interleaved").schedule_list() \
        == ("1f1b", "interleaved")
    with pytest.raises(ValueError, match="schedule"):
        _tiny_scenario(schedule="1f1b,zigzag")


def test_schedule_axis():
    from repro.dse.space import schedule_axis
    assert schedule_axis(("gpipe",)) == (("gpipe", 1),)
    assert schedule_axis(("1f1b", "interleaved")) == \
        (("1f1b", 1), ("interleaved", 2), ("interleaved", 4))


def test_event_rerank_rows_fixed_schedule_matches_replay_ranking():
    from repro.dse.search import event_rerank_rows, sweep_design_space
    sc = _tiny_scenario()
    sweep = sweep_design_space(sc.design_space(), backend=sc.backend)
    feas = np.nonzero(sweep.metrics["feasible"])[0]
    rows = feas[np.argsort(-sweep.metrics["throughput"][feas])][:12]
    rr = event_rerank_rows(sweep, rows, [("1f1b", 1)], backend="numpy")
    progs = []
    for i in rows:
        s = sweep.batch.take(np.array([int(i)])).to_strategies()[0]
        mcm = sweep.space.mcms[int(sweep.mcm_idx[i])]
        progs.append(compile_step(sweep.space.workload, s, mcm,
                                  fabric=str(sweep.fabric[i]),
                                  reuse=sweep.space.reuse,
                                  schedule="1f1b"))
    want = replay_batch(progs, backend="numpy")["step_time"]
    np.testing.assert_allclose(rr["step_time"], want, rtol=1e-9)
    assert np.array_equal(rr["order"], np.argsort(want, kind="stable"))
    assert set(rr["schedule"]) == {"1f1b"} and (rr["v"] == 1).all()


def test_study_schedule_search_reranks_and_stamps():
    from repro.api import Study
    res = Study(_tiny_scenario(schedule="search")).run()
    rr = res.provenance["event_rerank"]
    assert rr["n_reranked"] > 0
    assert rr["schedules"] == list(SCHEDULES)
    assert sum(rr["winners"].values()) == rr["n_reranked"]
    assert res.timings["rerank_s"] > 0
    best = res.records[res.best]
    assert best.metrics["event_schedule"] in SCHEDULES
    assert best.metrics["event_v"] >= 1
    assert best.metrics["event_step_time"] > 0
    assert best.metrics["event_throughput"] > 0
    # a single-schedule scenario skips the stage entirely
    r1 = Study(_tiny_scenario(schedule="1f1b")).run()
    assert "event_rerank" not in r1.provenance
    assert "rerank_s" not in r1.timings


def test_outer_event_replay_schedule_search():
    from repro.api import Study
    sc = _tiny_scenario(schedule="search", driver="chiplight-outer",
                        driver_kw={"rounds": 1, "walkers": 2,
                                   "event_replay": 2})
    res = Study(sc).run()
    assert res.provenance["n_event_replayed"] > 0
    w = res.traces[-1]["walkers"][0]
    assert w["event_thpt"] > 0 and w["event_step_time"] > 0


def test_outer_event_schedule_driver_kw_deprecated():
    import warnings
    from repro.api import Study
    sc = _tiny_scenario(driver="chiplight-outer",
                        driver_kw={"rounds": 1, "walkers": 2,
                                   "event_replay": 2,
                                   "event_schedule": "1f1b"})
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        res = Study(sc).run()
    assert sum(issubclass(r.category, DeprecationWarning)
               for r in rec) == 1
    assert res.provenance["n_event_replayed"] > 0
