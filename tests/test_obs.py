"""Tests for repro.obs — tracing spans, metrics registries, Chrome-trace
export — plus the instrumentation satellites: batch-replay fallback
observability, public cache/retrace stats, and the simulated-step
timeline reproducing the schedule-bubble fidelity finding."""
import json
import tracemalloc
import warnings

import pytest

from repro.configs import get_config
from repro.core.mcm import mcm_from_compute
from repro.core.optimizer import enumerate_strategies
from repro.core.simulator import simulate
from repro.core.workload import Workload
from repro.events import compile_step, replay, replay_batch
from repro.obs import (METRICS_SCHEMA, Tracer, chrome_trace_from_event_result,
                       chrome_trace_from_tracer, current_tracer, metrics, span,
                       tracing, track_idle, validate_chrome_trace)
from repro.obs.export import PID_DEVICES
from repro.obs.trace import _NULL_SPAN

TINY = Workload(model=get_config("tinyllama_1_1b"), seq_len=4096,
                global_batch=256)
MCM_TINY = mcm_from_compute(1e6, 16, 6)


def _tiny_scenario(**kw):
    from repro.api import Scenario
    return Scenario(model="tinyllama_1_1b", total_tflops=1e6, seq_len=4096,
                    global_batch=256, dies_per_mcm=(16,), m=(6,),
                    cpo_ratio=(0.6,), fabrics=("oi",), refine_top=3,
                    keep_top=16, **kw)


def _pipelined(min_nm=8):
    """Best feasible pipelined strategy on the tiny MCM."""
    best = None
    for s in enumerate_strategies(TINY, MCM_TINY):
        if s.pp <= 1 or s.n_micro < max(min_nm, s.pp):
            continue
        r = simulate(TINY, s, MCM_TINY)
        if r.feasible and (best is None or r.throughput > best[1]):
            best = (s, r.throughput)
    if best is None:
        pytest.skip("no pipelined strategy on the tiny MCM")
    return best[0]


# ---------------------------------------------------------------------------
# Tracer core: nesting, LIFO, monotonicity, disabled fast path
# ---------------------------------------------------------------------------
def test_span_nesting_depths_and_order():
    with tracing() as tr:
        with span("outer", k=1):
            with span("inner"):
                pass
            with span("inner2"):
                pass
    assert current_tracer() is None
    names = [e["name"] for e in tr.events]
    assert names == ["inner", "inner2", "outer"]   # completion order
    by = {e["name"]: e for e in tr.events}
    assert by["outer"]["depth"] == 0
    assert by["inner"]["depth"] == by["inner2"]["depth"] == 1
    assert by["outer"]["args"] == {"k": 1}
    assert by["inner"]["args"] is None
    # children nest inside the parent's [ts, ts+dur] window
    for child in ("inner", "inner2"):
        assert by[child]["ts_ns"] >= by["outer"]["ts_ns"]
        assert (by[child]["ts_ns"] + by[child]["dur_ns"]
                <= by["outer"]["ts_ns"] + by["outer"]["dur_ns"])
    assert all(e["dur_ns"] >= 0 for e in tr.events)


def test_span_records_rebuild_the_call_tree():
    with tracing() as tr:
        with span("root"):
            with span("a"):
                with span("a1"):
                    pass
                with span("a2"):
                    pass
            with span("b"):
                pass
        with span("root2"):
            pass
    ids = [e["id"] for e in tr.events]
    assert len(set(ids)) == len(ids)
    name = {e["id"]: e["name"] for e in tr.events}
    tree = {}
    for e in tr.events:
        parent = None if e["parent"] is None else name[e["parent"]]
        tree.setdefault(parent, []).append(e["name"])
    assert {k: sorted(v) for k, v in tree.items()} == {
        None: ["root", "root2"], "root": ["a", "b"], "a": ["a1", "a2"]}
    # ids number spans in the order they opened; depth is the tree's
    opened = [name[i] for i in sorted(ids)]
    assert opened == ["root", "a", "a1", "a2", "b", "root2"]
    by = {e["name"]: e for e in tr.events}
    for e in tr.events:
        want = 0 if e["parent"] is None else \
            by[name[e["parent"]]]["depth"] + 1
        assert e["depth"] == want


def test_span_set_adds_args():
    with tracing() as tr:
        with span("call", rows=3) as sp:
            sp.set(retraced=True)
        with span("bare") as sp:
            sp.set(k=1)
    by = {e["name"]: e for e in tr.events}
    assert by["call"]["args"] == {"rows": 3, "retraced": True}
    assert by["bare"]["args"] == {"k": 1}
    span("off").set(k=1)                   # the disabled span ignores it


def test_span_lifo_violation_raises():
    tr = Tracer()
    with tracing(tr):
        a = span("a")
        b = span("b")
        a.__enter__()
        b.__enter__()
        with pytest.raises(RuntimeError, match="LIFO"):
            a.__exit__(None, None, None)
        # clean up so tracing() doesn't also raise
        b.__exit__(None, None, None)
        a.__exit__(None, None, None)


def test_tracing_rejects_unclosed_spans():
    with pytest.raises(RuntimeError, match="never closed"):
        with tracing():
            span("leaked").__enter__()


def test_disabled_span_is_shared_singleton():
    assert current_tracer() is None
    s = span("hot", rows=123)
    assert s is _NULL_SPAN
    assert span("other") is s


def test_disabled_span_allocates_nothing():
    # the disabled path must stay allocation-free: safe in hot loops
    for _ in range(64):                                    # warm caches
        with span("warm", i=0):
            pass
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(1000):
        with span("hot"):
            pass
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(st.size_diff for st in
                after.compare_to(before, "lineno") if st.size_diff > 0)
    # tracemalloc's own bookkeeping costs a little; 1000 span dicts
    # would cost >60kB
    assert grown < 10_000


def test_span_exception_still_recorded():
    with tracing() as tr:
        with pytest.raises(ValueError):
            with span("boom"):
                raise ValueError("x")
    assert [e["name"] for e in tr.events] == ["boom"]


# ---------------------------------------------------------------------------
# Metrics registries: scoping, folding, tracer sampling
# ---------------------------------------------------------------------------
def test_metrics_scope_folds_into_parent():
    root_before = metrics.root().counters.get("t.x", 0)
    with metrics.scope() as outer:
        metrics.inc("t.x", 2)
        with metrics.scope() as inner:
            metrics.inc("t.x", 3)
            metrics.gauge("t.g", 7)
        assert inner.counters["t.x"] == 3
        assert outer.counters["t.x"] == 5          # folded on exit
        assert outer.gauges["t.g"] == 7
    assert metrics.root().counters["t.x"] == root_before + 5


def test_reduce_stacked_sums_counts_and_averages_gauges():
    import numpy as np
    stacked = {"moe.routed_rows": np.array([3.0, 5.0]),
               "moe.load_max_over_mean": np.array([1.0, 2.0]),
               "t.undeclared": np.array([1.0, 1.0])}
    assert metrics.reduce_stacked(stacked) == {
        "moe.routed_rows": 8.0, "moe.load_max_over_mean": 1.5,
        "t.undeclared": 2.0}


def test_metrics_snapshot_schema():
    m = metrics.Metrics()
    m.inc("a.b", 4)
    m.gauge("a.g", 1.5)
    snap = m.snapshot()
    assert snap == {"schema": METRICS_SCHEMA, "counters": {"a.b": 4},
                    "gauges": {"a.g": 1.5}}
    assert json.loads(json.dumps(snap)) == snap


def test_inc_samples_on_tracer():
    with tracing() as tr, metrics.scope():
        metrics.inc("t.sampled")
        metrics.inc("t.sampled", 2)
    assert [(n, v) for n, _, v in tr.counter_samples] == \
        [("t.sampled", 1.0), ("t.sampled", 3.0)]


# ---------------------------------------------------------------------------
# Chrome-trace export: structural validity of both trace flavours
# ---------------------------------------------------------------------------
def test_host_trace_chrome_valid():
    with tracing() as tr, metrics.scope():
        with span("study.run", scenario="t"):
            with span("study.scan"):
                metrics.inc("dse.cache.hits", 5)
    trace = chrome_trace_from_tracer(tr)
    counts = validate_chrome_trace(trace)
    assert counts["X"] == 2
    assert counts["C"] == 1
    assert counts["M"] >= 1
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert names == {"study.run", "study.scan"}


def test_simulated_step_trace_chrome_valid():
    s = _pipelined()
    prog = compile_step(TINY, s, MCM_TINY, schedule="1f1b")
    ev = replay(prog, record_timeline=True)
    trace = chrome_trace_from_event_result(ev, "tiny 1f1b")
    counts = validate_chrome_trace(trace)
    assert counts["X"] > 0 and counts["M"] > 0
    # one device track per pipeline stage
    tids = {e["tid"] for e in trace["traceEvents"]
            if e["ph"] == "X" and e["pid"] == PID_DEVICES}
    assert len(tids) == prog.n_stages
    assert trace["otherData"]["schedule"] == "1f1b"


def test_replay_without_timeline_has_no_device_events():
    s = _pipelined()
    ev = replay(compile_step(TINY, s, MCM_TINY, schedule="1f1b"))
    assert ev.device_timeline == []
    with pytest.raises(ValueError, match="record_timeline"):
        chrome_trace_from_event_result(ev, "x")


# ---------------------------------------------------------------------------
# Acceptance: the timeline reproduces the schedule-bubble finding —
# interleaving shrinks idle, measured from the trace's own durations
# ---------------------------------------------------------------------------
def test_timeline_interleaved_idle_below_gpipe():
    s = _pipelined()

    def idle(schedule):
        prog = compile_step(TINY, s, MCM_TINY, schedule=schedule)
        ev = replay(prog, record_timeline=True)
        trace = chrome_trace_from_event_result(ev, schedule)
        per_track = track_idle(trace)
        assert set(per_track) == set(range(prog.n_stages))
        return sum(t["idle_us"] for t in per_track.values()), ev

    idle_g, ev_g = idle("gpipe")
    idle_i, ev_i = idle("interleaved")
    assert idle_g > 0
    assert idle_i < 0.75 * idle_g
    # the trace-derived idle agrees with the engine's own bubble ratio
    assert ev_i.bubble < 0.75 * ev_g.bubble


# ---------------------------------------------------------------------------
# Study.run() provenance.metrics block + JSON round-trip
# ---------------------------------------------------------------------------
def test_study_metrics_block_and_roundtrip(tmp_path):
    from repro.api import Study, StudyResult
    res = Study(_tiny_scenario()).run()
    m = res.provenance["metrics"]
    assert m["schema"] == METRICS_SCHEMA
    assert m["wall_s"]["total"] > 0
    assert m["points_evaluated"] > 0
    assert m["points_per_s"] > 0
    assert 0.0 <= m["cache"]["hit_rate"] <= 1.0
    assert m["jax"]["retraces"] >= 0
    # the exhaustive driver takes the fused no-cache sweep, so its
    # counter set is empty — but the block must still be present
    assert isinstance(m["counters"], dict)

    path = tmp_path / "res.json"
    res.save(path)
    back = StudyResult.load(path)
    assert back.provenance["metrics"] == m


def test_study_traced_emits_stage_spans():
    from repro.api import Study
    with tracing() as tr:
        Study(_tiny_scenario()).run()
    names = {e["name"] for e in tr.events}
    assert {"study.run", "study.scan", "study.refine",
            "sweep", "refine", "study.space", "study.keep",
            "study.records"} <= names


def test_study_run_children_cover_its_stages():
    """An exhaustive study's own work is all inside named stages: the
    direct children of ``study.run`` are exactly these, in this order,
    and together take no longer than the run."""
    from repro.api import Study
    with tracing() as tr:
        Study(_tiny_scenario()).run()
    run, = [e for e in tr.events if e["name"] == "study.run"]
    kids = sorted((e for e in tr.events if e["parent"] == run["id"]),
                  key=lambda e: e["ts_ns"])
    assert [e["name"] for e in kids] == [
        "study.space", "study.scan", "study.keep", "study.records",
        "study.refine", "study.records"]
    assert sum(e["dur_ns"] for e in kids) <= run["dur_ns"]
    assert all(e["depth"] == run["depth"] + 1 for e in kids)


def _jax_study_traced(monkeypatch, **kw):
    """A study on ``backend="jax"`` (CPU) under a tracer and a metrics
    scope; also returns the arguments each device call received."""
    pytest.importorskip("jax")
    from repro.api import Study
    from repro.dse import batched_sim
    sent = []
    real = batched_sim._jax_terms_fn

    def spy(*key):
        fn = real(*key)

        def call(*args):
            sent.append(args)
            return fn(*args)
        return call

    monkeypatch.setattr(batched_sim, "_jax_terms_fn", spy)
    with tracing() as tr, metrics.scope() as m:
        Study(_tiny_scenario(backend="jax").replace(**kw)).run()
    return tr, m, sent


def test_jax_scan_spans_and_transfer_counters(monkeypatch):
    tr, m, sent = _jax_study_traced(monkeypatch, fabrics=("oi", "ib"))
    dev = [e for e in tr.events if e["name"] == "sim.device"]
    pad = [e for e in tr.events if e["name"] == "sim.pad"]
    assert len(dev) == len(pad) == len(sent) == 2
    assert {e["args"]["fabric"] for e in dev} == {"oi", "ib"}
    for e in dev:
        assert set(e["args"]) == {"fabric", "rows", "bucket", "retraced"}
        assert isinstance(e["args"]["retraced"], bool)
        assert e["args"]["bucket"] >= e["args"]["rows"] > 0
    # pad and device call are siblings under the scan
    assert all(p["parent"] == d["parent"] for p, d in zip(pad, dev))
    c = m.counters
    assert c["batched_sim.jax_calls"] == 2
    assert c["batched_sim.jax_rows"] == sum(e["args"]["rows"] for e in dev)
    assert c["batched_sim.jax_rows"] + c["batched_sim.jax_pad_rows"] == \
        sum(e["args"]["bucket"] for e in dev)
    assert c["batched_sim.h2d_bytes"] == sum(
        a.nbytes for args in sent for a in args)
    assert all(a.shape[0] == e["args"]["bucket"]
               for args, e in zip(sent, dev) for a in args)
    # outputs come back whole (bucket rows, before the slice)
    assert c["batched_sim.d2h_bytes"] > 0


def test_jax_scan_device_span_marks_retrace(monkeypatch):
    from repro.dse.batched_sim import _jax_terms_fn
    _jax_terms_fn.cache_clear()
    tr, _, _ = _jax_study_traced(monkeypatch)
    tr2, _, _ = _jax_study_traced(monkeypatch)
    first = [e["args"]["retraced"] for e in tr.events
             if e["name"] == "sim.device"]
    again = [e["args"]["retraced"] for e in tr2.events
             if e["name"] == "sim.device"]
    assert first == [True] and again == [False]


def test_span_mirrored_on_profiler_host_plane(tmp_path):
    """Under ``tracing()`` with jax imported, every span is also a
    ``jax.profiler`` annotation: a capture's host plane holds the
    study's stages and the device call, with their args."""
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData
    from repro.api import Study
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing():
            Study(_tiny_scenario(backend="jax")).run()
    finally:
        jax.profiler.stop_trace()
    xplane, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, dict(ev.stats))
    assert {"study.run", "study.keep", "sim.device"} <= set(host)
    assert host["sim.device"]["fabric"] == "oi"


def test_obs_never_imports_jax():
    """The tracer looks jax up and never imports it: numpy-only users
    of ``repro.obs`` stay free of it, traced or not."""
    import subprocess
    import sys
    code = ("import sys\n"
            "from repro.obs import span, tracing, metrics\n"
            "with tracing() as tr, metrics.scope():\n"
            "    with span('a', k=1):\n"
            "        with span('b'):\n"
            "            metrics.inc('t.x')\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "assert [e['name'] for e in tr.events] == ['b', 'a']\n")
    subprocess.run([sys.executable, "-c", code], check=True)


def test_driver_sweep_populates_cache_counters():
    from repro.api import Study
    res = Study(_tiny_scenario(driver="prf",
                               driver_kw={"budget": 256})).run()
    c = res.provenance["metrics"]["counters"]
    assert c["dse.cache.sim"] > 0
    assert res.provenance["metrics"]["cache"]["requests"] >= \
        res.provenance["metrics"]["cache"]["hits"]


# ---------------------------------------------------------------------------
# Satellite: interleaved batch replay is vectorized — no fallback left
# ---------------------------------------------------------------------------
def test_batch_replay_interleaved_no_fallback_counter():
    s = _pipelined()
    progs = [compile_step(TINY, s, MCM_TINY, schedule="interleaved"),
             compile_step(TINY, s, MCM_TINY, schedule="1f1b")]
    with metrics.scope() as m:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            out = replay_batch(progs)
    assert out["scalar_fallback"].tolist() == [False, False]
    assert m.counters["batch_replay.records"] == 2
    assert "batch_replay.scalar_fallback" not in m.counters
    assert not [w for w in caught
                if issubclass(w.category, RuntimeWarning)
                and "scalar event engine" in str(w.message)]


def test_batch_replay_vectorized_has_no_fallback():
    s = _pipelined()
    progs = [compile_step(TINY, s, MCM_TINY, schedule="1f1b")] * 3
    with metrics.scope() as m:
        out = replay_batch(progs)
    assert not out["scalar_fallback"].any()
    assert "batch_replay.scalar_fallback" not in m.counters


def test_validation_summary_reports_fallback():
    from repro.api import Study
    res = Study(_tiny_scenario(validate_top=2)).run()
    val = res.provenance["validate"]
    assert val["n_scalar_fallback"] >= 0
    assert 0.0 <= val["scalar_fallback_frac"] <= 1.0


# ---------------------------------------------------------------------------
# Satellite: public cache/retrace stats — repeated same-bucket sweeps
# must not retrace
# ---------------------------------------------------------------------------
def test_evaluator_stats_public():
    from itertools import islice
    from repro.dse.search import BatchedEvaluator
    from repro.dse.space import StrategyBatch
    ev = BatchedEvaluator(TINY, MCM_TINY, backend="numpy")
    grid = StrategyBatch.from_strategies(
        list(islice(enumerate_strategies(TINY, MCM_TINY), 32)))
    ev.evaluate(grid)
    ev.evaluate(grid)                              # cache-served
    st = ev.stats()
    assert st["dse.cache.sim"] == len(grid.keys())
    assert st["dse.cache.hits"] == len(grid.keys())
    assert st["dse.cache.fallback_rows"] >= 0


def test_repeated_same_bucket_sweep_zero_new_retraces():
    jax = pytest.importorskip("jax")
    del jax
    from repro.dse.batched_sim import jax_stats
    from repro.dse.search import sweep_design_space
    sc = _tiny_scenario()
    space = sc.design_space()
    sweep_design_space(space, backend="jax")           # warm the trace
    before = jax_stats()["traces"]
    with metrics.scope() as m:
        sweep_design_space(space, backend="jax")       # same bucket
    assert jax_stats()["traces"] == before
    assert m.counters.get("batched_sim.jax_retraces", 0) == 0
