"""The readers of the study's stage spans and the scan's device-call
counters, on a hand-made ``harness.Run``: each gives its number where
the run holds its span or counter, and ``None`` where it does not (a
program from before the span or counter existed)."""
import pytest

from chipbench import harness


def _run(spans=(), counters=None, units=4):
    return harness.Run(cell="qwen3_sweep", config={}, traffic={},
                       window_s=1.0, units=units, spans=list(spans),
                       counters=dict(counters or {}))


def _span(name, dur_ns):
    return {"name": name, "ts_ns": 0, "dur_ns": dur_ns, "depth": 1,
            "id": 0, "parent": None, "args": None}


@pytest.mark.parametrize("metric,span", [("keep_ms", "study.keep"),
                                         ("records_ms", "study.records"),
                                         ("sim_device_ms", "sim.device")])
def test_span_reader_per_study(metric, span):
    read = harness.load_reader(metric)
    # two spans of the stage over four studies, others ignored
    run = _run([_span(span, 3_000_000), _span(span, 5_000_000),
                _span("study.scan", 70_000_000)])
    assert read(run) == pytest.approx(2.0)
    assert read(_run([_span("study.scan", 1_000_000)])) is None
    assert read(_run([_span(span, 1_000_000)], units=0)) is None


def test_transfer_mb():
    read = harness.load_reader("transfer_mb")
    run = _run(counters={"batched_sim.h2d_bytes": 30_000_000,
                         "batched_sim.d2h_bytes": 10_000_000})
    assert read(run) == pytest.approx(10.0)
    assert read(_run(counters={"batched_sim.h2d_bytes": 1})) is None
    assert read(_run(counters={"batched_sim.jax_pad_rows": 8})) is None


def test_pad_share():
    read = harness.load_reader("pad_share")
    run = _run(counters={"batched_sim.jax_rows": 300,
                         "batched_sim.jax_pad_rows": 100})
    assert read(run) == pytest.approx(25.0)
    assert read(_run(counters={"batched_sim.jax_rows": 512})) == 0.0
    # a program older than the jax_rows counter counts pad rows only
    assert read(_run(counters={"batched_sim.jax_pad_rows": 8})) is None
    assert read(_run(counters={"batched_sim.jax_rows": 0})) is None
