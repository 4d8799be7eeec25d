"""The reduction from trace to metrics, on hand-made events and on a
small trace recorded on a TPU v5e (``data/v5e_small.xplane.pb``)."""
from pathlib import Path

import pytest

from chipbench import devtrace

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps_by_hand():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                (5, 8)]
    assert devtrace.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert devtrace.gaps([(2, 4)], 0, 3) == [(0, 2)]
    assert devtrace.gaps([], 0, 4) == [(0, 4)]


def _events():
    # window 1000..2000 ns; ops overlap, one runs past the window's end
    host = [("chipbench.window", 1000, 2000),
            ("chipbench.study", 1000, 1500),
            ("chipbench.study", 1500, 2000)]
    chips = {0: [("fusion.1", 900, 1100, False),
                 ("fusion.1", 1050, 1200, False),
                 ("flash_attention_fwd.3", 1300, 1400, True),
                 ("flash_attention_fwd", 1900, 2100, True)]}
    spans = [{"name": "study.run", "ts_ns": 10, "dur_ns": 480, "depth": 0},
             {"name": "study.scan", "ts_ns": 20, "dur_ns": 200, "depth": 1},
             {"name": "study.run", "ts_ns": 510, "dur_ns": 480, "depth": 0}]
    return host, chips, spans


def test_reduce_by_hand():
    host, chips, spans = _events()
    # the program's clock starts 990 ns behind the trace's
    r = devtrace.reduce_events(host, chips, 1, spans, span_t0_ns=0)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [1000, 1200] + [1300, 1400] + [1900, 2000] = 400 ns
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["ops"]["fusion.1"][:2] == [2, pytest.approx(250e-9)]
    # gaps: 1200..1300 and 1400..1900
    assert [g[1] for g in r["idle_gaps"]] == [pytest.approx(500e-9),
                                             pytest.approx(100e-9)]
    # 1650, the longest gap's midpoint, lies in the second study.run
    assert r["idle_gaps"][0][0] == "study.run"
    assert devtrace.kernel_calls(r, "flash_attention_fwd") == (
        2, pytest.approx(200e-9))
    assert devtrace.kernel_calls(r, "fusion") is None


def test_gap_named_by_program_span():
    host, chips, spans = _events()
    r = devtrace.reduce_events(host, chips, 1, spans, span_t0_ns=0)
    labels = dict((round(s * 1e9), n) for n, s in r["idle_gaps"])
    # 1200..1300 lies in the first study.run (aligned to 1000..1480)
    assert labels[100] == "study.run"


def test_no_window_or_no_chip_raises():
    host, chips, spans = _events()
    with pytest.raises(ValueError):
        devtrace.reduce_events(host[1:], chips, 1)
    with pytest.raises(ValueError):
        devtrace.reduce_events(host, {}, 1)


def test_recorded_trace():
    """A window recorded on a TPU v5e: two ``Study.run()`` calls, then two
    steps of an 8-layer InternLM2-1.8B at 1 x 4,096 tokens with full
    remat.  Busy time is checked against a plain sweep over the raw
    events; the kernel calls against counts by hand: per step, 8 layers
    x (forward + recompute) attention calls and twice that of RMSNorm
    (two norms per layer)."""
    c = devtrace.collect(devtrace.planes(DATA / "v5e_window.xplane.pb.gz"),
                         1)
    r = devtrace.reduce_events(c["host"], c["chips"], 1)
    w0, w1 = [(a, b) for n, a, b in c["host"]
              if n == devtrace.WINDOW][-1]
    # coverage count by endpoint deltas: busy wherever it is above zero
    deltas = sorted([(max(a, w0), 1) for _, a, b, _ in c["chips"][0]
                     if min(b, w1) > max(a, w0)]
                    + [(min(b, w1), -1) for _, a, b, _ in c["chips"][0]
                       if min(b, w1) > max(a, w0)])
    busy, depth, last = 0, 0, w0
    for t_, d in deltas:
        if depth > 0:
            busy += t_ - last
        depth += d
        last = t_
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert devtrace.kernel_calls(r, "flash_attention_fwd")[0] == 2 * 8 * 2
    assert devtrace.kernel_calls(r, "rmsnorm")[0] == 2 * 8 * 2 * 2
    assert all(not n.startswith("while") for n, _ in r["device_ops"])
