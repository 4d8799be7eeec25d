"""From a profiler trace of the window to the numbers the benchmark reports.

``jax.profiler`` writes one ``.xplane.pb`` per trace.  Its planes are the
host (``/host:CPU``, one line per thread, holding the harness's
``TraceAnnotation`` events) and one plane per chip (``/device:TPU:<n>``),
whose ``XLA Ops`` line holds every operation the chip ran, with its start
and duration in nanoseconds on the same clock as the host's events.

``reduce_events`` clips the chips' operations to the ``chipbench.window``
annotation and returns:

* ``window_s`` -- the annotation's length;
* ``busy_s``   -- the union of operation intervals on each chip, averaged
  over the chips the cell uses;
* ``ops``      -- per operation (the HLO instruction's name; loops and calls
  left out, their bodies counted), its count and summed seconds (per
  chip), and whether it is a custom call (a Pallas kernel);
* ``device_ops`` -- the ten names that took most time;
* ``idle_gaps``  -- the longest gaps between operations, each named by
  the innermost host span open across it: the program's ``repro.obs``
  spans where they were recorded (aligned to the trace's clock through
  the harness's annotation of the same call), else the harness's own
  annotations.
"""
from __future__ import annotations

import gzip
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# control flow whose span holds the ops of its body on the same line
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")

Interval = Tuple[int, int]


def latest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def planes(xspace_path: Path):
    """The planes of an ``.xplane.pb`` (or a gzipped one)."""
    from jax.profiler import ProfileData
    if str(xspace_path).endswith(".gz"):
        with gzip.open(xspace_path) as f:
            return ProfileData.from_serialized_xspace(f.read()).planes
    return ProfileData.from_file(str(xspace_path)).planes


def collect(plane_list, n_chips: int) -> Dict:
    """Host events and per-chip operation events, as plain tuples."""
    host: List[Tuple[str, int, int]] = []
    chips: Dict[int, List[Tuple[str, int, int, bool]]] = {}
    for pl in plane_list:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                for ev in ln.events:
                    host.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
            continue
        m = _DEVICE.match(pl.name)
        if not m or int(m.group(1)) >= n_chips:
            continue
        evs = chips.setdefault(int(m.group(1)), [])
        for ln in pl.lines:
            if ln.name != OPS_LINE:
                continue
            for ev in ln.events:
                name, custom = op_name(ev.name)
                evs.append((name, int(ev.start_ns), int(ev.end_ns), custom))
    return {"host": host, "chips": chips}


def op_name(text: str) -> Tuple[str, bool]:
    """(the HLO instruction's name, whether it is a custom call) from an
    op event's name, which on the TPU is the instruction's whole text:
    ``%flash_attention_fwd.19 = (...) custom-call(...), ...``."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return head, "custom-call(" in text or "tpu_custom_call" in text


def reduce_events(host, chips, n_chips: int, spans: Optional[List] = None,
                  span_t0_ns: int = 0) -> Dict:
    wins = [(a, b) for n, a, b in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0, w1 = wins[-1]
    if not chips or not any(chips.values()):
        raise ValueError("no operation of the chip in the trace")
    busy_total = 0
    ops: Dict[str, List] = {}
    merged_first = None
    for idx in sorted(chips):
        iv = []
        for name, a, b, custom in chips[idx]:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            iv.append((a, b))
            if _CONTAINER.match(name):
                continue          # a loop or call: its body's ops count
            o = ops.setdefault(name, [0, 0.0, custom])
            o[0] += 1
            o[1] += (b - a) / 1e9
        merged = union(iv)
        if merged_first is None:
            merged_first = merged
        busy_total += sum(b - a for a, b in merged)
    n = max(len(chips), 1)
    for o in ops.values():
        o[0] = o[0] / n
        o[1] = o[1] / n
    labels = _labeller(host, spans, span_t0_ns)
    idle = sorted(gaps(merged_first, w0, w1), key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "ops": ops,
        "device_ops": [[k, v[1]] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1][1])[:10]],
        "idle_gaps": [[labels(a, b), (b - a) / 1e9] for a, b in idle[:10]],
    }


def _labeller(host, spans, span_t0_ns: int):
    """Name a gap by the innermost span open across its midpoint."""
    own = [(a, b, n) for n, a, b in host
           if n.startswith("chipbench.") and n != WINDOW]
    aligned = []
    if spans:
        # the harness annotates each study; the program's study.run span
        # opens inside it, so the first pair fixes the clock offset
        ann = sorted(a for a, b, n in own if n == "chipbench.study")
        runs = sorted(span_t0_ns + s["ts_ns"] for s in spans
                      if s["name"] == "study.run")
        if ann and runs:
            k = min(len(ann), len(runs))
            off = sorted(ann[i] - runs[i] for i in range(k))[k // 2]
            aligned = [(span_t0_ns + s["ts_ns"] + off,
                        span_t0_ns + s["ts_ns"] + s["dur_ns"] + off,
                        s["name"], s["depth"]) for s in spans]

    def label(a: int, b: int) -> str:
        mid = (a + b) // 2
        inner = [s for s in aligned if s[0] <= mid < s[1]]
        if inner:
            return max(inner, key=lambda s: s[3])[2]
        mine = [s for s in own if s[0] <= mid < s[1]]
        if mine:
            return min(mine, key=lambda s: s[1] - s[0])[2]
        return "harness (between calls)"
    return label


def reduce_dir(trace_dir: Path, n_chips: int, spans=None,
               span_t0_ns: int = 0) -> Dict:
    c = collect(planes(latest_xplane(trace_dir)), n_chips)
    return reduce_events(c["host"], c["chips"], n_chips, spans, span_t0_ns)


def kernel_calls(device: Optional[Dict], kernel: str
                 ) -> Optional[Tuple[float, float]]:
    """(calls, summed seconds) per chip of the custom calls named after
    ``kernel`` (``<kernel>`` or ``<kernel>.<n>``); None where there are
    none."""
    if not device:
        return None
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)*$")
    hits = [(c, s) for name, (c, s, custom) in device["ops"].items()
            if custom and pat.match(name)]
    if not hits:
        return None
    return (sum(c for c, _ in hits), sum(s for _, s in hits))
