"""The benchmark's driver: one cell, one run.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by the name
``BENCHMARK.json`` gives it:

* ``configs[].file``                  -- the configuration (sizes, the
                                         runner that drives it);
* ``chipbench/traffic/<traffic>.json`` -- the traffic mix's parameters;
* ``chipbench/runners/<runner>.py``    -- a ``Cell`` class per kind of
                                         work (a study, a train step);
* ``chipbench/metrics/<metric>.py``    -- ``read(run)`` of one per-layer
                                         metric, ``None`` where the run
                                         holds nothing to read.

A run: check the chips, set up (warm every shape the window will use),
measure for ``--seconds``, read memory, free the program's state, run
the plain reference, print the numbers compared beside their limits and
the result line.  End-to-end metrics come from runs with ``--trace 0``;
``--trace 1`` traces the window with the profiler and reports the
per-layer metrics instead.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".jax_cache"          # fixed: the path keys the cache
TRACE_DIR = HERE / "out" / "trace"


class BenchError(RuntimeError):
    """The run cannot be made (no chip, a missing file)."""


# ---------------------------------------------------------------------------
# Discovery
# ---------------------------------------------------------------------------
def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclass
class CellSpec:
    name: str
    workload: Dict
    config_entry: Dict
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def find_cell(name: str, bench: Optional[Dict] = None,
              root: Path = ROOT) -> CellSpec:
    bench = bench or load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(work)}")
    w = work[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    entry = cfgs[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return CellSpec(name, w, entry, config, traffic, e2e, per_layer)


def load_runner(config: Dict):
    return importlib.import_module(f"chipbench.runners.{config['runner']}")


def load_reader(metric: str):
    """``read`` of ``chipbench/metrics/<metric>.py`` (names may hold dots,
    so the file is loaded by path)."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Seeds
# ---------------------------------------------------------------------------
def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one use of the run's seed (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 63), *stream]))


def key31(seed: int, *stream: int) -> int:
    """A non-negative 31-bit integer drawn from the seed, for
    ``jax.random.PRNGKey`` and the program's own seeds."""
    return int(rng(seed, *stream).integers(0, 1 << 31))


# ---------------------------------------------------------------------------
# What a traced run hands the per-layer readers
# ---------------------------------------------------------------------------
@dataclass
class Run:
    cell: str
    config: Dict
    traffic: Dict
    window_s: float                       # host seconds of the window
    units: int                            # studies or steps completed
    spans: List[Dict] = field(default_factory=list)   # repro.obs spans
    counters: Dict[str, float] = field(default_factory=dict)
    device: Optional[Dict] = None         # devtrace.reduce_events output
    peaks: Optional[Dict] = None          # this chip's row of peaks.json
    extra: Dict[str, Any] = field(default_factory=dict)   # runner facts

    def span_ns(self, name: str) -> int:
        """Summed duration of the spans named ``name``."""
        return sum(s["dur_ns"] for s in self.spans if s["name"] == name)


def load_peaks(kind: str) -> Dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["chips"]:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"chipbench/peaks.json; known: "
                         f"{sorted(table['chips'])}")
    return table["chips"][kind]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def _parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache_dir() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, set before jax starts; every program is kept."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction, whatever the machine sets: eviction reads an access
    # time file beside every entry, and an entry written without one
    # (by a writer with eviction off) would fail every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


def chips(n: int):
    """The first ``n`` TPU devices; raises where there are fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: jax runs on {devs[0].platform!r}; "
                         f"the benchmark measures the chip only")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, jax sees {len(devs)}")
    return devs[:n]


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.time() if t0 is None else t0
    args = _parse(argv)
    try:
        spec = find_cell(args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError("no src/repro beside chipbench/: run from a "
                             "checkout of the repository")
        use_cache_dir()
        devices = chips(int(spec.workload["chips"]))
    except (BenchError, FileNotFoundError, KeyError) as e:
        say(f"chipbench: {e}")
        return 2
    return run_cell(spec, args.seed, args.seconds, bool(args.trace),
                    devices, t0)


def run_cell(spec: CellSpec, seed: int, seconds: float, trace: bool,
             devices, t0: float) -> int:
    import jax
    runner = load_runner(spec.config)
    cell = runner.Cell(spec.name, spec.config, spec.traffic, seed, devices)
    cell.setup()
    setup_s = time.time() - t0
    say(f"chipbench: {spec.name} seed={seed} set-up {setup_s:.3f} s")

    tracer = None
    if trace:
        from repro.obs import tracing
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        with tracing() as tracer, \
                jax.profiler.TraceAnnotation("chipbench.window"):
            win = cell.window(seconds)
        jax.profiler.stop_trace()
    else:
        win = cell.window(seconds)
    memory = peak_memory(devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}

    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        from chipbench import devtrace
        reduced = devtrace.reduce_dir(TRACE_DIR, len(devices),
                                      spans=tracer.events,
                                      span_t0_ns=tracer.t0_ns)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"][:10],
                     "idle_gaps": reduced["idle_gaps"][:10]}
        run = Run(cell=spec.name, config=spec.config, traffic=spec.traffic,
                  window_s=win["window_s"], units=win["units"],
                  spans=list(tracer.events), counters=win["counters"],
                  device=reduced, peaks=load_peaks(dev.device_kind),
                  extra=win.get("extra", {}))
        for m in spec.per_layer:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in spec.end_to_end:
            value = setup_s if m["name"] == "setup_s" \
                else win["e2e"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}

    cell.free()
    checks = cell.check()
    correct = bool(checks) and all(v <= lim for _, v, lim in checks)
    for name, v, lim in checks:
        say(f"check {name} = {v!r} (limit {lim!r})")
    result = {"correct": correct, "attempted": int(win["attempted"]),
              "failed": int(win["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    print(json.dumps(result), flush=True)
    return 0


def window_loop(unit, seconds: float) -> Tuple[float, int, int]:
    """Closed loop: call ``unit()`` until ``seconds`` have passed; the
    window closes when the last call returns.  Returns (window seconds,
    calls completed, calls failed)."""
    t_start = time.perf_counter()
    done = failed = 0
    while time.perf_counter() - t_start < seconds:
        if unit():
            done += 1
        else:
            failed += 1
    return time.perf_counter() - t_start, done, failed
