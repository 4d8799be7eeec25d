"""Named counters and gauges with a frozen JSON snapshot schema.

Counters are monotonically increasing event counts
(``batch_replay.scalar_fallback``, ``dse.cache.hits``); gauges are
last-write-wins levels (``profile.achieved_tflops``).  Names are dotted
``<subsystem>.<noun>[.<qualifier>]`` — see DESIGN.md §observability for
the naming discipline.

Two accumulation levels:

* a process-global root registry (``root()``) that everything folds
  into eventually, and
* contextvar-stacked SCOPES (``with scope() as m:``) giving a region —
  one ``Study.run()``, one fidelity harness sweep — its own registry.
  On exit a scope folds its counts into its parent (outer scope or the
  root), so per-run metric blocks and whole-process totals coexist.

``inc``/``gauge`` write to the innermost scope and, when a tracer is
installed (``repro.obs.trace``), also emit a counter sample so Perfetto
renders the counter as a track over time.  ``snapshot()`` is the frozen
wire format (``METRICS_SCHEMA``) embedded in ``StudyResult.provenance``
and round-tripped through its JSON artifact.
"""
from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar
from typing import Dict, Optional

from repro.obs import trace as _trace

# Frozen snapshot schema: {"schema": 1, "counters": {name: number},
# "gauges": {name: number}}.  Bump only on incompatible change.
METRICS_SCHEMA = 1

# Declared metric names.  Every ``inc``/``gauge`` call site with a
# literal name must use a name listed here — enforced statically by
# ``repro.analysis`` (the determinism/schema rule), so a typo'd or
# undeclared metric name fails `cli lint` instead of silently forking
# the snapshot schema consumers key on.
KNOWN_COUNTERS = frozenset({
    "batch_replay.jax_calls",
    "batch_replay.jax_pad_rows",
    "batch_replay.jax_retraces",
    "batch_replay.records",
    "batch_replay.scalar_fallback",
    "batched_sim.d2h_bytes",
    "batched_sim.h2d_bytes",
    "batched_sim.jax_calls",
    "batched_sim.jax_pad_rows",
    "batched_sim.jax_retraces",
    "batched_sim.jax_rows",
    "compile_batch.records",
    "dse.cache.fallback_rows",
    "dse.cache.hits",
    "dse.cache.sim",
    "moe.gmm_pad_rows",
    "moe.routed_rows",
    "outer.event_replayed",
    "outer.variant_cache.hits",
    "outer.variants_evaluated",
    "pareto.dup_rows",
    "pareto.rows",
    "pareto.staircase_rows",
    "profile.kernels",
    "profile.measurements",
})
KNOWN_GAUGES = frozenset({
    "moe.load_max_over_mean",
    "profile.achieved_gbs",
    "profile.achieved_tflops",
})


class Metrics:
    """One registry of counters and gauges."""

    __slots__ = ("counters", "gauges")

    def __init__(self):
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}

    def inc(self, name: str, n: float = 1) -> float:
        v = self.counters.get(name, 0) + n
        self.counters[name] = v
        return v

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    def snapshot(self) -> dict:
        return {"schema": METRICS_SCHEMA,
                "counters": dict(self.counters),
                "gauges": dict(self.gauges)}

    def fold_into(self, parent: "Metrics") -> None:
        for k, v in self.counters.items():
            parent.counters[k] = parent.counters.get(k, 0) + v
        parent.gauges.update(self.gauges)


_ROOT = Metrics()
_SCOPE: ContextVar[Optional[Metrics]] = ContextVar(
    "repro_obs_metrics", default=None)


def root() -> Metrics:
    """The process-global registry every scope eventually folds into."""
    return _ROOT


def active() -> Metrics:
    """The innermost scope, or the root when none is open."""
    m = _SCOPE.get()
    return m if m is not None else _ROOT


def inc(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` in the active registry (and sample
    it on the installed tracer, if any)."""
    v = active().inc(name, n)
    tr = _trace.current_tracer()
    if tr is not None:
        tr.sample(name, v)


def gauge(name: str, value: float) -> None:
    """Set gauge ``name`` in the active registry (and, like ``inc``,
    sample it on the installed tracer so Perfetto renders the gauge as
    a counter track over time — e.g. the profiling harness's achieved
    FLOP/s)."""
    active().gauge(name, value)
    tr = _trace.current_tracer()
    if tr is not None:
        tr.sample(name, float(value))


def reduce_stacked(values: dict) -> dict:
    """Per-call values stacked on a leading axis (layers, micro-batches)
    -> one value each: declared gauges averaged, everything else summed
    as a count.  Works on any array type with ``mean``/``sum``."""
    return {k: v.mean(0) if k in KNOWN_GAUGES else v.sum(0)
            for k, v in values.items()}


@contextmanager
def scope():
    """Fresh registry for the block; folds into the parent on exit."""
    m = Metrics()
    token = _SCOPE.set(m)
    try:
        yield m
    finally:
        _SCOPE.reset(token)
        m.fold_into(active())
