"""Contextvar-scoped tracing spans — the host-side flight recorder.

A ``Tracer`` collects COMPLETED spans: every ``with span("name", k=v):``
block appends one ``{name, ts_ns, dur_ns, depth, id, parent, args}``
record when it exits, timestamped with ``time.perf_counter_ns`` relative
to the tracer's birth.  ``id`` is unique within the tracer (numbered in
the order spans open); ``parent`` is the ``id`` of the span that was
innermost when this one opened (``None`` at a root), so the call tree
rebuilds exactly from the records.  Spans nest lexically and are
LIFO-checked — closing a span that is not the innermost open one raises,
as does a clock that runs backwards, so a trace that exports cleanly is
structurally sound by construction.

While a tracer is installed and ``jax`` has already been imported by
someone else, each span also enters a ``jax.profiler.TraceAnnotation``
of the same name (args as its metadata, which jax encodes only while
the profiler records).  A ``jax.profiler`` capture then shows the
program's stages on its host plane, on the same clock as the chip's
operations.  This module never imports jax itself.

The layer is built to be left in hot loops permanently: when no tracer
is installed (the default), ``span()`` returns a module-level no-op
singleton — no allocation, no clock read, two dict lookups — so
instrumented code costs nothing when tracing is off (pinned by an
allocation guard in tests/test_obs.py).

Install a tracer for a region with::

    with tracing() as tr:
        with span("study.run", driver="exhaustive"):
            ...
    export.chrome_trace_from_tracer(tr)

The contextvar scoping means concurrent tasks (threads, asyncio) each
see their own tracer, and library code never needs a tracer argument.
"""
from __future__ import annotations

import sys
import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Dict, List, Optional, Tuple

_TRACER: ContextVar[Optional["Tracer"]] = ContextVar(
    "repro_obs_tracer", default=None)


class Tracer:
    """Accumulates completed spans and counter samples for one region."""

    def __init__(self):
        self.t0_ns = time.perf_counter_ns()
        self.events: List[Dict[str, Any]] = []
        # (name, ts_ns, value) — cumulative counter values over time,
        # exported as Chrome-trace "C" counter tracks
        self.counter_samples: List[Tuple[str, int, float]] = []
        self._stack: List["_Span"] = []
        self._next_id = 0

    def now_ns(self) -> int:
        return time.perf_counter_ns() - self.t0_ns

    def sample(self, name: str, value: float) -> None:
        self.counter_samples.append((name, self.now_ns(), float(value)))

    @property
    def depth(self) -> int:
        return len(self._stack)


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` if jax is already imported, else
    None (looked up, never imported: the numpy-only paths stay free of
    jax)."""
    prof = getattr(sys.modules.get("jax"), "profiler", None)
    return getattr(prof, "TraceAnnotation", None)


class _Span:
    """Live span; records itself on the owning tracer at ``__exit__``."""

    __slots__ = ("tracer", "name", "args", "start_ns", "_depth", "_id",
                 "_parent", "_ann")

    def __init__(self, tracer: Tracer, name: str,
                 args: Optional[Dict[str, Any]]):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.start_ns = 0
        self._depth = 0
        self._id = 0
        self._parent: Optional[int] = None
        self._ann = None

    def set(self, **args: Any) -> None:
        """Add args known only inside the span (e.g. whether a call
        compiled); they go on the record and the profiler annotation."""
        self.args = {**(self.args or {}), **args}
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self) -> "_Span":
        tr = self.tracer
        stack = tr._stack
        self._depth = len(stack)
        self._parent = stack[-1]._id if stack else None
        self._id = tr._next_id
        tr._next_id += 1
        stack.append(self)
        ann = _profiler_annotation()
        if ann is not None:
            self._ann = ann(self.name, **(self.args or {}))
            self._ann.__enter__()
        self.start_ns = tr.now_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tr = self.tracer
        if not tr._stack or tr._stack[-1] is not self:
            open_name = tr._stack[-1].name if tr._stack else None
            raise RuntimeError(
                f"span {self.name!r} closed out of LIFO order "
                f"(innermost open span: {open_name!r})")
        tr._stack.pop()
        end_ns = tr.now_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if end_ns < self.start_ns:
            raise RuntimeError(
                f"span {self.name!r}: end {end_ns} < start "
                f"{self.start_ns} — non-monotonic clock")
        tr.events.append({"name": self.name, "ts_ns": self.start_ns,
                          "dur_ns": end_ns - self.start_ns,
                          "depth": self._depth, "id": self._id,
                          "parent": self._parent, "args": self.args})
        return False


class _NullSpan:
    """Zero-cost stand-in handed out when tracing is disabled."""

    __slots__ = ()

    def set(self, **args: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, **args: Any):
    """Context manager timing one region.  With no tracer installed this
    returns a shared no-op singleton: safe (and free) in hot loops."""
    tr = _TRACER.get()
    if tr is None:
        return _NULL_SPAN
    return _Span(tr, name, args or None)


def current_tracer() -> Optional[Tracer]:
    return _TRACER.get()


@contextmanager
def tracing(tracer: Optional[Tracer] = None):
    """Install ``tracer`` (or a fresh one) for the dynamic extent of the
    block; yields the tracer for export."""
    tr = tracer if tracer is not None else Tracer()
    token = _TRACER.set(tr)
    try:
        yield tr
    finally:
        _TRACER.reset(token)
    if tr._stack:
        raise RuntimeError(
            f"{len(tr._stack)} span(s) never closed "
            f"(innermost: {tr._stack[-1].name!r})")
