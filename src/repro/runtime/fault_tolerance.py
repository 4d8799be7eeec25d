"""Fault-tolerant training runtime.

Mechanisms (designed for 1000+ node clusters, exercised single-host here):

  * checkpoint/restart — CheckpointManager (async, atomic, resharding);
    restart resumes bit-exactly because the data pipeline is stateless
    (batch = f(seed, step)).
  * preemption handling — SIGTERM/SIGINT flips a flag; the loop finishes
    the current step, writes a final checkpoint, exits cleanly (the
    standard TPU-pod maintenance-event protocol).
  * watchdog — a step deadline detects hung collectives (dead host /
    stuck NCCL-analogue); on a real pod the runner would kill + restart
    the job from the last checkpoint, here it raises.
  * straggler mitigation — per-step wall-times feed an EWMA; steps slower
    than ``straggler_factor`` x EWMA are logged with their step id so an
    orchestrator can quarantine the offending host; the synchronous-SGD
    semantics are unchanged (deterministic replay makes the quarantine
    cheap).
  * elastic rescale — restore() accepts a different mesh: shardings come
    from the CURRENT mesh, leaves are resharded on load.
"""
from __future__ import annotations

import signal
import time
from typing import Callable, Optional

import jax

from repro.obs import metrics as obs_metrics


def _record(metrics) -> None:
    """The step's declared metrics into ``repro.obs``: counters added,
    gauges set; the rest (loss, learning rate, ...) stay the caller's."""
    for name, value in metrics.items():
        if name in obs_metrics.KNOWN_COUNTERS:
            obs_metrics.inc(name, float(value))
        elif name in obs_metrics.KNOWN_GAUGES:
            obs_metrics.gauge(name, float(value))


class Watchdog:
    def __init__(self, deadline_s: float = 1800.0):
        self.deadline_s = deadline_s
        self._last = time.monotonic()

    def pet(self):
        self._last = time.monotonic()

    def check(self):
        if time.monotonic() - self._last > self.deadline_s:
            raise TimeoutError(
                f"step exceeded {self.deadline_s}s — hung collective or "
                f"dead host; restart from last checkpoint")


class FaultTolerantLoop:
    def __init__(self, train_step: Callable, ckpt_mgr, pipeline,
                 checkpoint_every: int = 50, watchdog_s: float = 1800.0,
                 straggler_factor: float = 3.0):
        self.train_step = train_step
        self.ckpt = ckpt_mgr
        self.pipeline = pipeline
        self.checkpoint_every = checkpoint_every
        self.watchdog = Watchdog(watchdog_s)
        self.straggler_factor = straggler_factor
        self.preempted = False
        self.step_times = []
        self.straggler_steps = []
        self._ewma: Optional[float] = None
        self._orig_handlers = {}

    # ------------------------------------------------------------------
    def _install_signals(self):
        def handler(signum, frame):
            self.preempted = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._orig_handlers[sig] = signal.signal(sig, handler)
            except ValueError:
                pass  # non-main thread (tests)

    def _restore_signals(self):
        for sig, h in self._orig_handlers.items():
            signal.signal(sig, h)

    # ------------------------------------------------------------------
    def resume_or_init(self, state, shardings=None):
        """Restore the latest committed checkpoint if one exists."""
        latest = self.ckpt.latest_step()
        if latest is None:
            return state, 0
        restored, extra = self.ckpt.restore(latest, state, shardings)
        seed = pstep = None
        for k, v in extra.items():        # pipeline state rides in extra
            if k.endswith("['seed']"):
                seed = int(v)
            elif k.endswith("['step']"):
                pstep = int(v)
        if seed is not None and pstep is not None:
            self.pipeline.restore({"seed": seed, "step": pstep})
        else:
            self.pipeline.restore({"seed": self.pipeline.state.seed,
                                   "step": latest})
        return restored, latest

    def run(self, state, n_steps: int, start_step: int = 0,
            on_metrics: Optional[Callable] = None):
        """Run up to ``n_steps`` total steps; returns (state, last_step)."""
        self._install_signals()
        try:
            step = start_step
            while step < n_steps and not self.preempted:
                t0 = time.time()
                batch = self.pipeline.batch_at(step)
                state, metrics = self.train_step(state, batch)
                jax.block_until_ready(
                    jax.tree.leaves(metrics)[0])
                dt = time.time() - t0
                self.watchdog.check()
                self.watchdog.pet()
                self.step_times.append(dt)
                if self._ewma is None:
                    self._ewma = dt
                elif dt > self.straggler_factor * self._ewma:
                    self.straggler_steps.append((step, dt, self._ewma))
                else:
                    self._ewma = 0.9 * self._ewma + 0.1 * dt
                step += 1
                self.pipeline.state = self.pipeline.state.advance()
                _record(metrics)
                if on_metrics is not None:
                    on_metrics(step, metrics, dt)
                if step % self.checkpoint_every == 0:
                    self.ckpt.save(step, state,
                                   extra=self.pipeline.checkpoint())
            if self.preempted:
                # graceful preemption: final synchronous checkpoint
                self.ckpt.async_write = False
                self.ckpt.save(step, state,
                               extra=self.pipeline.checkpoint())
            self.ckpt.wait()
            return state, step
        finally:
            self._restore_signals()
