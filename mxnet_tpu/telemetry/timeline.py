"""Step-timeline tracing: structured spans over a train step's lifecycle.

One pipelined train step passes through five host-observable phases —

    batch_fetch   producer pulls + stages the batch (prefetcher thread)
    h2d_wait      consumer wait on the staged device-resident batch
    dispatch      host time inside the compiled step call (enqueue)
    window        residency in the in-flight dispatch window (push->done);
                  carries the step's device counters (``counters``)
    retire        the blocking wait at the window boundary (FIFO oldest)

plus ``checkpoint`` for snapshot captures. Each instrumentation point
(engine.DispatchWindow, gluon.data.DevicePrefetcher, gluon.TrainLoop,
checkpoint.TrainCheckpointManager) wraps its region in
:meth:`StepTimeline.span` (``window``, which opens in one call and closes
in another, calls :meth:`StepTimeline.record`); the timeline

- feeds the ``mx_step_phase_seconds{phase=}`` histogram in the metrics
  registry (always),
- keeps a bounded ring of raw span events for exact p50/p99 summaries
  (tools/diagnose.py --telemetry), and
- when the host profiler is running, emits each span into the SAME
  Chrome-trace stream as the per-op events (``cat: "step"``, args
  carrying the step number and phase) — so host ops and step phases land
  on one chrome://tracing / Perfetto timeline.

While a :meth:`~StepTimeline.span` is open it also holds a
``jax.profiler.TraceAnnotation`` named ``mx:<phase>`` (``dispatch`` holds
the TrainLoop's ``StepTraceAnnotation("mx_train_step")`` instead, which
XProf groups device kernels by): under ``jax.profiler.start_trace`` the
span sits on the xplane host line of the thread it ran on, on the same
clock as the device's events. With no trace running the annotation is a
no-op of the runtime's.

Span recording is gated by :func:`active` at the call sites: on when
``MXNET_TELEMETRY`` is set (``mx.telemetry.enable()``) or when the host
profiler is running; the registry counters stay always-on regardless.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..base import MXNetError
from . import names
from .registry import default as _default_registry

__all__ = ["PHASES", "ANNOTATION_PREFIX", "StepTimeline", "timeline"]

#: the span vocabulary — documented in docs/OBSERVABILITY.md; record()
#: rejects anything else so the phase label stays bounded
PHASES = ("batch_fetch", "h2d_wait", "dispatch", "window", "retire",
          "checkpoint")


def _check_phase(phase: str):
    if phase not in PHASES:
        raise MXNetError(
            f"unknown step phase {phase!r}; the span vocabulary is "
            f"{PHASES} (docs/OBSERVABILITY.md)")


#: a span's name on the profiler's host line is this + its phase
ANNOTATION_PREFIX = "mx:"


class _Span:
    """One open span (:meth:`StepTimeline.span`)."""

    __slots__ = ("_timeline", "_phase", "_step", "_annotation", "_t0")

    def __init__(self, timeline, phase, step, annotation):
        self._timeline, self._phase, self._step = timeline, phase, step
        self._annotation = annotation

    def __enter__(self):
        if self._annotation is None:
            kw = {} if self._step is None else {"step": self._step}
            self._annotation = TraceAnnotation(
                ANNOTATION_PREFIX + self._phase, **kw)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is None:    # a region that raised is not a span
            self._timeline.record(self._phase, self._t0, t1,
                                  step=self._step)
        return False


class StepTimeline:
    """Bounded ring of step-phase spans + the phase-duration histogram."""

    def __init__(self, capacity: int = 8192):
        # five spans a step: room for a 20 s window of 12 ms steps, so a
        # reader of the window's spans (the benchmark's) sees every step
        self._events: "deque[dict]" = deque(maxlen=capacity)
        # bare on purpose: telemetry substrate: the audit's metrics path runs under it
        self._lock = threading.Lock()  # mx-lint: allow=MXA009
        self._hist = _default_registry().histogram(
            names.STEP_PHASE_SECONDS, label_key="phase")

    # ---------------- recording ----------------
    def span(self, phase: str, step: Optional[int] = None,
             annotation=None):
        """Context manager around one region: ``perf_counter`` stamps on
        entry and exit, :meth:`record` on a clean exit, and for as long
        as it is open a ``TraceAnnotation("mx:<phase>", step=step)`` on
        the calling thread's profiler line — or ``annotation``, where the
        site has one of its own to hold (the TrainLoop's step
        annotation). Call sites gate it on ``telemetry.active()``."""
        _check_phase(phase)
        return _Span(self, phase, step, annotation)

    def record(self, phase: str, t0: float, t1: float,
               step: Optional[int] = None,
               counters: Optional[dict] = None):
        """Record one span: ``t0``/``t1`` are ``time.perf_counter()``
        stamps; ``step`` is the global step number where the
        instrumentation point knows it (prefetcher spans use their own
        batch ordinal); ``counters`` is what the device computed about
        the step's own work (``telemetry.device_counters``; the
        ``window`` span of a retired step carries them, ``{}`` where its
        program emitted none). Also mirrors the span into the profiler's
        Chrome-trace stream when it is running."""
        _check_phase(phase)
        dur = max(0.0, t1 - t0)
        self._hist.observe(dur, label=phase)
        event = {"phase": phase, "step": step, "t0": t0, "t1": t1,
                 "dur": dur}
        if counters is not None:
            event["counters"] = counters
        with self._lock:
            self._events.append(event)
        self._emit_trace(event)

    @staticmethod
    def _emit_trace(event):
        from ..profiler import Profiler
        prof = Profiler.get()
        if prof.running and not prof.paused:
            args = {k: event[k] for k in ("step", "phase", "counters")
                    if k in event}
            prof.record(f"step:{event['phase']}", event["t0"], event["t1"],
                        cat="step", args=args)

    # ---------------- queries ----------------
    def events(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            evs = list(self._events)
        return evs if n is None else evs[-n:]

    def clear(self):
        with self._lock:
            self._events.clear()

    def summary(self, last_steps: Optional[int] = None) -> Dict[str, dict]:
        """Exact per-phase stats over the retained ring (optionally the
        spans of the last N distinct step numbers): count, total/p50/p99
        milliseconds — what ``tools/diagnose.py --telemetry`` prints."""
        evs = self.events()
        if last_steps is not None:
            steps = sorted({e["step"] for e in evs
                            if e["step"] is not None})
            keep = set(steps[-last_steps:])
            evs = [e for e in evs
                   if e["step"] is None or e["step"] in keep]
        by_phase: Dict[str, List[float]] = {}
        for e in evs:
            by_phase.setdefault(e["phase"], []).append(e["dur"])
        import numpy as onp
        out = {}
        for phase in PHASES:
            durs = by_phase.get(phase)
            if not durs:
                continue
            a = onp.asarray(durs)
            out[phase] = {
                "count": int(a.size),
                "total_ms": float(a.sum() * 1e3),
                "p50_ms": float(onp.percentile(a, 50) * 1e3),
                "p99_ms": float(onp.percentile(a, 99) * 1e3),
                "max_ms": float(a.max() * 1e3),
            }
        return out


_timeline = StepTimeline()


def timeline() -> StepTimeline:
    """The process-global step timeline (``mx.telemetry.timeline()``)."""
    return _timeline
