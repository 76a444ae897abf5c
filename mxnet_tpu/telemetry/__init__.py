"""mx.telemetry — unified runtime telemetry (docs/OBSERVABILITY.md).

Three cooperating pieces, replacing the scattered ad-hoc stats
(``guard.sync_counts``, ``engine_stats()``, ``compile_cache_stats()``,
hand-rolled bench plumbing) with one subsystem:

1. **Step-timeline tracing** (:mod:`.timeline`): structured spans for a
   train step's full lifecycle — batch fetch, prefetch h2d wait, host
   dispatch, window residency, retire — recorded from instrumentation
   points inside ``engine.DispatchWindow``, ``gluon.data
   .DevicePrefetcher``, ``gluon.TrainLoop``, and
   ``checkpoint.TrainCheckpointManager``, and emitted into the SAME
   Chrome-trace stream as the profiler's per-op events.
2. **Process-global metrics registry** (:mod:`.registry`): counters /
   gauges / histograms with bounded cardinality, named exclusively from
   the catalog in :mod:`.names`, behind pluggable exporters
   (:mod:`.exporters`): JSON :func:`snapshot`, Prometheus text file,
   periodic structured-log heartbeat.
3. **Anomaly watchdog** (:mod:`.watchdog`): the retire-to-retire step
   time, NaN/inf-loss and step-time-stall detection piggybacked on
   window retires.

Two further domains build on these: device memory (:mod:`.memory` —
HBM accounting, buffer census, OOM forensics) and training numerics
(:mod:`.numerics` — in-program grad/param health threaded through the
compiled step, divergence watchdog, NaN-origin forensics). What the
device computes about a step's own work (:mod:`.device_counters`: the
pairs a router gave its held experts) leaves the program the same way
and lands on the step's ``window`` span.

Cost model: registry counters/gauges are ALWAYS on (one uncontended
lock + float update per event, no host syncs — the transfer guard is
the enforcement mechanism). Span recording and the watchdog are gated
by :func:`enabled` — ``MXNET_TELEMETRY=1`` or :func:`enable` — and the
watchdog's NaN check adds one small device->host read per retire,
inside the already-blessed retire sync.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

from . import names
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       default as registry)
from .timeline import PHASES, StepTimeline, timeline
from .watchdog import Watchdog, stall_factor, watchdog
from . import memory
from .memory import BufferCensus, MemoryReport, census
from . import numerics
from .numerics import NumericsMonitor, StepNumerics
from . import device_counters
from .device_counters import StepAux
from .exporters import (SCHEMA_VERSION, Heartbeat, heartbeat_interval,
                        prometheus_file, prometheus_text, snapshot,
                        start_heartbeat, stop_heartbeat,
                        write_prometheus)

__all__ = ["names", "registry", "MetricsRegistry", "Counter", "Gauge",
           "Histogram", "timeline", "StepTimeline", "PHASES",
           "watchdog", "Watchdog", "stall_factor", "snapshot",
           "prometheus_text", "write_prometheus", "prometheus_file",
           "Heartbeat", "start_heartbeat", "stop_heartbeat",
           "heartbeat_interval", "SCHEMA_VERSION", "enabled", "enable",
           "span", "value", "reset", "memory", "census", "BufferCensus",
           "MemoryReport", "numerics", "NumericsMonitor",
           "StepNumerics", "device_counters", "StepAux"]

# every catalog series exists from import time: an exporter always shows
# the full schema (zero is information; absence is a question)
registry().ensure_catalog()

_OVERRIDE: Optional[bool] = None


def enabled() -> bool:
    """Whether the gated (span/watchdog) half of telemetry is on:
    ``MXNET_TELEMETRY`` truthy, or an :func:`enable` override. The
    always-on registry counters do not consult this."""
    if _OVERRIDE is not None:
        return _OVERRIDE
    v = os.environ.get("MXNET_TELEMETRY", "").strip().lower()
    return v not in ("", "0", "off", "false", "no")


def enable(on: bool = True):
    """Programmatic override of ``MXNET_TELEMETRY`` (``enable(None)``
    restores env control)."""
    global _OVERRIDE
    _OVERRIDE = on


def active() -> bool:
    """Span-recording gate for instrumentation points: telemetry is
    enabled OR the host profiler is running (so a profiler session gets
    step spans in its Chrome trace without MXNET_TELEMETRY)."""
    if enabled():
        return True
    from ..profiler import Profiler
    prof = Profiler.get()
    return prof.running and not prof.paused


_NO_SPAN = contextlib.nullcontext()


def span(phase: str, step: Optional[int] = None):
    """The gated span of an instrumentation point: ``timeline().span``
    while :func:`active`, else a shared do-nothing context — with
    telemetry off a site pays this one branch, stamps no clock and
    enters no profiler annotation."""
    if active():
        return timeline().span(phase, step)
    return _NO_SPAN


def value(name: str, label: Optional[str] = None):
    """Convenience read of one series from the default registry."""
    return registry().value(name, label)


def reset():
    """Zero every metric, clear the timeline ring and the watchdog state
    (registrations, cached metric objects, and collectors survive) —
    the test/bench isolation hook. The buffer census is NOT cleared:
    its weakref pools track live objects, not accumulated values, so
    zeroing would silently untrack still-live buffers registered once
    at compile time (``memory.census().clear()`` exists for tests that
    need a fresh census)."""
    registry().reset()
    timeline().clear()
    watchdog().reset()
    numerics.monitor().reset()
