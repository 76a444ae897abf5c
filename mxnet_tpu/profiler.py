"""Profiler: per-op event recording + Chrome-trace dump + XProf bridge.

Reference analog: src/profiler/ (Profiler singleton with mode bitmask,
per-device stat queues, Chrome tracing JSON via DumpProfile — profiler.h:251,
:299) and python/mxnet/profiler.py (set_config/set_state/dump/dumps).

TPU-native split: XLA owns device-side timing, so device kernels are
profiled with the JAX/XProf tracer (``tensorboard_dir`` option → TensorBoard
'Profile' tab). What this module records natively is the *host-side* op
stream — every imperative invoke, with dispatch wall time — dumped in Chrome
tracing format (chrome://tracing / Perfetto), plus aggregate tables like the
reference's ``dumps(); aggregate_stats=True``.

Async attribution (the reference's "dispatch vs run" distinction, made
explicit in the events rather than a docstring caveat): under the default
async engine an op event's duration is host DISPATCH time — the op
returns before the device ran it — so every per-op event carries
``args.phase = "dispatch"`` (``"sync"`` under MXNET_ENGINE_TYPE=
NaiveEngine, where ops block until complete and the duration is true
wall time). The moments work actually COMPLETES appear on the same
timeline as the step-phase spans the telemetry subsystem records
(``cat: "step"``: window residency push→retire and the blocking retire
wait, stamped from ``engine.DispatchWindow``'s retire timestamps, plus
batch_fetch/h2d_wait/dispatch/checkpoint) — see docs/OBSERVABILITY.md.
So a Chrome trace of a pipelined run is honest: dispatch-time op slices,
retire-time step boundaries, one merged stream.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Optional

from .base import MXNetError
from .ops import registry as _registry

__all__ = ["set_config", "set_state", "state", "dump", "dumps", "pause",
           "resume", "scope", "Profiler", "dump_memory", "memory_summary",
           "Domain", "Task", "Frame", "Event", "Counter", "Marker",
           "profiler_set_config", "profiler_set_state", "dump_profile",
           "set_kvstore_handle"]


class Profiler:
    """Process-global profiler (reference Profiler singleton)."""

    _instance = None
    # bare on purpose: profiler sits below the audit layer; leaf lock
    _lock = threading.Lock()  # mx-lint: allow=MXA009

    def __init__(self):
        self.filename = "profile.json"
        self.aggregate_stats = False
        self.tensorboard_dir: Optional[str] = None
        self.running = False
        self.paused = False
        self._events = []
        # bare on purpose: profiler sits below the audit layer; leaf lock
        self._ev_lock = threading.Lock()  # mx-lint: allow=MXA009
        self._scope = ""
        self._hook_installed = False
        self._tb_active = False

    @classmethod
    def get(cls) -> "Profiler":
        if cls._instance is None:
            with cls._lock:
                if cls._instance is None:
                    cls._instance = Profiler()
        return cls._instance

    # -- recording ---------------------------------------------------------

    def record(self, name: str, t_start: float, t_end: float,
               cat: str = "operator", args: Optional[dict] = None):
        """Append one complete ('X') slice; ``args`` lands in the Chrome
        event's args field — per-op events carry the dispatch/sync phase,
        step spans carry {step, phase} (docs/OBSERVABILITY.md)."""
        if not self.running or self.paused:
            return
        ev = {
            "name": (self._scope + name) if self._scope else name,
            "cat": cat, "ph": "X",
            "ts": t_start * 1e6, "dur": (t_end - t_start) * 1e6,
            "pid": os.getpid(), "tid": threading.get_ident() % 100000,
        }
        if args:
            ev["args"] = args
        with self._ev_lock:
            self._events.append(ev)

    @staticmethod
    def _op_phase() -> str:
        """Honest attribution for per-op durations: host 'dispatch' time
        under the async engine (the op returned before the device ran
        it), true 'sync' wall time under NaiveEngine."""
        from .engine import get as _engine_get
        return "sync" if _engine_get().is_naive else "dispatch"

    def _invoke_wrapper(self, name, fn):
        prof = self

        def wrapped(*args, **kwargs):
            if not prof.running or prof.paused:
                return fn(*args, **kwargs)
            import jax
            t0 = time.perf_counter()
            try:
                # host-side XProf event per framework op; device kernels are
                # attributed via the named_scope in the invoke funnel
                with jax.profiler.TraceAnnotation(name):
                    return fn(*args, **kwargs)
            finally:
                prof.record(name, t0, time.perf_counter(),
                            args={"phase": prof._op_phase()})
        return wrapped

    def _install_hook(self):
        if not self._hook_installed:
            _registry.add_invoke_wrapper(self._invoke_wrapper)
            self._hook_installed = True

    # -- state -------------------------------------------------------------

    def set_config(self, **kwargs):
        known = {"filename", "aggregate_stats", "tensorboard_dir",
                 # reference mode flags, accepted for parity (host stream
                 # records all imperative ops; XLA owns device timing):
                 "profile_all", "profile_symbolic", "profile_imperative",
                 "profile_memory", "profile_api", "continuous_dump"}
        for k, v in kwargs.items():
            if k not in known:
                raise MXNetError(f"unknown profiler option {k!r}")
            if k in ("filename", "aggregate_stats", "tensorboard_dir"):
                setattr(self, k, v)

    def set_state(self, state: str):
        if state not in ("run", "stop"):
            raise MXNetError("profiler state must be 'run' or 'stop'")
        if state == "run":
            self._install_hook()
            self.running = True
            if self.tensorboard_dir and not self._tb_active:
                import jax
                jax.profiler.start_trace(self.tensorboard_dir)
                self._tb_active = True
        else:
            self.running = False
            if self._tb_active:
                import jax
                jax.profiler.stop_trace()
                self._tb_active = False

    def dump(self, finished: bool = True):
        """Write accumulated events as Chrome tracing JSON."""
        with self._ev_lock:
            events = list(self._events)
            if finished:
                self._events.clear()
        with open(self.filename, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)

    def dumps(self, reset: bool = False) -> str:
        """Aggregate per-op table (reference aggregate_stats output)."""
        with self._ev_lock:
            events = list(self._events)
            if reset:
                self._events.clear()
        agg = {}
        for e in events:
            st = agg.setdefault(e["name"], [0, 0.0, float("inf"), 0.0])
            st[0] += 1
            st[1] += e["dur"]
            st[2] = min(st[2], e["dur"])
            st[3] = max(st[3], e["dur"])
        lines = [f"{'Name':<40s}{'Calls':>8s}{'Total(us)':>14s}"
                 f"{'Min(us)':>12s}{'Max(us)':>12s}{'Avg(us)':>12s}"]
        for name in sorted(agg, key=lambda n: -agg[n][1]):
            c, tot, mn, mx = agg[name]
            lines.append(f"{name:<40s}{c:>8d}{tot:>14.1f}{mn:>12.1f}"
                         f"{mx:>12.1f}{tot / c:>12.1f}")
        return "\n".join(lines)


def set_config(**kwargs):
    Profiler.get().set_config(**kwargs)


def set_state(state: str = "stop"):
    Profiler.get().set_state(state)


def state() -> str:
    return "run" if Profiler.get().running else "stop"


def dump(finished: bool = True):
    Profiler.get().dump(finished)


def dumps(reset: bool = False) -> str:
    return Profiler.get().dumps(reset)


def dump_memory(path: str = "memory.pprof") -> str:
    """Write a device-memory profile (reference storage profiler,
    src/profiler/storage_profiler.cc + pooled_storage_manager.h:207 hook;
    here the allocator is XLA's, so the profile is jax's pprof-format
    device memory snapshot — inspect with `pprof` or upload to
    TensorBoard's memory viewer)."""
    import jax
    blob = jax.profiler.device_memory_profile()
    with open(path, "wb") as f:
        f.write(blob)
    return path


def memory_summary() -> dict:
    """Per-device memory totals (the aggregate the reference printed
    from its storage profiler), routed through the telemetry catalog:
    each read refreshes the ``mx_mem_device_bytes_in_use`` /
    ``_peak_bytes`` / ``_limit_bytes`` gauges instead of living in an
    ad-hoc dict only this call ever saw.

    Backends with allocator counters (TPU/GPU BFC) report
    ``{bytes_in_use, peak_bytes_in_use, bytes_limit, source:
    "allocator"}``. XLA:CPU exposes NO allocator stats — the documented
    fallback prices every live ``jax.Array`` shard on its device
    (``source: "live_arrays"``; peak/limit stay None because live
    accounting has no high-water mark) rather than returning the silent
    Nones this function used to."""
    from .telemetry.memory import device_memory_stats
    return device_memory_stats()


def pause():
    Profiler.get().paused = True


def resume():
    Profiler.get().paused = False


@contextlib.contextmanager
def scope(name: str):
    """Prefix recorded op names (reference __profiler_scope__ attr,
    c_api_ndarray.cc:104); also emits a JAX trace annotation so the scope
    shows up in XProf device traces."""
    prof = Profiler.get()
    old = prof._scope
    prof._scope = old + name.rstrip(":") + ":"
    try:
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        prof._scope = old


# ---------------------------------------------------------------------------
# instrumentation object API (reference profiler.py:228-520: Domain,
# Task, Frame, Event, Counter, Marker over the MXProfile* C API). Here
# each object writes straight into the profiler's Chrome-trace event
# stream: durations as 'X' slices categorized by domain, counters as
# 'C' samples, markers as 'i' instants — visible in chrome://tracing
# next to the per-op events.
# ---------------------------------------------------------------------------

class Domain:
    """Category grouping for instrumentation objects (reference :228)."""

    def __init__(self, name):
        self.name = name

    def __str__(self):
        return self.name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _DurationObject:
    """start()/stop() pair recording one Chrome-trace slice."""

    _cat_suffix = ""

    def __init__(self, domain, name):
        self.name = name
        self._domain = domain
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if self._t0 is None:
            raise MXNetError(f"{type(self).__name__} {self.name!r}: "
                             "stop() before start()")
        Profiler.get().record(self.name, self._t0, time.perf_counter(),
                              cat=str(self._domain) + self._cat_suffix)
        self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def __str__(self):
        return self.name


class Task(_DurationObject):
    """Accumulated logical unit of work (reference :287)."""


class Frame(_DurationObject):
    """Per-pass discrete duration, e.g. one video frame
    (reference :329)."""

    _cat_suffix = ":frame"


class Event(_DurationObject):
    """Per-thread demarcated event without a domain (reference :371)."""

    def __init__(self, name):
        super().__init__(_EVENT_DOMAIN, name)


_EVENT_DOMAIN = Domain("event")


class Counter:
    """Numeric counter sampled into the trace (reference :420):
    set_value/increment/decrement emit Chrome 'C' events."""

    def __init__(self, domain, name, value=None):
        self.name = name
        self._domain = domain
        self._value = 0
        if value is not None:
            self.set_value(value)

    def _emit(self):
        prof = Profiler.get()
        if not prof.running or prof.paused:
            return
        with prof._ev_lock:
            prof._events.append({
                "name": self.name, "cat": str(self._domain), "ph": "C",
                "ts": time.perf_counter() * 1e6, "pid": os.getpid(),
                "tid": threading.get_ident() % 100000,
                "args": {"value": self._value},
            })

    def set_value(self, value):
        self._value = value
        self._emit()

    def increment(self, delta=1):
        self._value += delta
        self._emit()

    def decrement(self, delta=1):
        self._value -= delta
        self._emit()

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self

    def __str__(self):
        return self.name


class Marker:
    """Instant marker (reference :470): mark(scope) emits a Chrome 'i'
    event with the given scope ('process'|'thread'|'global')."""

    _SCOPES = {"process": "p", "thread": "t", "global": "g"}

    def __init__(self, domain, name):
        self.name = name
        self._domain = domain

    def mark(self, scope="process"):
        prof = Profiler.get()
        if not prof.running or prof.paused:
            return
        with prof._ev_lock:
            prof._events.append({
                "name": self.name, "cat": str(self._domain), "ph": "i",
                "ts": time.perf_counter() * 1e6, "pid": os.getpid(),
                "tid": threading.get_ident() % 100000,
                "s": self._SCOPES.get(scope, "p"),
            })


# deprecated 1.x aliases (reference profiler.py keeps them with warnings)
def profiler_set_config(mode="symbolic", filename="profile.json"):
    import warnings
    warnings.warn("profiler.profiler_set_config is deprecated; use "
                  "profiler.set_config", DeprecationWarning, stacklevel=2)
    set_config(filename=filename)


def profiler_set_state(state="stop"):
    import warnings
    warnings.warn("profiler.profiler_set_state is deprecated; use "
                  "profiler.set_state", DeprecationWarning, stacklevel=2)
    set_state(state)


def dump_profile():
    import warnings
    warnings.warn("profiler.dump_profile is deprecated; use "
                  "profiler.dump", DeprecationWarning, stacklevel=2)
    dump(True)


def set_kvstore_handle(handle=None):
    """No-op shim (reference wires the kvstore's server-side profiler
    over the C API; kvstore here is in-process, so its ops already land
    in this profiler's stream)."""
    return None
