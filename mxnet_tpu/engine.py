"""Execution engine facade.

The reference schedules every op through a dependency engine with versioned
variables (reference: include/mxnet/engine.h:117-318, src/engine/threaded_engine.h).
On TPU, XLA/PjRt dispatch is already asynchronous and ordered per-buffer, so
the engine's dependency tracking is absorbed by the runtime. What survives is
the *semantic* surface the reference exposes and tests
(tests/python/unittest/test_engine.py):

- engine selection (``MXNET_ENGINE_TYPE``): ``ThreadedEnginePerDevice`` (the
  async default — ops return immediately, results materialize later) vs
  ``NaiveEngine`` (synchronous oracle — every op blocks until complete; the
  race-free debugging mode, reference src/engine/naive_engine.cc:51).
- ``wait_for_all`` / per-array ``wait_to_read`` sync points where async
  exceptions surface (reference src/engine/threaded_engine.cc:422-436).
- op bulking knobs (``set_bulk_size``) — a no-op here because XLA fuses
  compiled programs; kept for API parity.
"""
from __future__ import annotations

import contextlib
import time
import weakref
from collections import deque
from typing import Any, Callable, Optional

import jax

from .analysis.threads import mx_lock
from .base import MXNetError, get_env
from .testing.faults import fault_point

# telemetry is imported lazily (the package initializes subsystems in
# dependency order) and cached; the registry half is always-on, the
# span/watchdog half gates itself on MXNET_TELEMETRY
_TELEM = None


def _telemetry():
    global _TELEM
    if _TELEM is None:
        from . import telemetry as _t
        _TELEM = _t
    return _TELEM


# elastic device-loss detection (elastic/detect.py), lazily reached the
# same way — it classifies failures escaping the retire seam
_EDET = None


def _edetect():
    global _EDET
    if _EDET is None:
        from .elastic import detect as _d
        _EDET = _d
    return _EDET

__all__ = ["Engine", "get", "set_bulk_size", "bulk", "DispatchWindow",
           "inflight_steps"]


class Engine:
    """Process-global engine facade (reference Engine::Get singleton)."""

    _instance = None
    _lock = mx_lock("engine.singleton")

    def __init__(self, kind: str):
        self.kind = kind
        self._bulk_size = 0

    @property
    def is_naive(self) -> bool:
        return self.kind == "NaiveEngine"

    def maybe_sync(self, arrays):
        """NaiveEngine blocks after every op — the synchronous oracle mode."""
        if self.is_naive:
            for a in arrays:
                jax.block_until_ready(a)

    def wait_for_all(self):
        """Block until all pending async work completes; raises deferred
        errors (reference Engine::WaitForAll; rethrow contract
        threaded_engine.cc:422-436). Deferred computation errors MUST
        propagate from here — only the absence of the barrier API itself is
        tolerated, never an error it reports."""
        # drain live dispatch windows first: their retire path attributes
        # an async failure to the STEP that faulted, which this barrier
        # alone cannot do
        for w in list(_live_windows):
            w.drain()
        barrier = getattr(jax, "effects_barrier", None)
        if barrier is not None:
            barrier()
        # Sync all locally-addressable devices; PjRt surfaces async errors
        # here (remote workers sync their own — reference WaitForAll is
        # per-process too).
        for d in jax.local_devices():
            sync = getattr(d, "synchronize_all_activity", None)
            if sync is None:
                break
            sync()

    def set_bulk_size(self, size: int) -> int:
        """Reference ThreadedEngine::set_bulk_size (threaded_engine.h:414).
        XLA fusion makes bulking implicit; we retain the knob."""
        old, self._bulk_size = self._bulk_size, int(size)
        return old

    @property
    def bulk_size(self) -> int:
        return self._bulk_size


#: live DispatchWindows, drained by Engine.wait_for_all (mx.nd.waitall)
_live_windows: "weakref.WeakSet" = weakref.WeakSet()


def inflight_steps(default: int = 2) -> int:
    """The bounded dispatch-window size: how many train-step futures
    the host may keep outstanding before it blocks on the oldest.
    ``MXNET_INFLIGHT_STEPS`` when set, else ``default``; a value that
    does not parse gives ``default``, a negative one 0.
    ``NaiveEngine`` forces 0 — every step retires synchronously, the
    race-free oracle mode."""
    if get().is_naive:
        return 0
    try:
        return max(0, int(get_env("MXNET_INFLIGHT_STEPS", str(default))))
    except (TypeError, ValueError):
        return default


class DispatchWindow:
    """Bounded in-flight async dispatch — ``Engine::PushAsync`` /
    ``WaitForVar`` semantics on PjRt.

    JAX arrays are already futures: a compiled step RETURNS immediately
    while the device works. What the reference engine adds — and this
    class reproduces — is the *bounded* part: ``push()`` records each
    step's async result, and only when more than ``max_inflight`` results
    are outstanding does the host block, on the OLDEST one (FIFO, the
    WaitForVar of step N-k). That keeps the host a fixed number of steps
    ahead of the device instead of either running unboundedly ahead or
    (the pre-engine behavior) syncing every step.

    Error contract (reference threaded_engine.cc:422-436): an async
    failure surfaces at the retire of the step that faulted — wrapped in
    an :class:`MXNetError` naming that step's tag — never silently at a
    later sync point with an unrelated traceback.

    The retire wait is the ONE blessed host sync of the pipelined hot
    loop: it runs under ``analysis.guard.allow_transfers`` and is counted
    separately (``window_retire``) from the unblessed NDArray syncs the
    transfer guard flags.
    """

    def __init__(self, max_inflight: Optional[int] = None,
                 sync_fn: Optional[Callable[[Any], Any]] = None,
                 what: str = "train step"):
        self.max_inflight = inflight_steps() if max_inflight is None \
            else max(0, int(max_inflight))
        self._sync = sync_fn if sync_fn is not None \
            else jax.block_until_ready
        self._what = what
        self._pending: "deque[tuple]" = deque()
        # pushes/retires run on the dispatching thread, but abandon()
        # arrives from recovery paths (elastic supervisor, fleet
        # failover) — _pending and stats mutations are guarded; the
        # blocking sync itself stays OUTSIDE the critical section so
        # an abandon never waits behind a dead device
        self._mu = mx_lock("engine.window")
        self.stats = {"pushes": 0, "retires": 0, "errors": 0,
                      "max_pending": 0}
        self._last_retire_t: Optional[float] = None
        t = _telemetry()
        reg = t.registry()
        self._m_pushes = reg.counter(t.names.WINDOW_PUSHES)
        self._m_retires = reg.counter(t.names.WINDOW_RETIRES)
        self._m_errors = reg.counter(t.names.WINDOW_ERRORS)
        self._m_occupancy = reg.gauge(t.names.WINDOW_OCCUPANCY)
        self._m_capacity = reg.gauge(t.names.WINDOW_CAPACITY)
        self._m_capacity.set(self.max_inflight)
        _live_windows.add(self)

    def __len__(self) -> int:
        return len(self._pending)

    def push(self, payload, tag=None, aux=None):
        """Record one dispatched async result; returns immediately unless
        the window is over capacity, in which case the OLDEST entry
        retires (blocks until that step completed). ``aux`` is the
        step's optional ``telemetry.StepAux`` riding alongside the
        payload (a numerics record, the device counters its ops
        emitted): what the device computed is read at this entry's
        retire — inside the same blessed sync, after the step's program
        has completed — so both stay sync-free."""
        st = self.stats
        with self._mu:
            st["pushes"] += 1
            self._pending.append((tag, payload, aux, time.perf_counter()))
            if len(self._pending) > st["max_pending"]:
                st["max_pending"] = len(self._pending)
            depth = len(self._pending)
        self._m_pushes.inc()
        # re-assert per push: gauges survive telemetry.reset() zeroing
        self._m_capacity.set(self.max_inflight)
        self._m_occupancy.set(depth)
        while len(self._pending) > self.max_inflight:
            self._retire_oldest()

    def _retire_oldest(self):
        from .analysis import guard as _tguard
        with self._mu:
            if not self._pending:
                return      # abandoned concurrently by a recovery path
            tag, payload, aux, t_push = self._pending.popleft()
            depth = len(self._pending)
        self._m_occupancy.set(depth)
        _tguard.count_sync("window_retire")
        # chaos-harness seam: a revoked device surfaces exactly here in
        # a pipelined run — at the blocking wait on an in-flight step
        fault_point("window.retire", "before")
        with _tguard.allow_transfers("dispatch-window retire"):
            try:
                with _telemetry().span("retire", step=tag):
                    self._sync(payload)
            except MXNetError as e:
                with self._mu:
                    self.stats["errors"] += 1
                self._m_errors.inc()
                _telemetry().memory.maybe_record_oom(
                    e, "dispatch-window retire", step=tag)
                _edetect().maybe_record_device_lost(
                    e, "dispatch-window retire", step=tag)
                raise
            except Exception as e:
                with self._mu:
                    self.stats["errors"] += 1
                self._m_errors.inc()
                # a deferred RESOURCE_EXHAUSTED surfaces HERE, steps
                # after the allocation that failed — write the ranked
                # post-mortem before wrapping (telemetry/memory.py);
                # a deferred device loss likewise gets its device_lost
                # anomaly (elastic/detect.py) before the wrap
                _telemetry().memory.maybe_record_oom(
                    e, "dispatch-window retire", step=tag)
                _edetect().maybe_record_device_lost(
                    e, "dispatch-window retire", step=tag)
                raise MXNetError(
                    f"async {self._what} "
                    f"{tag if tag is not None else '<untagged>'} failed "
                    f"(deferred error surfaced at its in-flight-window "
                    f"retire): {type(e).__name__}: {e}") from e
            with self._mu:
                self.stats["retires"] += 1
            self._m_retires.inc()
            # still inside the blessed retire region: the watchdog's
            # NaN peek at the (already completed) payload is the one
            # designed device->host read telemetry adds
            self._observe_retire(tag, payload, aux, t_push)
        fault_point("window.retire", "after")

    def _observe_retire(self, tag, payload, aux, t_push):
        """The ``window`` span (push -> done; the ``retire`` span is the
        blocking sync itself, recorded around it) + watchdog feed for
        one retire — gated on MXNET_TELEMETRY / an active profiler; must
        never kill a run.
        The aux's numerics part (when the step was compiled with
        numerics instrumentation) is consumed FIRST and regardless of
        the telemetry gate — MXNET_NUMERICS is its own opt-in. Its
        device counters are read only past the gate, onto the ``window``
        span; with telemetry off they are dropped unread."""
        t = _telemetry()
        try:
            if aux is not None and aux.numerics is not None:
                t.numerics.monitor().observe_retire(tag, aux.numerics)
            if not t.active():
                self._last_retire_t = None
                return
            t_done = time.perf_counter()
            t.timeline().record("window", t_push, t_done, step=tag,
                                counters=t.device_counters.observe(aux))
            dt = None if self._last_retire_t is None \
                else t_done - self._last_retire_t
            self._last_retire_t = t_done
            if t.enabled():
                t.watchdog().observe_retire(tag, payload=payload, dt=dt)
                # memory-budget headroom check, piggybacked on the same
                # blessed retire (no sync of its own; no-op unless
                # MXNET_MEMORY_BUDGET is set)
                t.memory.maybe_check_budget(step=tag)
        except Exception:            # pragma: no cover - defensive
            import logging
            logging.getLogger("mxnet_tpu.telemetry").warning(
                "window retire telemetry failed", exc_info=True)

    def drain(self):
        """Retire every outstanding entry (WaitForVar on all of them);
        deferred errors surface here attributed to their step."""
        while self._pending:
            self._retire_oldest()

    def abandon(self) -> list:
        """Discard every in-flight entry WITHOUT syncing — the recovery
        path after a device loss, where waiting on work dispatched to a
        dead device would only raise again. Returns the discarded tags
        (the steps whose results are gone; the checkpoint is the source
        of truth for them)."""
        with self._mu:
            tags = [t for t, _p, _a, _ts in self._pending]
            self._pending.clear()
            self.stats["abandoned"] = self.stats.get("abandoned", 0) \
                + len(tags)
        self._m_occupancy.set(0)
        return tags

    def drain_partial(self):
        """Recovery-drain: retire entries that still complete (in FIFO
        order — work the device finished before it was lost), then
        DISCARD everything after the first failure. Returns
        ``(retired, discarded_tags)``. The first failure is logged, not
        raised — the caller already holds the failure that started the
        recovery."""
        retired = 0
        while self._pending:
            try:
                self._retire_oldest()
                retired += 1
            except Exception as e:
                import logging
                logging.getLogger("mxnet_tpu.engine").warning(
                    "recovery drain: retire failed (%s: %s); discarding "
                    "%d in-flight step(s)", type(e).__name__, e,
                    len(self._pending))
                return retired, self.abandon()
        return retired, []


_host_engine = None
_host_lock = mx_lock("engine.host")


def host():
    """The native C++ host-task engine (src/native/engine.cc) — versioned-
    variable dependency scheduling for host work (IO, decode, checkpoint
    writes), the part of the reference's ThreadedEngine that XLA does NOT
    absorb. Returns None when the native lib is unavailable."""
    global _host_engine
    if _host_engine is None:
        with _host_lock:
            if _host_engine is None:
                from . import _native
                if _native.available():
                    n = int(get_env("MXNET_CPU_WORKER_NTHREADS", "0"))
                    _host_engine = _native.NativeEngine(num_threads=n)
    return _host_engine


def get() -> Engine:
    if Engine._instance is None:
        with Engine._lock:
            if Engine._instance is None:
                kind = get_env("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")
                if kind not in ("NaiveEngine", "ThreadedEngine",
                                "ThreadedEnginePerDevice", "ThreadedEnginePooled"):
                    kind = "ThreadedEnginePerDevice"
                Engine._instance = Engine(kind)
    return Engine._instance


def set_bulk_size(size: int) -> int:
    return get().set_bulk_size(size)


@contextlib.contextmanager
def bulk(size: int):
    """Reference ``mx.engine.bulk`` context manager (python/mxnet/engine.py)."""
    old = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(old)
