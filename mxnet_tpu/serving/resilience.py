"""Resilient serving: typed failures, admission control, auto-recovery.

PR 12's serving engine is fast; this module makes it survivable — the
request-scheduler and failure-recovery discipline of the TensorFlow
serving paths (arXiv:1605.08695 §4.3) composed from the elastic
machinery PR 11 already built, with AOT re-warm from the persistent
compile cache (arXiv:1810.09868) making predictor rebuilds cheap:

- **Typed failure taxonomy.** Every way an accepted request can fail is
  a distinct exception type the client can branch on:
  :class:`DeadlineExceeded` (the request's latency budget expired while
  it queued — dropped at dequeue, never dispatched),
  :class:`Overloaded` (shed at admission: queue full, projected wait
  past the deadline, circuit breaker open, or drain in progress —
  ``.reason`` says which), :class:`ServingShutdown` (the dispatcher
  died or the batcher closed with the request still pending — the
  anti-hang guarantee). All subclass ``MXNetError``.
- **Admission control / load shedding** (``MXNET_SERVING_SHED``):
  rejecting at ``submit`` when the projected queue wait (from the
  batcher's EWMA micro-batch service time) already exceeds the
  request's deadline keeps *accepted* requests inside their p99 under
  overload, instead of everyone timing out together.
- **:class:`CircuitBreaker`**: closed → open (fast-fail new submits
  while recovery runs) → half-open (post-recovery probe) → closed,
  exported as ``mx_serving_breaker_state``.
- **:class:`ServingSupervisor`**: the serving twin of
  ``elastic.ElasticSupervisor`` — classifies failures at the dispatch
  and window-retire seams via ``elastic.detect.classify``, rebuilds
  the predictor over ``parallel.dist.available_devices()`` with AOT
  buckets warm-started from the persistent compile cache, re-enqueues
  in-flight requests exactly once (bounded backoff retries for
  ``transient``; ``fatal``/``oom`` propagate), and drains gracefully
  on SIGTERM/:class:`~mxnet_tpu.elastic.PreemptionNotice`.

Telemetry: ``mx_serving_rejected_total{reason}``,
``mx_serving_deadline_missed_total``, ``mx_serving_retries_total``,
``mx_serving_recoveries_total``, ``mx_serving_breaker_state``,
``mx_serving_drain_seconds`` through the names.py catalog
(docs/OBSERVABILITY.md; docs/SERVING.md "Resilient serving").
"""
from __future__ import annotations

import logging
import os
import time
from typing import Callable, List, Optional, Sequence

from ..analysis.threads import mx_lock, mx_rlock
from ..base import MXNetError

__all__ = ["DeadlineExceeded", "Overloaded", "ServingShutdown",
           "CircuitBreaker", "ServingSupervisor", "default_deadline_ms",
           "shed_mode", "queue_timeout_s", "transient_retries"]

_LOG = logging.getLogger("mxnet_tpu.serving")

_TELEM = None


def _telemetry():
    global _TELEM
    if _TELEM is None:
        from .. import telemetry as _t
        _TELEM = _t
    return _TELEM


# ---------------------------------------------------------------- errors
class DeadlineExceeded(MXNetError):
    """The request's latency budget expired while it waited in the
    queue: dropped at dequeue — never padded into a bucket, never
    dispatched — so the device's work all lands inside someone's
    deadline. Counted under ``mx_serving_deadline_missed_total``."""


class Overloaded(MXNetError):
    """The request was shed at admission (``.reason`` ∈ {``queue``,
    ``deadline``, ``breaker``, ``draining``, ``kvcache``}): the service
    preserved
    the p99 of already-accepted traffic instead of queueing work it
    cannot finish in time. Counted under
    ``mx_serving_rejected_total{reason}``. Retryable — after backoff,
    against another replica, or once the breaker closes."""

    def __init__(self, msg: str, reason: str = "queue"):
        super().__init__(msg)
        self.reason = reason


class ServingShutdown(MXNetError):
    """The batcher can no longer serve this request: the dispatcher
    thread died, or ``close()``/``drain()`` ran with the request still
    pending. Every pending future receives this instead of hanging
    forever — the anti-hang half of the resilience contract."""


# ---------------------------------------------------------------- env gates
def default_deadline_ms() -> Optional[float]:
    """``MXNET_SERVING_DEADLINE_MS``: default per-request latency
    budget applied when ``submit(deadline_ms=)`` is not given. Unset,
    empty, or <= 0 means no deadline."""
    v = os.environ.get("MXNET_SERVING_DEADLINE_MS", "").strip()
    if not v:
        return None
    try:
        ms = float(v)
    except ValueError:
        return None
    return ms if ms > 0 else None


def shed_mode(default: str = "deadline") -> str:
    """``MXNET_SERVING_SHED``: admission-control policy —

    - ``off`` — no shedding; a full queue blocks ``submit`` up to the
      queue timeout (then :class:`Overloaded`);
    - ``deadline`` (default) — additionally reject at ``submit`` when
      the projected queue wait (EWMA service time x batches ahead)
      already exceeds the request's deadline; requests without a
      deadline behave as ``off``;
    - ``queue`` — never block: a full queue rejects immediately.
    """
    v = os.environ.get("MXNET_SERVING_SHED", "").strip().lower()
    return v if v in ("off", "deadline", "queue") else default


def queue_timeout_s(default_ms: float = 120000.0) -> float:
    """``MXNET_SERVING_QUEUE_TIMEOUT_MS``: how long a blocking
    ``submit`` may wait on a full queue before it is shed with a typed
    :class:`Overloaded` (the previously implicit 120 s bound, now
    explicit). <= 0 means reject immediately."""
    try:
        v = float(os.environ.get("MXNET_SERVING_QUEUE_TIMEOUT_MS",
                                 str(default_ms)))
    except (TypeError, ValueError):
        v = default_ms
    return max(0.0, v) / 1e3


def transient_retries(default: int = 2) -> int:
    """``MXNET_SERVING_RETRIES``: bounded re-dispatch budget per
    request for ``transient``-classified dispatch failures (IO blips,
    injected faults). Device-loss re-enqueue is separately capped at
    exactly one."""
    try:
        v = int(os.environ.get("MXNET_SERVING_RETRIES", default))
    except (TypeError, ValueError):
        return default
    return max(0, v)


# ---------------------------------------------------------------- breaker
class CircuitBreaker:
    """Three-state circuit breaker for the serving admission path.

    ``closed`` (normal traffic) → ``open`` (every :meth:`allow` is
    False — the supervisor trips it when recovery starts, or
    ``failure_threshold`` consecutive failures accumulate) →
    ``half_open`` (probe traffic allowed: the supervisor moves here
    once the predictor is rebuilt, or ``cooldown_s`` elapses) →
    ``closed`` again on the first recorded success; a failure while
    half-open re-opens.

    State is exported as ``mx_serving_breaker_state`` (0 closed,
    1 half-open, 2 open) and every transition is kept in
    :attr:`transitions` for the diagnose panel. ``clock=`` injection
    makes the cooldown deterministic under test.
    """

    CLOSED = "closed"
    HALF_OPEN = "half_open"
    OPEN = "open"
    _LEVEL = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}

    def __init__(self, failure_threshold: int = 1,
                 cooldown_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self._lock = mx_lock("serving.breaker")
        self._clock = clock
        self._threshold = max(1, int(failure_threshold))
        self._cooldown = cooldown_s
        self._state = self.CLOSED
        self._failures = 0
        self._opened_at: Optional[float] = None
        self.transitions: List[tuple] = [(self.CLOSED, clock(), "init")]
        t = _telemetry()
        self._m_state = t.registry().gauge(t.names.SERVING_BREAKER_STATE)
        self._m_state.set(0)

    def _set(self, state: str, cause: str):
        """Transition (call under the lock)."""
        if state == self._state:
            return
        self._state = state
        if state == self.OPEN:
            self._opened_at = self._clock()
        if len(self.transitions) < 256:
            self.transitions.append((state, self._clock(), cause))
        self._m_state.set(self._LEVEL[state])

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def allow(self) -> bool:
        """Whether a new submit may pass. Open + elapsed cooldown
        auto-transitions to half-open and admits the probe."""
        with self._lock:
            if self._state == self.CLOSED:
                return True
            if self._state == self.OPEN:
                if self._cooldown is not None and \
                        self._opened_at is not None and \
                        self._clock() - self._opened_at >= self._cooldown:
                    self._set(self.HALF_OPEN, "cooldown")
                    return True
                return False
            return True          # half-open: probe traffic flows

    def record_failure(self, cause: str = "failure"):
        with self._lock:
            self._failures += 1
            if self._state == self.HALF_OPEN or \
                    self._failures >= self._threshold:
                self._set(self.OPEN, cause)

    def record_success(self):
        with self._lock:
            self._failures = 0
            if self._state == self.HALF_OPEN:
                self._set(self.CLOSED, "probe_ok")

    def trip(self, cause: str = "recovery"):
        """Force open (the supervisor's recovery entry)."""
        with self._lock:
            self._set(self.OPEN, cause)

    def half_open(self, cause: str = "recovered"):
        with self._lock:
            if self._state == self.OPEN:
                self._set(self.HALF_OPEN, cause)

    def close(self, cause: str = "reset"):
        with self._lock:
            self._failures = 0
            self._set(self.CLOSED, cause)


# ---------------------------------------------------------------- supervisor
class ServingSupervisor:
    """Keep a serving deployment alive across device loss, transient
    dispatch failures, and preemption — the serving twin of
    :class:`~mxnet_tpu.elastic.ElasticSupervisor`::

        def build():                        # deterministic!
            net = make_net()                # params materialized
            return mx.serving.CompiledPredictor(net,
                                                bucket_sizes=(1, 2, 4, 8))

        sup = mx.serving.ServingSupervisor(build, example=(x_row,),
                                           max_batch=8, timeout_ms=2.0)
        fut = sup.submit(x)                 # breaker-guarded
        out = fut.result(30)
        sup.drain()                         # graceful shutdown

    ``build()`` constructs a FRESH :class:`CompiledPredictor`; it runs
    once per formation under ``jax.default_device(available_devices()
    [0])`` so a rebuilt predictor's params land on a surviving device,
    and ``example`` (a tuple of one-row args) is passed to
    ``warmup()`` so every AOT bucket is re-compiled — warm-started
    from the persistent compile cache, so recovery pays cache hits, not
    fresh XLA compiles.

    Failure handling (the :func:`~mxnet_tpu.elastic.classify`
    taxonomy) at the batcher's dispatch and window-retire seams:

    - ``device_lost`` — trip the breaker (new submits fast-fail with
      :class:`Overloaded` ``reason="breaker"``), abandon the poisoned
      in-flight window, rebuild the predictor over the surviving
      world, re-enqueue every in-flight request EXACTLY ONCE (a
      request lost twice fails with the device-loss error), move the
      breaker to half-open; the first successful retire closes it.
    - ``transient`` — re-enqueue with exponential backoff, bounded by
      ``MXNET_SERVING_RETRIES`` per request.
    - ``fatal`` / ``oom`` — propagate: the affected futures fail with
      the original error (a smaller world cannot cure a shape bug,
      and re-dispatching an OOM only re-OOMs).

    ``drain_on_preemption`` (default True) polls the process-global
    :class:`~mxnet_tpu.elastic.PreemptionNotice` from the dispatch
    loop: SIGTERM flips the batcher to drain mode — reject new
    (:class:`Overloaded` ``reason="draining"``), flush forming +
    in-flight, close — so no accepted request is silently lost. Pass a
    STRING instead of True to poll a *scoped* notice
    (``elastic.notice(scope)``): a notice for that scope drains only
    this supervisor — the fleet's per-replica drain-then-retire path —
    while the process-global notice still drains everyone.
    """

    def __init__(self, build: Callable, example: Optional[Sequence] = None,
                 *, max_batch: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 depth: Optional[int] = None,
                 inflight: Optional[int] = None,
                 max_requeues: int = 1,
                 max_retries: Optional[int] = None,
                 backoff_base: float = 0.05, backoff_max: float = 2.0,
                 breaker: Optional[CircuitBreaker] = None,
                 drain_on_preemption=True,
                 clock: Callable[[], float] = time.perf_counter,
                 start: bool = True):
        from .batcher import DynamicBatcher
        from ..elastic import detect as _detect
        self._build = build
        self._example = tuple(example) if example is not None else None
        self._max_requeues = max(0, int(max_requeues))
        self._max_retries = transient_retries() if max_retries is None \
            else max(0, int(max_retries))
        self._backoff_base = float(backoff_base)
        self._backoff_max = float(backoff_max)
        self._detect = _detect
        self._lock = mx_rlock("serving.supervisor")
        self._transient_streak = 0
        self._closed = False
        self.breaker = breaker if breaker is not None else CircuitBreaker()
        self.stats = {"recoveries": 0, "requeued": 0, "retried": 0,
                      "failed_requeues": 0, "recovery_downtime_s": 0.0,
                      "drains": 0}
        self.last_recovery: Optional[dict] = None
        t = _telemetry()
        reg = t.registry()
        self._m_retries = reg.counter(t.names.SERVING_RETRIES,
                                      label_key="cause")
        self._m_recoveries = reg.counter(t.names.SERVING_RECOVERIES,
                                         label_key="cause")
        self._predictor = self._form(first=True)
        self._batcher = DynamicBatcher(
            self._predictor, max_batch=max_batch, timeout_ms=timeout_ms,
            depth=depth, inflight=inflight, clock=clock, start=start)
        self._batcher.breaker = self.breaker
        self._batcher.on_batch_failure = self._on_batch_failure
        self._batcher.on_batch_retired = self._on_batch_retired
        self.notice_scope = drain_on_preemption \
            if isinstance(drain_on_preemption, str) else None
        if drain_on_preemption:
            # a scoped notice's requested() also honours the process-
            # global flag, so a real SIGTERM still drains every scope
            n = self._detect.notice(self.notice_scope)
            self._batcher.drain_check = n.requested

    # ---------------- public surface ----------------
    @property
    def predictor(self):
        """The live predictor (rebuilt at every recovery)."""
        return self._predictor

    @property
    def batcher(self):
        return self._batcher

    def submit(self, *args, deadline_ms=None, timeout=None):
        """Breaker-guarded submit; returns a
        :class:`~mxnet_tpu.serving.ServingFuture`. Raises typed
        :class:`Overloaded`/:class:`ServingShutdown` at admission."""
        return self._batcher.submit(*args, deadline_ms=deadline_ms,
                                    timeout=timeout)

    def drain(self):
        """Graceful shutdown: reject new, flush forming + in-flight,
        close (``mx_serving_drain_seconds``)."""
        self.stats["drains"] += 1
        self._batcher.drain()
        self._closed = True

    def close(self):
        self._batcher.close()
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ---------------- formation ----------------
    def _form(self, first: bool = False):
        """Build (or rebuild) the predictor on the surviving world and
        AOT-warm its buckets (compile-cache hits make this cheap)."""
        import jax
        from ..parallel import dist as _dist
        devs = _dist.available_devices()
        if not devs:
            raise MXNetError("serving: no devices survive; cannot "
                             "(re)build the predictor")
        with jax.default_device(devs[0]):
            pred = self._build()
            if self._example is not None:
                pred.warmup(*self._example)
        if not first:
            _LOG.warning(
                "serving: predictor rebuilt on %s (%d bucket program(s)"
                " AOT-warmed)", devs[0], pred.n_traces)
        return pred

    # ---------------- failure handling (dispatcher thread) ----------------
    def _on_batch_failure(self, reqs, exc, seam: str) -> bool:
        """Batcher hook: classify and recover. Returns True when the
        requests were handled (re-enqueued or failed here); False lets
        the batcher apply its default fail-the-futures path."""
        cause = self._detect.classify(exc)
        if cause == "device_lost":
            self._recover(list(reqs), exc, seam, cause)
            return True
        if cause == "transient":
            return self._retry_transient(list(reqs), exc, seam)
        return False             # fatal / oom / stall: propagate

    def _on_batch_retired(self):
        """Batcher hook after a successful window retire: a half-open
        breaker closes, the transient backoff streak resets."""
        self._transient_streak = 0
        self.breaker.record_success()

    def _retry_transient(self, reqs, exc, seam) -> bool:
        with self._lock:
            self._transient_streak += 1
            streak = self._transient_streak
        retry, fail = [], []
        for r in reqs:
            if r.retries >= self._max_retries:
                fail.append(r)
            else:
                r.retries += 1
                retry.append(r)
        for r in fail:
            self.stats["failed_requeues"] += 1
            r.future._fail(MXNetError(
                f"serving request failed after {r.retries} transient "
                f"retr{'ies' if r.retries != 1 else 'y'} "
                f"(MXNET_SERVING_RETRIES): {type(exc).__name__}: {exc}"))
        if not retry:
            return True
        delay = min(self._backoff_max,
                    self._backoff_base * (2 ** (streak - 1)))
        _LOG.warning(
            "serving: transient failure at %s (%s: %s); re-enqueueing "
            "%d request(s) after %.2fs backoff", seam,
            type(exc).__name__, exc, len(retry), delay)
        if delay > 0:
            time.sleep(delay)
        for r in retry:
            r.future._rearm()
            self._m_retries.inc(label="transient")
        self.stats["retried"] += len(retry)
        self._batcher.requeue(retry)
        return True

    def _recover(self, reqs, exc, seam, cause):
        """Device loss: breaker open → abandon in-flight → rebuild the
        predictor over the surviving devices → re-enqueue exactly once
        → breaker half-open. Runs on the dispatcher thread; the whole
        body is a blessed transfer region (recovery syncs are by
        design, like checkpoint restores)."""
        from ..analysis import guard as _tguard
        with self._lock:
            t0 = time.monotonic()
            self.breaker.trip(cause)
            # belt-and-braces anomaly (chain-marked: no-op when an
            # instrumented seam already recorded it)
            self._detect.maybe_record_device_lost(exc, f"serving {seam}")
            extra = self._batcher.abandon_inflight()
            seen = {id(r) for r in reqs}
            reqs = reqs + [r for r in extra if id(r) not in seen]
            reqs.sort(key=lambda r: r.t_submit)
            with _tguard.allow_transfers("serving recovery"):
                pred = self._rebuild(exc)
            if pred is None:     # rebuild failed: nothing left to serve
                for r in reqs:
                    self.stats["failed_requeues"] += 1
                    r.future._fail(ServingShutdown(
                        f"serving recovery failed after {cause} at "
                        f"{seam}: {type(exc).__name__}: {exc}"))
                return
            self._predictor = pred
            self._batcher.rebind(pred)
            requeue = []
            for r in reqs:
                if r.requeues >= self._max_requeues:
                    self.stats["failed_requeues"] += 1
                    r.future._fail(MXNetError(
                        f"serving request lost to repeated device "
                        f"failure (re-enqueued {r.requeues}x): "
                        f"{type(exc).__name__}: {exc}"))
                else:
                    r.requeues += 1
                    r.future._rearm()
                    self._m_retries.inc(label=cause)
                    requeue.append(r)
            self._batcher.requeue(requeue)
            self.stats["requeued"] += len(requeue)
            self.breaker.half_open()
            downtime = time.monotonic() - t0
            self.stats["recoveries"] += 1
            self.stats["recovery_downtime_s"] += downtime
            self.last_recovery = {
                "cause": cause, "seam": seam, "downtime_s": downtime,
                "requeued": len(requeue),
                "failed": len(reqs) - len(requeue),
                "time_unix": time.time()}
            self._m_recoveries.inc(label=cause)
            _LOG.warning(
                "serving: recovered from %s at %s in %.2fs "
                "(%d request(s) re-enqueued, %d failed)", cause, seam,
                downtime, len(requeue), len(reqs) - len(requeue))

    def _rebuild(self, exc):
        """Bounded-retry predictor rebuild; None when every attempt
        failed (the world is gone)."""
        attempts = max(1, self._detect.max_retries())
        last = exc
        for i in range(attempts):
            try:
                return self._form()
            except Exception as e:       # noqa: BLE001 - classify below
                last = e
                delay = min(self._backoff_max,
                            self._backoff_base * (2 ** i))
                _LOG.warning(
                    "serving: predictor rebuild attempt %d/%d failed "
                    "(%s: %s); retrying in %.2fs", i + 1, attempts,
                    type(e).__name__, e, delay)
                time.sleep(delay)
        _LOG.error("serving: predictor rebuild exhausted %d attempts "
                   "(%s: %s)", attempts, type(last).__name__, last)
        return None
