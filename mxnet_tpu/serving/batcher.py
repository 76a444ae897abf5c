"""Dynamic request batching (``serving.DynamicBatcher``).

The request-scheduler half of the serving engine (the dispatch
discipline of arXiv:1605.08695 applied to inference): concurrent
single-request traffic is coalesced into the bucketed batch shapes the
compile cache keys on, so N clients hit one compiled program per bucket
instead of N one-row dispatches.

Mechanics:

- **Bounded queue.** ``submit()`` enqueues a request (any leading-dim
  row count) into a bounded queue (``MXNET_SERVING_QUEUE_DEPTH``) and
  returns a :class:`ServingFuture`; a full queue blocks the caller up
  to ``MXNET_SERVING_QUEUE_TIMEOUT_MS`` and then sheds with a typed
  :class:`~mxnet_tpu.serving.Overloaded` — backpressure, not unbounded
  memory, and never a bare ``queue.Full``.
- **Deadlines + admission control.** ``submit(deadline_ms=)`` (default
  ``MXNET_SERVING_DEADLINE_MS``) rides the queue with the request;
  expired requests are dropped AT DEQUEUE (never padded/dispatched)
  with a typed :class:`~mxnet_tpu.serving.DeadlineExceeded`, and under
  ``MXNET_SERVING_SHED=deadline`` a request whose projected queue wait
  (EWMA micro-batch service time x batches ahead) already exceeds its
  deadline is rejected at ``submit`` — accepted requests keep their
  p99 instead of everyone timing out (docs/SERVING.md "Resilient
  serving").
- **Coalesce until full or stale.** The dispatcher gathers requests
  until ``MXNET_SERVING_MAX_BATCH`` rows are waiting or the OLDEST
  waiting request has aged ``MXNET_SERVING_BATCH_TIMEOUT_MS`` — the
  classic batching-delay/latency trade. The coalesced rows are padded
  to the predictor's next shape bucket (zero rows; the valid-row count
  is the mask) and dispatched as ONE program call.
- **Pipelined decode.** Each micro-batch's async outputs ride a
  bounded :class:`~mxnet_tpu.engine.DispatchWindow` — the host keeps
  forming + dispatching batch N+1 while the device runs batch N, and
  only blocks on the OLDEST in-flight batch when the window fills; the
  device never idles between micro-batches. The window retire is the
  ONE blessed host sync of the serving hot loop (request latency is
  recorded there); client-side ``future.result()`` reads are the
  response sync, outside the hot region.
- **Failure containment.** A dispatch or retire failure reaches the
  ``on_batch_failure`` hook (a :class:`~mxnet_tpu.serving
  .ServingSupervisor` classifies and recovers — device loss rebuilds
  the predictor and re-enqueues the affected requests exactly once);
  without a handler the affected futures fail with the error. A dead
  dispatcher thread or a ``close()`` with requests still pending fails
  every pending future with a typed :class:`~mxnet_tpu.serving
  .ServingShutdown` — an accepted request NEVER hangs. :meth:`drain`
  is the graceful path: reject new, flush forming + in-flight, close.
- **Observability.** ``mx_serving_*`` series through the telemetry
  catalog: requests/batches/rejected/deadline-missed counters,
  queue-depth and in-flight gauges, batch-occupancy/request-latency/
  drain-duration histograms (docs/OBSERVABILITY.md).

Deterministic testing: inject ``clock=`` and construct with
``start=False``, then drive :meth:`process_once` by hand — the
timeout/full flush decisions AND the deadline/admission arithmetic
consult only the injected clock (tests/test_serving.py and
tests/test_serving_resilience.py pin the semantics with a fake clock).
"""
from __future__ import annotations

import logging
import os
import queue
import threading
import time
from functools import partial
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from ..analysis import guard as _tguard
from ..analysis.threads import mx_condition, mx_lock, register_queue
from ..base import MXNetError
from ..engine import DispatchWindow
from ..ndarray.ndarray import NDArray
from ..testing.faults import fault_point
from .resilience import (DeadlineExceeded, Overloaded, ServingShutdown,
                         default_deadline_ms, queue_timeout_s, shed_mode)

__all__ = ["DynamicBatcher", "ServingFuture", "max_batch_rows",
           "batch_timeout_s", "queue_depth"]

_LOG = logging.getLogger("mxnet_tpu.serving")

_TELEM = None


def _telemetry():
    global _TELEM
    if _TELEM is None:
        from .. import telemetry as _t
        _TELEM = _t
    return _TELEM


def max_batch_rows(default: int = 32) -> int:
    """``MXNET_SERVING_MAX_BATCH``: max coalesced rows per dispatch
    (at least 1; ``default`` when unset or unparseable).  It trades
    occupancy against padding waste and must fit the predictor's bucket
    ladder; per-request results are bit-identical at any setting."""
    try:
        return max(1, int(os.environ.get("MXNET_SERVING_MAX_BATCH",
                                         str(default))))
    except ValueError:
        return default


def batch_timeout_s(default_ms: float = 2.0) -> float:
    """How long the oldest waiting request may age before a partial
    batch flushes, as SECONDS: ``MXNET_SERVING_BATCH_TIMEOUT_MS``
    (milliseconds, at least 0; ``default_ms`` when unset or
    unparseable)."""
    try:
        v = float(os.environ.get("MXNET_SERVING_BATCH_TIMEOUT_MS",
                                 str(default_ms)))
    except ValueError:
        v = default_ms
    return max(0.0, v) / 1e3


def queue_depth(default: int = 1024) -> int:
    """``MXNET_SERVING_QUEUE_DEPTH``: bounded request-queue capacity
    (a full queue blocks ``submit`` up to the queue timeout, then
    sheds — backpressure)."""
    try:
        v = int(os.environ.get("MXNET_SERVING_QUEUE_DEPTH", str(default)))
    except ValueError:
        return default
    return max(1, v)


@partial(jax.jit, static_argnums=2)
def _row_slice(x, off, n):
    """One compiled slicer per (shape, n): the offset is traced, so
    slicing responses out of a batch costs no per-offset compiles."""
    return jax.lax.dynamic_slice_in_dim(x, off, n, axis=0)


def _build_response(out_leaves, out_tree, off, rows, bucket):
    """Client-side response materialization (``ServingFuture.result``):
    block on the micro-batch's outputs — the response sync, on the
    client's own thread — then slice this request's rows out. Leaves
    without the batch's leading dim (scalars, per-model aux) pass
    through whole."""
    jax.block_until_ready([l._data for l in out_leaves
                           if isinstance(l, NDArray)])
    sliced = [
        NDArray(_row_slice(l._data, off, rows))
        if isinstance(l, NDArray) and getattr(l._data, "ndim", 0) >= 1
        and int(l._data.shape[0]) == bucket else l
        for l in out_leaves]
    return jax.tree_util.tree_unflatten(out_tree, sliced)


class ServingFuture:
    """Handle for one submitted request's result.

    Resolves when its micro-batch DISPATCHES (with a lazy builder over
    the batch's async outputs); :meth:`result` blocks until the device
    finished the batch — the response-side sync, on the client's
    thread, outside the serving hot region — then slices this
    request's rows out. The per-request slice dispatch happens on the
    CLIENT thread, keeping the dispatcher's hot loop to one program
    call per micro-batch.

    Under a :class:`~mxnet_tpu.serving.ServingSupervisor` the future
    is RE-ARMABLE: when the request's micro-batch is lost to a device
    failure, recovery re-enqueues the request and the future resolves
    again against the re-dispatched batch (the ``_epoch`` counter
    disambiguates); a client already blocked in :meth:`result` rides
    through the recovery instead of observing the poisoned buffers.
    Terminal failures arrive as typed errors — never a hang.
    """

    __slots__ = ("_cv", "_build", "_out", "_err", "_done", "_epoch",
                 "_supervised", "replica", "version")

    def __init__(self):
        self._cv = mx_condition("serving.future")
        self._build = None
        self._out = None
        self._err = None
        self._done = False
        self._epoch = 0
        self._supervised = False
        # routing breadcrumbs (FleetRouter tags these): which replica
        # served the request and that replica's weight version
        self.replica: Optional[str] = None
        self.version: Optional[int] = None

    def _resolve(self, build):
        with self._cv:
            self._build, self._err, self._done = build, None, True
            self._cv.notify_all()

    def _fail(self, err):
        with self._cv:
            if self._done and self._err is None and self._out is not None:
                return           # a delivered result is final
            self._err, self._done = err, True
            self._cv.notify_all()

    def _rearm(self):
        """Recovery: put the future back in flight (pending its
        re-dispatched micro-batch)."""
        with self._cv:
            self._build = self._err = self._out = None
            self._done = False
            self._epoch += 1
            self._cv.notify_all()

    def done(self) -> bool:
        with self._cv:
            return self._done

    def _cv_wait(self, deadline) -> bool:
        """One bounded wait tick under the cv; False when the client
        timeout passed."""
        if deadline is None:
            self._cv.wait()
            return True
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return False
        self._cv.wait(remaining)
        return True

    def result(self, timeout: Optional[float] = None):
        """Block until the response is computed and return it (the
        net's output structure, NDArray leaves, this request's rows
        only). Raises the typed serving error
        (:class:`~mxnet_tpu.serving.DeadlineExceeded` /
        :class:`~mxnet_tpu.serving.Overloaded` /
        :class:`~mxnet_tpu.serving.ServingShutdown`) or the dispatch
        error if its batch failed terminally."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._cv:
                while not self._done:
                    if not self._cv_wait(deadline):
                        raise MXNetError(
                            f"serving request not completed within "
                            f"{timeout}s (batcher stopped? queue "
                            "saturated?)")
                if self._err is not None:
                    raise self._err
                if self._out is not None:
                    return self._out
                epoch, build = self._epoch, self._build
            try:
                out = build()
            except BaseException as e:
                if self._await_redispatch(epoch, e, deadline):
                    continue
                raise
            with self._cv:
                if self._epoch == epoch and self._err is None:
                    self._out = out
            return out

    def _await_redispatch(self, epoch, exc, deadline) -> bool:
        """The resolved response's builder failed on the client thread.
        When the batcher is supervised and the failure is
        recovery-class, the supervisor is seeing the SAME failure at
        the retire seam — wait (bounded by the client timeout) for it
        to either re-arm this future or fail it typed, instead of
        surfacing the poisoned-buffer error."""
        if not self._supervised:
            return False
        try:
            from ..elastic import detect
            if detect.classify(exc) not in ("device_lost", "transient"):
                return False
        except Exception:        # pragma: no cover - defensive
            return False
        with self._cv:
            while self._epoch == epoch and self._done \
                    and self._err is None:
                if not self._cv_wait(deadline):
                    return False
            return True


class _Request:
    __slots__ = ("args", "rows", "t_submit", "future", "deadline",
                 "retries", "requeues")

    def __init__(self, args, rows, t_submit, future, deadline=None):
        self.args = args
        self.rows = rows
        self.t_submit = t_submit
        self.future = future
        self.deadline = deadline   # absolute, on the batcher clock
        self.retries = 0           # transient re-dispatches so far
        self.requeues = 0          # device-loss re-enqueues so far


class DynamicBatcher:
    """Coalesce concurrent requests into one predictor's shape buckets.

        pred = mx.serving.CompiledPredictor(net)
        with mx.serving.DynamicBatcher(pred) as b:
            futs = [b.submit(x_i) for x_i in requests]
            outs = [f.result() for f in futs]

    Thread-safe ``submit``; one background dispatcher thread owns the
    hot loop (``start=False`` for manual :meth:`process_once` driving).

    Resilience hooks (wired by :class:`~mxnet_tpu.serving
    .ServingSupervisor`; all default off): ``breaker`` (a
    :class:`~mxnet_tpu.serving.CircuitBreaker` consulted at admission),
    ``on_batch_failure(reqs, exc, seam) -> bool`` (classify + recover;
    True = requests were re-enqueued/failed by the handler),
    ``on_batch_retired()`` (success feedback closing a half-open
    breaker), ``drain_check()`` (polled by the dispatch loop; True
    initiates a graceful drain — the preemption-notice bridge).
    """

    def __init__(self, predictor, max_batch: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 depth: Optional[int] = None,
                 inflight: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 start: bool = True):
        self._predictor = predictor
        self.max_batch = max_batch_rows() if max_batch is None \
            else max(1, int(max_batch))
        if self.max_batch > predictor.bucket_sizes[-1]:
            raise MXNetError(
                f"max_batch={self.max_batch} exceeds the predictor's "
                f"largest shape bucket ({predictor.bucket_sizes[-1]})")
        self._timeout_s = batch_timeout_s() if timeout_ms is None \
            else max(0.0, float(timeout_ms)) / 1e3
        self._clock = clock
        self._queue: "queue.Queue[_Request]" = queue.Queue(
            maxsize=queue_depth() if depth is None else max(1, int(depth)))
        register_queue("serving.batcher", self._queue)  # thread dumps
        self._forming: List[_Request] = []
        self._inflight: dict = {}   # tag -> (requests, t_dispatch)
        self._window = DispatchWindow(max_inflight=inflight,
                                      what="serving micro-batch",
                                      sync_fn=self._retire_sync)
        self._batch_no = 0
        self._stop = threading.Event()
        self._drain_now = threading.Event()
        self._thread = None
        self._draining = False
        self._dead: Optional[BaseException] = None
        # seed the admission EWMA from the predictor's warmup() timing
        # (when it ran): deadline shedding projects from request 1
        # instead of admitting blindly until the first retire lands
        self._ewma_service: Optional[float] = self._service_seed(predictor)
        # resilience hooks (ServingSupervisor wires these)
        self.breaker = None
        self.on_batch_failure = None
        self.on_batch_retired = None
        self.drain_check = None
        # chaos-harness context tag: the FleetController sets this to
        # the replica name so point@ctx fault rules target one replica
        self.fault_ctx: Optional[str] = None
        self.stats = {"requests": 0, "batches": 0, "rows": 0,
                      "padded_rows": 0, "flush_full": 0,
                      "flush_timeout": 0, "flush_idle": 0,
                      "flush_force": 0, "errors": 0, "rejected": 0,
                      "deadline_missed": 0, "requeued": 0,
                      "recovered_batches": 0, "shutdown_failed": 0}
        # stats is written from both the client surface (submit/reject)
        # and the dispatcher thread; every mutation holds this lock so
        # concurrent submits never lose increments
        self._stats_mu = mx_lock("serving.batcher.stats")
        t = _telemetry()
        reg = t.registry()
        self._m_requests = reg.counter(t.names.SERVING_REQUESTS)
        self._m_batches = reg.counter(t.names.SERVING_BATCHES)
        self._m_queue = reg.gauge(t.names.SERVING_QUEUE_DEPTH)
        self._m_inflight = reg.gauge(t.names.SERVING_INFLIGHT)
        self._m_occupancy = reg.histogram(t.names.SERVING_OCCUPANCY)
        self._m_latency = reg.histogram(t.names.SERVING_LATENCY)
        self._m_rejected = reg.counter(t.names.SERVING_REJECTED,
                                       label_key="reason")
        self._m_deadline = reg.counter(t.names.SERVING_DEADLINE_MISSED)
        self._m_drain = reg.histogram(t.names.SERVING_DRAIN_SECONDS)
        if start:
            self._thread = threading.Thread(
                target=self._serve_loop, name="mx-serving-batcher",
                daemon=True)
            self._thread.start()

    # ---------------- client surface ----------------
    def _reject(self, reason: str, msg: str):
        with self._stats_mu:
            self.stats["rejected"] += 1
        self._m_rejected.inc(label=reason)
        raise Overloaded(msg, reason=reason)

    def submit(self, *args, deadline_ms: Optional[float] = None,
               timeout: Optional[float] = None) -> ServingFuture:
        """Enqueue one request (array leaves with a leading row dim,
        typically one row) and return its future.

        ``deadline_ms`` — this request's latency budget (default
        ``MXNET_SERVING_DEADLINE_MS``; <= 0 disables): expired-in-queue
        requests fail with :class:`~mxnet_tpu.serving.DeadlineExceeded`
        and are never dispatched, and ``MXNET_SERVING_SHED=deadline``
        sheds at admission when the projected wait already exceeds it.
        ``timeout`` — max blocking wait on a full queue (default
        ``MXNET_SERVING_QUEUE_TIMEOUT_MS``); a still-full queue sheds
        with :class:`~mxnet_tpu.serving.Overloaded` (reason
        ``queue``). Never raises a bare ``queue.Full``."""
        fault_point("serving.admit", "before", ctx=self.fault_ctx)
        if self._dead is not None:
            raise ServingShutdown(
                f"serving dispatcher thread died "
                f"({type(self._dead).__name__}: {self._dead}); "
                "the batcher cannot accept requests")
        if self._stop.is_set():
            raise ServingShutdown("DynamicBatcher is closed")
        if self._draining:
            self._reject("draining",
                         "serving drain in progress (preemption/"
                         "shutdown) — new requests are rejected while "
                         "accepted ones flush")
        if self.breaker is not None and not self.breaker.allow():
            self._reject("breaker",
                         "serving circuit breaker is open (recovery in "
                         "progress) — fast-failing instead of queueing "
                         "into a dead device")
        rows = self._rows_of(args)
        if rows > self.max_batch:
            raise MXNetError(
                f"request of {rows} rows exceeds max_batch="
                f"{self.max_batch} (MXNET_SERVING_MAX_BATCH)")
        if deadline_ms is None:
            deadline_ms = default_deadline_ms()
        elif deadline_ms <= 0:
            deadline_ms = None
        now = self._clock()
        deadline = None if deadline_ms is None \
            else now + deadline_ms / 1e3
        mode = shed_mode()
        if mode == "deadline" and deadline is not None:
            est = self.estimated_wait_s(rows)
            if est is not None and now + est > deadline:
                self._reject(
                    "deadline",
                    f"projected queue wait {est * 1e3:.1f} ms exceeds "
                    f"the request deadline ({deadline_ms:.0f} ms) — "
                    "shedding at admission so accepted requests keep "
                    "their p99 (MXNET_SERVING_SHED=deadline)")
        fut = ServingFuture()
        fut._supervised = self.on_batch_failure is not None
        req = _Request(args, rows, now, fut, deadline=deadline)
        block_s = queue_timeout_s() if timeout is None \
            else max(0.0, float(timeout))
        try:
            if mode == "queue" or block_s <= 0:
                self._queue.put_nowait(req)
            else:
                self._queue.put(req, timeout=block_s)
        except queue.Full:
            self._reject(
                "queue",
                f"serving queue saturated ({self._queue.maxsize} "
                "requests) — the service is overloaded "
                "(MXNET_SERVING_QUEUE_DEPTH / "
                "MXNET_SERVING_QUEUE_TIMEOUT_MS)")
        if self._stop.is_set() and not fut.done():
            # the batcher closed the instant we enqueued: the drain's
            # final fail-pending sweep may already have run, so nobody
            # will ever pop this request. Fail the future (typed, for
            # any holder) and raise like the up-front closed check —
            # an accepted request can never hang, and a router retries
            # the next replica (the sched-harness submit-vs-drain
            # invariant).
            err = ServingShutdown(
                "serving closed while this request was being accepted "
                "— it was never dispatched")
            fut._fail(err)
            raise err
        with self._stats_mu:
            self.stats["requests"] += 1
        self._m_requests.inc()
        self._m_queue.set(self._queue.qsize() + len(self._forming))
        return fut

    @staticmethod
    def _service_seed(predictor) -> Optional[float]:
        seed = getattr(predictor, "service_time_seed_s", None)
        try:
            seed = float(seed) if seed is not None else None
        except (TypeError, ValueError):
            return None
        return seed if seed and seed > 0 else None

    def estimated_wait_s(self, rows: int = 0) -> Optional[float]:
        """Projected wait until a request submitted NOW would retire:
        (waiting rows incl. its own, bucketed at ``max_batch``) plus
        the in-flight micro-batches, times the EWMA micro-batch
        service time. The EWMA is seeded from the predictor's
        ``warmup()`` execution timing when available; None only when
        neither a warmup seed nor a retire has happened yet (no
        estimate — admit; the queue bound still protects memory)."""
        ewma = self._ewma_service
        if ewma is None:
            return None
        waiting = self._queue.qsize() + self._forming_rows() + rows
        batches = (waiting + self.max_batch - 1) // self.max_batch \
            + len(self._window)
        return batches * ewma

    @property
    def batch_fill(self) -> Optional[float]:
        """Valid rows / dispatched bucket rows — the padding waste
        ratio (1.0 = every dispatched row was a real request)."""
        with self._stats_mu:
            total = self.stats["rows"] + self.stats["padded_rows"]
            return self.stats["rows"] / total if total else None

    def flush(self):
        """Dispatch whatever is waiting (regardless of age/size) and
        retire every in-flight micro-batch."""
        while self.process_once(force=True):
            pass
        self._window.drain()
        self._m_inflight.set(0)

    def drain(self):
        """Graceful shutdown: flip to drain mode (new submits shed with
        :class:`~mxnet_tpu.serving.Overloaded` reason ``draining``),
        flush every forming + in-flight request, then close — no
        accepted request is silently lost. The flush runs on the
        dispatcher thread when one exists (single owner of the forming
        list); duration lands in ``mx_serving_drain_seconds``.
        Idempotent."""
        t0 = self._clock()
        # monotonic latch (False -> True only, never cleared); both the
        # dispatcher's preemption drain and this public path may set it
        # concurrently and either order is correct, so the race is
        # benign by construction
        self._draining = True  # mx-lint: allow=MXA008
        if self._thread is not None:
            self._drain_now.set()
            self._thread.join(timeout=60.0)
            self._thread = None
            self._stop.set()
            # the in-loop drain flushed + failed leftovers + observed
            # the histogram; this is the belt-and-braces pass for a
            # thread that exited through a non-drain path
            self._fail_pending(ServingShutdown(
                "serving drained before this request could be "
                "dispatched"))
            return
        if self._stop.is_set():
            return               # already closed
        try:
            while self.process_once(force=True):
                pass
            self._window.drain()
            self._m_inflight.set(0)
        finally:
            self._stop.set()
            self._fail_pending(ServingShutdown(
                "serving drained before this request could be "
                "dispatched"))
            self._m_drain.observe(max(0.0, self._clock() - t0))

    def close(self):
        """Stop the dispatcher thread, flush remaining requests, drain
        the window; anything still undispatchable fails with a typed
        :class:`~mxnet_tpu.serving.ServingShutdown` (never a hung
        future). Idempotent."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        try:
            if self._dead is None:
                self.flush()
        finally:
            self._fail_pending(ServingShutdown(
                "DynamicBatcher closed with this request still "
                "pending (dispatch failed or dispatcher unavailable)"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ---------------- batching core ----------------
    @staticmethod
    def _rows_of(args) -> int:
        for l in jax.tree_util.tree_leaves(
                args, is_leaf=lambda t: isinstance(t, NDArray)):
            d = l._data if isinstance(l, NDArray) else l
            if getattr(d, "ndim", 0) >= 1:
                return int(d.shape[0])
        raise MXNetError("serving request has no array leaf with a "
                         "leading batch dim")

    def _forming_rows(self) -> int:
        return sum(r.rows for r in self._forming)

    def _drain_queue(self, cap: Optional[int] = None):
        while cap is None or self._forming_rows() < cap:
            try:
                self._forming.append(self._queue.get_nowait())
            except queue.Empty:
                break

    def _expire_forming(self):
        """Drop requests whose deadline already expired while they
        queued: each fails with a typed ``DeadlineExceeded`` and is
        NEVER padded into a bucket or dispatched — the device's work
        all lands inside someone's budget."""
        if not self._forming:
            return
        now = self._clock()
        kept = []
        for r in self._forming:
            if r.deadline is not None and now >= r.deadline:
                with self._stats_mu:
                    self.stats["deadline_missed"] += 1
                self._m_deadline.inc()
                r.future._fail(DeadlineExceeded(
                    f"request deadline expired after "
                    f"{(now - r.t_submit) * 1e3:.1f} ms in queue — "
                    "dropped at dequeue, never dispatched "
                    "(MXNET_SERVING_DEADLINE_MS / submit(deadline_ms=))"))
            else:
                kept.append(r)
        self._forming = kept

    def _fail_pending(self, err: BaseException):
        """Fail every request still waiting (queue + forming) with a
        typed error — the anti-hang guarantee on shutdown/dispatcher
        death."""
        self._drain_queue()
        pending, self._forming = self._forming, []
        for r in pending:
            if not r.future.done():
                with self._stats_mu:
                    self.stats["shutdown_failed"] += 1
                r.future._fail(err)
        self._m_queue.set(0)

    def requeue(self, reqs: List[_Request]):
        """Re-enqueue recovered requests at the FRONT of the forming
        list (supervisor recovery path, dispatcher thread). Original
        submit times are preserved, so the age-based flush re-dispatches
        them promptly; original deadlines still apply."""
        if not reqs:
            return
        # dispatcher-thread-only path: the supervisor's recovery hook
        # runs on the thread that owns the forming list (the docstring
        # contract), so this is single-owner, not a cross-thread write
        self._forming[0:0] = list(reqs)  # mx-lint: allow=MXA008
        with self._stats_mu:
            self.stats["requeued"] += len(reqs)
        self._m_queue.set(self._queue.qsize() + len(self._forming))

    def rebind(self, predictor):
        """Swap in a rebuilt predictor (supervisor recovery); the
        coalescing cap must still fit the new bucket ladder."""
        if self.max_batch > predictor.bucket_sizes[-1]:
            raise MXNetError(
                f"max_batch={self.max_batch} exceeds the rebuilt "
                f"predictor's largest shape bucket "
                f"({predictor.bucket_sizes[-1]})")
        self._predictor = predictor
        if self._ewma_service is None:
            self._ewma_service = self._service_seed(predictor)

    def abandon_inflight(self) -> List[_Request]:
        """Discard every in-flight micro-batch WITHOUT syncing (work
        dispatched to a lost device would only raise again) and return
        the requests that rode them — the supervisor re-enqueues or
        fails each exactly once."""
        self._window.abandon()
        recs = list(self._inflight.values())
        self._inflight.clear()
        self._m_inflight.set(0)
        return [r for reqs, _t in recs for r in reqs]

    def _take_batch(self) -> List[_Request]:
        batch, rows = [], 0
        while self._forming and rows + self._forming[0].rows \
                <= self.max_batch:
            r = self._forming.pop(0)
            batch.append(r)
            rows += r.rows
        return batch

    def process_once(self, force: bool = False) -> bool:
        """Manual-drive: pull waiting requests, drop expired ones, and
        dispatch ONE batch if the flush condition holds (>= max_batch
        rows waiting, the oldest request older than the batch timeout,
        or ``force``). Returns whether a batch was dispatched. Uses
        only the injected clock — fake-clock tests drive the semantics
        deterministically."""
        self._drain_queue()
        self._expire_forming()
        if not self._forming:
            return False
        reason = None
        if self._forming_rows() >= self.max_batch:
            reason = "full"
        elif self._clock() - self._forming[0].t_submit >= self._timeout_s:
            reason = "timeout"
        elif force:
            reason = "force"
        if reason is None:
            return False
        self._dispatch(self._take_batch(), reason)
        return True

    def _serve_loop(self):
        """Dispatcher thread body: the work-conserving coalescing loop,
        wrapped so the thread CANNOT die silently — an escaping error
        fails every pending future with a typed ``ServingShutdown``
        instead of leaving clients blocked forever."""
        try:
            self._serve_loop_inner()
        except BaseException as e:   # noqa: BLE001 - anti-hang contract
            self._dead = e
            _LOG.error(
                "serving dispatcher thread DIED (%s: %s); failing "
                "pending futures with ServingShutdown",
                type(e).__name__, e, exc_info=True)
            try:
                self._fail_pending(ServingShutdown(
                    f"serving dispatcher thread died: "
                    f"{type(e).__name__}: {e}"))
            except Exception:    # pragma: no cover - defensive
                _LOG.warning("failing pending futures failed",
                             exc_info=True)

    def _serve_loop_inner(self):
        """Work-conserving coalescing: requests gather until the batch
        is full or the oldest waiting request has aged past the
        timeout — but an IDLE device short-circuits the linger (when
        nothing is queued and nothing is in flight, batching delay
        buys no occupancy, it only adds latency), and the linger
        itself is spent draining the in-flight window, so the device
        never idles between micro-batches."""
        idle_poll = max(self._timeout_s, 0.005)
        while not self._stop.is_set():
            if self._drain_now.is_set() or self._wants_drain():
                self._drain_in_loop()
                return
            try:
                if not self._forming:
                    # idle: retire finished in-flight batches so their
                    # latencies are recorded and errors surface, then
                    # block for the next request
                    if len(self._window):
                        self._window.drain()
                        self._m_inflight.set(0)
                    try:
                        self._forming.append(
                            self._queue.get(timeout=idle_poll))
                    except queue.Empty:
                        continue
                # coalesce until full, stale, or device-idle
                deadline = self._forming[0].t_submit + self._timeout_s
                while self._forming_rows() < self.max_batch:
                    try:
                        self._forming.append(self._queue.get_nowait())
                        continue
                    except queue.Empty:
                        pass
                    if not len(self._window):
                        break    # device idle: ship what we have NOW
                    remaining = deadline - self._clock()
                    if remaining <= 0:
                        break
                    # the device is busy with an in-flight batch: spend
                    # the linger retiring it (the retire IS the wait)
                    self._window.drain()
                    self._m_inflight.set(0)
                    remaining = deadline - self._clock()
                    if remaining <= 0:
                        break
                    try:
                        self._forming.append(
                            self._queue.get(timeout=remaining))
                    except queue.Empty:
                        break
                if self._forming_rows() >= self.max_batch:
                    reason = "full"
                elif self._clock() - self._forming[0].t_submit \
                        >= self._timeout_s:
                    reason = "timeout"
                else:
                    reason = "idle"   # device idle cut the linger short
                self._expire_forming()
                if not self._forming:
                    continue
                self._dispatch(self._take_batch(), reason)
            except Exception as e:   # keep serving after a bad batch
                # a deferred failure surfacing at a window drain (not
                # inside _retire_sync's own guard) still reaches the
                # recovery handler: the in-flight records know which
                # requests rode the poisoned batches
                if self._handle_batch_failure([], e, "dispatcher"):
                    continue
                _LOG.warning("serving dispatch failed (%s: %s)",
                             type(e).__name__, e, exc_info=True)
                with self._stats_mu:
                    self.stats["errors"] += 1

    def _wants_drain(self) -> bool:
        """Poll the drain hook (the ServingSupervisor's preemption-
        notice bridge) — never lets a hook error kill the loop."""
        if self.drain_check is None or self._draining:
            return False
        try:
            return bool(self.drain_check())
        except Exception:        # pragma: no cover - defensive
            return False

    def _drain_in_loop(self):
        """Preemption-notice drain, on the dispatcher thread: reject
        new, flush forming + in-flight, fail anything undispatchable
        typed, stop."""
        t0 = self._clock()
        self._draining = True
        _LOG.warning(
            "serving: drain requested — rejecting new requests and "
            "flushing %d waiting + %d in-flight",
            self._queue.qsize() + len(self._forming), len(self._window))
        try:
            while self.process_once(force=True):
                pass
            self._window.drain()
            self._m_inflight.set(0)
        except Exception:        # pragma: no cover - defensive
            _LOG.warning("serving drain flush failed", exc_info=True)
        self._fail_pending(ServingShutdown(
            "serving drained (preemption) before this request could "
            "be dispatched"))
        self._stop.set()
        # second sweep AFTER the stop flag: a submit that raced its
        # enqueue between the first sweep and the flag would otherwise
        # sit in a stopped batcher forever
        self._fail_pending(ServingShutdown(
            "serving drained (preemption) before this request could "
            "be dispatched"))
        self._m_drain.observe(max(0.0, self._clock() - t0))

    # ---------------- dispatch ----------------
    def _handle_batch_failure(self, reqs, exc, seam: str) -> bool:
        """Route a batch failure to the resilience handler (the
        ServingSupervisor). True = the requests were re-enqueued or
        failed by the handler; False = apply the default path."""
        handler = self.on_batch_failure
        if handler is None:
            return False
        try:
            handled = bool(handler(reqs, exc, seam))
        except Exception:        # pragma: no cover - defensive
            _LOG.error("serving failure handler raised; falling back "
                       "to failing the batch", exc_info=True)
            return False
        if handled:
            with self._stats_mu:
                self.stats["recovered_batches"] += 1
        return handled

    def _dispatch(self, reqs: List[_Request], reason: str):
        """One micro-batch: concatenate + pad to bucket, ONE predictor
        call, resolve each request's future with its (lazy) row slice,
        push the async outputs into the pipeline window. The whole body
        is a transfer-guard hot region — nothing in here may sync; the
        window retire is the one blessed wait."""
        if not reqs:
            return
        try:
            with _tguard.hot_scope("DynamicBatcher.dispatch"):
                self._dispatch_inner(reqs, reason)
        except BaseException as e:
            if self._handle_batch_failure(reqs, e, "dispatch"):
                return
            for r in reqs:
                if not r.future.done():
                    r.future._fail(e)
            raise

    def _dispatch_inner(self, reqs: List[_Request], reason: str):
        pred = self._predictor
        rows = sum(r.rows for r in reqs)
        bucket = pred.bucket_for(rows)
        n_pos = len(reqs[0].args)
        if any(len(r.args) != n_pos for r in reqs):
            raise MXNetError("coalesced requests disagree on argument "
                             "count — one model signature per batcher")
        batch_args = tuple(
            self._concat_pad([r.args[i] for r in reqs], rows, bucket)
            for i in range(n_pos))
        # chaos-harness seam: a revoked device surfaces here when the
        # loss hits at dispatch time (testing/faults.py)
        fault_point("serving.dispatch", "before", ctx=self.fault_ctx)
        outs = pred.predict(*batch_args)
        out_leaves, out_tree = jax.tree_util.tree_flatten(
            outs, is_leaf=lambda t: isinstance(t, NDArray))
        off = 0
        for r in reqs:
            r.future._resolve(partial(
                _build_response, out_leaves, out_tree, off, r.rows,
                bucket))
            off += r.rows
        self._batch_no += 1
        tag = self._batch_no
        self._inflight[tag] = (list(reqs), self._clock())
        payload = (tag, tuple(l._data for l in out_leaves
                              if isinstance(l, NDArray)))
        with self._stats_mu:
            self.stats["batches"] += 1
            self.stats["rows"] += rows
            self.stats["padded_rows"] += bucket - rows
            self.stats["flush_" + reason] += 1
        self._m_batches.inc()
        self._m_occupancy.observe(rows / bucket)
        self._window.push(payload, tag=tag)
        self._m_inflight.set(len(self._window))
        self._m_queue.set(self._queue.qsize() + len(self._forming))

    @staticmethod
    def _concat_pad(leaves, rows: int, bucket: int):
        """Concatenate one argument position across requests and pad
        to the bucket — async device ops only, no host sync."""
        datas = [l._data if isinstance(l, NDArray) else jnp.asarray(l)
                 for l in leaves]
        if bucket > rows:
            datas.append(jnp.zeros((bucket - rows,)
                                   + tuple(datas[0].shape[1:]),
                                   datas[0].dtype))
        out = datas[0] if len(datas) == 1 else jnp.concatenate(datas,
                                                               axis=0)
        return NDArray(out)

    def _retire_sync(self, payload):
        """Window sync hook: block on the micro-batch's outputs (the
        blessed retire), then record each rider request's end-to-end
        latency and fold the batch's service time into the EWMA the
        admission controller projects from. A retire FAILURE carries
        its riders to the resilience handler — device loss re-enqueues
        them through recovery instead of poisoning their futures."""
        tag, datas = payload
        try:
            # chaos-harness seam: a deferred device loss surfaces at
            # the blocking wait on the in-flight micro-batch
            fault_point("serving.retire", "before", ctx=self.fault_ctx)
            jax.block_until_ready(list(datas))
        except BaseException as e:
            rec = self._inflight.pop(tag, None)
            if rec is not None and \
                    self._handle_batch_failure(rec[0], e, "retire"):
                return           # riders re-enqueued; failure handled
            raise
        rec = self._inflight.pop(tag, None)
        now = self._clock()
        if rec is not None:
            reqs, t_dispatch = rec
            dt = max(0.0, now - t_dispatch)
            self._ewma_service = dt if self._ewma_service is None \
                else 0.3 * dt + 0.7 * self._ewma_service
            for r in reqs:
                self._m_latency.observe(max(0.0, now - r.t_submit))
        if self.on_batch_retired is not None:
            try:
                self.on_batch_retired()
            except Exception:    # pragma: no cover - defensive
                _LOG.warning("serving retire hook failed", exc_info=True)
        fault_point("serving.retire", "after", ctx=self.fault_ctx)
