"""Serving fleet controller: multi-replica routing + failover + rollout.

One :class:`~mxnet_tpu.serving.ServingSupervisor` keeps one replica
alive; this module runs a FLEET of them — one CompiledPredictor +
DynamicBatcher + supervisor per device group, all AOT-warmed from the
shared persistent compile cache (the first replica pays the XLA
compiles; every later spawn/restart pays cache hits) — and puts a
router in front:

- **:class:`FleetController`** — spawns ``MXNET_FLEET_REPLICAS``
  replicas, each built under ``jax.default_device(<its device>)`` so
  params and AOT executables land per-replica; owns the replica
  lifecycle state machine (``serving`` → ``draining``/``recovering``
  → ``retired``).
- **:class:`FleetRouter`** — ``submit()`` picks the serving replica
  with the lowest projected queue wait (each batcher's admission EWMA
  × queued batches), routing around open breakers, draining, and dead
  replicas. When NO replica can take traffic the caller gets a typed
  :class:`~mxnet_tpu.serving.Overloaded` (``reason="fleet"``) —
  never a hang.
- **Replica-loss failover** — a ``device_lost`` at any replica's
  dispatch/retire seam moves that replica's in-flight AND queued
  requests onto the surviving replicas EXACTLY ONCE (the same
  ``requeues`` budget the single-replica supervisor enforces; their
  :class:`~mxnet_tpu.serving.ServingFuture`\\ s re-arm, so a client
  already blocked in ``result()`` rides through), then restarts the
  replica on a spare device with bounded backoff. ``fatal``/``oom``
  causes propagate — a bigger fleet cannot cure a shape bug.
- **Autoscaling** — ``maybe_scale()`` grows the fleet when the fleet
  queue-wait EWMA exceeds ``MXNET_FLEET_SCALE_UP_WAIT_MS`` (and a
  device is free), and drain-then-retires the emptiest replica when
  the fleet is idle below ``MXNET_FLEET_SCALE_DOWN_WAIT_MS``, bounded
  by ``MXNET_FLEET_MIN_REPLICAS``/``MXNET_FLEET_MAX_REPLICAS``.
- **Drain-then-retire** — a scoped preemption notice
  (``elastic.notice("fleet/replica-N")``) drains exactly that replica
  (flush accepted, reject new, retire); the process-global notice
  still drains every replica.
- **Zero-downtime weight rollout** — :meth:`FleetController
  .swap_weights` walks the replicas ONE AT A TIME: drain (accepted
  requests finish on the old weights), load the CRC-verified
  checkpoint (``checkpoint.atomic``), swap params in place (the AOT
  executables take params by handle — no recompile), warm-probe,
  return to rotation. The checkpoint is validated BEFORE any replica
  drains, so a corrupt checkpoint aborts typed
  (:class:`~mxnet_tpu.checkpoint.CheckpointCorruptError`) with the
  fleet still serving the OLD weights; at most one weight version of
  skew is ever in flight.

Telemetry: ``mx_fleet_replicas{state}``,
``mx_fleet_routed_requests_total{replica}``,
``mx_fleet_replica_restarts_total``, ``mx_fleet_weight_swaps_total``,
``mx_fleet_scale_events_total{direction}``,
``mx_fleet_queue_wait_seconds`` (docs/OBSERVABILITY.md). Every
lifecycle transition is a structured :class:`FleetEvent` in
``controller.events`` (the ``tools/diagnose.py --fleet`` panel).

Deterministic testing: ``start=False`` runs every batcher in
manual-drive mode — drive :meth:`FleetController.pump` with an
injected ``clock=``; replica restarts then run inline (no background
thread, no wall-clock backoff). The chaos harness targets one replica
via ``point@ctx`` fault rules (``testing/faults.py``), e.g.
``serving.dispatch@replica-1:before=1:revoke:d3``.
"""
from __future__ import annotations

import logging
import os
import queue
import threading
import time
from functools import partial
from typing import Callable, List, Optional, Sequence

from ..analysis import guard as _tguard
from ..analysis.threads import mx_lock, mx_rlock
from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from ..testing.faults import fault_point
from .batcher import DynamicBatcher
from .resilience import (CircuitBreaker, Overloaded, ServingShutdown,
                         ServingSupervisor)

__all__ = ["FleetController", "FleetRouter", "FleetEvent",
           "fleet_replicas", "fleet_min_replicas", "fleet_max_replicas",
           "fleet_scale_up_wait_s", "fleet_scale_down_wait_s",
           "fleet_restart_retries"]

_LOG = logging.getLogger("mxnet_tpu.serving")

_TELEM = None


def _telemetry():
    global _TELEM
    if _TELEM is None:
        from .. import telemetry as _t
        _TELEM = _t
    return _TELEM


# ---------------------------------------------------------------- env gates
def fleet_replicas(default: int = 1) -> int:
    """``MXNET_FLEET_REPLICAS``: initial replica count (each needs its
    own device from ``parallel.dist.available_devices()``)."""
    try:
        v = int(os.environ.get("MXNET_FLEET_REPLICAS", str(default)))
    except (TypeError, ValueError):
        return default
    return max(1, v)


def fleet_min_replicas(default: int = 1) -> int:
    """``MXNET_FLEET_MIN_REPLICAS``: scale-down floor."""
    try:
        v = int(os.environ.get("MXNET_FLEET_MIN_REPLICAS", str(default)))
    except (TypeError, ValueError):
        return default
    return max(1, v)


def fleet_max_replicas(default: int = 0) -> int:
    """``MXNET_FLEET_MAX_REPLICAS``: scale-up ceiling; <= 0 means
    "one per available device"."""
    try:
        v = int(os.environ.get("MXNET_FLEET_MAX_REPLICAS", str(default)))
    except (TypeError, ValueError):
        return default
    return v


def fleet_scale_up_wait_s(default_ms: float = 200.0) -> float:
    """``MXNET_FLEET_SCALE_UP_WAIT_MS``: fleet queue-wait EWMA above
    which ``maybe_scale()`` adds a replica (high-water mark), as
    seconds."""
    try:
        v = float(os.environ.get("MXNET_FLEET_SCALE_UP_WAIT_MS",
                                 str(default_ms)))
    except (TypeError, ValueError):
        v = default_ms
    return max(0.0, v) / 1e3


def fleet_scale_down_wait_s(default_ms: float = 5.0) -> float:
    """``MXNET_FLEET_SCALE_DOWN_WAIT_MS``: fleet queue-wait EWMA below
    which ``maybe_scale()`` drain-then-retires the emptiest replica
    (low-water mark), as seconds. <= 0 disables scale-down."""
    try:
        v = float(os.environ.get("MXNET_FLEET_SCALE_DOWN_WAIT_MS",
                                 str(default_ms)))
    except (TypeError, ValueError):
        v = default_ms
    return v / 1e3


def fleet_restart_retries(default: int = 2) -> int:
    """``MXNET_FLEET_RESTART_RETRIES``: extra attempts (beyond the
    first) to restart a lost replica before it is retired."""
    try:
        v = int(os.environ.get("MXNET_FLEET_RESTART_RETRIES",
                               str(default)))
    except (TypeError, ValueError):
        return default
    return max(0, v)


# ---------------------------------------------------------------- events
class FleetEvent:
    """One structured fleet lifecycle record: ``kind`` (spawn /
    replica_lost / failover / restart / restart_failed / replica_dead /
    drain / retire / preempt_drain / preempt_retire / scale_up /
    scale_down / swap_begin / swap_drain / swap_done / swap_abort /
    swap_complete), the replica it concerns (None = fleet-wide), the
    controller-clock timestamp, and a detail dict."""

    __slots__ = ("kind", "replica", "t", "detail")

    def __init__(self, kind: str, replica: Optional[str], t: float,
                 detail: Optional[dict] = None):
        self.kind = kind
        self.replica = replica
        self.t = t
        self.detail = dict(detail) if detail else {}

    def __repr__(self):
        who = f" {self.replica}" if self.replica else ""
        return f"<FleetEvent {self.kind}{who} t={self.t:.3f} " \
               f"{self.detail}>"


class _Replica:
    """One serving replica's bookkeeping (the supervisor does the
    work; this records identity + lifecycle state for the router)."""

    SERVING = "serving"
    DRAINING = "draining"
    RECOVERING = "recovering"
    RETIRED = "retired"
    STATES = (SERVING, DRAINING, RECOVERING, RETIRED)

    __slots__ = ("name", "index", "device", "sup", "scope", "version",
                 "state", "error", "_managed")

    def __init__(self, name, index, device, sup, scope, version):
        self.name = name
        self.index = index
        self.device = device
        self.sup = sup
        self.scope = scope
        self.version = version
        self.state = self.SERVING
        self.error: Optional[BaseException] = None
        self._managed = False    # a fleet op (swap/scale) owns it now

    def routable(self) -> bool:
        if self.state != self.SERVING:
            return False
        b = self.sup.batcher
        if b._draining or b._stop.is_set() or b._dead is not None:
            return False
        br = self.sup.breaker
        return br is None or br.state != CircuitBreaker.OPEN


# ---------------------------------------------------------------- router
class FleetRouter:
    """Least-projected-wait router over a :class:`FleetController`'s
    serving replicas. ``submit()`` mirrors the single-replica
    ``ServingSupervisor.submit`` contract (same typed errors, same
    :class:`~mxnet_tpu.serving.ServingFuture`), plus ``fut.replica`` /
    ``fut.version`` breadcrumbs naming who served it."""

    def __init__(self, controller: "FleetController"):
        self._c = controller

    def submit(self, *args, deadline_ms: Optional[float] = None,
               timeout: Optional[float] = None):
        """Route one request to the serving replica with the lowest
        projected queue wait; a replica that sheds at admission
        (:class:`~mxnet_tpu.serving.Overloaded`) is skipped and the
        next-emptiest tried. Raises ``Overloaded(reason="fleet")``
        when no replica is available or every one rejected — an
        accepted request lands on exactly one replica; a rejected one
        fails typed, never hangs."""
        c = self._c
        c.poll()
        rows = DynamicBatcher._rows_of(args)
        cands = []
        with c._lock:
            for rep in c._replicas:
                if not rep.routable():
                    continue
                est = rep.sup.batcher.estimated_wait_s(rows)
                cands.append((est if est is not None else 0.0,
                              rep.index, rep))
        cands.sort(key=lambda t: (t[0], t[1]))
        if not cands:
            c.stats["rejected_fleet"] += 1
            raise Overloaded(
                "fleet: no replica can take traffic (all draining, "
                "recovering, retired, or breaker-open) — retry after "
                "backoff", reason="fleet")
        last: Optional[BaseException] = None
        for est, _idx, rep in cands:
            # chaos-harness seam: routing-decision fault injection
            # (error/delay/revoke), targetable per replica via @ctx
            fault_point("serving.route", "before", ctx=rep.name)
            try:
                fut = rep.sup.submit(*args, deadline_ms=deadline_ms,
                                     timeout=timeout)
            except (Overloaded, ServingShutdown) as e:
                last = e
                continue
            fut.replica = rep.name
            fut.version = rep.version
            c.stats["routed"] += 1
            c._m_routed.inc(label=rep.name)
            c._m_queue_wait.observe(est)
            c._note_wait(est)
            if c.autoscale:
                c.maybe_scale()
            return fut
        c.stats["rejected_fleet"] += 1
        raise Overloaded(
            f"fleet: every serving replica rejected the request "
            f"(last: {type(last).__name__}: {last})",
            reason="fleet") from last


# ---------------------------------------------------------------- controller
class FleetController:
    """Run N independent serving replicas behind one router::

        def build():                          # deterministic!
            net = make_net()                  # params materialized
            return mx.serving.CompiledPredictor(net,
                                               bucket_sizes=(1, 2, 4))

        fleet = mx.serving.FleetController(build, example=(x_row,),
                                           replicas=3, max_batch=4)
        fut = fleet.router.submit(x)          # least-wait routing
        out = fut.result(30)
        fleet.swap_weights(ckpt_root)         # zero-downtime rollout
        fleet.close()

    ``build()`` must construct a FRESH CompiledPredictor; the
    controller wraps it in ``jax.default_device(<replica device>)`` so
    each replica's params land on its own device, and every replica
    after the first warms its AOT buckets from the shared
    persistent compile cache.

    ``start=False`` puts every batcher in manual-drive mode (tests):
    drive :meth:`pump`, inject ``clock=``; failover restarts run
    inline with no backoff sleep.
    """

    def __init__(self, build: Callable,
                 example: Optional[Sequence] = None, *,
                 replicas: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 depth: Optional[int] = None,
                 inflight: Optional[int] = None,
                 min_replicas: Optional[int] = None,
                 max_replicas: Optional[int] = None,
                 autoscale: bool = False,
                 backoff_base: float = 0.05, backoff_max: float = 2.0,
                 clock: Callable[[], float] = time.perf_counter,
                 start: bool = True):
        from ..elastic import detect as _detect
        from ..parallel import dist as _dist
        self._build = build
        self._example = tuple(example) if example is not None else None
        self._batcher_kwargs = dict(max_batch=max_batch,
                                    timeout_ms=timeout_ms, depth=depth,
                                    inflight=inflight)
        self._clock = clock
        self._start = bool(start)
        self._detect = _detect
        self._dist = _dist
        self._backoff_base = float(backoff_base)
        self._backoff_max = float(backoff_max)
        self._lock = mx_rlock("serving.fleet")
        self._scale_lock = mx_lock("serving.fleet.scale")
        self._replicas: List[_Replica] = []
        self._next_idx = 0
        self.version = 0         # current weight version (swaps bump it)
        self.autoscale = bool(autoscale)
        self.queue_wait_ewma: Optional[float] = None
        self.events: List[FleetEvent] = []
        self.stats = {"routed": 0, "rejected_fleet": 0, "failovers": 0,
                      "requeued": 0, "failed_requeues": 0, "restarts": 0,
                      "swaps": 0, "scale_ups": 0, "scale_downs": 0,
                      "drains": 0}
        t = _telemetry()
        reg = t.registry()
        self._m_replicas = reg.gauge(t.names.FLEET_REPLICAS,
                                     label_key="state")
        self._m_routed = reg.counter(t.names.FLEET_ROUTED,
                                     label_key="replica")
        self._m_restarts = reg.counter(t.names.FLEET_RESTARTS)
        self._m_swaps = reg.counter(t.names.FLEET_SWAPS)
        self._m_scale = reg.counter(t.names.FLEET_SCALE_EVENTS,
                                    label_key="direction")
        self._m_queue_wait = reg.histogram(t.names.FLEET_QUEUE_WAIT)
        n = fleet_replicas() if replicas is None else max(1, int(replicas))
        devs = _dist.available_devices()
        if n > len(devs):
            raise MXNetError(
                f"fleet: {n} replicas requested but only {len(devs)} "
                "device(s) available (MXNET_FLEET_REPLICAS)")
        self.min_replicas = fleet_min_replicas() if min_replicas is None \
            else max(1, int(min_replicas))
        mx_r = fleet_max_replicas() if max_replicas is None \
            else int(max_replicas)
        self.max_replicas = mx_r if mx_r > 0 else len(devs)
        for _ in range(n):
            dev = self._pick_device()
            if dev is None:      # pragma: no cover - guarded above
                raise MXNetError("fleet: ran out of devices mid-spawn")
            self._spawn(dev)
        self.router = FleetRouter(self)

    # ---------------- introspection ----------------
    @property
    def replicas(self) -> List[_Replica]:
        return list(self._replicas)

    def live(self) -> List[_Replica]:
        """Replicas currently able to take routed traffic."""
        with self._lock:
            return [r for r in self._replicas if r.routable()]

    def describe(self) -> dict:
        """Structured fleet snapshot (the ``diagnose --fleet``
        panel)."""
        with self._lock:
            reps = [{
                "name": r.name, "state": r.state,
                "device": str(r.device), "version": r.version,
                "breaker": r.sup.breaker.state
                if r.sup.breaker else None,
                "queued": r.sup.batcher._queue.qsize()
                + len(r.sup.batcher._forming),
                "inflight": len(r.sup.batcher._window),
                "est_wait_s": r.sup.batcher.estimated_wait_s(1),
                "error": f"{type(r.error).__name__}: {r.error}"
                if r.error else None,
            } for r in self._replicas]
        return {"replicas": reps, "version": self.version,
                "min_replicas": self.min_replicas,
                "max_replicas": self.max_replicas,
                "autoscale": self.autoscale,
                "queue_wait_ewma_s": self.queue_wait_ewma,
                "stats": dict(self.stats),
                "events": [repr(e) for e in self.events[-16:]]}

    # ---------------- lifecycle plumbing ----------------
    def _event(self, kind: str, replica: Optional[str],
               detail: Optional[dict] = None):
        ev = FleetEvent(kind, replica, self._clock(), detail)
        if len(self.events) < 1024:
            self.events.append(ev)
        _LOG.info("fleet: %s%s %s", kind,
                  f" [{replica}]" if replica else "", ev.detail)

    def _update_gauge(self):
        counts = {s: 0 for s in _Replica.STATES}
        for r in self._replicas:
            counts[r.state] += 1
        for s, c in counts.items():
            self._m_replicas.set(float(c), label=s)

    def _note_wait(self, est: float):
        w = max(0.0, float(est))
        self.queue_wait_ewma = w if self.queue_wait_ewma is None \
            else 0.2 * w + 0.8 * self.queue_wait_ewma

    def _pick_device(self, exclude: Optional[_Replica] = None):
        """A device no live replica occupies (revoked devices are
        already excluded by ``available_devices()``)."""
        used = {r.device for r in self._replicas
                if r is not exclude and r.state != _Replica.RETIRED}
        for d in self._dist.available_devices():
            if d not in used:
                return d
        return None

    def _pinned_build(self, device) -> Callable:
        """``build`` under ``jax.default_device(device)``. That moves
        only UNCOMMITTED arrays: parameters and requests made in the
        default context are committed to local device 0 by
        ``Context.jax_device`` (CPU rig and TPU host alike), so today
        every replica computes there and ``device`` is bookkeeping for
        revocation. One replica per chip needs ``build`` to create its
        net under ``mx.tpu(i)`` AND the router to place each request on
        its replica's device; neither exists yet (ROADMAP Reach 7)."""
        base = self._build
        def build():
            import jax
            with jax.default_device(device):
                return base()
        return build

    def _make_supervisor(self, device, scope) -> ServingSupervisor:
        return ServingSupervisor(
            self._pinned_build(device), example=self._example,
            drain_on_preemption=scope, clock=self._clock,
            start=self._start, **self._batcher_kwargs)

    def _wire(self, rep: _Replica):
        """Point the replica's failure handling at the FLEET (device
        loss fails over to survivors instead of rebuilding in place)
        and tag its chaos-fault context with the replica name."""
        b = rep.sup.batcher
        b.on_batch_failure = partial(self._on_replica_failure, rep)
        b.fault_ctx = rep.name

    def _spawn(self, device) -> _Replica:
        idx = self._next_idx
        self._next_idx += 1
        name = f"replica-{idx}"
        scope = f"fleet/{name}"
        self._detect.notice(scope).clear()
        sup = self._make_supervisor(device, scope)
        rep = _Replica(name, idx, device, sup, scope, self.version)
        self._wire(rep)
        with self._lock:
            self._replicas.append(rep)
            self._update_gauge()
        self._event("spawn", name, {"device": str(device)})
        return rep

    # ---------------- replica-loss failover ----------------
    def _on_replica_failure(self, rep: _Replica, reqs, exc,
                            seam: str) -> bool:
        """Batcher hook (runs on that replica's dispatcher thread).
        ``transient`` retries in place via the replica's own
        supervisor; ``device_lost`` fails over to the survivors;
        ``fatal``/``oom``/``stall`` propagate to the futures."""
        cause = self._detect.classify(exc)
        if cause == "transient":
            return rep.sup._retry_transient(list(reqs), exc, seam)
        if cause != "device_lost":
            return False
        self._failover(rep, list(reqs), exc, seam)
        return True

    def _failover(self, rep: _Replica, reqs, exc, seam: str):
        """Move the lost replica's riders + queue onto the survivors
        exactly once, stop its batcher, and restart it on a spare
        device (background thread in threaded mode; inline with no
        backoff in manual mode)."""
        with self._lock:
            rep.state = _Replica.RECOVERING
            rep.error = exc
            self._update_gauge()
            self._event("replica_lost", rep.name, {
                "seam": seam, "error": f"{type(exc).__name__}: {exc}"})
            rep.sup.breaker.trip("fleet failover")
            self._detect.maybe_record_device_lost(exc, f"fleet {seam}")
            b = rep.sup.batcher
            riders = list(reqs) + b.abandon_inflight()
            # the handler runs on the dispatcher thread — the single
            # owner of _forming — so stealing the backlog here is safe
            b._drain_queue()
            riders += b._forming
            b._forming = []
            seen, uniq = set(), []
            for r in riders:
                if id(r) not in seen:
                    seen.add(id(r))
                    uniq.append(r)
            uniq.sort(key=lambda r: r.t_submit)
            b._stop.set()        # the dispatch loop exits after we return
            moved = failed = 0
            for r in uniq:
                if r.future.done():
                    continue
                if r.requeues >= 1:
                    self.stats["failed_requeues"] += 1
                    r.future._fail(MXNetError(
                        f"serving request lost to repeated device "
                        f"failure (re-enqueued {r.requeues}x): "
                        f"{type(exc).__name__}: {exc}"))
                    failed += 1
                    continue
                target = self._pick_target(rep, r.rows)
                if target is None:
                    self.stats["failed_requeues"] += 1
                    r.future._fail(Overloaded(
                        "fleet failover: no surviving replica could "
                        "absorb this request", reason="fleet"))
                    failed += 1
                    continue
                r.requeues += 1
                r.future._rearm()
                r.future.replica = target.name
                r.future.version = target.version
                try:
                    target.sup.batcher._queue.put_nowait(r)
                except queue.Full:
                    self.stats["failed_requeues"] += 1
                    r.future._fail(Overloaded(
                        "fleet failover: survivor queue saturated",
                        reason="fleet"))
                    failed += 1
                    continue
                moved += 1
            # belt-and-braces anti-hang: anything that raced into the
            # dead queue after the steal fails typed, like close()
            b._fail_pending(ServingShutdown(
                "replica lost; request arrived during fleet failover"))
            self.stats["failovers"] += 1
            self.stats["requeued"] += moved
            self._event("failover", rep.name, {
                "seam": seam, "moved": moved, "failed": failed})
        if self._start:
            threading.Thread(
                target=self._restart_replica, args=(rep, exc),
                name=f"mx-fleet-restart-{rep.name}",
                daemon=True).start()
        else:
            self._restart_replica(rep, exc, backoff=False)

    def _pick_target(self, rep: _Replica, rows: int) -> \
            Optional[_Replica]:
        """Surviving replica with the lowest projected wait (failover
        bypasses the router: the riders were already admitted once)."""
        best, best_w = None, None
        for r in self._replicas:
            if r is rep or not r.routable():
                continue
            w = r.sup.batcher.estimated_wait_s(rows)
            w = 0.0 if w is None else w
            if best_w is None or w < best_w:
                best, best_w = r, w
        return best

    def _restart_replica(self, rep: _Replica, exc,
                         backoff: bool = True):
        """Bounded-retry restart on a spare device: a fresh supervisor
        (fresh predictor, AOT buckets warm from the compile cache,
        fresh breaker). ``fatal``/``oom`` build failures retire the
        replica with the error recorded — they propagate, not loop."""
        attempts = max(1, fleet_restart_retries() + 1)
        delay = self._backoff_base
        last = exc
        for i in range(attempts):
            try:
                dev = self._pick_device(exclude=rep)
                if dev is None:
                    raise MXNetError(
                        "fleet: no spare device to restart "
                        f"{rep.name} on (world shrank)")
                self._detect.notice(rep.scope).clear()
                with _tguard.allow_transfers("fleet replica restart"):
                    sup = self._make_supervisor(dev, rep.scope)
                with self._lock:
                    rep.sup = sup
                    rep.device = dev
                    rep.version = self.version
                    rep.error = None
                    self._wire(rep)
                    rep.state = _Replica.SERVING
                    self.stats["restarts"] += 1
                    self._m_restarts.inc()
                    self._update_gauge()
                    self._event("restart", rep.name, {
                        "device": str(dev), "attempt": i + 1})
                return
            except Exception as e:   # noqa: BLE001 - classify below
                last = e
                cause = self._detect.classify(e)
                _LOG.warning(
                    "fleet: restart of %s attempt %d/%d failed "
                    "(%s: %s; cause=%s)", rep.name, i + 1, attempts,
                    type(e).__name__, e, cause)
                if cause in ("fatal", "oom"):
                    break        # propagate: a retry cannot cure these
                if backoff and delay > 0:
                    time.sleep(delay)
                    delay = min(self._backoff_max, delay * 2)
        with self._lock:
            rep.state = _Replica.RETIRED
            rep.error = last
            self._update_gauge()
            self._event("restart_failed", rep.name, {
                "error": f"{type(last).__name__}: {last}",
                "attempts": attempts})

    # ---------------- drain / retire / preemption ----------------
    def drain_then_retire(self, rep: _Replica,
                          cause: str = "manual"):
        """Flush the replica's accepted requests (old weights keep
        serving them), reject new, retire it from the rotation."""
        with self._lock:
            if rep.state == _Replica.RETIRED:
                return
            rep.state = _Replica.DRAINING
            rep._managed = True
            self._update_gauge()
            self._event("drain", rep.name, {"cause": cause})
        try:
            rep.sup.drain()
            self.stats["drains"] += 1
        finally:
            with self._lock:
                rep.state = _Replica.RETIRED
                rep._managed = False
                self._update_gauge()
                self._event("retire", rep.name, {"cause": cause})

    def poll(self):
        """Cheap housekeeping (the router calls it per submit): notice
        replicas whose dispatcher self-drained on a scoped preemption
        notice or died, and — in manual-drive mode — run the scoped
        drain on the calling thread."""
        to_drain: List[_Replica] = []
        with self._lock:
            for rep in self._replicas:
                if rep._managed:
                    continue
                b = rep.sup.batcher
                if rep.state == _Replica.SERVING:
                    if b._dead is not None:
                        rep.state = _Replica.RETIRED
                        rep.error = b._dead
                        self._update_gauge()
                        self._event("replica_dead", rep.name, {
                            "error": f"{type(b._dead).__name__}: "
                                     f"{b._dead}"})
                    elif b._stop.is_set():
                        rep.state = _Replica.RETIRED
                        self._update_gauge()
                        self._event("preempt_retire", rep.name, {})
                    elif b._draining:
                        rep.state = _Replica.DRAINING
                        self._update_gauge()
                        self._event("preempt_drain", rep.name, {})
                    elif not self._start and \
                            self._detect.notice(rep.scope).requested():
                        to_drain.append(rep)
                elif rep.state == _Replica.DRAINING and \
                        b._stop.is_set():
                    rep.state = _Replica.RETIRED
                    self._update_gauge()
                    self._event("preempt_retire", rep.name, {})
        for rep in to_drain:
            self.drain_then_retire(rep, cause="preemption")

    # ---------------- autoscaling ----------------
    def maybe_scale(self) -> Optional[str]:
        """One autoscale decision from the fleet queue-wait EWMA:
        ``"up"`` (spawned a replica), ``"down"`` (drained + retired
        the emptiest), or None. Never blocks the caller on a
        concurrent scale op (try-lock)."""
        ewma = self.queue_wait_ewma
        if ewma is None:
            return None
        if not self._scale_lock.acquire(blocking=False):
            return None
        try:
            with self._lock:
                serving = [r for r in self._replicas
                           if r.state == _Replica.SERVING]
            n = len(serving)
            if ewma >= fleet_scale_up_wait_s() and \
                    n < self.max_replicas:
                dev = self._pick_device()
                if dev is None:
                    return None
                rep = self._spawn(dev)
                self.stats["scale_ups"] += 1
                self._m_scale.inc(label="up")
                self._event("scale_up", rep.name, {
                    "queue_wait_ewma_s": ewma, "serving": n + 1})
                return "up"
            down = fleet_scale_down_wait_s()
            if down > 0 and ewma <= down and n > self.min_replicas:
                empt = min(
                    serving,
                    key=lambda r:
                    (r.sup.batcher.estimated_wait_s(0) or 0.0,
                     -r.index))
                self.stats["scale_downs"] += 1
                self._m_scale.inc(label="down")
                self._event("scale_down", empt.name, {
                    "queue_wait_ewma_s": ewma, "serving": n - 1})
                self.drain_then_retire(empt, cause="scale_down")
                return "down"
            return None
        finally:
            self._scale_lock.release()

    # ---------------- zero-downtime weight rollout ----------------
    def swap_weights(self, checkpoint: str) -> dict:
        """Rolling weight swap: validate the checkpoint FIRST (a
        corrupt one aborts typed with every replica still serving the
        OLD weights), then walk the serving replicas one at a time —
        drain (accepted requests finish on the old weights), swap the
        params in place (the AOT executables take params by handle: no
        recompile), warm-probe, return to rotation. At most one weight
        version of skew is in flight at any instant; zero accepted
        requests are dropped.

        ``checkpoint`` — a committed step directory, or a checkpoint
        root (its newest VALID step is used). Raises
        :class:`~mxnet_tpu.checkpoint.CheckpointCorruptError` /
        ``MXNetError`` on a bad checkpoint; a per-replica apply
        failure rolls that replica back to the old weights and
        re-raises with the fleet still serving."""
        from ..checkpoint import atomic as _atomic
        path = self._resolve_checkpoint(checkpoint)
        _atomic.validate_checkpoint(path)    # corrupt -> typed abort
        arrays, manifest = _atomic.read_checkpoint(path)
        params = {k: v for k, v in arrays.items()
                  if k.startswith("param/")}
        if not params:
            raise MXNetError(
                f"fleet swap: checkpoint {path} holds no param/ "
                "arrays — nothing to roll out")
        array_meta = {k: v for k, v in manifest["arrays"].items()
                      if k.startswith("param/")}
        new_version = self.version + 1
        t0 = time.monotonic()
        self._event("swap_begin", None, {
            "path": path, "version": new_version})
        swapped = 0
        for rep in list(self._replicas):
            if rep.state != _Replica.SERVING:
                continue
            self._swap_one(rep, params, array_meta,
                           manifest.get("meta", {}), new_version)
            swapped += 1
        self.version = new_version
        self.stats["swaps"] += 1
        self._m_swaps.inc()
        self._event("swap_complete", None, {
            "version": new_version, "replicas": swapped,
            "duration_s": time.monotonic() - t0})
        return {"version": new_version, "replicas": swapped,
                "path": path}

    @staticmethod
    def _resolve_checkpoint(checkpoint: str) -> str:
        from ..checkpoint import atomic as _atomic
        p = os.path.abspath(checkpoint)
        if os.path.exists(os.path.join(p, _atomic.MANIFEST)):
            return p
        found = _atomic.latest_valid(p)
        if found is None:
            raise MXNetError(
                f"fleet swap: no valid checkpoint under {p}")
        return found[1]

    def _swap_one(self, rep: _Replica, params, array_meta, meta,
                  new_version: int):
        from ..checkpoint import state as _ckstate
        with self._lock:
            rep.state = _Replica.DRAINING
            rep._managed = True
            self._update_gauge()
            self._event("swap_drain", rep.name,
                        {"version": new_version})
        try:
            rep.sup.drain()      # accepted traffic finishes on OLD
            net = getattr(rep.sup.predictor, "_net", None)
            if net is None:
                raise MXNetError(
                    f"fleet swap: {rep.name}'s predictor exposes no "
                    "bound net to load weights into")
            plist = list(net.collect_params().values())
            snapshot = [(p, p._data) for p in plist]
            try:
                with _tguard.allow_transfers("fleet weight swap"):
                    st = _ckstate.TrainState(dict(params), dict(meta),
                                             dict(array_meta))
                    _ckstate.apply_train_state(st, net=net,
                                               strict=True)
            except BaseException:
                for p, d in snapshot:    # old weights, bit-exact
                    p._data = d
                raise
            self._respawn_batcher(rep)
            with _tguard.allow_transfers("fleet swap warm probe"):
                self._warm_probe(rep)
            with self._lock:
                rep.version = new_version
                rep.state = _Replica.SERVING
                rep._managed = False
                self._update_gauge()
                self._event("swap_done", rep.name,
                            {"version": new_version})
        except BaseException as e:
            try:
                self._respawn_batcher(rep)
            except Exception:    # pragma: no cover - defensive
                _LOG.warning("fleet: batcher respawn after aborted "
                             "swap failed", exc_info=True)
            with self._lock:
                rep.state = _Replica.SERVING
                rep._managed = False
                self._update_gauge()
                self._event("swap_abort", rep.name, {
                    "error": f"{type(e).__name__}: {e}"})
            raise

    def _respawn_batcher(self, rep: _Replica):
        """Fresh batcher after a drain (the drained one is closed);
        the admission EWMA carries over — same predictor, same
        service time."""
        sup = rep.sup
        old = sup._batcher
        b = DynamicBatcher(sup.predictor, clock=self._clock,
                           start=self._start, **self._batcher_kwargs)
        b.breaker = sup.breaker
        b.on_batch_retired = sup._on_batch_retired
        b.drain_check = self._detect.notice(rep.scope).requested
        if old is not None and old._ewma_service is not None:
            b._ewma_service = old._ewma_service
        sup._batcher = b
        sup._closed = False
        self._wire(rep)

    def _warm_probe(self, rep: _Replica):
        """One blocking forward through the swapped predictor before
        it rejoins the rotation — the first routed request must not
        pay a surprise, and a weight/arch mismatch surfaces HERE
        (typed, rolled back by the caller) instead of on traffic."""
        if self._example is None:
            return
        import jax
        pred = rep.sup.predictor
        padded, _rows = pred.pad_to_bucket(*self._example)
        res = pred.predict(*padded)
        jax.block_until_ready([
            l._data for l in jax.tree_util.tree_leaves(
                res, is_leaf=lambda t: isinstance(t, NDArray))
            if isinstance(l, NDArray)])

    # ---------------- manual drive + shutdown ----------------
    def pump(self, force: bool = False) -> bool:
        """Manual-drive (``start=False``): one dispatch pass + window
        retire on every serving replica, then :meth:`poll`. Returns
        whether any replica dispatched a batch."""
        did = False
        for rep in list(self._replicas):
            if rep.state != _Replica.SERVING:
                continue
            b = rep.sup.batcher
            if b._stop.is_set() or b._dead is not None:
                continue
            if b.process_once(force=force):
                did = True
            if rep.state == _Replica.SERVING and len(b._window):
                b._window.drain()
                b._m_inflight.set(0)
        self.poll()
        return did

    def drain(self):
        """Graceful fleet shutdown: drain every replica (accepted
        requests flush), retire all."""
        for rep in list(self._replicas):
            if rep.state in (_Replica.SERVING, _Replica.DRAINING):
                self.drain_then_retire(rep, cause="shutdown")

    def close(self):
        for rep in list(self._replicas):
            if rep.state != _Replica.RETIRED:
                try:
                    rep.sup.close()
                except Exception:    # pragma: no cover - defensive
                    _LOG.warning("fleet: close of %s failed", rep.name,
                                 exc_info=True)
                rep.state = _Replica.RETIRED
        with self._lock:
            self._update_gauge()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
