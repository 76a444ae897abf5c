"""Resilient serving (docs/SERVING.md "Resilient serving").

Pins the resilience contracts on top of PR 12's serving engine:

- typed failure taxonomy: DeadlineExceeded / Overloaded(reason) /
  ServingShutdown — an accepted request ends in exactly one of
  {result, typed failure}, NEVER a hang;
- per-request deadlines: expired requests are dropped at dequeue
  (never padded/dispatched); admission control sheds at submit when
  the EWMA-projected queue wait exceeds the deadline
  (MXNET_SERVING_SHED=off|deadline|queue), all on the injected fake
  clock;
- circuit breaker open/half-open/close transitions;
- graceful drain: reject new, flush forming + in-flight, close;
- dispatcher-death propagation into every pending future;
- ServingSupervisor auto-recovery: device loss rebuilds the predictor
  over available_devices() and re-enqueues in-flight requests exactly
  once; transient failures retry bounded; fatal propagates;
- the chaos acceptance: revoke mid-traffic under
  MXNET_TRANSFER_GUARD=raise — zero lost accepted requests, exactly
  one recovery, bit-exact results post-recovery, zero unblessed syncs.
"""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.elastic import detect
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving import loadgen
from mxnet_tpu.serving.resilience import CircuitBreaker
from mxnet_tpu.testing import faults

IN, HIDDEN, CLASSES = 16, 32, 4


@pytest.fixture(autouse=True)
def _clean_harness():
    """Every test leaves the chaos harness disarmed, devices restored,
    and the preemption notice cleared."""
    yield
    faults.reset()
    detect.notice().clear()


def make_net(in_units=IN, hidden=HIDDEN, classes=CLASSES):
    onp.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="relu", in_units=in_units),
            nn.Dense(classes, in_units=hidden))
    net.initialize()
    net(mx.nd.array(onp.zeros((1, in_units), "float32")))
    return net


def rows(n, in_units=IN, seed=0):
    return onp.random.RandomState(seed).randn(n, in_units) \
        .astype("float32")


@pytest.fixture
def pred():
    return serving.CompiledPredictor(make_net(),
                                     bucket_sizes=(1, 2, 4, 8))


def manual_batcher(pred, clk, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("timeout_ms", 5.0)
    return serving.DynamicBatcher(pred, start=False,
                                  clock=lambda: clk[0], **kw)


def build_pred():
    # deterministic, per the ServingSupervisor build() contract: every
    # (re)build must produce the same params, so recovery is bit-exact
    mx.random.seed(7)
    return serving.CompiledPredictor(make_net(), bucket_sizes=(1, 2, 4, 8))


# ---------------------------------------------------------------------------
# env accessors
# ---------------------------------------------------------------------------

def test_shed_mode_parsing(monkeypatch):
    monkeypatch.delenv("MXNET_SERVING_SHED", raising=False)
    assert serving.shed_mode() == "deadline"          # the default
    for v in ("off", "deadline", "queue"):
        monkeypatch.setenv("MXNET_SERVING_SHED", v)
        assert serving.shed_mode() == v
    monkeypatch.setenv("MXNET_SERVING_SHED", "bogus")
    assert serving.shed_mode() == "deadline"


def test_default_deadline_parsing(monkeypatch):
    monkeypatch.delenv("MXNET_SERVING_DEADLINE_MS", raising=False)
    assert serving.default_deadline_ms() is None
    monkeypatch.setenv("MXNET_SERVING_DEADLINE_MS", "25")
    assert serving.default_deadline_ms() == 25.0
    monkeypatch.setenv("MXNET_SERVING_DEADLINE_MS", "0")
    assert serving.default_deadline_ms() is None
    monkeypatch.setenv("MXNET_SERVING_DEADLINE_MS", "junk")
    assert serving.default_deadline_ms() is None


def test_queue_timeout_parsing(monkeypatch):
    monkeypatch.delenv("MXNET_SERVING_QUEUE_TIMEOUT_MS", raising=False)
    assert serving.queue_timeout_s() == pytest.approx(120.0)
    monkeypatch.setenv("MXNET_SERVING_QUEUE_TIMEOUT_MS", "250")
    assert serving.queue_timeout_s() == pytest.approx(0.25)
    monkeypatch.setenv("MXNET_SERVING_QUEUE_TIMEOUT_MS", "-5")
    assert serving.queue_timeout_s() == 0.0


# ---------------------------------------------------------------------------
# deadlines: expiry at dequeue (fake clock)
# ---------------------------------------------------------------------------

def test_expired_request_dropped_at_dequeue(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)
    miss0 = telemetry.value(telemetry.names.SERVING_DEADLINE_MISSED) or 0
    fut = b.submit(mx.nd.array(rows(1)), deadline_ms=3.0)
    clk[0] = 0.004                        # past the 3 ms deadline
    assert b.process_once(force=True) is False   # nothing dispatched
    with pytest.raises(serving.DeadlineExceeded, match="never dispatched"):
        fut.result(5)
    assert b.stats["batches"] == 0        # never padded/dispatched
    assert b.stats["deadline_missed"] == 1
    assert (telemetry.value(telemetry.names.SERVING_DEADLINE_MISSED)
            or 0) - miss0 == 1
    b.close()


def test_unexpired_request_dispatches_normally(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)
    fut = b.submit(mx.nd.array(rows(1)), deadline_ms=50.0)
    clk[0] = 0.006                        # past the batch timeout only
    assert b.process_once() is True
    assert fut.result(10).shape == (1, CLASSES)
    b.close()


def test_deadline_boundary_exact(pred):
    # a request AT its deadline is expired; one a tick under is served
    clk = [0.0]
    b = manual_batcher(pred, clk)
    f_dead = b.submit(mx.nd.array(rows(1)), deadline_ms=10.0)
    clk[0] = 0.010
    assert b.process_once(force=True) is False
    with pytest.raises(serving.DeadlineExceeded):
        f_dead.result(5)
    f_live = b.submit(mx.nd.array(rows(1)), deadline_ms=10.0)
    clk[0] = 0.010 + 0.0099
    assert b.process_once(force=True) is True
    assert f_live.result(10).shape == (1, CLASSES)
    b.close()


def test_env_default_deadline_applies(pred, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_DEADLINE_MS", "3")
    monkeypatch.setenv("MXNET_SERVING_SHED", "off")
    clk = [0.0]
    b = manual_batcher(pred, clk)
    fut = b.submit(mx.nd.array(rows(1)))       # deadline from env
    clk[0] = 0.004
    assert b.process_once(force=True) is False
    with pytest.raises(serving.DeadlineExceeded):
        fut.result(5)
    # deadline_ms=0 opts a single request out of the env default
    f2 = b.submit(mx.nd.array(rows(1)), deadline_ms=0)
    clk[0] = 60.0
    assert b.process_once(force=True) is True
    assert f2.result(10).shape == (1, CLASSES)
    b.close()


# ---------------------------------------------------------------------------
# admission control / shedding (fake clock, seeded EWMA)
# ---------------------------------------------------------------------------

def test_shed_deadline_rejects_on_projected_wait(pred, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_SHED", "deadline")
    clk = [0.0]
    b = manual_batcher(pred, clk)
    b._ewma_service = 0.050               # 50 ms per micro-batch
    rej0 = telemetry.value(telemetry.names.SERVING_REJECTED,
                           "deadline") or 0
    # 1 waiting batch x 50 ms projected > 20 ms deadline: shed
    with pytest.raises(serving.Overloaded, match="projected queue wait") \
            as ei:
        b.submit(mx.nd.array(rows(1)), deadline_ms=20.0)
    assert ei.value.reason == "deadline"
    assert (telemetry.value(telemetry.names.SERVING_REJECTED, "deadline")
            or 0) - rej0 == 1
    # same request with budget for one batch: admitted
    fut = b.submit(mx.nd.array(rows(1)), deadline_ms=100.0)
    assert b.process_once(force=True) is True
    assert fut.result(10).shape == (1, CLASSES)
    # no deadline: never shed by projection
    assert b.submit(mx.nd.array(rows(1))) is not None
    b.flush()
    b.close()


def test_shed_off_admits_regardless_of_projection(pred, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_SHED", "off")
    clk = [0.0]
    b = manual_batcher(pred, clk)
    b._ewma_service = 10.0                # hopeless projection
    fut = b.submit(mx.nd.array(rows(1)), deadline_ms=5.0)
    assert fut is not None                # admitted anyway (off)
    b.flush()
    b.close()


def test_shed_queue_rejects_without_blocking(pred, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_SHED", "queue")
    clk = [0.0]
    b = manual_batcher(pred, clk, depth=1)
    b.submit(mx.nd.array(rows(1)))
    t0 = time.perf_counter()
    with pytest.raises(serving.Overloaded, match="saturated") as ei:
        b.submit(mx.nd.array(rows(1)), timeout=30.0)   # timeout ignored
    assert ei.value.reason == "queue"
    assert time.perf_counter() - t0 < 1.0              # no blocking
    b.flush()
    b.close()


def test_queue_full_is_typed_overloaded(pred):
    # the former raw 120 s queue.put: bound explicit, error typed
    clk = [0.0]
    b = manual_batcher(pred, clk, depth=1)
    rej0 = telemetry.value(telemetry.names.SERVING_REJECTED, "queue") or 0
    b.submit(mx.nd.array(rows(1)))
    with pytest.raises(serving.Overloaded, match="saturated") as ei:
        b.submit(mx.nd.array(rows(1)), timeout=0.02)
    assert ei.value.reason == "queue"
    assert isinstance(ei.value, MXNetError)            # still an MXNetError
    assert (telemetry.value(telemetry.names.SERVING_REJECTED, "queue")
            or 0) - rej0 == 1
    b.flush()
    b.close()


def test_estimated_wait_formula(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)                      # max_batch 4
    assert b.estimated_wait_s(1) is None               # no EWMA yet
    b._ewma_service = 0.010
    # 1 row waiting -> 1 batch, empty window
    assert b.estimated_wait_s(1) == pytest.approx(0.010)
    # 5 rows -> 2 batches
    assert b.estimated_wait_s(5) == pytest.approx(0.020)
    b.close()


def test_ewma_updates_at_retire(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)
    b.submit(mx.nd.array(rows(1)))
    assert b.process_once(force=True) is True
    clk[0] = 0.030                        # 30 ms of "device time"
    b.flush()                             # retire records service time
    assert b._ewma_service == pytest.approx(0.030)
    b.close()


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def test_breaker_opens_at_threshold():
    clk = [0.0]
    br = CircuitBreaker(failure_threshold=3, clock=lambda: clk[0])
    assert br.state == "closed" and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == "closed"           # under threshold
    br.record_failure()
    assert br.state == "open" and not br.allow()


def test_breaker_cooldown_half_open_then_closes():
    clk = [0.0]
    br = CircuitBreaker(failure_threshold=1, cooldown_s=5.0,
                        clock=lambda: clk[0])
    br.record_failure()
    assert br.state == "open" and not br.allow()
    clk[0] = 4.9
    assert not br.allow()                 # cooldown not elapsed
    clk[0] = 5.1
    assert br.allow()                     # the probe
    assert br.state == "half_open"
    br.record_success()
    assert br.state == "closed" and br.allow()


def test_breaker_reopens_on_half_open_failure():
    clk = [0.0]
    br = CircuitBreaker(failure_threshold=2, cooldown_s=1.0,
                        clock=lambda: clk[0])
    br.trip("recovery")
    clk[0] = 2.0
    assert br.allow() and br.state == "half_open"
    br.record_failure()                   # probe failed
    assert br.state == "open"
    states = [s for s, _t, _c in br.transitions]
    assert states == ["closed", "open", "half_open", "open"]


def test_breaker_explicit_transitions_and_gauge():
    br = CircuitBreaker()
    assert telemetry.value(telemetry.names.SERVING_BREAKER_STATE) == 0
    br.trip("recovery")
    assert telemetry.value(telemetry.names.SERVING_BREAKER_STATE) == 2
    br.half_open()
    assert telemetry.value(telemetry.names.SERVING_BREAKER_STATE) == 1
    br.close()
    assert telemetry.value(telemetry.names.SERVING_BREAKER_STATE) == 0


def test_open_breaker_fast_fails_submit(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)
    b.breaker = CircuitBreaker()
    b.breaker.trip("recovery")
    with pytest.raises(serving.Overloaded, match="circuit breaker") as ei:
        b.submit(mx.nd.array(rows(1)))
    assert ei.value.reason == "breaker"
    b.breaker.close()
    assert b.submit(mx.nd.array(rows(1))) is not None
    b.flush()
    b.close()


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

def test_drain_flushes_accepted_then_rejects_new(pred):
    pred.warmup(mx.nd.array(rows(1)))
    hist = telemetry.registry().get(telemetry.names.SERVING_DRAIN_SECONDS)
    d0 = hist.count()
    b = serving.DynamicBatcher(pred, max_batch=8, timeout_ms=50.0)
    futs = [b.submit(mx.nd.array(rows(1, seed=i))) for i in range(5)]
    b.drain()
    for f in futs:                        # accepted requests all land
        assert f.result(30).shape == (1, CLASSES)
    with pytest.raises((serving.Overloaded, serving.ServingShutdown)):
        b.submit(mx.nd.array(rows(1)))
    assert hist.count() - d0 == 1         # drain duration recorded
    b.drain()                             # idempotent
    b.close()


def test_drain_manual_mode(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)
    fut = b.submit(mx.nd.array(rows(1)))
    b.drain()
    assert fut.result(10).shape == (1, CLASSES)
    with pytest.raises(serving.ServingShutdown):
        b.submit(mx.nd.array(rows(1)))


def test_drain_check_preemption_bridge(pred):
    """The supervisor's SIGTERM path: the dispatch loop polls
    drain_check and drains itself."""
    pred.warmup(mx.nd.array(rows(1)), buckets=(1, 2, 4, 8))
    b = serving.DynamicBatcher(pred, max_batch=8, timeout_ms=1.0)
    want = threading.Event()
    b.drain_check = want.is_set
    futs = [b.submit(mx.nd.array(rows(1, seed=i))) for i in range(4)]
    want.set()
    deadline = time.time() + 15
    while not b._stop.is_set() and time.time() < deadline:
        time.sleep(0.005)
    assert b._stop.is_set(), "drain_check never initiated the drain"
    for f in futs:
        assert f.result(30).shape == (1, CLASSES)
    with pytest.raises((serving.Overloaded, serving.ServingShutdown)):
        b.submit(mx.nd.array(rows(1)))
    b.close()


# ---------------------------------------------------------------------------
# dispatcher death -> ServingShutdown (the anti-hang regression)
# ---------------------------------------------------------------------------

def test_dispatcher_death_fails_pending_futures(pred):
    b = serving.DynamicBatcher(pred, max_batch=4, timeout_ms=60000.0,
                               start=False)
    f1 = b.submit(mx.nd.array(rows(1)))
    f2 = b.submit(mx.nd.array(rows(1, seed=1)))

    def boom():
        raise RuntimeError("loop machinery bug")

    b._serve_loop_inner = boom
    t = threading.Thread(target=b._serve_loop, daemon=True)
    t.start()
    t.join(10)
    assert not t.is_alive()
    for f in (f1, f2):                    # typed, not a hang
        with pytest.raises(serving.ServingShutdown, match="died"):
            f.result(5)
    with pytest.raises(serving.ServingShutdown, match="died"):
        b.submit(mx.nd.array(rows(1)))
    assert b.stats["shutdown_failed"] == 2


def test_close_with_backlog_never_hangs(pred):
    # close() flushes the backlog; anything undispatchable fails typed
    clk = [0.0]
    b = manual_batcher(pred, clk)
    fut = b.submit(mx.nd.array(rows(1)))
    b.close()                             # flush dispatches the backlog
    assert fut.result(10).shape == (1, CLASSES)


# ---------------------------------------------------------------------------
# ServingSupervisor: classified recovery
# ---------------------------------------------------------------------------

def make_supervisor(example=False, **kw):
    ex = (mx.nd.array(rows(1)),) if example else None
    kw.setdefault("max_batch", 8)
    kw.setdefault("timeout_ms", 1.0)
    return serving.ServingSupervisor(build_pred, example=ex, **kw)


def test_supervisor_serves_plain_traffic():
    X = rows(8, seed=3)
    with make_supervisor() as sup:
        futs = [sup.submit(mx.nd.array(X[i:i + 1])) for i in range(8)]
        outs = [f.result(30) for f in futs]
    assert all(o.shape == (1, CLASSES) for o in outs)
    assert sup.stats["recoveries"] == 0
    assert sup.breaker.state == "closed"


def submit_with_retry(sup, x, budget_s=60.0):
    """A real client's posture: an Overloaded rejection (breaker open
    while recovery runs, queue full) is retryable — back off and
    resubmit. Bounded, so a broken service still fails the test."""
    deadline = time.time() + budget_s
    while True:
        try:
            return sup.submit(x)
        except serving.Overloaded:
            if time.time() >= deadline:
                raise
            time.sleep(0.01)


def test_supervisor_device_loss_recovery_requeues_once(rows_match):
    X = rows(8, seed=3)
    singles = [build_pred().predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(8)]
    rec0 = telemetry.value(telemetry.names.SERVING_RECOVERIES,
                           "device_lost") or 0
    with make_supervisor() as sup:
        faults.configure("serving.dispatch:before=1:revoke:1")
        futs = [submit_with_retry(sup, mx.nd.array(X[i:i + 1]))
                for i in range(8)]
        outs = [f.result(60).asnumpy() for f in futs]
        assert sup.stats["recoveries"] == 1
        assert sup.stats["requeued"] >= 1     # the revoked batch's riders
        assert sup.stats["failed_requeues"] == 0
        assert sup.last_recovery["cause"] == "device_lost"
        assert sup.last_recovery["downtime_s"] < 60
    # the half-open breaker closes at the first successful retire —
    # guaranteed by the close()-time window drain at the latest
    states = [s for s, _t, _c in sup.breaker.transitions]
    assert states == ["closed", "open", "half_open", "closed"]
    for i in range(8):                    # recovery preserves answers
        assert rows_match(outs[i], singles[i])
    assert (telemetry.value(telemetry.names.SERVING_RECOVERIES,
                            "device_lost") or 0) - rec0 == 1


def test_supervisor_second_loss_fails_typed():
    """Re-enqueue is EXACTLY once: a request lost twice fails with the
    device-loss error instead of looping forever."""
    X = rows(1, seed=5)
    with make_supervisor() as sup:
        faults.configure("serving.dispatch:before=1:revoke:1;"
                         "serving.dispatch:before=2:revoke:1")
        fut = sup.submit(mx.nd.array(X))
        with pytest.raises(MXNetError, match="repeated device"):
            fut.result(60)
        assert sup.stats["recoveries"] == 2
        assert sup.stats["failed_requeues"] == 1


def test_supervisor_transient_retry_succeeds():
    X = rows(4, seed=7)
    ret0 = telemetry.value(telemetry.names.SERVING_RETRIES,
                           "transient") or 0
    with make_supervisor(backoff_base=0.01) as sup:
        faults.configure("serving.dispatch:before=1:error")
        futs = [sup.submit(mx.nd.array(X[i:i + 1])) for i in range(4)]
        outs = [f.result(60) for f in futs]
        assert all(o.shape == (1, CLASSES) for o in outs)
        assert sup.stats["retried"] >= 1       # the faulted batch's riders
        assert sup.stats["failed_requeues"] == 0
        assert sup.stats["recoveries"] == 0    # no rebuild for transient
    assert (telemetry.value(telemetry.names.SERVING_RETRIES, "transient")
            or 0) - ret0 >= 1


def test_supervisor_transient_budget_exhausted():
    X = rows(1, seed=9)
    with make_supervisor(max_retries=0, backoff_base=0.01) as sup:
        faults.configure("serving.dispatch:before=1:error")
        fut = sup.submit(mx.nd.array(X))
        with pytest.raises(MXNetError, match="transient"):
            fut.result(60)
        assert sup.stats["failed_requeues"] == 1


def test_supervisor_fatal_propagates():
    # wrong feature width against a proven program: classified fatal —
    # no recovery, the future fails with the dispatch error
    with make_supervisor(example=True) as sup:
        good = sup.submit(mx.nd.array(rows(1)))
        assert good.result(30).shape == (1, CLASSES)
        bad = sup.submit(mx.nd.array(
            onp.zeros((1, IN + 3), "float32")))
        with pytest.raises(Exception):
            bad.result(30)
        assert sup.stats["recoveries"] == 0
        assert sup.stats["retried"] == 0


def test_supervisor_drain_on_preemption_notice():
    X = rows(4, seed=11)
    hist = telemetry.registry().get(telemetry.names.SERVING_DRAIN_SECONDS)
    d0 = hist.count()
    sup = make_supervisor()
    try:
        futs = [sup.submit(mx.nd.array(X[i:i + 1])) for i in range(4)]
        detect.notice().trigger()
        deadline = time.time() + 15
        while not sup.batcher._stop.is_set() and time.time() < deadline:
            time.sleep(0.005)
        assert sup.batcher._stop.is_set(), "preemption never drained"
        for f in futs:                    # accepted requests all land
            assert f.result(30).shape == (1, CLASSES)
        with pytest.raises((serving.Overloaded, serving.ServingShutdown)):
            sup.submit(mx.nd.array(X[:1]))
        assert hist.count() - d0 == 1
    finally:
        detect.notice().clear()
        sup.close()


def test_fault_point_serving_admit(pred):
    """The third chaos seam: faults injected at admission surface on
    the submitting client's thread."""
    clk = [0.0]
    b = manual_batcher(pred, clk)
    faults.configure("serving.admit:before=1:error")
    with pytest.raises(faults.FaultInjectedError):
        b.submit(mx.nd.array(rows(1)))
    faults.configure(None)
    assert b.submit(mx.nd.array(rows(1))) is not None
    b.flush()
    b.close()


# ---------------------------------------------------------------------------
# loadgen outcome census
# ---------------------------------------------------------------------------

def test_loadgen_outcome_census_closed():
    def issue(i):
        if i % 4 == 0:
            raise serving.Overloaded("shed", reason="queue")
        if i % 4 == 1:
            raise serving.DeadlineExceeded("late")
        if i % 4 == 2:
            raise RuntimeError("boom")

    rep = loadgen.run_closed_loop(issue, concurrency=2, requests=40)
    assert rep["outcomes"] == {"ok": 10, "rejected": 10,
                               "deadline_missed": 10, "error": 10}
    assert rep["issued"] == 40 and rep["requests"] == 10
    assert rep["reject_rate"] == pytest.approx(0.25)
    assert rep["deadline_miss_rate"] == pytest.approx(0.25)
    assert rep["goodput_qps"] is not None
    assert rep["goodput_qps"] <= rep["qps"]


def test_loadgen_slow_completion_counts_as_deadline_missed():
    def issue(i):
        if i % 2:
            time.sleep(0.03)

    rep = loadgen.run_closed_loop(issue, concurrency=1, requests=10,
                                  deadline_s=0.01)
    assert rep["outcomes"]["ok"] == 5
    assert rep["outcomes"]["deadline_missed"] == 5


def test_loadgen_open_loop_counts_submit_rejections():
    def submit(i):
        if i % 2:
            raise serving.Overloaded("shed at admission",
                                     reason="deadline")
        return lambda *_: None

    rep = loadgen.run_open_loop(submit, rate_qps=2000.0, requests=20)
    assert rep["outcomes"]["rejected"] == 10
    assert rep["outcomes"]["ok"] == 10
    assert rep["reject_rate"] == pytest.approx(0.5)


def test_classify_outcome_walks_cause_chain():
    try:
        try:
            raise serving.Overloaded("inner", reason="queue")
        except serving.Overloaded as inner:
            raise MXNetError("wrapped") from inner
    except MXNetError as e:
        assert loadgen.classify_outcome(e) == "rejected"
    assert loadgen.classify_outcome(RuntimeError("x")) == "error"
    assert loadgen.classify_outcome(
        serving.DeadlineExceeded("late")) == "deadline_missed"


# ---------------------------------------------------------------------------
# chaos acceptance: revoke mid-traffic, zero lost accepted requests
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_revoke_mid_traffic_zero_lost(monkeypatch, rows_match):
    """Sustained concurrent traffic across a revoke -> recover ->
    restore cycle under MXNET_TRANSFER_GUARD=raise: every accepted
    request ends in exactly one of {result, typed failure} with zero
    hangs, exactly one recovery is recorded with bounded downtime,
    post-recovery results stay bit-exact vs single dispatch, and the
    serving hot loop performs zero unblessed host syncs."""
    N = 32
    X = rows(N, seed=13)
    singles = [build_pred().predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(N)]
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    monkeypatch.setenv("MXNET_SERVING_SHED", "off")
    rec0 = telemetry.value(telemetry.names.SERVING_RECOVERIES,
                           "device_lost") or 0
    sync0 = telemetry.value(telemetry.names.HOST_SYNCS,
                            "wait_to_read") or 0
    results = [None] * N
    errors = [None] * N
    with make_supervisor(example=True, timeout_ms=2.0) as sup:
        faults.configure("serving.dispatch:before=2:revoke:1")

        def client(i):
            try:
                results[i] = submit_with_retry(
                    sup, mx.nd.array(X[i:i + 1])).result(60)
            except MXNetError as e:
                errors[i] = e

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True) for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
        hung = [i for i, t in enumerate(threads) if t.is_alive()]
        assert not hung, f"clients hung: {hung}"
        assert sup.stats["recoveries"] == 1
        assert sup.stats["recovery_downtime_s"] < 60
        faults.restore_devices()           # the world grows back
        # post-restore traffic flows on the recovered predictor
        late = sup.submit(mx.nd.array(X[:1]))
        assert late.result(30) is not None
    # zero unblessed syncs in the serving hot loop (results still async)
    assert (telemetry.value(telemetry.names.HOST_SYNCS, "wait_to_read")
            or 0) - sync0 == 0
    # every request: exactly one terminal state, and — with clients
    # retrying typed Overloaded rejections like real traffic — every
    # single one is eventually SERVED across the revocation
    for i in range(N):
        assert (results[i] is None) != (errors[i] is None), \
            f"request {i} has no terminal state"
        assert errors[i] is None, \
            f"request {i}: terminal failure {errors[i]!r}"
    for i in range(N):                     # bit-exact incl. post-recovery
        assert rows_match(results[i].asnumpy(), singles[i]), \
            f"request {i} differs from single dispatch post-recovery"
    assert (telemetry.value(telemetry.names.SERVING_RECOVERIES,
                            "device_lost") or 0) - rec0 == 1


@pytest.mark.chaos
def test_chaos_revoke_at_retire_seam():
    """A deferred device loss surfacing at the window retire (not at
    dispatch) recovers identically: the in-flight riders re-enqueue
    and resolve."""
    N = 8
    X = rows(N, seed=17)
    with make_supervisor(timeout_ms=1.0, inflight=2) as sup:
        faults.configure("serving.retire:before=1:revoke:1")
        futs = []
        for i in range(N):
            try:
                futs.append(sup.submit(mx.nd.array(X[i:i + 1])))
            except serving.Overloaded:
                futs.append(None)          # shed while breaker open
        outs = []
        for f in futs:
            if f is None:
                continue
            try:
                outs.append(f.result(60))
            except serving.Overloaded:
                pass
        # the retire (and with it the injected loss + recovery) runs on
        # the dispatcher thread, concurrent with the clients' response
        # reads — wait for it rather than racing it
        deadline = time.time() + 30
        while sup.stats["recoveries"] < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert sup.stats["recoveries"] == 1
        assert outs, "no request survived the retire-seam revocation"
        assert all(o.shape == (1, CLASSES) for o in outs)
