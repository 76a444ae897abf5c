"""Serving fleet controller (docs/SERVING.md "Serving fleet").

Pins the fleet contracts on top of the single-replica resilience stack:

- least-projected-wait routing with ``fut.replica``/``fut.version``
  breadcrumbs; open breakers / draining / retired replicas get ZERO new
  requests; all replicas unavailable is a typed
  ``Overloaded(reason="fleet")``, never a hang;
- replica-loss failover: a dead replica's in-flight + queued requests
  re-enqueue EXACTLY once onto the survivors, the replica restarts on a
  spare device (one ``mx_fleet_replica_restarts_total`` increment), a
  request lost twice fails typed;
- scoped preemption notices drain exactly the named replica; the
  process-global notice drains every replica (all on a fake clock);
- zero-downtime rolling weight swap: validated-first checkpoints, one
  replica draining at a time (<= 1 version of skew), zero dropped
  accepted requests, post-swap outputs bit-exact vs a fresh predictor,
  corrupt checkpoints abort typed with the OLD weights serving;
- autoscaling up/down against the queue-wait EWMA watermarks;
- the satellites: warmup-seeded admission EWMA, per-token deadline
  re-projection in the decode engine (pages returned), loadgen
  per-replica census, and the ``mx_fleet_*`` catalog entries;
- the chaos acceptance: 3 replicas, a replica-targeted device
  revocation mid-burst under MXNET_TRANSFER_GUARD=raise — zero lost
  accepted requests, zero hangs, exactly one restart, zero unblessed
  syncs.
"""
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import CheckpointCorruptError, atomic
from mxnet_tpu.checkpoint.state import capture_train_state
from mxnet_tpu.elastic import detect
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving import loadgen
from mxnet_tpu.serving.fleet import _Replica
from mxnet_tpu.testing import faults

IN, HIDDEN, CLASSES = 16, 32, 4
BUCKETS = (1, 2, 4)


@pytest.fixture(autouse=True)
def _clean_harness():
    """Every test leaves the chaos harness disarmed, devices restored,
    and every (scoped) preemption notice cleared. The gc.collect keeps
    fleet garbage (threads, device buffers) from billing a GC pause to
    a later test's step-time watchdog."""
    yield
    faults.reset()
    detect.notice().clear()
    detect.clear_scoped_notices()
    import gc
    gc.collect()


def make_net(seed=7):
    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(HIDDEN, activation="relu", in_units=IN),
            nn.Dense(CLASSES, in_units=HIDDEN))
    net.initialize()
    net(mx.nd.array(onp.zeros((1, IN), "float32")))
    return net


def build_pred(seed=7):
    # deterministic, per the build() contract: every (re)build must
    # produce the same params, so failover/restart is bit-exact
    return serving.CompiledPredictor(make_net(seed), bucket_sizes=BUCKETS)


def rows(n, seed=0):
    return onp.random.RandomState(seed).randn(n, IN).astype("float32")


def make_fleet(clk, n=3, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("timeout_ms", 5.0)
    return serving.FleetController(
        build_pred, example=(mx.nd.array(rows(1)),), replicas=n,
        clock=lambda: clk[0], start=False, **kw)


def seed_waits(fleet, waits):
    """Pin each replica's admission EWMA so routing is deterministic."""
    for rep, w in zip(fleet.replicas, waits):
        rep.sup.batcher._ewma_service = w


def pump_until_done(fleet, futs, rounds=50):
    for _ in range(rounds):
        if all(f.done() for f in futs):
            return
        fleet.pump(force=True)
    raise AssertionError("futures did not resolve under pump()")


def test_replicas_share_the_compile_cache():
    """`import mxnet_tpu` armed ONE persistent compile cache (at
    JAX_COMPILATION_CACHE_DIR, else a fixed path in the checkout): the
    first replica compiles each bucket, every later build — warm spawn
    is the product behavior — AOT-warm-starts from it."""
    from mxnet_tpu import runtime
    before = runtime.compile_cache_stats()
    assert before["enabled"]
    fleet = make_fleet([0.0], n=2)
    fleet.close()
    assert runtime.compile_cache_stats()["hits"] > before["hits"]


# ---------------------------------------------------------------------------
# env accessors
# ---------------------------------------------------------------------------

def test_fleet_env_parsing(monkeypatch):
    for var in ("MXNET_FLEET_REPLICAS", "MXNET_FLEET_MIN_REPLICAS",
                "MXNET_FLEET_MAX_REPLICAS", "MXNET_FLEET_SCALE_UP_WAIT_MS",
                "MXNET_FLEET_SCALE_DOWN_WAIT_MS",
                "MXNET_FLEET_RESTART_RETRIES"):
        monkeypatch.delenv(var, raising=False)
    assert serving.fleet_replicas() == 1
    assert serving.fleet_min_replicas() == 1
    assert serving.fleet_max_replicas() == 0
    assert serving.fleet_scale_up_wait_s() == pytest.approx(0.2)
    assert serving.fleet_scale_down_wait_s() == pytest.approx(0.005)
    assert serving.fleet_restart_retries() == 2
    monkeypatch.setenv("MXNET_FLEET_REPLICAS", "3")
    monkeypatch.setenv("MXNET_FLEET_SCALE_UP_WAIT_MS", "50")
    monkeypatch.setenv("MXNET_FLEET_SCALE_DOWN_WAIT_MS", "-1")
    monkeypatch.setenv("MXNET_FLEET_RESTART_RETRIES", "0")
    assert serving.fleet_replicas() == 3
    assert serving.fleet_scale_up_wait_s() == pytest.approx(0.05)
    assert serving.fleet_scale_down_wait_s() < 0      # disables
    assert serving.fleet_restart_retries() == 0
    monkeypatch.setenv("MXNET_FLEET_REPLICAS", "junk")
    assert serving.fleet_replicas() == 1


def test_fleet_rejects_more_replicas_than_devices():
    import jax
    too_many = len(jax.devices()) + 1
    with pytest.raises(MXNetError, match="device"):
        serving.FleetController(build_pred, replicas=too_many,
                                start=False)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_router_picks_lowest_projected_wait():
    clk = [0.0]
    fleet = make_fleet(clk, 3)
    try:
        seed_waits(fleet, [0.5, 0.001, 0.5])
        fut = fleet.router.submit(mx.nd.array(rows(1)))
        assert fut.replica == "replica-1"
        assert fut.version == 0
        assert fleet.stats["routed"] == 1
        # the emptiest changes as queues build: replica-1 now holds a
        # request, so a far-cheaper peer wins the next decision
        seed_waits(fleet, [0.5, 0.5, 0.0001])
        fut2 = fleet.router.submit(mx.nd.array(rows(1, seed=1)))
        assert fut2.replica == "replica-2"
        pump_until_done(fleet, [fut, fut2])
        assert fut.result(10).shape == (1, CLASSES)
        assert fut2.result(10).shape == (1, CLASSES)
    finally:
        fleet.close()


def test_router_skips_open_breaker_zero_new_requests():
    """An open breaker gets ZERO new routed requests — the router
    filters it out entirely (no admission attempt, no queue entry)."""
    clk = [0.0]
    fleet = make_fleet(clk, 3)
    try:
        seed_waits(fleet, [0.001, 0.5, 0.5])   # victim would win
        victim = fleet.replicas[0]
        victim.sup.breaker.trip("test")
        assert not victim.routable()
        for i in range(4):
            fut = fleet.router.submit(mx.nd.array(rows(1, seed=i)))
            assert fut.replica != victim.name
        assert victim.sup.batcher._queue.qsize() == 0
        assert len(victim.sup.batcher._forming) == 0
        assert (telemetry.value(telemetry.names.FLEET_ROUTED,
                                victim.name) or 0) == 0
        victim.sup.breaker.close()
        fut = fleet.router.submit(mx.nd.array(rows(1)))
        assert fut.replica == victim.name      # back in rotation
    finally:
        fleet.close()


def test_router_all_unavailable_is_typed_overloaded():
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        rej0 = fleet.stats["rejected_fleet"]
        for rep in fleet.replicas:
            rep.sup.breaker.trip("test")
        with pytest.raises(serving.Overloaded, match="no replica") as ei:
            fleet.router.submit(mx.nd.array(rows(1)))
        assert ei.value.reason == "fleet"
        assert isinstance(ei.value, MXNetError)
        assert fleet.stats["rejected_fleet"] == rej0 + 1
    finally:
        fleet.close()


def test_router_falls_through_replica_rejection():
    """A replica that sheds at admission is skipped; the next candidate
    serves. Every replica rejecting surfaces as reason='fleet'."""
    clk = [0.0]
    fleet = make_fleet(clk, 2, depth=1)
    try:
        a, b = fleet.replicas
        seed_waits(fleet, [0.001, 0.5])
        # saturate a's queue so its admission rejects (shed=queue style:
        # depth 1, one rider waiting, submit with timeout=0)
        a.sup.batcher._queue.put_nowait(
            object.__new__(type("X", (), {})))  # placeholder occupies depth
        fut = fleet.router.submit(mx.nd.array(rows(1)), timeout=0.01)
        assert fut.replica == b.name
    finally:
        a.sup.batcher._drain_queue()
        fleet.close()


def test_route_fault_point_targets_one_replica():
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        seed_waits(fleet, [0.001, 0.5])
        faults.configure("serving.route@replica-0:before=1:error")
        with pytest.raises(faults.FaultInjectedError):
            fleet.router.submit(mx.nd.array(rows(1)))
        faults.configure(None)
        fut = fleet.router.submit(mx.nd.array(rows(1)))
        assert fut.replica == "replica-0"      # untargeted peer unharmed
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# replica-loss failover (manual drive, fake clock)
# ---------------------------------------------------------------------------

def test_failover_moves_riders_exactly_once_and_restarts(rows_match):
    N = 6
    X = rows(N, seed=3)
    singles = [build_pred().predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(N)]
    clk = [0.0]
    restarts0 = telemetry.value(telemetry.names.FLEET_RESTARTS) or 0
    fleet = make_fleet(clk, 3)
    try:
        victim = fleet.replicas[2]
        old_device = victim.device
        seed_waits(fleet, [0.5, 0.5, 0.001])   # all traffic -> victim
        futs = [fleet.router.submit(mx.nd.array(X[i:i + 1]))
                for i in range(N)]
        assert all(f.replica == victim.name for f in futs)
        faults.configure(f"serving.dispatch@{victim.name}:before=1"
                         f":revoke:d{victim.device.id}")
        pump_until_done(fleet, futs)
        outs = [f.result(10).asnumpy() for f in futs]
        for i in range(N):                     # failover preserves answers
            assert rows_match(outs[i], singles[i])
        assert fleet.stats["failovers"] == 1
        assert fleet.stats["requeued"] >= 1
        assert fleet.stats["failed_requeues"] == 0
        assert fleet.stats["restarts"] == 1
        assert (telemetry.value(telemetry.names.FLEET_RESTARTS) or 0) \
            - restarts0 == 1
        # restarted on a spare device, serving again, fresh breaker
        assert victim.state == _Replica.SERVING
        assert victim.device != old_device
        assert victim.sup.breaker.state == "closed"
        kinds = [e.kind for e in fleet.events if e.replica == victim.name]
        assert kinds[-3:] == ["replica_lost", "failover", "restart"]
        # riders carry the survivor breadcrumb after the re-arm
        assert all(f.replica != victim.name or f.done() for f in futs)
        # post-restart traffic flows through the revived replica
        seed_waits(fleet, [0.5, 0.5, 0.001])
        late = fleet.router.submit(mx.nd.array(X[:1]))
        assert late.replica == victim.name
        pump_until_done(fleet, [late])
        # alone in the bucket of 1: the program `singles` ran
        assert (late.result(10).asnumpy() == singles[0]).all()
    finally:
        fleet.close()


def test_request_lost_twice_fails_typed():
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        a, b = fleet.replicas
        seed_waits(fleet, [0.001, 0.5])
        fut = fleet.router.submit(mx.nd.array(rows(1)))
        assert fut.replica == a.name
        faults.configure(
            f"serving.dispatch@{a.name}:before=1:revoke:d{a.device.id};"
            f"serving.dispatch@{b.name}:before=1:revoke:d{b.device.id}")
        for _ in range(20):
            if fut.done():
                break
            fleet.pump(force=True)
        with pytest.raises(MXNetError, match="repeated device"):
            fut.result(5)
        assert fleet.stats["failed_requeues"] == 1
        assert fleet.stats["failovers"] == 2
    finally:
        fleet.close()


def test_restart_exhaustion_retires_replica(monkeypatch):
    """Every restart attempt failing (world shrank to nothing spare)
    retires the replica with the error recorded — no infinite loop."""
    monkeypatch.setenv("MXNET_FLEET_RESTART_RETRIES", "1")
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        a = fleet.replicas[0]
        monkeypatch.setattr(fleet, "_pick_device",
                            lambda exclude=None: None)
        seed_waits(fleet, [0.001, 0.5])
        fut = fleet.router.submit(mx.nd.array(rows(1)))
        faults.configure(f"serving.dispatch@{a.name}:before=1"
                         f":revoke:d{a.device.id}")
        pump_until_done(fleet, [fut])          # rider lands on survivor
        assert fut.result(10).shape == (1, CLASSES)
        assert a.state == _Replica.RETIRED
        assert isinstance(a.error, MXNetError)
        assert any(e.kind == "restart_failed" for e in fleet.events)
        assert fleet.stats["restarts"] == 0
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# scoped preemption drain (fake clock)
# ---------------------------------------------------------------------------

def test_scoped_notice_drains_only_named_replica():
    clk = [0.0]
    fleet = make_fleet(clk, 3)
    try:
        target = fleet.replicas[1]
        seed_waits(fleet, [0.5, 0.001, 0.5])
        futs = [fleet.router.submit(mx.nd.array(rows(1, seed=i)))
                for i in range(3)]
        assert all(f.replica == target.name for f in futs)
        detect.notice(target.scope).trigger()
        fleet.poll()                           # manual-mode drain
        assert target.state == _Replica.RETIRED
        for f in futs:                         # accepted requests land
            assert f.result(10).shape == (1, CLASSES)
        others = [r for r in fleet.replicas if r is not target]
        assert all(r.state == _Replica.SERVING for r in others)
        # the survivors still serve routed traffic
        fut = fleet.router.submit(mx.nd.array(rows(1)))
        assert fut.replica != target.name
        pump_until_done(fleet, [fut])
        assert fut.result(10).shape == (1, CLASSES)
        kinds = [(e.kind, e.replica) for e in fleet.events
                 if e.kind in ("drain", "retire")]
        assert kinds == [("drain", target.name), ("retire", target.name)]
    finally:
        fleet.close()


def test_global_notice_drains_every_replica():
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        detect.notice().trigger()
        fleet.poll()
        assert all(r.state == _Replica.RETIRED for r in fleet.replicas)
        with pytest.raises(serving.Overloaded) as ei:
            fleet.router.submit(mx.nd.array(rows(1)))
        assert ei.value.reason == "fleet"
    finally:
        detect.notice().clear()
        fleet.close()


def test_training_supervisor_ignores_scoped_notices():
    """A replica-scoped notice must never pause training: the elastic
    supervisor polls only the process-global notice."""
    detect.notice("fleet/replica-0").trigger()
    assert detect.notice("fleet/replica-0").requested()
    assert not detect.notice().requested()
    detect.clear_scoped_notices()
    assert not detect.notice("fleet/replica-0").requested()
    # and the global notice reaches scoped listeners (drain everything)
    detect.notice().trigger()
    assert detect.notice("fleet/replica-0").requested()


# ---------------------------------------------------------------------------
# autoscaling (fake clock)
# ---------------------------------------------------------------------------

def test_autoscale_up_and_down(monkeypatch):
    monkeypatch.setenv("MXNET_FLEET_SCALE_UP_WAIT_MS", "100")
    monkeypatch.setenv("MXNET_FLEET_SCALE_DOWN_WAIT_MS", "5")
    clk = [0.0]
    fleet = make_fleet(clk, 2, min_replicas=1, max_replicas=3)
    try:
        fleet.queue_wait_ewma = 0.5            # way past the high water
        assert fleet.maybe_scale() == "up"
        assert len([r for r in fleet.replicas
                    if r.state == _Replica.SERVING]) == 3
        assert fleet.stats["scale_ups"] == 1
        assert fleet.maybe_scale() is None     # at max_replicas
        fleet.queue_wait_ewma = 0.001          # idle below the low water
        assert fleet.maybe_scale() == "down"
        assert fleet.stats["scale_downs"] == 1
        serving_now = [r for r in fleet.replicas
                       if r.state == _Replica.SERVING]
        assert len(serving_now) == 2
        fleet.queue_wait_ewma = 0.001
        fleet.maybe_scale()
        fleet.queue_wait_ewma = 0.001
        assert fleet.maybe_scale() is None     # floor: min_replicas=1
        assert len([r for r in fleet.replicas
                    if r.state == _Replica.SERVING]) == 1
    finally:
        fleet.close()


def test_autoscale_down_disabled(monkeypatch):
    monkeypatch.setenv("MXNET_FLEET_SCALE_DOWN_WAIT_MS", "0")
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        fleet.queue_wait_ewma = 0.0
        assert fleet.maybe_scale() is None
        assert fleet.stats["scale_downs"] == 0
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# zero-downtime rolling weight swap
# ---------------------------------------------------------------------------

def write_ckpt(tmp_path, seed=23, step=1):
    """A committed checkpoint holding a DIFFERENT deterministic net's
    params (what a training run would have produced)."""
    st = capture_train_state(net=make_net(seed), step=step)
    root = os.path.join(str(tmp_path), "ckpt")
    return atomic.write_checkpoint(root, step, st.arrays,
                                   array_meta=st.array_meta,
                                   meta=st.meta), root


def test_rolling_swap_zero_drop_bit_exact(tmp_path, rows_match):
    N = 4
    X = rows(N, seed=5)
    old_out = [build_pred().predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(N)]
    new_out = [build_pred(23).predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(N)]
    path, root = write_ckpt(tmp_path)
    swaps0 = telemetry.value(telemetry.names.FLEET_SWAPS) or 0
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        seed_waits(fleet, [0.001, 0.001])
        # accepted-but-unserved traffic rides through the rollout
        inflight = [fleet.router.submit(mx.nd.array(X[i:i + 1]))
                    for i in range(N)]
        res = fleet.swap_weights(root)         # resolves newest valid
        assert res["version"] == 1 and res["replicas"] == 2
        assert res["path"] == path
        assert fleet.version == 1
        assert all(r.version == 1 for r in fleet.replicas)
        assert (telemetry.value(telemetry.names.FLEET_SWAPS) or 0) \
            - swaps0 == 1
        # zero dropped: the in-flight requests flushed during the
        # drain, ON THE OLD WEIGHTS
        for i, f in enumerate(inflight):
            assert rows_match(f.result(10).asnumpy(), old_out[i])
        # <= 1 version of skew: replicas drained strictly one at a time
        order = [(e.kind, e.replica) for e in fleet.events
                 if e.kind in ("swap_drain", "swap_done")]
        assert order == [("swap_drain", "replica-0"),
                         ("swap_done", "replica-0"),
                         ("swap_drain", "replica-1"),
                         ("swap_done", "replica-1")]
        # post-swap traffic is bit-exact vs a fresh predictor built
        # from the new weights
        for i in range(N):
            fut = fleet.router.submit(mx.nd.array(X[i:i + 1]))
            assert fut.version == 1
            pump_until_done(fleet, [fut])
            assert (fut.result(10).asnumpy() == new_out[i]).all()
    finally:
        fleet.close()


def test_corrupt_checkpoint_aborts_typed_old_weights_serve(tmp_path):
    X = rows(2, seed=5)
    old_out = [build_pred().predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(2)]
    path, _root = write_ckpt(tmp_path)
    # flip bytes in one committed array file: CRC must catch it
    arrays_dir = os.path.join(path, "arrays")
    victim_file = os.path.join(arrays_dir,
                               sorted(os.listdir(arrays_dir))[0])
    with open(victim_file, "r+b") as f:
        f.seek(-4, os.SEEK_END)
        f.write(b"\xde\xad\xbe\xef")
    swaps0 = telemetry.value(telemetry.names.FLEET_SWAPS) or 0
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        with pytest.raises(CheckpointCorruptError):
            fleet.swap_weights(path)
        # typed abort BEFORE any replica drained: everything serving
        # the OLD weights at the OLD version, no swap recorded
        assert fleet.version == 0
        assert all(r.state == _Replica.SERVING for r in fleet.replicas)
        assert all(r.version == 0 for r in fleet.replicas)
        assert (telemetry.value(telemetry.names.FLEET_SWAPS) or 0) \
            == swaps0
        assert not any(e.kind.startswith("swap_drain")
                       for e in fleet.events)
        seed_waits(fleet, [0.001, 0.5])
        fut = fleet.router.submit(mx.nd.array(X[:1]))
        pump_until_done(fleet, [fut])
        assert (fut.result(10).asnumpy() == old_out[0]).all()
    finally:
        fleet.close()


def test_swap_missing_checkpoint_is_typed(tmp_path):
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        with pytest.raises(MXNetError, match="no valid checkpoint"):
            fleet.swap_weights(str(tmp_path / "empty"))
    finally:
        fleet.close()


def test_manager_latest_path_feeds_swap(tmp_path):
    """TrainCheckpointManager.latest_path() is the training→serving
    rollout handle."""
    from mxnet_tpu.checkpoint import TrainCheckpointManager
    root = str(tmp_path / "mgr")
    mgr = TrainCheckpointManager(root, keep_last=2)
    assert mgr.latest_path() is None
    st = capture_train_state(net=make_net(23), step=5)
    mgr.save_state(st)
    p = mgr.latest_path()
    assert p is not None and os.path.isdir(p)
    atomic.validate_checkpoint(p)              # swap-ready


# ---------------------------------------------------------------------------
# satellites: warmup-seeded EWMA, decode mid-stream shed, loadgen census
# ---------------------------------------------------------------------------

def test_warmup_seeds_admission_ewma():
    """Cold-start admission blindness fix: a warmed predictor hands its
    AOT execution timing to the batcher, so deadline shedding projects
    from request 1 instead of admitting blindly until the first
    retire."""
    pred = build_pred()
    assert pred.service_time_seed_s is None
    cold = serving.DynamicBatcher(pred, start=False, max_batch=4)
    assert cold._ewma_service is None
    assert cold.estimated_wait_s(1) is None    # blind before warmup
    cold.close()
    pred.warmup(mx.nd.array(rows(1)))
    assert pred.service_time_seed_s is not None
    assert pred.service_time_seed_s > 0
    warm = serving.DynamicBatcher(pred, start=False, max_batch=4)
    assert warm._ewma_service == pytest.approx(pred.service_time_seed_s)
    assert warm.estimated_wait_s(1) is not None
    warm.close()


def test_warm_seed_sheds_from_first_request(monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_SHED", "deadline")
    pred = build_pred()
    pred.warmup(mx.nd.array(rows(1)))
    pred.service_time_seed_s = 0.050           # pin a slow seed
    clk = [0.0]
    b = serving.DynamicBatcher(pred, start=False, max_batch=4,
                               clock=lambda: clk[0])
    with pytest.raises(serving.Overloaded) as ei:
        b.submit(mx.nd.array(rows(1)), deadline_ms=20.0)
    assert ei.value.reason == "deadline"       # shed on request ONE
    b.close()


def test_decode_midstream_deadline_shed_returns_pages():
    """Per-token deadline re-projection: a stream whose TPOT EWMA says
    the remaining tokens cannot finish in budget is shed MID-stream
    with a typed DeadlineExceeded, and its KV pages return to the
    pool."""
    clk = [0.0]
    model = serving.TinyDecoder(vocab=32, d_model=16, num_heads=2,
                                seed=0)
    eng = serving.DecodeEngine(model, ladder=(1, 2), max_context=64,
                               page_size=8, start=False,
                               clock=lambda: clk[0])
    eng.warmup()
    free0 = eng.kv.free_pages()
    stream = eng.submit(onp.array([3, 1], onp.int32), max_new=24,
                        deadline_ms=200.0)
    # each retire lands 60 fake-clock ms after the last: TPOT EWMA ~=
    # 60 ms, so after a couple of tokens the remaining ~22 x 60 ms
    # projection blows the 200 ms budget mid-stream
    for _ in range(30):
        if stream.done:
            break
        clk[0] += 0.060
        eng.step_once()
        eng.sync()
    with pytest.raises(serving.DeadlineExceeded, match="mid-flight"):
        stream.result(5)
    rec = stream.record()
    assert 0 < rec["tokens"] < 24              # shed MID-stream
    assert eng.stats["shed_midstream"] == 1
    assert eng.stats["deadline_missed"] >= 1
    assert eng.kv.free_pages() == free0        # pages back in the pool
    assert all(r is None for r in eng._occupant)
    eng.close()


def test_decode_stream_without_deadline_never_shed_midstream():
    clk = [0.0]
    model = serving.TinyDecoder(vocab=32, d_model=16, num_heads=2,
                                seed=0)
    eng = serving.DecodeEngine(model, ladder=(1, 2), max_context=64,
                               page_size=8, start=False,
                               clock=lambda: clk[0])
    eng.warmup()
    stream = eng.submit(onp.array([3, 1], onp.int32), max_new=4)
    for _ in range(30):
        if stream.done:
            break
        clk[0] += 60.0                         # hopeless pace, no budget
        eng.step_once()
        eng.sync()
    assert len(stream.result(5)) == 4          # runs to completion
    assert eng.stats["shed_midstream"] == 0
    eng.close()


class _FakeFut:
    def __init__(self, replica, exc=None):
        self.replica = replica
        self._exc = exc

    def result(self, timeout=None):
        if self._exc is not None:
            raise self._exc


def test_loadgen_fleet_census_round_robin():
    subs = [lambda *a, **kw: _FakeFut("r0"),
            lambda *a, **kw: _FakeFut("r1")]
    rep = loadgen.run_closed_loop(
        loadgen.fleet_issue(subs, lambda i: (i,)),
        concurrency=2, requests=10)
    assert rep["outcomes"]["ok"] == 10
    census = rep["replicas"]
    assert census["r0"]["outcomes"]["ok"] == 5
    assert census["r1"]["outcomes"]["ok"] == 5
    assert census["r0"]["qps"] > 0
    assert "p99_ms" in census["r0"]


def test_loadgen_fleet_census_attributes_failures():
    def sub(i, *a, **kw):
        if i % 2:
            return _FakeFut("r1", serving.DeadlineExceeded("late"))
        return _FakeFut("r0")

    rep = loadgen.run_closed_loop(
        loadgen.fleet_issue([sub], lambda i: (i,)),
        concurrency=1, requests=8)
    census = rep["replicas"]
    assert census["r0"]["outcomes"]["ok"] == 4
    assert census["r1"]["outcomes"]["deadline_missed"] == 4
    assert rep["outcomes"] == {"ok": 4, "rejected": 0,
                               "deadline_missed": 4, "error": 0}


def test_fleet_metric_names_cataloged():
    from mxnet_tpu.telemetry import names
    for const, kind in (("FLEET_REPLICAS", "gauge"),
                        ("FLEET_ROUTED", "counter"),
                        ("FLEET_RESTARTS", "counter"),
                        ("FLEET_SWAPS", "counter"),
                        ("FLEET_SCALE_EVENTS", "counter"),
                        ("FLEET_QUEUE_WAIT", "histogram")):
        name = getattr(names, const)
        assert name.startswith("mx_fleet_")
        assert name in names.CATALOG
        assert names.CATALOG[name]["kind"] == kind


def test_replica_gauge_tracks_states():
    clk = [0.0]
    fleet = make_fleet(clk, 2)
    try:
        assert telemetry.value(telemetry.names.FLEET_REPLICAS,
                               "serving") == 2
        fleet.drain_then_retire(fleet.replicas[0])
        assert telemetry.value(telemetry.names.FLEET_REPLICAS,
                               "serving") == 1
        assert telemetry.value(telemetry.names.FLEET_REPLICAS,
                               "retired") == 1
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# chaos acceptance: replica-targeted revoke mid-burst, threaded fleet
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_fleet_kill_one_replica_mid_burst(monkeypatch, rows_match):
    """3 threaded replicas, a 28-request concurrent burst, one
    replica-targeted device revocation mid-traffic under
    MXNET_TRANSFER_GUARD=raise: zero lost accepted requests, zero
    hangs, exactly one mx_fleet_replica_restarts_total increment, the
    victim back in rotation on a spare device, bit-exact results, and
    zero unblessed host syncs in the serving hot loops."""
    N = 28
    X = rows(N, seed=13)
    singles = [build_pred().predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(N)]
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    monkeypatch.setenv("MXNET_SERVING_SHED", "off")
    restarts0 = telemetry.value(telemetry.names.FLEET_RESTARTS) or 0
    sync0 = telemetry.value(telemetry.names.HOST_SYNCS,
                            "wait_to_read") or 0
    results = [None] * N
    errors = [None] * N
    fleet = serving.FleetController(
        build_pred, example=(mx.nd.array(rows(1)),), replicas=3,
        max_batch=4, timeout_ms=2.0)
    try:
        victim = fleet.replicas[-1]
        # steer the burst's head deterministically at the victim (a
        # near-zero service EWMA makes its projected wait the floor),
        # so the targeted dispatch fault is guaranteed to fire; real
        # retire timings take the EWMAs over once traffic flows
        victim.sup.batcher._ewma_service = 1e-6
        faults.configure(f"serving.dispatch@{victim.name}:before=2"
                         f":revoke:d{victim.device.id}")

        def client(i):
            deadline = time.time() + 60
            while True:
                try:
                    results[i] = fleet.router.submit(
                        mx.nd.array(X[i:i + 1])).result(60)
                    return
                except (serving.Overloaded, serving.ServingShutdown):
                    # typed retryable signals: breaker fast-fail, fleet
                    # saturation, or "arrived during fleet failover"
                    if time.time() >= deadline:
                        raise
                    time.sleep(0.01)
                except MXNetError as e:
                    errors[i] = e
                    return

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True) for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
        hung = [i for i, t in enumerate(threads) if t.is_alive()]
        assert not hung, f"clients hung: {hung}"
        # the background restart may still be in flight: wait for it
        deadline = time.time() + 30
        while time.time() < deadline and not any(
                e.kind in ("restart", "restart_failed")
                for e in fleet.events):
            time.sleep(0.02)
        assert any(e.kind == "restart" for e in fleet.events), \
            "victim replica never restarted"
        assert fleet.stats["failovers"] == 1
        assert fleet.stats["restarts"] == 1
        assert (telemetry.value(telemetry.names.FLEET_RESTARTS) or 0) \
            - restarts0 == 1
        assert victim.state == _Replica.SERVING
        faults.restore_devices()
        late = fleet.router.submit(mx.nd.array(X[:1]))
        assert late.result(30) is not None
    finally:
        fleet.close()
    # zero unblessed syncs in the fleet's serving hot loops (results
    # are still async handles at this point — checked BEFORE asnumpy)
    assert (telemetry.value(telemetry.names.HOST_SYNCS, "wait_to_read")
            or 0) - sync0 == 0
    # zero lost accepted: every request has exactly one terminal state
    # and (clients retry typed rejections) every one SERVED
    for i in range(N):
        assert (results[i] is None) != (errors[i] is None), \
            f"request {i} has no terminal state"
        assert errors[i] is None, \
            f"request {i}: terminal failure {errors[i]!r}"
    for i in range(N):
        assert rows_match(results[i].asnumpy(), singles[i]), \
            f"request {i} differs from single dispatch"


@pytest.mark.chaos
def test_chaos_rolling_swap_under_traffic(tmp_path, monkeypatch, rows_match):
    """Rolling swap while threaded traffic flows, under
    MXNET_TRANSFER_GUARD=raise: zero dropped accepted requests and
    every result bit-exact against the OLD or the NEW weights (never a
    torn mix), with the fleet at the new version afterwards."""
    N = 24
    X = rows(N, seed=19)
    old_out = [build_pred().predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(N)]
    new_out = [build_pred(23).predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(N)]
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    monkeypatch.setenv("MXNET_SERVING_SHED", "off")
    _path, root = write_ckpt(tmp_path)
    results = [None] * N
    errors = [None] * N
    fleet = serving.FleetController(
        build_pred, example=(mx.nd.array(rows(1)),), replicas=3,
        max_batch=4, timeout_ms=2.0)
    try:
        def client(i):
            deadline = time.time() + 60
            while True:
                try:
                    results[i] = fleet.router.submit(
                        mx.nd.array(X[i:i + 1])).result(60)
                    return
                except (serving.Overloaded, serving.ServingShutdown):
                    if time.time() >= deadline:
                        raise
                    time.sleep(0.005)
                except MXNetError as e:
                    errors[i] = e
                    return

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True) for i in range(N)]
        for t in threads:
            t.start()
        time.sleep(0.05)                       # traffic in flight
        res = fleet.swap_weights(root)
        assert res["replicas"] == 3
        for t in threads:
            t.join(90)
        hung = [i for i, t in enumerate(threads) if t.is_alive()]
        assert not hung, f"clients hung: {hung}"
        for i in range(N):
            assert errors[i] is None and results[i] is not None, \
                f"request {i}: {errors[i]!r}"
            got = results[i].asnumpy()
            assert rows_match(got, old_out[i]) or \
                rows_match(got, new_out[i]), \
                f"request {i} matches neither weight version"
        assert fleet.version == 1
        assert all(r.version == 1 for r in fleet.replicas
                   if r.state == _Replica.SERVING)
        # post-swap: the whole fleet answers with the NEW weights
        fut = fleet.router.submit(mx.nd.array(X[:1]))
        assert (fut.result(30).asnumpy() == new_out[0]).all()
    finally:
        fleet.close()
