"""Unified runtime telemetry (ISSUE 6): step-timeline tracing, metrics
registry + exporters, anomaly watchdog.

Acceptance bar:

- a pipelined TrainLoop run with MXNET_TELEMETRY=1 and
  MXNET_TRANSFER_GUARD=raise completes with ZERO unblessed host syncs
  while producing a full registry export (window-occupancy, sync-count,
  compile-cache, checkpoint-latency series) — the guard IS the
  regression test for "always-on-cheap";
- the Chrome trace merges per-op events (phase-tagged dispatch/sync)
  and per-step phase spans (window/retire stamped from the
  DispatchWindow's retire timestamps) in one stream;
- an injected NaN loss and an artificial stall each raise exactly ONE
  structured anomaly event attributed to the correct step number;
- exporters: Prometheus text-format golden output, JSON snapshot schema
  stability, heartbeat interval/shutdown.
"""
import json
import os
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, nd, profiler, telemetry
from mxnet_tpu.analysis import guard as tguard
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, TrainLoop, nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.data.prefetcher import DevicePrefetcher
from mxnet_tpu.telemetry import names
from mxnet_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Zero the process-global telemetry state around every test (metric
    objects cached by instrumentation points survive; values reset)."""
    telemetry.stop_heartbeat()
    telemetry.reset()
    yield
    telemetry.enable(None)
    telemetry.stop_heartbeat()
    telemetry.reset()


def _build(seed=3):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(3, in_units=8))
    net.initialize()
    return net


def _batch(bs=8, seed=0):
    rng = onp.random.RandomState(seed)
    x = nd.array(rng.randn(bs, 4).astype("float32"))
    y = nd.array(rng.randint(0, 3, size=(bs,)).astype("int32"))
    return x, y


def _loop(net=None, inflight=2, **kwargs):
    net = net or _build()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    return TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=inflight, **kwargs)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_events_total", label_key="kind")
    c.inc(label="a")
    c.inc(2.5, label="a")
    c.inc(label="b")
    assert c.value("a") == 3.5 and c.value("b") == 1.0
    with pytest.raises(MXNetError, match="cannot decrease"):
        c.inc(-1, label="a")
    g = reg.gauge("t_level_now")
    assert g.value() is None
    g.set(2.0)
    g.add(0.5)
    assert g.value() == 2.5
    h = reg.histogram("t_wait_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5):
        h.observe(v)
    assert h.count() == 4 and abs(h.sum() - 0.605) < 1e-9
    # p50 falls in the (0.01, 0.1] bucket
    assert 0.01 <= h.percentile(50) <= 0.1
    # get-or-create returns the SAME object; kind drift raises
    assert reg.counter("t_events_total") is c
    with pytest.raises(MXNetError, match="already registered"):
        reg.gauge("t_events_total")


def test_labeled_cardinality_is_bounded():
    reg = MetricsRegistry()
    c = reg.counter("t_many_total", label_key="k")
    for i in range(names.MAX_LABEL_VALUES + 10):
        c.inc(label=f"v{i:03d}")
    vals = c.values()
    assert len(vals) == names.MAX_LABEL_VALUES + 1   # + overflow slot
    assert vals[names.OVERFLOW_LABEL] == 10.0


def test_unlabeled_metric_rejects_labels_and_vice_versa():
    reg = MetricsRegistry()
    c = reg.counter("t_plain_total")
    with pytest.raises(MXNetError, match="without a label"):
        c.inc(label="x")
    lc = reg.counter("t_tagged_total", label_key="kind")
    with pytest.raises(MXNetError, match="requires a"):
        lc.inc()


def test_reset_zeroes_in_place_and_keeps_objects():
    reg = MetricsRegistry()
    c = reg.counter("t_keep_total")
    c.inc(5)
    reg.reset()
    assert c.value() == 0.0
    assert reg.counter("t_keep_total") is c


# ---------------------------------------------------------------------------
# exporters: Prometheus golden, snapshot schema, heartbeat
# ---------------------------------------------------------------------------

def test_prometheus_text_golden():
    reg = MetricsRegistry()
    c = reg.counter("golden_events_total", help="events", label_key="kind")
    c.inc(2, label="a")
    c.inc(label="b")
    g = reg.gauge("golden_level_now", help="level")
    g.set(1.5)
    h = reg.histogram("golden_wait_seconds", help="wait",
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    expected = "\n".join([
        '# HELP golden_events_total events',
        '# TYPE golden_events_total counter',
        'golden_events_total{kind="a"} 2',
        'golden_events_total{kind="b"} 1',
        '# HELP golden_level_now level',
        '# TYPE golden_level_now gauge',
        'golden_level_now 1.5',
        '# HELP golden_wait_seconds wait',
        '# TYPE golden_wait_seconds histogram',
        'golden_wait_seconds_bucket{le="0.1"} 1',
        'golden_wait_seconds_bucket{le="1.0"} 2',
        'golden_wait_seconds_bucket{le="+Inf"} 3',
        'golden_wait_seconds_sum 5.55',
        'golden_wait_seconds_count 3',
    ]) + "\n"
    assert telemetry.prometheus_text(reg) == expected


def test_write_prometheus_env_default_and_atomicity(tmp_path,
                                                    monkeypatch):
    path = str(tmp_path / "metrics" / "mx.prom")
    monkeypatch.setenv("MXNET_PROMETHEUS_FILE", path)
    out = telemetry.write_prometheus()
    assert out == path and os.path.exists(path)
    assert not os.path.exists(path + ".tmp")   # atomic rename, no debris
    text = open(path).read()
    # the default registry always exports the full catalog
    for name in names.CATALOG:
        assert f"# TYPE {name} " in text
    monkeypatch.delenv("MXNET_PROMETHEUS_FILE")
    with pytest.raises(MXNetError, match="MXNET_PROMETHEUS_FILE"):
        telemetry.write_prometheus()


def test_snapshot_schema_stability():
    snap = telemetry.snapshot()
    assert set(snap) == {"schema_version", "time_unix", "counters",
                         "gauges", "histograms", "anomalies"}
    assert snap["schema_version"] == telemetry.SCHEMA_VERSION == 1
    assert set(snap["anomalies"]) == {"count", "recent"}
    # every catalog series is present even at zero — including the
    # acceptance-named ones
    for name in (names.WINDOW_OCCUPANCY, names.WINDOW_CAPACITY):
        assert name in snap["gauges"]
    for name in (names.HOST_SYNCS, names.COMPILE_CACHE_HITS,
                 names.COMPILE_CACHE_MISSES, names.TRAIN_STEPS):
        assert name in snap["counters"]
    for name in (names.CHECKPOINT_CAPTURE_SECONDS,
                 names.CHECKPOINT_SAVE_SECONDS,
                 names.STEP_PHASE_SECONDS, names.STEP_TIME_SECONDS):
        assert name in snap["histograms"]
    json.dumps(snap)   # must be JSON-serializable as-is


def test_heartbeat_interval_and_shutdown(caplog):
    import logging
    before = telemetry.value(names.HEARTBEATS)
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.telemetry"):
        hb = telemetry.start_heartbeat(interval=0.05, write_file=False)
        assert telemetry.start_heartbeat(interval=0.05) is hb  # singleton
        deadline = time.time() + 3.0
        while hb.beats < 2 and time.time() < deadline:
            time.sleep(0.02)
    assert hb.beats >= 2, "heartbeat did not fire on its interval"
    telemetry.stop_heartbeat()
    assert not hb.running
    beats = hb.beats
    time.sleep(0.12)
    assert hb.beats == beats, "heartbeat kept firing after stop"
    telemetry.stop_heartbeat()          # idempotent
    assert telemetry.value(names.HEARTBEATS) - before == beats
    lines = [r.message for r in caplog.records
             if r.message.startswith("mx-telemetry ")]
    assert lines, "heartbeat emitted no structured log line"
    payload = json.loads(lines[0].split(" ", 1)[1])
    assert names.TRAIN_STEPS in payload and "anomalies" in payload


def test_heartbeat_requires_positive_interval(monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY_HEARTBEAT_SEC", raising=False)
    with pytest.raises(MXNetError, match="positive interval"):
        telemetry.Heartbeat()


def test_atexit_flush_writes_final_snapshot(tmp_path, monkeypatch):
    """A run that exits BEFORE the first heartbeat interval still leaves
    a final Prometheus snapshot + one structured log line: the atexit
    hook beats once and stops the thread (exporters._atexit_flush —
    installed via atexit.register; exercised directly here since a real
    interpreter exit can't run inside the test)."""
    import atexit
    from mxnet_tpu.telemetry import exporters
    path = str(tmp_path / "final.prom")
    monkeypatch.setenv("MXNET_PROMETHEUS_FILE", path)
    # the hook is registered with the interpreter
    assert exporters._atexit_installed
    hb = telemetry.start_heartbeat(interval=3600.0)   # never fires alone
    assert hb.beats == 0 and not os.path.exists(path)
    exporters._atexit_flush()
    assert os.path.exists(path), "no final Prometheus snapshot written"
    assert hb.beats == 1
    assert not hb.running, "atexit flush must also stop the thread"
    text = open(path).read()
    assert f"# TYPE {names.HEARTBEATS} counter" in text
    # idempotent: a second flush (stopped heartbeat) refreshes the file
    os.remove(path)
    exporters._atexit_flush()
    assert os.path.exists(path)
    assert hb.beats == 1, "stopped heartbeat must not beat again"
    atexit.unregister(exporters._atexit_flush)   # keep the test process
    exporters._atexit_installed = False          # clean for re-install
    exporters._install_atexit()
    assert exporters._atexit_installed


def test_atexit_flush_without_heartbeat_refreshes_file(tmp_path,
                                                       monkeypatch):
    from mxnet_tpu.telemetry import exporters
    path = str(tmp_path / "nohb.prom")
    monkeypatch.setenv("MXNET_PROMETHEUS_FILE", path)
    telemetry.stop_heartbeat()
    exporters._atexit_flush()
    assert os.path.exists(path)
    monkeypatch.delenv("MXNET_PROMETHEUS_FILE")
    exporters._atexit_flush()    # unconfigured: clean no-op
    monkeypatch.setenv("MXNET_TELEMETRY_HEARTBEAT_SEC", "0.25")
    hb = telemetry.Heartbeat()
    assert hb.interval == 0.25 and not hb.running


# ---------------------------------------------------------------------------
# enabling / gating
# ---------------------------------------------------------------------------

def test_enabled_env_parsing(monkeypatch):
    for v, want in (("", False), ("0", False), ("off", False),
                    ("no", False), ("1", True), ("true", True),
                    ("on", True)):
        monkeypatch.setenv("MXNET_TELEMETRY", v)
        assert telemetry.enabled() is want, (v, want)
    monkeypatch.delenv("MXNET_TELEMETRY")
    assert telemetry.enabled() is False
    telemetry.enable(True)
    assert telemetry.enabled() is True
    telemetry.enable(None)
    assert telemetry.enabled() is False


def test_counters_always_on_spans_gated(monkeypatch):
    """Registry counters tick with telemetry OFF; timeline spans do
    not (they need MXNET_TELEMETRY or a running profiler)."""
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    w = engine.DispatchWindow(max_inflight=0, sync_fn=lambda p: None)
    w.push("p", tag=1)
    assert telemetry.value(names.WINDOW_RETIRES) == 1
    assert telemetry.timeline().events() == []
    telemetry.enable(True)
    w.push("p", tag=2)
    phases = {e["phase"] for e in telemetry.timeline().events()}
    assert phases == {"window", "retire"}


# ---------------------------------------------------------------------------
# watchdog: stall + NaN semantics (unit level, exact attribution)
# ---------------------------------------------------------------------------

def test_stall_anomaly_fires_exactly_once_with_step():
    wd = telemetry.watchdog()
    for i in range(8):
        wd.observe_retire(i, dt=0.01)
    assert wd.anomalies() == []
    wd.observe_retire(42, dt=0.2)        # 20x the EWMA
    events = wd.anomalies("stall")
    assert len(events) == 1
    assert events[0]["step"] == 42
    assert telemetry.value(names.ANOMALIES, "stall") == 1
    # recovery re-arms; a second distinct stall fires again
    for i in range(3):
        wd.observe_retire(50 + i, dt=0.01)
    wd.observe_retire(60, dt=0.3)
    assert len(wd.anomalies("stall")) == 2
    # the stalled samples were NOT folded into the EWMA
    assert telemetry.value(names.STEP_TIME_EWMA) < 0.02


def test_stall_factor_env(monkeypatch):
    monkeypatch.setenv("MXNET_WATCHDOG_STALL_FACTOR", "30")
    wd = telemetry.watchdog()
    for i in range(8):
        wd.observe_retire(i, dt=0.01)
    wd.observe_retire(9, dt=0.2)         # 20x < 30x: not a stall
    assert wd.anomalies("stall") == []
    monkeypatch.setenv("MXNET_WATCHDOG_STALL_FACTOR", "bogus")
    assert telemetry.stall_factor() == 4.0


def test_nan_anomaly_fires_once_per_episode():
    wd = telemetry.watchdog()
    finite = onp.ones(4, "float32")
    poisoned = onp.array([1.0, onp.nan], "float32")
    wd.observe_retire(1, payload=finite)
    wd.observe_retire(2, payload=poisoned)
    wd.observe_retire(3, payload=poisoned)   # same episode: no re-fire
    events = wd.anomalies("nan_loss")
    assert [e["step"] for e in events] == [2]
    wd.observe_retire(4, payload=finite)     # recovery
    wd.observe_retire(5, payload=poisoned)   # new episode
    assert [e["step"] for e in wd.anomalies("nan_loss")] == [2, 5]
    # int payloads are never fetched/flagged
    wd.observe_retire(6, payload=onp.array([1, 2], "int32"))
    assert len(wd.anomalies()) == 2


# ---------------------------------------------------------------------------
# timeline
# ---------------------------------------------------------------------------

def test_timeline_rejects_unknown_phase():
    with pytest.raises(MXNetError, match="span vocabulary"):
        telemetry.timeline().record("warpdrive", 0.0, 1.0)


def test_timeline_summary_percentiles():
    tl = telemetry.timeline()
    for i in range(100):
        tl.record("dispatch", 0.0, 0.001 * (i + 1), step=i)
    s = tl.summary()["dispatch"]
    assert s["count"] == 100
    assert abs(s["p50_ms"] - 50.5) < 1.0
    assert s["p99_ms"] > 95.0
    # last_steps filters by distinct step number
    s10 = tl.summary(last_steps=10)["dispatch"]
    assert s10["count"] == 10 and s10["p50_ms"] > 90.0


# ---------------------------------------------------------------------------
# the acceptance run: pipelined + guarded + checkpointed + exported
# ---------------------------------------------------------------------------

def test_pipelined_telemetry_zero_unblessed_syncs(tmp_path, monkeypatch):
    """MXNET_TELEMETRY=1 + MXNET_TRANSFER_GUARD=raise + a 12-step
    prefetched pipelined run with periodic checkpoints: zero unblessed
    host syncs, and the export carries the window-occupancy, sync-count,
    compile-cache, and checkpoint-latency series."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    # this test is about sync discipline + exported series, not stall
    # detection (test_artificial_stall_one_anomaly_in_window pins that)
    # — an OS/GC hiccup during the ~2ms steps must not bill a stall
    # anomaly against the zero-anomalies assertion on a loaded CI box
    monkeypatch.setenv("MXNET_WATCHDOG_STALL_FACTOR", "50")
    loop = _loop(checkpoint_dir=str(tmp_path / "ckpt"),
                 checkpoint_every=6)
    x, y = _batch()
    loop.step(x, y)                  # compile outside the counted region
    loop.synchronize()
    telemetry.reset()
    tguard.reset_sync_counts()
    for bx, by in loop.prefetch((x, y) for _ in range(12)):
        loop.step(bx, by)            # raises on any unblessed sync
    loop.synchronize()
    loop.wait()                      # drain the background ckpt write
    assert loop.compiled_step.mode == "fused"
    counts = tguard.sync_counts()
    assert counts.get("wait_to_read", 0) == 0
    assert counts.get("window_retire", 0) == 12

    snap = telemetry.snapshot()
    assert snap["counters"][names.TRAIN_STEPS] == 12
    assert snap["counters"][names.WINDOW_RETIRES] == 12
    assert snap["counters"][names.HOST_SYNCS] == {"window_retire": 12.0}
    assert snap["counters"][names.PREFETCH_BATCHES] == 12
    assert snap["gauges"][names.WINDOW_OCCUPANCY] == 0   # drained
    assert snap["gauges"][names.WINDOW_CAPACITY] == 2
    assert names.COMPILE_CACHE_HITS in snap["counters"]
    assert snap["gauges"][names.COMPILE_CACHE_ENABLED] == 1.0  # by import
    # checkpoint-latency series observed real saves (steps 6 and 12)
    assert snap["counters"][names.CHECKPOINT_SAVES] == 2
    assert snap["histograms"][names.CHECKPOINT_CAPTURE_SECONDS][
        "count"] == 2
    assert snap["histograms"][names.CHECKPOINT_SAVE_SECONDS]["count"] == 2
    assert snap["histograms"][names.CHECKPOINT_SAVE_SECONDS]["sum"] > 0
    # every hot-loop phase has 12 observations
    phases = snap["histograms"][names.STEP_PHASE_SECONDS]
    for phase in ("dispatch", "window", "retire"):
        assert phases[phase]["count"] == 12, phase
    assert phases["checkpoint"]["count"] == 2
    assert snap["anomalies"]["count"] == 0
    # the same run exports cleanly as Prometheus text
    text = telemetry.prometheus_text()
    assert 'mx_guard_host_syncs_total{kind="window_retire"} 12' in text
    assert "mx_engine_window_occupancy 0" in text


def test_injected_nan_loss_one_anomaly_at_correct_step(monkeypatch):
    """A NaN batch at one known global step raises exactly ONE nan_loss
    anomaly attributed to that step, even though every later loss is
    poisoned too (episode semantics) and retires lag by the window."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    loop = _loop()
    x, y = _batch()
    xnan = nd.array(onp.full((8, 4), onp.nan, "float32"))
    loop.step(x, y)
    loop.synchronize()
    telemetry.reset()
    inject_at = loop.global_step + 7
    for i in range(12):
        loop.step(xnan if loop.global_step + 1 == inject_at else x, y)
    loop.synchronize()
    events = telemetry.watchdog().anomalies()
    assert len(events) == 1
    assert events[0]["kind"] == "nan_loss"
    assert events[0]["step"] == inject_at
    assert telemetry.value(names.ANOMALIES, "nan_loss") == 1
    snap = telemetry.snapshot()
    assert snap["anomalies"]["count"] == 1
    assert snap["anomalies"]["recent"][0]["step"] == inject_at


def test_artificial_stall_one_anomaly_in_window(monkeypatch):
    """An artificially slow retire in a live DispatchWindow raises
    exactly one stall anomaly named with the slow step's tag."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_WATCHDOG_STALL_FACTOR", "8")
    slow_tag = 30

    def sync(payload):
        time.sleep(0.25 if payload == "slow" else 0.002)

    w = engine.DispatchWindow(max_inflight=0, sync_fn=sync)
    for i in range(10):
        w.push("fast", tag=i)
    assert telemetry.watchdog().anomalies() == []
    w.push("slow", tag=slow_tag)
    w.push("fast", tag=slow_tag + 1)
    w.push("fast", tag=slow_tag + 2)
    events = telemetry.watchdog().anomalies("stall")
    assert len(events) == 1
    assert events[0]["step"] == slow_tag
    assert "ms" in events[0]["message"]


# ---------------------------------------------------------------------------
# merged Chrome trace (profiler satellite)
# ---------------------------------------------------------------------------

def test_chrome_trace_merges_op_events_and_step_spans(tmp_path,
                                                      monkeypatch):
    """One dump holds BOTH per-op events (phase-tagged: dispatch-time
    durations are labeled as such, not passed off as run time) and the
    step-phase spans stamped from the DispatchWindow retire
    timestamps."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    loop = _loop()
    x, y = _batch()
    loop.step(x, y)
    loop.synchronize()
    trace = str(tmp_path / "trace.json")
    profiler.set_config(filename=trace)
    profiler.set_state("run")
    try:
        _ = nd.abs(x * -1)               # imperative op -> operator event
        for _ in range(4):
            loop.step(x, y)
        loop.synchronize()
    finally:
        profiler.set_state("stop")
    profiler.dump()
    events = json.load(open(trace))["traceEvents"]
    ops = [e for e in events if e.get("cat") == "operator"]
    steps = [e for e in events if e.get("cat") == "step"]
    assert ops, "no per-op events in the merged trace"
    assert all(e["args"]["phase"] == "dispatch" for e in ops), \
        "async op durations must be labeled as dispatch time"
    got_phases = {e["args"]["phase"] for e in steps}
    assert {"dispatch", "window", "retire"} <= got_phases
    retires = [e for e in steps if e["args"]["phase"] == "retire"]
    assert len(retires) == 4
    assert all(isinstance(e["args"]["step"], int) for e in retires)
    # retire spans end at the retire timestamp: after their window span
    # start (same step), proving the trace is stamped from the window
    for r in retires:
        win = [e for e in steps if e["args"]["phase"] == "window"
               and e["args"]["step"] == r["args"]["step"]]
        assert win and r["ts"] >= win[0]["ts"]


def test_profiler_alone_gets_step_spans(monkeypatch, tmp_path):
    """A running profiler is enough for step spans (no MXNET_TELEMETRY):
    a profile of a pipelined run shows step boundaries by default."""
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    loop = _loop()
    x, y = _batch()
    loop.step(x, y)
    loop.synchronize()
    trace = str(tmp_path / "trace.json")
    profiler.set_config(filename=trace)
    profiler.set_state("run")
    try:
        for _ in range(3):
            loop.step(x, y)
        loop.synchronize()
    finally:
        profiler.set_state("stop")
    profiler.dump()
    events = json.load(open(trace))["traceEvents"]
    assert any(e.get("cat") == "step" for e in events)
    # but the watchdog stayed off: profiling must not add loss fetches
    assert telemetry.watchdog().anomalies() == []


def test_naive_engine_ops_are_sync_phase(monkeypatch):
    monkeypatch.setattr(engine.Engine._instance, "kind", "NaiveEngine",
                        raising=False)
    try:
        assert profiler.Profiler._op_phase() == "sync"
    finally:
        monkeypatch.undo()
    assert profiler.Profiler._op_phase() == "dispatch"


# ---------------------------------------------------------------------------
# prefetcher + engine registry series
# ---------------------------------------------------------------------------

def test_prefetcher_feeds_registry():
    x, y = _batch()
    pf = DevicePrefetcher([(x, y)] * 5, depth=2)
    out = list(pf)
    assert len(out) == 5
    assert telemetry.value(names.PREFETCH_BATCHES) == 5
    wait = telemetry.registry().get(names.PREFETCH_INPUT_WAIT).value()
    assert wait >= 0 and wait == pytest.approx(
        pf.stats["input_wait_ms"] / 1e3, rel=0.05)


def test_window_occupancy_gauge_tracks_pending():
    w = engine.DispatchWindow(max_inflight=3, sync_fn=lambda p: None)
    for i in range(3):
        w.push(i, tag=i)
        assert telemetry.value(names.WINDOW_OCCUPANCY) == i + 1
    w.drain()
    assert telemetry.value(names.WINDOW_OCCUPANCY) == 0
    assert telemetry.value(names.WINDOW_PUSHES) == 3
    assert telemetry.value(names.WINDOW_RETIRES) == 3


def test_window_error_counter():
    def sync(p):
        if p == "bad":
            raise RuntimeError("boom")

    w = engine.DispatchWindow(max_inflight=0, sync_fn=sync)
    w.push("ok", tag=1)
    with pytest.raises(MXNetError):
        w.push("bad", tag=2)
    assert telemetry.value(names.WINDOW_ERRORS) == 1
