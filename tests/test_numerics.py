"""Training-numerics observability (ISSUE 8): in-program grad/param
health, divergence watchdog, NaN-origin forensics.

Acceptance bar:

- numerics=on is BIT-exact on params/loss vs numerics=off for
  sgd-mom/adam x fused/zero;
- under the dp=4 ZeRO sharded update the reported norms are the TRUE
  global norms (parity vs a host recomputation of the full-batch
  gradient);
- an injected non-finite gradient produces exactly ONE nonfinite_grad
  anomaly (episode semantics across the dispatch window) plus one
  atomic golden-schema post-mortem dump naming the planted op;
- a 12-step pipelined run with MXNET_NUMERICS=per_layer and
  MXNET_TRANSFER_GUARD=raise completes with zero unblessed host syncs
  while the mx_numerics_* series fill;
- the eager NaN guard (inspector) feeds the same anomaly channel, is
  idempotent, and restores cleanly; TensorInspector dumps are atomic.
"""
import json
import math
import os

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import _tape, autograd, engine, inspector, nd, telemetry
from mxnet_tpu.analysis import guard as tguard
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, TrainLoop, nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.ops import registry as opreg
from mxnet_tpu.parallel import make_mesh, shard_batch
from mxnet_tpu.telemetry import names, numerics
from mxnet_tpu.testing import faults

DP = 4


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.enable(None)
    telemetry.reset()


def _mesh():
    return make_mesh({"dp": DP}, jax.devices()[:DP])


def _build(seed=3):
    """Includes a non-divisible flat size (Dense(5): weight 40, bias 5)
    so the ZeRO padded shard layout is exercised."""
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(5, in_units=8, activation="relu"))
    net.add(nn.Dense(3, in_units=5))
    net.initialize()
    return net


def _batch(bs=8, seed=0):
    rng = onp.random.RandomState(seed)
    x = nd.array(rng.randn(bs, 4).astype("float32"))
    y = nd.array(rng.randint(0, 3, size=(bs,)).astype("int32"))
    return x, y


def _compiled(net, opt, kwargs, numerics_mode=None):
    trainer = Trainer(net.collect_params(), opt, dict(kwargs))
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    return trainer.compile_step(lambda a, b: loss_blk(net(a), b),
                                numerics=numerics_mode)


def _assert_params_bitexact(net_a, net_b):
    for (k, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                sorted(net_b.collect_params().items())):
        onp.testing.assert_array_equal(pa.data().asnumpy(),
                                       pb.data().asnumpy(), err_msg=k)


# ---------------------------------------------------------------------------
# mode parsing / plumbing
# ---------------------------------------------------------------------------

def test_mode_parsing(monkeypatch):
    assert numerics.mode("off") is None
    assert numerics.mode("global") == "global"
    assert numerics.mode("per_layer") == "per_layer"
    assert numerics.mode("per-layer") == "per_layer"
    for v, want in (("", None), ("0", None), ("off", None),
                    ("1", "global"), ("global", "global"),
                    ("per_layer", "per_layer")):
        monkeypatch.setenv("MXNET_NUMERICS", v)
        assert numerics.mode() == want, (v, want)
    monkeypatch.delenv("MXNET_NUMERICS")
    assert numerics.mode() is None


def test_spike_factor_and_drift_tol_env(monkeypatch):
    monkeypatch.setenv("MXNET_GRADNORM_SPIKE_FACTOR", "25")
    assert numerics.spike_factor() == 25.0
    monkeypatch.setenv("MXNET_GRADNORM_SPIKE_FACTOR", "bogus")
    assert numerics.spike_factor() == 10.0
    monkeypatch.setenv("MXNET_MASTER_DRIFT_TOL", "0.5")
    assert numerics.master_drift_tol() == 0.5
    monkeypatch.delenv("MXNET_MASTER_DRIFT_TOL")
    assert numerics.master_drift_tol() == 1e-2


def test_numerics_off_no_aux():
    net = _build()
    step = _compiled(net, "sgd", {"learning_rate": 0.1})
    x, y = _batch()
    step(x, y)
    assert step.numerics is None
    assert step.take_numerics() is None
    assert step.numerics_values() is None


def test_set_numerics_rebuckets():
    """Switching the mode on a live step compiles a fresh instrumented
    bucket (the mode is part of the cache signature) and aux appears."""
    net = _build()
    step = _compiled(net, "sgd", {"learning_rate": 0.1})
    x, y = _batch()
    step(x, y)
    assert step.n_traces == 1 and step.take_numerics() is None
    step.set_numerics("global")
    step(x, y)
    assert step.n_traces == 2
    vals = step.numerics_values()
    assert vals is not None and vals["grad_norm"] > 0
    step.set_numerics(None)
    step(x, y)
    assert step.n_traces == 2          # original bucket still cached
    assert step.take_numerics() is None


# ---------------------------------------------------------------------------
# bit-exact on-vs-off parity (the acceptance contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt,kwargs", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
])
def test_on_off_bitexact_fused(opt, kwargs):
    x, y = _batch()
    net_a = _build()
    step_a = _compiled(net_a, opt, kwargs)
    losses_a = [step_a(x, y).asnumpy().copy() for _ in range(4)]
    assert step_a.mode == "fused"

    net_b = _build()
    step_b = _compiled(net_b, opt, kwargs, numerics_mode="per_layer")
    losses_b = []
    for _ in range(4):
        losses_b.append(step_b(x, y).asnumpy().copy())
        assert step_b.take_numerics() is not None
    assert step_b.mode == "fused" and step_b.numerics == "per_layer"
    for la, lb in zip(losses_a, losses_b):
        onp.testing.assert_array_equal(la, lb)
    _assert_params_bitexact(net_a, net_b)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual mesh")
@pytest.mark.parametrize("opt,kwargs", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
])
def test_on_off_bitexact_zero(monkeypatch, opt, kwargs):
    monkeypatch.setenv("MXNET_ZERO_SHARD_MIN_SIZE", "1")
    x, y = _batch()
    with _mesh() as mesh:
        xs, ys = shard_batch(x, mesh), shard_batch(y, mesh)
        net_a = _build()
        step_a = _compiled(net_a, opt, kwargs)
        losses_a = [step_a(xs, ys).asnumpy().copy() for _ in range(4)]
        assert step_a.zero_sharded

        net_b = _build()
        step_b = _compiled(net_b, opt, kwargs, numerics_mode="global")
        losses_b = [step_b(xs, ys).asnumpy().copy() for _ in range(4)]
        assert step_b.zero_sharded and step_b.take_numerics() is not None
    for la, lb in zip(losses_a, losses_b):
        onp.testing.assert_array_equal(la, lb)
    _assert_params_bitexact(net_a, net_b)


# ---------------------------------------------------------------------------
# true-global-norm parity vs host recomputation at dp=4 ZeRO
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual mesh")
def test_zero_global_norm_parity_vs_host(monkeypatch):
    """The psum-composed in-program statistics of a dp=4 ZeRO step
    equal a host recomputation of the FULL-batch gradient norms — every
    replica reports the true global number, not its shard's."""
    monkeypatch.setenv("MXNET_ZERO_SHARD_MIN_SIZE", "1")
    x, y = _batch()
    rescale = 1.0 / x.shape[0]

    net_h = _build()
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        l = loss_blk(net_h(x), y)
    l.backward()
    host_layers, host_gsq, host_psq = {}, 0.0, 0.0
    for k, p in sorted(net_h.collect_params().items()):
        if p.grad_req == "null":
            continue
        g = p.grad().asnumpy().astype("float64") * rescale
        host_layers[k] = math.sqrt((g ** 2).sum())
        host_gsq += (g ** 2).sum()
        host_psq += (p.data().asnumpy().astype("float64") ** 2).sum()

    net_z = _build()
    step = _compiled(net_z, "adam", {"learning_rate": 1e-2},
                     numerics_mode="per_layer")
    with _mesh() as mesh:
        step(shard_batch(x, mesh), shard_batch(y, mesh))
        vals = step.numerics_values()
    assert step.zero_sharded
    assert vals["nonfinite_total"] == 0
    onp.testing.assert_allclose(vals["grad_norm"], math.sqrt(host_gsq),
                                rtol=1e-4)
    onp.testing.assert_allclose(vals["param_norm"], math.sqrt(host_psq),
                                rtol=1e-4)
    assert set(vals["layer_grad_norm"]) == set(host_layers)
    for k, v in vals["layer_grad_norm"].items():
        onp.testing.assert_allclose(v, host_layers[k], rtol=1e-3,
                                    err_msg=k)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual mesh")
def test_zero_multi_precision_master_drift(monkeypatch):
    """bf16 params + multi_precision on the mesh: the aux reports the
    fp32-master-vs-weight drift, tiny on a healthy step (bf16 rounding
    only) — no master_drift anomaly fires."""
    monkeypatch.setenv("MXNET_ZERO_SHARD_MIN_SIZE", "1")
    net = _build()
    net.cast("bfloat16")
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 1e-2, "multi_precision": True})
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b),
                                numerics="global")
    x, y = _batch()
    with _mesh() as mesh:
        step(shard_batch(x.astype("bfloat16"), mesh),
             shard_batch(y, mesh))
        vals = step.numerics_values()
    assert step.zero_sharded
    assert "master_drift" in vals
    assert 0 <= vals["master_drift"] < numerics.master_drift_tol()
    assert "bfloat16" in vals["nonfinite"]
    assert telemetry.watchdog().anomalies("master_drift") == []


# ---------------------------------------------------------------------------
# divergence watchdog: episode semantics
# ---------------------------------------------------------------------------

def _feed(mon, step_no, gsq=1.0, psq=100.0, usq=1e-4, nonfinite=0,
          **extra):
    raw = {"grad_sq": onp.float32(gsq), "param_sq": onp.float32(psq),
           "upd_sq": onp.float32(usq),
           "nonfinite": {"float32": onp.int32(nonfinite)}}
    raw.update(extra)
    rec = telemetry.StepNumerics("global", raw, ["p0"], {})
    return mon.observe_retire(step_no, rec)


def test_grad_spike_episode_fires_once():
    mon = numerics.monitor()
    for i in range(8):
        _feed(mon, i, gsq=1.0)
    assert telemetry.watchdog().anomalies() == []
    _feed(mon, 42, gsq=1e6)             # norm 1000 >> 10x EWMA of 1
    events = telemetry.watchdog().anomalies("grad_spike")
    assert [e["step"] for e in events] == [42]
    _feed(mon, 43, gsq=1e6)             # same episode: no re-fire
    assert len(telemetry.watchdog().anomalies("grad_spike")) == 1
    # the spiking samples were NOT folded into the EWMA
    assert telemetry.value(names.NUMERICS_GRAD_NORM_EWMA) < 2.0
    for i in range(3):                  # recovery re-arms
        _feed(mon, 50 + i, gsq=1.0)
    _feed(mon, 60, gsq=1e6)
    assert len(telemetry.watchdog().anomalies("grad_spike")) == 2


def test_update_ratio_out_of_band_episode():
    mon = numerics.monitor()
    for i in range(8):
        _feed(mon, i, usq=1e-4)         # ratio 1e-3
    _feed(mon, 9, usq=400.0)            # ratio 2.0 >> 10x EWMA
    events = telemetry.watchdog().anomalies("update_ratio")
    assert [e["step"] for e in events] == [9]
    _feed(mon, 10, usq=400.0)
    assert len(telemetry.watchdog().anomalies("update_ratio")) == 1


def test_nonfinite_counter_and_master_drift_episode(monkeypatch):
    mon = numerics.monitor()
    _feed(mon, 1, master_drift=onp.float32(1e-4))
    assert telemetry.watchdog().anomalies("master_drift") == []
    _feed(mon, 2, master_drift=onp.float32(0.5))
    _feed(mon, 3, master_drift=onp.float32(0.5))
    events = telemetry.watchdog().anomalies("master_drift")
    assert [e["step"] for e in events] == [2]
    _feed(mon, 4, nonfinite=7)
    assert telemetry.value(names.NUMERICS_NONFINITE, "float32") == 7
    assert len(telemetry.watchdog().anomalies("nonfinite_grad")) == 1


# ---------------------------------------------------------------------------
# injected non-finite gradient: one anomaly + one golden-schema dump
# ---------------------------------------------------------------------------

def test_injected_inf_grad_one_anomaly_and_dump(tmp_path, monkeypatch):
    """An overflow batch at one known step, retired through a live
    dispatch window: exactly ONE nonfinite_grad anomaly attributed to
    that step (later poisoned steps stay in the episode), one atomic
    schema-v1 post-mortem dump whose NaN-origin forensics names the
    planted op (exp), with the per-layer table and lr/step context."""
    dump_dir = tmp_path / "dumps"
    monkeypatch.setenv("MXNET_NUMERICS_DUMP_DIR", str(dump_dir))
    net = _build()
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    # the planted op: exp overflows to inf on the injected batch
    step = trainer.compile_step(
        lambda a, b: loss_blk(net(nd.exp(a)), b), numerics="global")
    x, y = _batch()
    xinf = nd.array(onp.full((8, 4), 120.0, "float32"))
    w = engine.DispatchWindow(max_inflight=2)
    for i in range(1, 9):
        l = step(xinf if i == 5 else x, y)
        w.push(l._data, tag=i, aux=step.take_aux())
    w.drain()

    events = telemetry.watchdog().anomalies("nonfinite_grad")
    assert len(events) == 1
    assert events[0]["step"] == 5
    assert "exp" in events[0]["message"]
    assert telemetry.value(names.ANOMALIES, "nonfinite_grad") == 1
    assert telemetry.value(names.NUMERICS_DUMPS) == 1

    dumps = sorted(dump_dir.glob("mx_numerics_*.json"))
    assert len(dumps) == 1
    assert not list(dump_dir.glob("*.tmp*")), "non-atomic dump debris"
    d = json.load(open(dumps[0]))
    # golden schema (v1)
    assert d["schema_version"] == numerics.DUMP_SCHEMA_VERSION == 1
    for key in ("time_unix", "kind", "step", "offending_op", "grad_norm",
                "param_norm", "update_ratio", "nonfinite", "layers",
                "context", "hints"):
        assert key in d, key
    assert d["kind"] == "nonfinite_grad" and d["step"] == 5
    assert "exp" in d["offending_op"]
    assert d["nonfinite"]["float32"] > 0
    # ranked per-layer table from the forensic re-execution
    assert d["layers"] and d["layers"][0]["nonfinite"] > 0
    assert {"param", "shape", "dtype", "grad_norm", "param_norm",
            "nonfinite"} <= set(d["layers"][0])
    # lr / step context
    assert d["context"]["learning_rate"] == pytest.approx(0.1)
    assert d["context"]["optimizer"] == "SGD"
    assert d["context"]["batch_size"] == 8
    assert d["hints"]


def test_nonfinite_without_dump_dir_still_one_anomaly(monkeypatch):
    monkeypatch.delenv("MXNET_NUMERICS_DUMP_DIR", raising=False)
    net = _build()
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1})
    step = trainer.compile_step(
        lambda a, b: loss_blk(net(nd.exp(a)), b), numerics="global")
    x, y = _batch()
    step(nd.array(onp.full((8, 4), 120.0, "float32")), y)
    step.numerics_values()
    events = telemetry.watchdog().anomalies("nonfinite_grad")
    assert len(events) == 1
    assert "MXNET_NUMERICS_DUMP_DIR" in events[0]["message"]
    assert telemetry.value(names.NUMERICS_DUMPS) == 0


# ---------------------------------------------------------------------------
# the acceptance run: pipelined + guarded + per_layer, zero unblessed syncs
# ---------------------------------------------------------------------------

def test_guarded_12step_per_layer_zero_unblessed_syncs(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    monkeypatch.setenv("MXNET_NUMERICS", "per_layer")
    net = _build()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=2)
    assert loop.compiled_step.numerics == "per_layer"
    x, y = _batch()
    loop.step(x, y)                  # compile outside the counted region
    loop.synchronize()
    telemetry.reset()
    tguard.reset_sync_counts()
    for bx, by in loop.prefetch((x, y) for _ in range(12)):
        loop.step(bx, by)            # raises on any unblessed sync
    loop.synchronize()
    counts = tguard.sync_counts()
    assert counts.get("wait_to_read", 0) == 0
    assert counts.get("window_retire", 0) == 12
    # the numerics series filled from the blessed retires alone
    assert telemetry.value(names.NUMERICS_GRAD_NORM) > 0
    assert telemetry.value(names.NUMERICS_PARAM_NORM) > 0
    assert telemetry.value(names.NUMERICS_UPDATE_RATIO) == 12
    layer_vals = telemetry.registry().get(
        names.NUMERICS_LAYER_GRAD_NORM).values()
    assert layer_vals and all(v >= 0 for v in layer_vals.values())
    assert telemetry.watchdog().anomalies() == []
    last = numerics.monitor().last()
    assert last is not None and last["step"] == loop.global_step
    # the new series export cleanly
    text = telemetry.prometheus_text()
    assert "mx_numerics_grad_norm " in text
    assert "mx_numerics_update_ratio_count 12" in text


# ---------------------------------------------------------------------------
# inspector satellites: eager NaN guard + atomic dumps
# ---------------------------------------------------------------------------

def test_nan_guard_idempotent_install_remove():
    base = len(opreg._INVOKE_WRAPPERS)
    inspector.install_nan_guard()
    inspector.install_nan_guard()        # must not double-wrap
    assert len(opreg._INVOKE_WRAPPERS) == base + 1
    inspector.remove_nan_guard()
    inspector.remove_nan_guard()         # idempotent
    assert len(opreg._INVOKE_WRAPPERS) == base


def test_nan_guard_restores_previous_output_check():
    hits = []
    sentinel = lambda name, outs: hits.append(name)   # noqa: E731
    prev = _tape.set_output_check(sentinel)
    try:
        inspector.install_nan_guard()
        inspector.remove_nan_guard()
        assert _tape._output_check is sentinel, \
            "remove_nan_guard clobbered another subsystem's hook"
    finally:
        inspector.remove_nan_guard()
        _tape.set_output_check(prev)


def test_nan_guard_telemetry_episode_and_exception_safety():
    inspector.install_nan_guard()
    try:
        a = nd.array([1.0, 2.0])
        bad = nd.array([-1.0])
        nd.abs(a)
        for _ in range(2):               # consecutive violations: one event
            with pytest.raises(MXNetError, match="non-finite"):
                nd.log(bad)
        assert len(telemetry.watchdog().anomalies("nonfinite_eager")) == 1
        assert telemetry.value(names.ANOMALIES, "nonfinite_eager") == 1
        nd.abs(a)                        # clean checked op re-arms
        with pytest.raises(MXNetError, match="non-finite"):
            nd.sqrt(nd.array([-4.0]))
        assert len(telemetry.watchdog().anomalies("nonfinite_eager")) == 2
    finally:
        # the exceptions above must not have corrupted install state
        inspector.remove_nan_guard()
    assert not inspector._guard_installed
    nd.log(nd.array([-1.0]))             # guard really gone: no raise


def test_inspector_dump_atomic_under_fault(tmp_path):
    """A fault injected at the dump's commit point (the same
    tmp+fsync+os.replace helper nd.save uses) leaves NO partial file
    and no temp debris; a retry reuses the sequence number."""
    insp = inspector.TensorInspector(nd.array([[1.0, 2.0]]), tag="numdump")
    inspector._dump_counters.pop("numdump", None)   # tag counters are global
    p1 = insp.dump_to_file("numdump", str(tmp_path))
    assert p1.endswith("numdump_1.npy")
    onp.testing.assert_array_equal(onp.load(p1), [[1.0, 2.0]])
    faults.configure("inspector.dump:before=1:error")
    try:
        with pytest.raises(OSError):
            insp.dump_to_file("numdump", str(tmp_path))
    finally:
        faults.reset()
    assert sorted(os.listdir(tmp_path)) == ["numdump_1.npy"], \
        "fault-injected dump left partial/temp files"
    p2 = insp.dump_to_file("numdump", str(tmp_path))
    assert p2.endswith("numdump_2.npy") and os.path.exists(p2)
