"""Device counters (ISSUE 37): what the device computed about a step's own
work leaves the compiled step with its outputs, rides the dispatch window
in the step's one aux record and lands on the ``window`` span.

- ``emit`` is a no-op without a collector, and a value written under a
  transformation the collector cannot see out of is dropped and counted;
- every step program (plain fused, ZeRO, split) returns the counters of a
  two-layer ``SparseMoE`` net, with and without the numerics aux;
- the first step's ``moe_held_pairs`` is ``SparseMoE.routing_stats`` on
  the same batch and weights, expert for expert;
- turning telemetry on after warm-up retraces and compiles nothing, and
  with telemetry off no counter is read;
- a program without an emitter has no output more than it had.
"""
import json

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler, runtime, telemetry
from mxnet_tpu.analysis import guard as tguard
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, TrainLoop, nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.parallel import make_mesh, shard_batch
from mxnet_tpu.telemetry import device_counters, names

PAIRS = names.COUNTER_MOE_HELD_PAIRS
DP = 4


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.enable(None)
    telemetry.reset()


class TwoExpertLayers(HybridBlock):
    """Two dropless expert layers, each holding 4 of its router's 8."""

    def __init__(self):
        super().__init__()
        self.a = nn.SparseMoE(16, 32, 8, 2, held=(2, 4))
        self.b = nn.SparseMoE(16, 32, 8, 2, held=(0, 4), score="sigmoid")
        self.out = nn.Dense(4, in_units=16)

    def forward(self, x):
        x = x + self.a(x)
        return self.out(x + self.b(x))


def _expert_net(seed=5):
    mx.random.seed(seed)
    net = TwoExpertLayers()
    net.initialize()
    return net


def _dense_net(seed=5):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=16, activation="relu"),
            nn.Dense(4, in_units=8))
    net.initialize()
    return net


def _batch(bs=32, seed=0):
    rng = onp.random.RandomState(seed)
    return (nd.array(rng.randn(bs, 16).astype("float32")),
            nd.array(rng.randint(0, 4, size=(bs,)).astype("int32")))


def _compiled(net, numerics=None, kvstore="device"):
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9},
                      kvstore=kvstore)
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    return trainer.compile_step(lambda a, b: loss_blk(net(a), b),
                                numerics=numerics)


def _loop(net, **kwargs):
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    return TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=2, **kwargs)


def _dropped():
    return telemetry.registry().counter(
        names.DEVICE_COUNTER_DROPPED, label_key="name").values()


# ---------------------------------------------------------------------------
# the collector
# ---------------------------------------------------------------------------

def test_emit_without_a_collector_does_nothing():
    device_counters.emit(PAIRS, jnp.arange(4))
    net = _expert_net()
    x, _ = _batch()
    net(x)                      # eager
    net.hybridize()
    net(x)                      # hybridize() alone: a jit, no collector
    assert _dropped() == {}


def test_emit_refuses_a_name_the_catalog_lacks():
    with pytest.raises(MXNetError, match="DEVICE_COUNTERS"):
        device_counters.emit("rogue_counter", jnp.arange(4))


def test_collect_yields_by_name_in_trace_order_and_closes():
    with device_counters.collect() as emitted:
        device_counters.emit(PAIRS, jnp.arange(4))
        device_counters.emit(PAIRS, nd.array(onp.ones(4, "int32")))
    assert [v.tolist() for v in emitted[PAIRS]] == [[0, 1, 2, 3],
                                                    [1, 1, 1, 1]]
    device_counters.emit(PAIRS, jnp.arange(4))      # closed again
    assert len(emitted[PAIRS]) == 2


def _under_checkpoint(x):
    def inner(z):
        device_counters.emit(PAIRS, (z > 0).sum(axis=0).astype(jnp.int32))
        return z * 2
    return jax.checkpoint(inner)(x)


def _under_scan(x):
    def body(carry, row):
        device_counters.emit(PAIRS, (row > 0).astype(jnp.int32))
        return carry + row.sum(), None
    return jax.lax.scan(body, 0.0, x)[0] + x


def _under_jit(x):
    @jax.jit
    def inner(z):
        device_counters.emit(PAIRS, (z > 0).sum(axis=0).astype(jnp.int32))
        return z * 2
    return inner(x)


def _under_custom_vjp(x):
    @jax.custom_vjp
    def double(z):
        return z * 2

    def fwd(z):
        device_counters.emit(PAIRS, (z > 0).sum(axis=0).astype(jnp.int32))
        return z * 2, None

    double.defvjp(fwd, lambda _, ct: (ct * 2,))
    return double(x)


@pytest.mark.parametrize("site", [_under_checkpoint, _under_scan,
                                  _under_jit, _under_custom_vjp])
def test_a_value_from_an_inner_trace_is_dropped_and_counted(site):
    """Nothing leaks: the program traces, runs, and returns only what was
    written at the loss function's own level."""
    def loss(x):
        with device_counters.collect() as emitted:
            device_counters.emit(PAIRS, jnp.arange(4, dtype=jnp.int32))
            y = site(x)
        return jnp.sum(y), emitted

    (_, emitted), _ = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        jnp.ones((3, 4)))
    assert [v.tolist() for v in emitted[PAIRS]] == [[0, 1, 2, 3]]
    assert _dropped() == {PAIRS: 1.0}


def test_sites_of_unlike_shapes_do_not_stack():
    got = device_counters.stacked(
        {PAIRS: [jnp.arange(4), jnp.arange(8)]})
    assert got == {} and _dropped() == {PAIRS: 2.0}
    assert device_counters.stacked({}) == {}


# ---------------------------------------------------------------------------
# the three step programs
# ---------------------------------------------------------------------------

def _want(net, x):
    return [net.a.routing_stats(x)["pairs"].tolist(),
            net.b.routing_stats(x + net.a(x))["pairs"].tolist()]


@pytest.mark.parametrize("numerics", [None, "global"])
@pytest.mark.parametrize("program", ["fused", "zero", "split"])
def test_every_step_program_returns_the_counters(monkeypatch, program,
                                                 numerics):
    net = _expert_net()
    x, y = _batch()
    want = _want(net, x)
    if program == "zero":
        if len(jax.devices()) < DP:
            pytest.skip("needs the virtual mesh")
        monkeypatch.setenv("MXNET_ZERO_SHARD_MIN_SIZE", "1")
        with make_mesh({"dp": DP}, jax.devices()[:DP]) as mesh:
            step = _compiled(net, numerics)
            step(shard_batch(x, mesh), shard_batch(y, mesh))
        assert step.zero_sharded
    elif program == "split":
        from mxnet_tpu.kvstore.kvstore import KVStoreDist
        kv = KVStoreDist("dist_sync")
        kv._force_fuse = True
        step = _compiled(net, numerics, kvstore=kv)
        step(x, y)
        numerics = None         # not wired for the split mode: off
    else:
        step = _compiled(net, numerics)
        step(x, y)
    assert step.mode == "fused" and step.n_traces == 1
    aux = step.take_aux()
    assert onp.asarray(aux.counters[PAIRS]).tolist() == want
    assert aux.counters[PAIRS].dtype == jnp.int32
    assert (aux.numerics is not None) == bool(numerics)
    if numerics:
        assert aux.numerics.host_values()["grad_norm"] > 0
    assert step.take_aux() is None          # popped
    assert _dropped() == {}


def test_take_numerics_keeps_its_meaning():
    net = _expert_net()
    x, y = _batch()
    step = _compiled(net, "global")
    step(x, y)
    rec = step.take_numerics()
    assert isinstance(rec, telemetry.StepNumerics)
    assert step.take_numerics() is None and step.take_aux() is None
    step(x, y)
    assert step.numerics_values()["grad_norm"] > 0
    plain = _compiled(_expert_net())
    plain(x, y)
    assert plain.take_numerics() is None


def test_a_net_without_experts_has_no_aux():
    step = _compiled(_dense_net())
    step(*_batch())
    assert step.take_aux() is None


# ---------------------------------------------------------------------------
# the window, the timeline, the gauges
# ---------------------------------------------------------------------------

def _windows():
    return [e for e in telemetry.timeline().events()
            if e["phase"] == "window"]


def test_first_steps_pairs_are_routing_stats_expert_for_expert():
    net = _expert_net()
    x, y = _batch()
    want = _want(net, x)
    telemetry.enable(True)
    loop = _loop(net)
    loop.step(x, y)
    loop.synchronize()
    first, = _windows()
    assert first["step"] == 1 and first["counters"] == {PAIRS: want}
    assert all(isinstance(n, int) for row in first["counters"][PAIRS]
               for n in row)
    reg = telemetry.registry()
    assert reg.gauge(names.MOE_HELD_PAIRS, label_key="layer").values() \
        == {"0": float(sum(want[0])), "1": float(sum(want[1]))}
    ratio = reg.gauge(names.MOE_EXPERT_LOAD_MAX_RATIO,
                      label_key="layer").values()
    assert ratio["0"] == pytest.approx(max(want[0]) * 4 / sum(want[0]))
    assert ratio["1"] >= 1.0


def test_a_step_that_emitted_nothing_records_an_empty_dict():
    telemetry.enable(True)
    loop = _loop(_dense_net())
    x, y = _batch()
    for _ in range(3):
        loop.step(x, y)
    loop.synchronize()
    assert [e["counters"] for e in _windows()] == [{}, {}, {}]
    # no other span carries the key
    assert all("counters" not in e for e in telemetry.timeline().events()
               if e["phase"] != "window")


def test_enabling_telemetry_after_warmup_retraces_and_compiles_nothing():
    """``run.py``'s own sequence: off, warm-up, on, the window."""
    telemetry.enable(False)
    net = _expert_net()
    loop = _loop(net)
    x, y = _batch()
    for _ in range(4):
        loop.step(x, y)
    loop.synchronize()
    assert _windows() == []
    stats = runtime.compile_cache_stats()
    before = stats["hits"] + stats["misses"]
    telemetry.enable(True)
    telemetry.timeline().clear()
    for _ in range(5):
        loop.step(x, y)
    loop.synchronize()
    stats = runtime.compile_cache_stats()
    assert stats["hits"] + stats["misses"] == before
    assert loop.compiled_step.n_traces == 1
    assert [e["step"] for e in _windows()] == [5, 6, 7, 8, 9]
    assert all(len(e["counters"][PAIRS]) == 2 for e in _windows())


def test_with_telemetry_off_no_counter_is_read(monkeypatch):
    """Twelve pipelined steps under the raising guard: the blessed
    retire is the only sync, and the counters are dropped unread."""
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    telemetry.enable(False)
    reads = []
    real = device_counters.observe
    monkeypatch.setattr(device_counters, "observe",
                        lambda aux: reads.append(aux) or real(aux))
    loop = _loop(_expert_net())
    x, y = _batch()
    tguard.reset_sync_counts()
    for _ in range(12):
        loop.step(x, y)
    loop.synchronize()
    assert tguard.sync_counts() == {"window_retire": 12}
    assert reads == [] and _windows() == []
    assert telemetry.registry().gauge(
        names.MOE_HELD_PAIRS, label_key="layer").values() == {}
    # and on, under the same guard, the read is inside the blessed retire
    telemetry.enable(True)
    tguard.reset_sync_counts()
    for _ in range(3):
        loop.step(x, y)
    loop.synchronize()
    assert tguard.sync_counts() == {"window_retire": 3}
    assert len(reads) == 3 and len(_windows()) == 3


def test_the_chrome_trace_carries_the_counters(tmp_path):
    telemetry.enable(True)
    loop = _loop(_expert_net())
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
    events = [e for e in json.load(open(trace))["traceEvents"]
              if e.get("cat") == "step"]
    windows = [e for e in events if e["args"]["phase"] == "window"]
    assert len(windows) == 3
    for e in windows:
        assert len(e["args"]["counters"][PAIRS]) == 2
        assert isinstance(e["args"]["step"], int)
    assert all("counters" not in e["args"] for e in events
               if e["args"]["phase"] != "window")


# ---------------------------------------------------------------------------
# a program without an emitter is the program it was
# ---------------------------------------------------------------------------

def _lowered(net):
    step = _compiled(net)
    x, y = _batch()
    info = step.lower_entry(x, y)
    outputs = len(jax.tree_util.tree_leaves(info["lowered"].out_info))
    return info["lowered"].as_text(), outputs, info


def test_a_program_without_an_emitter_has_no_output_more(monkeypatch):
    text, outputs, info = _lowered(_dense_net())
    # parameters, their momenta, the loss: nothing for the empty aux
    assert outputs == info["n_params"] + info["n_state_leaves"] + 1
    # byte for byte the text of a build that never heard of the channel
    import contextlib
    monkeypatch.setattr(device_counters, "collect",
                        lambda: contextlib.nullcontext({}))
    monkeypatch.setattr(device_counters, "stacked", lambda emitted: {})
    assert _lowered(_dense_net())[0] == text


def test_the_counters_are_one_output_more_of_an_expert_program(
        monkeypatch):
    text, outputs, info = _lowered(_expert_net())
    assert outputs == info["n_params"] + info["n_state_leaves"] + 2
    monkeypatch.setattr(device_counters, "emit", lambda name, value: None)
    silent, fewer, _ = _lowered(_expert_net())
    assert fewer == outputs - 1 and silent != text
