"""The streams a training run names its own seconds with (ISSUE 26): the
phase scopes of the step programs, ``runtime.compile_log()``, and the
timeline's spans as annotations on the profiler's clock."""
import glob
import importlib
import re

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, runtime, telemetry
from mxnet_tpu.gluon import Trainer, TrainLoop, fused_step, nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.parallel import make_mesh, shard_batch
from mxnet_tpu.telemetry import names

# the package's ``timeline`` attribute is the accessor function
timeline_mod = importlib.import_module("mxnet_tpu.telemetry.timeline")


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.enable(None)
    telemetry.reset()


def _build():
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(3, in_units=8))
    net.initialize()
    return net


def _batch(bs=8):
    rng = onp.random.RandomState(0)
    return (nd.array(rng.randn(bs, 4).astype("float32")),
            nd.array((onp.arange(bs) % 3).astype("float32")))


def _batches(n):
    for _ in range(n):
        yield _batch()


# ---------------------------------------------------------------------------
# (a) every op of a step program sits under a phase scope
# ---------------------------------------------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _phases_of(hlo_text: str) -> set:
    """The phase of every op the program's own name stack named (an
    ``op_name`` that starts with ``jit(``; parameters carry their
    argument's name and a reducer's scalar computation its primitive's)
    — asserting that each has one."""
    found = set()
    ops = [n for n in _OP_NAME.findall(hlo_text) if n.startswith("jit(")]
    assert ops
    for name in ops:
        mine = [p for p in name.split("/") if p in fused_step.PHASE_SCOPES]
        assert len(mine) >= 1, f"no phase scope around {name!r}"
        found.add(mine[0])
    return found


def _fused_program(numerics):
    net = _build()
    trainer = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    loss = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss(net(a), b),
                                numerics=numerics)
    x, y = _batch()
    step(x, y)
    return [step.lower_entry(x, y)["lowered"]]


def _zero_program(numerics):
    net = _build()
    trainer = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    loss = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss(net(a), b),
                                numerics=numerics)
    x, y = _batch()
    with make_mesh({"dp": 4}, jax.devices()[:4]) as mesh:
        xs, ys = shard_batch(x, mesh), shard_batch(y, mesh)
        step(xs, ys)
        assert step.zero_sharded
        return [step.lower_entry(xs, ys)["lowered"]]


def _split_programs(numerics):
    from mxnet_tpu.kvstore.kvstore import KVStoreDist
    kv = KVStoreDist("dist_sync")
    kv._force_fuse = True
    net = _build()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9}, kvstore=kv)
    loss = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss(net(a), b))
    x, y = _batch()
    step(x, y)
    grad = step.lower_entry(x, y)
    assert grad["kind"] == "split"
    # program B, the donated update, over this trainer's own state
    entry, _ = step._entry_for((x, y), {})
    ws = tuple(p._data._data for p in trainer._params)
    sts = tuple(tuple(s._data for s in st) for st in step._ensure_states())
    n = len(ws)
    n_traces = step.n_traces
    update = entry["update"].lower(
        ws, sts, onp.zeros(n, onp.float32), onp.zeros(n, onp.float32),
        onp.ones(n, onp.int32), onp.float32(1.0), onp.float32(0.0), ws)
    step._n_traces = n_traces
    return [grad["lowered"], update]


@pytest.mark.parametrize("build,numerics,expected", [
    (_fused_program, None, {"loss_and_grad", "optimizer_update"}),
    (_fused_program, "per_layer",
     {"loss_and_grad", "optimizer_update", "numerics"}),
    (_zero_program, None,
     {"loss_and_grad", "optimizer_update", "grad_reduce"}),
    (_zero_program, "per_layer",
     {"loss_and_grad", "optimizer_update", "grad_reduce", "numerics"}),
    (_split_programs, None, {"loss_and_grad", "optimizer_update"}),
], ids=["fused", "fused-numerics", "zero", "zero-numerics", "split"])
def test_every_op_of_a_step_program_has_a_phase_scope(build, numerics,
                                                      expected):
    found = set()
    for lowered in build(numerics):
        found |= _phases_of(lowered.compile().as_text())
    assert found == expected


def test_backward_ops_carry_a_transpose_part_inside_the_phase():
    """What the benchmark's forward/backward split rests on: the outer
    scope is a ``/`` part of its own and leaves the inner ones alone."""
    text = _fused_program(None)[0].compile().as_text()
    ops = set(_OP_NAME.findall(text))
    assert any(re.match(r"jit\(fused_step\)/loss_and_grad/jvp\(\w+\)/", n)
               or re.match(r"jit\(fused_step\)/loss_and_grad/\w+/", n)
               for n in ops), sorted(ops)[:8]
    backward = [n for n in ops if any(p.startswith("transpose(")
                                      for p in n.split("/"))]
    assert backward and all("loss_and_grad" in n.split("/")
                            for n in backward)
    assert any("fully_connected" in n for n in backward)
    assert not any(p.startswith("transpose(") for n in ops
                   if "optimizer_update" in n.split("/")
                   for p in n.split("/"))


# ---------------------------------------------------------------------------
# (b) the compile log
# ---------------------------------------------------------------------------

def _fresh_jit():
    def streams_probe(x):
        return jnp.tanh(x * 3.0).sum()
    return jax.jit(streams_probe)


def test_compile_log_gains_a_programs_phases_once(monkeypatch):
    # a ring of its own: the process's fills up behind a worker's earlier
    # files (65,536 events), and then holds no more events after than before
    monkeypatch.setattr(runtime, "_COMPILE_LOG", runtime._CompileLog())
    fn = _fresh_jit()
    x = jnp.ones((5, 3))
    x.block_until_ready()
    before = runtime.compile_log()
    programs = telemetry.value(names.COMPILE_PROGRAMS)
    fn(x)
    log = runtime.compile_log()
    new = log["events"][len(before["events"]):]
    mine = [e for e in new if "streams_probe" in e["fun_name"]]
    assert {e["phase"] for e in mine} == {"trace", "lower",
                                          "backend_compile"}
    assert all(e["t0"] <= e["t1"] for e in new)
    order = [e["phase"] for e in mine]
    assert order.index("trace") < order.index("lower") \
        < order.index("backend_compile")
    assert {e["phase"] for e in new} <= {"trace", "lower",
                                         "backend_compile", "cache_read"}
    assert telemetry.value(names.COMPILE_PROGRAMS) - programs == \
        sum(e["phase"] == "backend_compile" for e in new)
    for phase in ("trace", "lower", "backend_compile"):
        assert telemetry.value(names.COMPILE_SECONDS, phase) > 0
    fn(x)       # warm: the listeners do not fire
    assert len(runtime.compile_log()["events"]) == len(log["events"])
    assert runtime.compile_log()["dropped"] == before["dropped"]


def test_compile_log_counts_what_a_full_ring_drops(monkeypatch):
    monkeypatch.setattr(runtime, "_COMPILE_LOG",
                        runtime._CompileLog(capacity=2))
    _fresh_jit()(jnp.ones((2, 7)))
    log = runtime.compile_log()
    assert len(log["events"]) == 2
    assert log["dropped"] >= 1          # trace, lower, backend_compile
    assert log["events"][-1]["phase"] == "backend_compile"


# ---------------------------------------------------------------------------
# (c) spans on the profiler's clock, (d) nothing when off
# ---------------------------------------------------------------------------

def _loop():
    net = _build()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    return TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss())


def _run(loop, steps):
    for batch in loop.prefetch(_batches(steps)):
        loop.step(*batch)
    loop.synchronize()


def test_spans_are_annotations_on_the_step_annotations_line(tmp_path):
    from jax.profiler import ProfileData
    loop = _loop()
    _run(loop, 2)                       # compiled and warm
    telemetry.reset()
    telemetry.enable(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("streams_window"):
            _run(loop, 3)
    finally:
        jax.profiler.stop_trace()
    telemetry.enable(False)
    ring = telemetry.timeline().events()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
               {k: v for k, v in e.stats}) for e in line.events
              if e.name.startswith(("mx", "streams_window"))]
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines]
    main = [evs for evs in lines
            if any(n == "streams_window" for n, *_ in evs)]
    assert len(main) == 1
    main = main[0]
    (_, lo, hi, _), = [e for e in main if e[0] == "streams_window"]
    by_name = {}
    for name, start, end, stats in main:
        by_name.setdefault(name, []).append((start, end, stats))
    # the step annotation, the consumer's wait and the retire: one line
    assert len(by_name["mx_train_step"]) == 3
    assert "mx:dispatch" not in by_name     # one annotation, not two
    for name, phase in (("mx:h2d_wait", "h2d_wait"),
                        ("mx:retire", "retire")):
        spans = [e for e in ring if e["phase"] == phase]
        assert len(by_name[name]) == len(spans) >= 3
        assert all(lo <= s and e <= hi for s, e, _ in by_name[name])
        assert [st["step"] for _, _, st in by_name[name]] == \
            [e["step"] for e in spans]
        # the annotation encloses the stamps the ring holds
        for (s, e, _), span in zip(by_name[name], spans):
            assert (e - s) * 1e-9 >= span["dur"] - 1e-4
    assert [st["step_num"] for _, _, st in by_name["mx_train_step"]] == \
        [e["step"] for e in ring if e["phase"] == "dispatch"]
    # the producer's spans: the prefetcher thread's own line
    fetch = [evs for evs in lines
             if any(n == "mx:batch_fetch" for n, *_ in evs)]
    assert len(fetch) == 1 and fetch[0] is not main
    assert len([e for e in ring if e["phase"] == "batch_fetch"]) == 3
    # ``window`` spans two calls: a record(), never an annotation
    assert len([e for e in ring if e["phase"] == "window"]) == 3
    assert not any(n == "mx:window" for evs in lines for n, *_ in evs)


def test_with_telemetry_off_span_sites_do_nothing(monkeypatch):
    entered = []

    class Counting(timeline_mod.TraceAnnotation):
        def __enter__(self):
            entered.append(self)
            return super().__enter__()

    monkeypatch.setattr(timeline_mod, "TraceAnnotation", Counting)
    loop = _loop()
    telemetry.enable(False)
    _run(loop, 3)
    assert telemetry.timeline().events() == []
    assert entered == []
    assert telemetry.span("retire") is telemetry.span("h2d_wait")
    telemetry.enable(True)              # and the same sites, on
    _run(loop, 3)
    phases = {e["phase"] for e in telemetry.timeline().events()}
    assert phases == {"batch_fetch", "h2d_wait", "dispatch", "window",
                      "retire"}
    # dispatch holds the loop's StepTraceAnnotation, the rest their own
    assert len(entered) == sum(
        e["phase"] in ("batch_fetch", "h2d_wait", "retire")
        for e in telemetry.timeline().events()) + 1   # the source's end


def test_a_region_that_raises_leaves_no_span():
    telemetry.enable(True)
    with pytest.raises(ZeroDivisionError):
        with telemetry.span("checkpoint", step=7):
            1 / 0
    assert telemetry.timeline().events() == []
    with pytest.raises(mx.base.MXNetError):
        telemetry.timeline().span("no_such_phase")
