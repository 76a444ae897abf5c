"""Async dispatch engine (ISSUE 5): pipelined train steps, device-side
input prefetch, sync-free metrics.

Acceptance bar, all counter-based (never wall-clock):

- the dispatcher never blocks until ``MXNET_INFLIGHT_STEPS`` futures are
  outstanding (DispatchWindow unit counters + a jax.block_until_ready
  census over a real pipelined TrainLoop);
- prefetched batches land with the step's exact sharding (dp-sharded
  batch dim on a mesh when divisible, replicated otherwise, default
  device placement without a mesh);
- a faulting step N raises at or before the sync of step N — named as
  step N — never silently at N+k with the wrong traceback;
- bit-exact loss parity pipelined-vs-synchronous for sgd-mom/adam ×
  fused/zero;
- with MXNET_TRANSFER_GUARD=raise a pipelined >=10-step TrainLoop run
  performs ZERO unblessed host syncs inside the hot loop (the guard IS
  the regression test);
- metric accumulators run sync-free on device inputs and match the host
  float64 path;
- the persistent compilation cache is armed at import, at
  JAX_COMPILATION_CACHE_DIR or one fixed path in the checkout.
"""
import os

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import engine, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.analysis import guard as tguard
from mxnet_tpu.gluon import Trainer, TrainLoop, nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.data.prefetcher import DevicePrefetcher
from mxnet_tpu.parallel import make_mesh


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


# ---------------------------------------------------------------------------
# DispatchWindow semantics (pure counters, injected sync_fn)
# ---------------------------------------------------------------------------

def test_window_never_blocks_until_full():
    """PushAsync contract: with window W, pushes 1..W trigger ZERO
    retires; push W+1 retires exactly the oldest. FIFO order."""
    synced = []
    w = engine.DispatchWindow(max_inflight=3, sync_fn=synced.append)
    for i in range(3):
        w.push(f"p{i}", tag=i)
        assert synced == [], f"blocked early at push {i}"
    assert len(w) == 3
    w.push("p3", tag=3)
    assert synced == ["p0"]          # oldest only
    for i in range(4, 10):
        w.push(f"p{i}", tag=i)
    assert synced == [f"p{i}" for i in range(7)]
    w.drain()
    assert synced == [f"p{i}" for i in range(10)]
    assert w.stats["pushes"] == 10 and w.stats["retires"] == 10
    assert len(w) == 0


def test_window_zero_is_synchronous_oracle():
    synced = []
    w = engine.DispatchWindow(max_inflight=0, sync_fn=synced.append)
    for i in range(4):
        w.push(i, tag=i)
        assert synced == list(range(i + 1)), "window 0 must sync per push"


def test_window_error_attributed_to_faulting_step():
    """A fault in step 3 must raise when step 3 retires (at push 3+W) —
    named as step 3 — and the window must stay usable after."""
    def sync(payload):
        if payload == "boom3":
            raise RuntimeError("device exploded")

    w = engine.DispatchWindow(max_inflight=2, sync_fn=sync)
    payloads = ["ok0", "ok1", "ok2", "boom3", "ok4", "ok5"]
    raised_at = None
    for i, p in enumerate(payloads):
        try:
            w.push(p, tag=i)
        except MXNetError as e:
            raised_at = i
            assert "3" in str(e) and "device exploded" in str(e)
            break
    # retire of step 3 happens at push 5 (window 2) — at or before the
    # sync of step 3, never later
    assert raised_at == 5
    assert w.stats["errors"] == 1
    w.push("ok6", tag=6)            # engine remains usable post-error
    w.drain()


def test_window_error_surfaces_on_drain():
    def sync(payload):
        if payload == "bad":
            raise RuntimeError("late fault")

    w = engine.DispatchWindow(max_inflight=8, sync_fn=sync)
    w.push("fine", tag=1)
    w.push("bad", tag=2)
    with pytest.raises(MXNetError, match="2"):
        w.drain()
    w.drain()                       # remains usable; nothing pending
    assert len(w) == 0


def test_inflight_steps_env_and_naive(monkeypatch):
    monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "5")
    assert engine.inflight_steps() == 5
    monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "not-a-number")
    assert engine.inflight_steps() == 2
    monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "-3")
    assert engine.inflight_steps() == 0
    # NaiveEngine forces the synchronous oracle regardless of the window
    prev = engine.Engine._instance
    try:
        engine.Engine._instance = engine.Engine("NaiveEngine")
        monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "7")
        assert engine.inflight_steps() == 0
    finally:
        engine.Engine._instance = prev


# ---------------------------------------------------------------------------
# TrainLoop pipelining (counter-based over the real jit path)
# ---------------------------------------------------------------------------

def test_train_loop_dispatch_counters():
    """Over N steps with window W: retires observed DURING the loop are
    exactly N - W (each over-capacity push retires one), and the N
    async losses were pushed without the loop ever forcing them."""
    net = _build()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=2)
    x, y = _batch()
    tguard.reset_sync_counts()
    for _ in range(7):
        loop.step(x, y)
    counts = tguard.sync_counts()
    assert counts.get("window_retire", 0) == 5      # 7 - W
    assert counts.get("wait_to_read", 0) == 0, \
        "the pipelined loop must not force the loss"
    assert loop.engine_stats()["pending"] == 2
    loop.synchronize()
    assert tguard.sync_counts()["window_retire"] == 7
    assert loop.engine_stats()["pending"] == 0
    s = loop.engine_stats()
    assert s["pushes"] == 7 and s["inflight_window"] == 2


def test_train_loop_inflight_env_default(monkeypatch):
    monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "4")
    net = _build()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss())
    assert loop.engine_stats()["inflight_window"] == 4


def test_waitall_drains_train_loop_window():
    net = _build()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=4)
    x, y = _batch()
    for _ in range(3):
        loop.step(x, y)
    assert loop.engine_stats()["pending"] == 3
    nd.waitall()
    assert loop.engine_stats()["pending"] == 0


# ---------------------------------------------------------------------------
# device prefetch: sharding + overlap machinery
# ---------------------------------------------------------------------------

def test_prefetcher_default_device_placement():
    rng = onp.random.RandomState(0)
    host = [(rng.randn(8, 4).astype("float32"),
             rng.randint(0, 3, size=(8,)).astype("int32"))
            for _ in range(4)]
    pf = DevicePrefetcher(iter(host), depth=2)
    out = list(pf)
    assert len(out) == 4
    for (hx, hy), (dx, dy) in zip(host, out):
        assert isinstance(dx, jax.Array) and isinstance(dy, jax.Array)
        onp.testing.assert_array_equal(onp.asarray(dx), hx)
        onp.testing.assert_array_equal(onp.asarray(dy), hy)
    assert pf.stats["prefetch_batches"] == 4


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual mesh")
def test_prefetcher_mesh_sharding():
    """Batches land with the fused step's exact layout: dim0 divisible
    by dp → batch-sharded NamedSharding; non-divisible → replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh({"dp": 4}, jax.devices()[:4])
    rng = onp.random.RandomState(0)
    divisible = nd.array(rng.randn(8, 4).astype("float32"))
    ragged = nd.array(rng.randn(6, 4).astype("float32"))
    pf = DevicePrefetcher(iter([(divisible, ragged)]), depth=2, mesh=mesh)
    (dx, dr), = list(pf)
    assert isinstance(dx, nd.NDArray) and isinstance(dr, nd.NDArray)
    assert isinstance(dx._data.sharding, NamedSharding)
    assert dx._data.sharding.spec == P("dp", None)
    assert dr._data.sharding.spec == P()        # replicated fallback
    onp.testing.assert_array_equal(dx.asnumpy(), divisible.asnumpy())


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual mesh")
def test_train_loop_prefetch_uses_step_sharding():
    """loop.prefetch stages with CompiledTrainStep.input_placement —
    under an active dp mesh the batch arrives pre-sharded and the fused
    step's own placement passes it through untouched."""
    from jax.sharding import PartitionSpec as P
    with make_mesh({"dp": 4}, jax.devices()[:4]):
        net = _build()
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9})
        loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss())
        x, y = _batch(bs=8)
        seen = []
        for bx, by in loop.prefetch((x, y) for _ in range(3)):
            seen.append(bx._data.sharding.spec)
            loop.step(bx, by)
        loop.synchronize()
    assert seen == [P("dp", None)] * 3
    assert loop.compiled_step.mode == "fused"


def test_prefetcher_propagates_worker_error():
    def batches():
        yield onp.zeros((2, 2), "float32")
        raise ValueError("dataset exploded")

    pf = DevicePrefetcher(batches(), depth=2)
    it = iter(pf)
    next(it)
    with pytest.raises(ValueError, match="dataset exploded"):
        next(it)


def test_prefetcher_early_break_stops_producer():
    produced = []

    def batches():
        for i in range(1000):
            produced.append(i)
            yield onp.full((2,), i, "float32")

    pf = DevicePrefetcher(batches(), depth=2)
    for i, b in enumerate(pf):
        if i == 2:
            break
    # bounded staging: the producer cannot have run far ahead of the
    # depth-2 queue (+1 in-hand +1 being staged)
    assert len(produced) <= 2 + 2 + 2


def test_dataloader_device_prefetch():
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset
    rng = onp.random.RandomState(0)
    xs = rng.randn(32, 4).astype("float32")
    ys = rng.randint(0, 3, size=(32,)).astype("int32")
    ds = ArrayDataset(xs, ys)
    plain = [tuple(b.asnumpy() for b in batch)
             for batch in DataLoader(ds, batch_size=8)]
    dl = DataLoader(ds, batch_size=8, device=True, prefetch_to_device=2)
    staged = list(dl)
    assert len(staged) == len(plain) == 4
    for (px, py), (sx, sy) in zip(plain, staged):
        assert isinstance(sx, nd.NDArray)
        assert isinstance(sx._data, jax.Array)
        onp.testing.assert_array_equal(sx.asnumpy(), px)
        onp.testing.assert_array_equal(sy.asnumpy(), py)
    stats = dl.device_prefetch_stats
    assert stats is not None and stats["prefetch_batches"] == 4


# ---------------------------------------------------------------------------
# parity: pipelined vs synchronous must be bit-exact
# ---------------------------------------------------------------------------

def _run_loop(opt, kwargs, inflight, steps=6, mesh_ctx=None, prefetch=False):
    net = _build(seed=11)
    trainer = Trainer(net.collect_params(), opt, dict(kwargs))
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=inflight)
    x, y = _batch(bs=8, seed=5)
    losses = []
    if prefetch:
        for bx, by in loop.prefetch((x, y) for _ in range(steps)):
            losses.append(loop.step(bx, by))
    else:
        for _ in range(steps):
            losses.append(loop.step(x, y))
    loop.synchronize()
    # host reads AFTER the run — the values were async the whole time
    vals = [l.asnumpy() for l in losses]
    params = {k: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    return vals, params, loop


@pytest.mark.parametrize("opt,kwargs", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
])
def test_pipelined_parity_fused(opt, kwargs):
    sync_vals, sync_params, sloop = _run_loop(opt, kwargs, inflight=0)
    pipe_vals, pipe_params, ploop = _run_loop(opt, kwargs, inflight=3,
                                              prefetch=True)
    assert sloop.compiled_step.mode == "fused"
    assert ploop.compiled_step.mode == "fused"
    for a, b in zip(sync_vals, pipe_vals):
        onp.testing.assert_array_equal(a, b)   # BIT-exact
    for k in sync_params:
        onp.testing.assert_array_equal(sync_params[k], pipe_params[k])


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual mesh")
@pytest.mark.parametrize("opt,kwargs", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
])
def test_pipelined_parity_zero_sharded(opt, kwargs):
    with make_mesh({"dp": 4}, jax.devices()[:4]):
        sync_vals, sync_params, sloop = _run_loop(opt, kwargs, inflight=0)
    with make_mesh({"dp": 4}, jax.devices()[:4]):
        pipe_vals, pipe_params, ploop = _run_loop(opt, kwargs, inflight=3,
                                                  prefetch=True)
    assert sloop.compiled_step.zero_sharded
    assert ploop.compiled_step.zero_sharded
    for a, b in zip(sync_vals, pipe_vals):
        onp.testing.assert_array_equal(a, b)
    for k in sync_params:
        onp.testing.assert_array_equal(sync_params[k], pipe_params[k])


# ---------------------------------------------------------------------------
# the transfer guard IS the regression test (acceptance criterion)
# ---------------------------------------------------------------------------

def test_pipelined_loop_zero_unblessed_syncs(monkeypatch):
    """MXNET_TRANSFER_GUARD=raise + a pipelined >=10-step prefetched run:
    the ONLY host syncs are the blessed window retires. Any unblessed
    sync inside the hot loop raises and fails this test."""
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    net = _build()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=2)
    x, y = _batch()
    tguard.reset_sync_counts()
    tguard.clear_events()
    losses = []
    for bx, by in loop.prefetch((x, y) for _ in range(12)):
        losses.append(loop.step(bx, by))   # raises on any unblessed sync
    loop.synchronize()
    assert loop.compiled_step.mode == "fused"
    counts = tguard.sync_counts()
    assert counts.get("wait_to_read", 0) == 0
    assert counts.get("window_retire", 0) == 12
    assert tguard.events() == []
    # outside the hot loop the values read freely
    assert onp.isfinite(losses[-1].asnumpy()).all()


def test_guard_flags_hostile_sync_in_pipelined_loop(monkeypatch):
    """Negative control: a loss_fn that syncs (float/asnumpy) inside the
    hot loop must RAISE under the armed guard, not silently demote the
    run to one device round-trip per step."""
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    net = _build()
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})

    def hostile(a, b):
        out = net(a)
        _ = float(out.asnumpy().sum())     # the classic silent stall
        return loss_blk(out, b)

    step = trainer.compile_step(hostile)
    x, y = _batch()
    with pytest.raises(MXNetError, match="hot region"):
        step(x, y)


# ---------------------------------------------------------------------------
# sync-free metrics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("factory,binary", [
    (lambda m: m.Accuracy(), False),
    (lambda m: m.TopKAccuracy(top_k=2), False),
    (lambda m: m.MAE(), None),
    (lambda m: m.MSE(), None),
    (lambda m: m.RMSE(), None),
    (lambda m: m.CrossEntropy(), False),
    (lambda m: m.Perplexity(), False),
    (lambda m: m.F1(), True),
    (lambda m: m.MCC(), True),
    (lambda m: m.BinaryAccuracy(), True),
    (lambda m: m.MeanPairwiseDistance(), None),
    (lambda m: m.MeanCosineSimilarity(), None),
])
def test_metric_device_accumulation_sync_free(factory, binary):
    """Two batches through each metric: the device path performs ZERO
    host syncs during update (proven by the armed guard) and get()
    matches the host float64 path."""
    from mxnet_tpu import metric
    rng = onp.random.RandomState(7)
    batches = []
    for seed in (0, 1):
        r = onp.random.RandomState(seed)
        if binary is None:                     # regression-style
            label = r.randn(16, 4).astype("float32")
            pred = r.randn(16, 4).astype("float32")
        elif binary:                           # {0,1} labels, 2-col pred
            label = r.randint(0, 2, size=(16,)).astype("int64")
            pred = r.rand(16, 2).astype("float32")
            if isinstance(factory(metric), metric.BinaryAccuracy):
                pred = r.rand(16).astype("float32")
        else:                                  # 3-class
            label = r.randint(0, 3, size=(16,)).astype("int64")
            pred = r.rand(16, 3).astype("float32")
            pred /= pred.sum(-1, keepdims=True)
        batches.append((label, pred))
    del rng

    m_host, m_dev = factory(metric), factory(metric)
    for label, pred in batches:
        m_host.update(label, pred)
    with tguard.transfer_guard("raise", scope="metric.update"):
        for label, pred in batches:
            m_dev.update(nd.array(label), nd.array(pred))
    name_h, v_host = m_host.get()
    name_d, v_dev = m_dev.get()
    assert name_h == name_d
    assert m_dev.num_inst == m_host.num_inst
    onp.testing.assert_allclose(v_dev, v_host, rtol=1e-4, atol=1e-5)


def test_metric_loss_device_sync_free():
    from mxnet_tpu import metric
    r = onp.random.RandomState(0)
    v = r.randn(8, 3).astype("float32")
    m_host, m_dev = metric.Loss(), metric.Loss()
    m_host.update(None, v)
    with tguard.transfer_guard("raise"):
        m_dev.update(None, nd.array(v))
    onp.testing.assert_allclose(m_dev.get()[1], m_host.get()[1],
                                rtol=1e-5)


def test_metric_host_path_unchanged():
    """Numpy inputs keep the reference float64 host accumulation — no
    device arrays appear in the accumulator."""
    from mxnet_tpu import metric
    m = metric.Accuracy()
    m.update(onp.array([0, 1, 1]), onp.array([[1, 0], [0, 1], [1, 0]],
                                             "float32"))
    assert isinstance(m.sum_metric, float)
    assert m.get()[1] == pytest.approx(2.0 / 3.0)


# ---------------------------------------------------------------------------
# persistent compile cache (JAX_COMPILATION_CACHE_DIR, else one fixed path)
# ---------------------------------------------------------------------------

def _rearm(monkeypatch):
    """setup_compile_cache() as a fresh import would run it, recording
    every directory the code itself hands to jax.config."""
    import jax as _jax
    from mxnet_tpu import runtime
    monkeypatch.setitem(runtime._CACHE_STATS, "enabled", False)
    set_in_code = []
    real_update = _jax.config.update

    def update(name, value):
        if name == "jax_compilation_cache_dir":
            set_in_code.append(value)
        else:
            real_update(name, value)

    monkeypatch.setattr(_jax.config, "update", update)
    # the import's hit/miss listener stays the only one
    monkeypatch.setattr(_jax.monitoring, "register_event_listener",
                        lambda cb: None)
    return runtime.setup_compile_cache(), set_in_code


def test_compile_cache_placed_from_outside(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the cache is where JAX put it
    and no code sets another directory."""
    from mxnet_tpu import runtime
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cache_dir, set_in_code = _rearm(monkeypatch)
    assert cache_dir == str(tmp_path) and set_in_code == []
    stats = runtime.compile_cache_stats()
    assert stats["enabled"] and stats["dir"] == str(tmp_path)


def test_compile_cache_fixed_path_without_env(monkeypatch):
    """Unset, the cache is armed at import at ONE fixed path in the
    checkout — the path is part of the cache key, so a temp name, pid or
    time would never hit. Idempotent."""
    import mxnet_tpu as mx
    from mxnet_tpu import runtime
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(mx.__file__))), ".jax_cache")
    cache_dir, set_in_code = _rearm(monkeypatch)
    assert cache_dir == fixed and set_in_code == [fixed]
    assert runtime.setup_compile_cache() == fixed and len(set_in_code) == 1


def test_compile_cache_armed_by_import_counts_hits():
    """`import mxnet_tpu` armed the cache (floors zeroed), so a program
    compiled twice from distinct function objects misses, then hits."""
    import jax as _jax
    from mxnet_tpu import runtime
    assert runtime.compile_cache_stats()["enabled"]
    assert _jax.config.jax_compilation_cache_dir == \
        runtime.compile_cache_stats()["dir"]
    salt = float(onp.random.RandomState().rand())     # a program no
    x = onp.arange(7, dtype="float32")                # earlier run cached
    before = runtime.compile_cache_stats()
    _jax.jit(lambda a: a * salt + 1.5)(x).block_until_ready()
    mid = runtime.compile_cache_stats()
    _jax.jit(lambda a: a * salt + 1.5)(x).block_until_ready()
    after = runtime.compile_cache_stats()
    assert mid["misses"] == before["misses"] + 1
    assert after["hits"] == mid["hits"] + 1
    assert after["misses"] == mid["misses"]
