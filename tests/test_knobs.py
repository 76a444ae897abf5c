"""The performance knobs: one accessor a knob, next to its constant,
reading one environment variable.

Pins, for every knob that has a variable:

- unset, the accessor returns the shipped default (stated HERE as a
  literal, so a changed default fails this file and has to be argued);
- a set value reaches the accessor, and through it the consumer
  (dispatch window, ZeRO plan, batcher, kernel sizers);
- input from outside the program never raises: a value that does not
  parse gives the default, one out of range the clamp;
- knobs change speed, never numbers: the loss trajectory is the same
  to the bit at any setting.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine
from mxnet_tpu.gluon import Trainer, TrainLoop, fused_step, nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.ops import kernels
from mxnet_tpu.serving import batcher, decode

MIB = 1024 * 1024

# variable: (accessor, shipped default, (raw, value) that is honoured,
#            [(raw, value) that does not parse or is out of range])
KNOBS = {
    "MXNET_VMEM_TILE_BUDGET": (
        kernels.vmem_tile_budget, 4 * MIB, ("2097152", 2 * MIB),
        [("garbage", 4 * MIB), ("", 4 * MIB), ("inf", 4 * MIB),
         ("1", 64 * 1024), ("1e12", 16 * MIB)]),
    "MXNET_ZERO_SHARD_MIN_SIZE": (
        fused_step._zero_min_size, 2048, ("512", 512),
        [("garbage", 2048), ("", 2048), ("0", 1), ("-5", 1)]),
    "MXNET_ZERO_BUCKET_BYTES": (
        fused_step._zero_bucket_bytes, 4 * MIB, ("16384", 16384),
        [("garbage", 4 * MIB), ("", 4 * MIB), ("-1", 0)]),
    "MXNET_INFLIGHT_STEPS": (
        engine.inflight_steps, 2, ("5", 5),
        [("garbage", 2), ("", 2), ("2.5", 2), ("-3", 0)]),
    "MXNET_SERVING_MAX_BATCH": (
        batcher.max_batch_rows, 32, ("16", 16),
        [("garbage", 32), ("", 32), ("0", 1), ("-4", 1)]),
    "MXNET_SERVING_BATCH_TIMEOUT_MS": (
        batcher.batch_timeout_s, 0.002, ("0.5", 0.0005),
        [("garbage", 0.002), ("", 0.002), ("-1", 0.0)]),
    "MXNET_DECODE_SLOTS": (
        decode.slot_ladder, (1, 2, 4, 8), ("16, 1,4", (1, 4, 16)),
        [("garbage", (1, 2, 4, 8)), ("", (1, 2, 4, 8)),
         ("0,2", (1, 2, 4, 8)), ("-1", (1, 2, 4, 8))]),
    "MXNET_DECODE_KV_PAGE_SIZE": (
        decode.kv_page_size, 16, ("8", 8),
        [("garbage", 16), ("", 16), ("0", 1), ("100000", 4096)]),
    "MXNET_DECODE_PREFILL_CHUNK": (
        decode.prefill_chunk, 16, ("32", 32),
        [("garbage", 16), ("", 16), ("0", 1), ("100000", 4096)]),
    "MXNET_DECODE_SPEC_K": (
        decode.spec_k, 0, ("6", 6),
        [("garbage", 0), ("", 0), ("-1", 0), ("100", 64)]),
    "MXNET_DECODE_PREFIX_SHARE": (
        decode.prefix_share, True, ("0", False),
        [("garbage", True), ("", True), ("2", True), ("-1", True)]),
}


@pytest.fixture(autouse=True)
def clear_env(monkeypatch):
    for var in KNOBS:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.delenv("MXNET_ENGINE_TYPE", raising=False)


def _same(got, want):
    return type(got) is type(want) and got == pytest.approx(want)


@pytest.mark.parametrize("case", ["unset", "set", "bad"])
@pytest.mark.parametrize("var", sorted(KNOBS))
def test_knob(monkeypatch, var, case):
    accessor, default, (raw, value), bad = KNOBS[var]
    if case == "unset":
        assert _same(accessor(), default)
    elif case == "set":
        monkeypatch.setenv(var, raw)
        assert _same(accessor(), value)
        monkeypatch.delenv(var)
        assert _same(accessor(), default)       # read at each use
    else:
        for raw, value in bad:
            monkeypatch.setenv(var, raw)
            assert _same(accessor(), value), (var, raw)


def test_resolution_precedence(monkeypatch):
    """env > the caller's default > nothing else."""
    assert engine.inflight_steps(default=3) == 3
    assert batcher.max_batch_rows(default=8) == 8
    assert batcher.batch_timeout_s(default_ms=4.0) == pytest.approx(4e-3)
    monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "5")
    monkeypatch.setenv("MXNET_SERVING_MAX_BATCH", "16")
    monkeypatch.setenv("MXNET_SERVING_BATCH_TIMEOUT_MS", "0.5")
    assert engine.inflight_steps(default=3) == 5
    assert batcher.max_batch_rows(default=8) == 16
    assert batcher.batch_timeout_s(default_ms=4.0) == pytest.approx(0.5e-3)
    monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "garbage")
    assert engine.inflight_steps(default=3) == 3


IN, HIDDEN, CLASSES, BS = 16, 32, 8, 8


def make_net():
    mx.random.seed(42)
    onp.random.seed(42)
    net = nn.HybridSequential()
    net.add(nn.Dense(HIDDEN, activation="relu", in_units=IN),
            nn.Dense(CLASSES, in_units=HIDDEN))
    net.initialize()
    net(mx.nd.array(onp.zeros((1, IN), "float32")))
    return net


def make_loop():
    net = make_net()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9},
                      kvstore=None)
    return TrainLoop(net, trainer, SoftmaxCrossEntropyLoss())


def test_consumer_seams_read_the_environment(monkeypatch):
    """What the accessors feed: the dispatch window's depth, the
    batcher's cap and linger, the engine's ladder and page geometry."""
    from mxnet_tpu import serving
    monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "6")
    monkeypatch.setenv("MXNET_SERVING_MAX_BATCH", "4")
    monkeypatch.setenv("MXNET_SERVING_BATCH_TIMEOUT_MS", "0.5")
    monkeypatch.setenv("MXNET_DECODE_KV_PAGE_SIZE", "8")
    assert engine.DispatchWindow().max_inflight == 6
    assert make_loop().engine_stats()["inflight_window"] == 6
    pred = serving.CompiledPredictor(make_net(), bucket_sizes=(1, 2, 4, 8))
    b = serving.DynamicBatcher(pred, start=False)
    assert b.max_batch == 4
    assert b._timeout_s == pytest.approx(0.5e-3)
    assert serving.PagedKVCache(1, 2, 16, num_pages=5).page_size == 8


def test_vmem_accessor_env_and_clamp(monkeypatch):
    assert kernels.vmem_tile_budget() == kernels.VMEM_TILE_BUDGET_BYTES
    monkeypatch.setenv("MXNET_VMEM_TILE_BUDGET", str(8 * MIB))
    assert kernels.vmem_tile_budget() == 8 * MIB
    # clamped to the scoped default above, to 64 KiB below
    monkeypatch.setenv("MXNET_VMEM_TILE_BUDGET", str(10**12))
    assert kernels.vmem_tile_budget() == kernels.VMEM_SCOPED_DEFAULT_BYTES
    monkeypatch.setenv("MXNET_VMEM_TILE_BUDGET", "1")
    assert kernels.vmem_tile_budget() == 64 * 1024


def _sizers():
    from mxnet_tpu.ops.attention import _head_group
    from mxnet_tpu.ops.kernels import norm as knorm
    from mxnet_tpu.ops.kernels import opt_update as kopt
    from mxnet_tpu.ops.kernels import rnn_scan as krnn
    return (kernels.vmem_tile_budget(),
            krnn._vmem_plan(64, 8, 4, 128, 4, False)[0],
            _head_group(8, 128, 128), knorm._budget_rows(128),
            kopt._block_rows_cap())


def test_vmem_budget_feeds_all_four_kernel_sizers(monkeypatch):
    """One accessor, four consumers: shrinking the budget shrinks the
    rnn timestep block, the attention head group, and the norm/opt
    row-block caps together."""
    big = _sizers()
    monkeypatch.setenv("MXNET_VMEM_TILE_BUDGET", str(64 * 1024))
    small = _sizers()
    assert small[0] < big[0]
    for b, s in zip(big[1:], small[1:]):
        assert s <= b
    assert small[3] < big[3] and small[4] < big[4]


def test_rnn_interpret_block_is_one_whatever_the_budget(monkeypatch):
    """The VMEM budget sizes the compiled-TPU timestep block but NOT
    the interpret parity tier, which stays at block 1 — that is what
    keeps the fp32 forward bit-identical to the scan reference: no
    knob can change the numbers the parity sweep pins."""
    from mxnet_tpu.ops.kernels import rnn_scan as krnn
    args = (64, 8, 4, 128, 4)           # seq, N, gates, Hp, itemsize
    auto = krnn._vmem_plan(*args, False)[0]
    monkeypatch.setenv("MXNET_VMEM_TILE_BUDGET", str(64 * 1024))
    assert krnn._vmem_plan(*args, False)[0] < auto
    assert krnn._vmem_plan(*args, True)[0] == 1
    monkeypatch.setenv("MXNET_VMEM_TILE_BUDGET", str(16 * MIB))
    assert krnn._vmem_plan(*args, False)[0] >= auto
    assert krnn._vmem_plan(*args, True)[0] == 1


def run_trajectory(monkeypatch, env, steps=6):
    """Loss trajectory of the canonical seeded TrainLoop with ``env``
    set (empty = shipped defaults)."""
    with monkeypatch.context() as m:
        for k, v in env.items():
            m.setenv(k, v)
        loop = make_loop()
        rs = onp.random.RandomState(0)
        x = mx.nd.array(rs.randn(BS, IN).astype("float32"))
        y = mx.nd.array(rs.randint(0, CLASSES, size=(BS,)).astype("int32"))
        losses = [loop.step(x, y) for _ in range(steps)]
        loop.synchronize()
        return [float(l._data.mean()) for l in losses]


def test_knobs_are_bit_exact_on_losses(monkeypatch):
    """Knobs change SPEED, never numerics: the window depth and the
    kernel budget at non-default values produce bit-identical loss
    trajectories (window parity pinned since PR 5)."""
    base = run_trajectory(monkeypatch, {})
    tuned = run_trajectory(monkeypatch, {
        "MXNET_INFLIGHT_STEPS": "4",
        "MXNET_VMEM_TILE_BUDGET": str(MIB)})
    assert tuned == base
    sync = run_trajectory(monkeypatch, {"MXNET_INFLIGHT_STEPS": "0"})
    assert sync == base
