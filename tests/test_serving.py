"""mx.serving — AOT-compiled predictor + dynamic batcher (docs/SERVING.md).

Pins the serving-engine contracts:

- shape-bucket quantization and AOT warmup (one compiled program per
  bucket, zero retraces under live traffic);
- fake-clock DynamicBatcher semantics: timeout flush, max-batch flush,
  idle/force flush, pad-to-bucket with valid-row masking;
- BIT-EXACT batched-vs-single outputs (a row's result must not depend
  on its batch-mates or the padding);
- pipelined-vs-sync parity (in-flight window 2 vs 0);
- the guarded zero-sync hot loop: under MXNET_TRANSFER_GUARD=raise the
  dispatch path performs NO unblessed host sync, and a forward that
  hides a host sync is flushed out as an error;
- bf16/int8 predictor variants through the AMP/quantization paths.
"""
import threading

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn

IN, HIDDEN, CLASSES = 16, 32, 4


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


# ---------------------------------------------------------------------------
# CompiledPredictor: buckets, AOT, retraces
# ---------------------------------------------------------------------------

def test_bucket_for_rounds_up(pred):
    assert pred.bucket_for(1) == 1
    assert pred.bucket_for(3) == 4
    assert pred.bucket_for(8) == 8
    with pytest.raises(MXNetError, match="largest shape bucket"):
        pred.bucket_for(9)


def test_pad_to_bucket_returns_mask(pred):
    x = mx.nd.array(rows(3))
    (padded,), valid = pred.pad_to_bucket(x)
    assert padded.shape == (4, IN) and valid == 3
    assert onp.asarray(padded.asnumpy()[3]).sum() == 0.0   # zero rows


def test_predict_returns_async_ndarray(pred):
    out = pred.predict(mx.nd.array(rows(1)))
    assert isinstance(out, mx.nd.NDArray)
    assert out.shape == (1, CLASSES)


def test_warmup_compiles_every_bucket_once(pred):
    flops = pred.warmup(mx.nd.array(rows(1)))
    assert set(flops) == {1, 2, 4, 8}
    assert pred.n_traces == 4
    # live traffic at every bucket: ZERO further retraces (the AOT
    # executables serve it)
    for n in (1, 2, 3, 4, 7, 8):
        padded, valid = pred.pad_to_bucket(mx.nd.array(rows(n)))
        out = pred.predict(*padded)
        assert out.shape[0] == pred.bucket_for(n)
    assert pred.n_traces == 4


def test_bucket_retrace_count_without_warmup(pred):
    # unwarmed: one trace per DISTINCT bucket, repeats are cache hits
    for n in (1, 1, 2, 2, 4, 1):
        padded, _ = pred.pad_to_bucket(mx.nd.array(rows(n)))
        pred.predict(*padded)
    assert pred.n_traces == 3


def test_predictor_requires_materialized_params():
    net = nn.HybridSequential()
    net.add(nn.Dense(4))      # no in_units, never forwarded: deferred
    net.initialize()
    with pytest.raises(MXNetError, match="materialized"):
        serving.CompiledPredictor(net)


# ---------------------------------------------------------------------------
# static-analysis gates on the serving program
# ---------------------------------------------------------------------------

def test_predict_program_analysis(pred):
    x = mx.nd.array(rows(4))
    report = pred.analyze(x)
    assert report.mode == "predict"
    assert report.ok, report.summary()
    assert not report.collectives.ops          # single-device forward
    assert report.host_transfers == []


def test_predict_memory_report(pred):
    x = mx.nd.array(rows(4))
    r = pred.memory_report(x)
    assert r is not None and r.peak_bytes > 0
    # no-arg merge covers the analyzed bucket
    merged = pred.memory_report()
    assert merged.peak_bytes >= r.peak_bytes


# ---------------------------------------------------------------------------
# DynamicBatcher: fake-clock semantics
# ---------------------------------------------------------------------------

def manual_batcher(pred, clk, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("timeout_ms", 5.0)
    return serving.DynamicBatcher(pred, start=False,
                                  clock=lambda: clk[0], **kw)


def test_fake_clock_timeout_flush(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)
    fut = b.submit(mx.nd.array(rows(1)))
    assert b.process_once() is False          # young and not full
    clk[0] = 0.0049
    assert b.process_once() is False          # still inside the window
    clk[0] = 0.0051
    assert b.process_once() is True           # oldest aged past 5 ms
    assert b.stats["flush_timeout"] == 1
    assert fut.result(10).shape == (1, CLASSES)
    b.close()


def test_fake_clock_max_batch_flush(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)
    futs = [b.submit(mx.nd.array(rows(1, seed=i))) for i in range(4)]
    # clock did NOT advance: the flush is size-triggered
    assert b.process_once() is True
    assert b.stats["flush_full"] == 1
    assert b.stats["rows"] == 4 and b.stats["padded_rows"] == 0
    for f in futs:
        assert f.result(10).shape == (1, CLASSES)
    b.close()


def test_fake_clock_force_flush_and_fill(pred):
    clk = [0.0]
    b = manual_batcher(pred, clk)
    fut = b.submit(mx.nd.array(rows(3)))
    assert b.process_once() is False
    assert b.process_once(force=True) is True
    assert b.stats["flush_force"] == 1
    # 3 valid rows dispatched in the 4-row bucket
    assert b.stats["rows"] == 3 and b.stats["padded_rows"] == 1
    assert b.batch_fill == pytest.approx(0.75)
    assert fut.result(10).shape == (3, CLASSES)
    b.close()


def test_process_once_empty_is_noop(pred):
    b = manual_batcher(pred, [0.0])
    assert b.process_once() is False
    assert b.process_once(force=True) is False
    b.close()


def test_oversized_request_rejected(pred):
    b = manual_batcher(pred, [0.0])
    with pytest.raises(MXNetError, match="max_batch"):
        b.submit(mx.nd.array(rows(5)))
    b.close()


def test_queue_backpressure(pred):
    b = manual_batcher(pred, [0.0], depth=1)
    b.submit(mx.nd.array(rows(1)))
    with pytest.raises(MXNetError, match="saturated"):
        b.submit(mx.nd.array(rows(1)), timeout=0.05)
    b.flush()
    b.close()


def test_future_timeout_message(pred):
    b = manual_batcher(pred, [0.0])
    fut = b.submit(mx.nd.array(rows(1)))
    with pytest.raises(MXNetError, match="not completed"):
        fut.result(0.01)
    b.flush()
    assert fut.result(10).shape == (1, CLASSES)
    b.close()


def test_dispatch_error_fails_futures(pred):
    pred.warmup(mx.nd.array(rows(1)), buckets=(1,))
    clk = [0.0]
    b = manual_batcher(pred, clk)
    # wrong feature width: the bucket trace fails at dispatch, and the
    # proven predictor must NOT silently demote to eager
    fut = b.submit(mx.nd.array(onp.zeros((1, IN + 3), "float32")))
    with pytest.raises(Exception):
        b.process_once(force=True)
    with pytest.raises(Exception):
        fut.result(10)
    b.close()


# ---------------------------------------------------------------------------
# batched-vs-single parity
# ---------------------------------------------------------------------------

def test_batched_bit_exact_vs_single(pred, rows_match):
    pred.warmup(mx.nd.array(rows(1)))
    X = rows(8, seed=3)
    singles = [pred.predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(8)]
    with serving.DynamicBatcher(pred, max_batch=8,
                                timeout_ms=20.0) as b:
        futs = [b.submit(mx.nd.array(X[i:i + 1])) for i in range(8)]
        batched = [f.result(30).asnumpy() for f in futs]
    for i in range(8):
        assert rows_match(batched[i], singles[i]), \
            f"row {i} differs between batched and single dispatch"


def test_pad_mask_parity_multi_row_request(pred, rows_match):
    # a 3-row request padded into the 4-bucket must return the
    # single-dispatch rows — padding never leaks into valid outputs
    pred.warmup(mx.nd.array(rows(1)))
    X = rows(3, seed=5)
    singles = [pred.predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(3)]
    with serving.DynamicBatcher(pred, max_batch=4,
                                timeout_ms=5.0) as b:
        out = b.submit(mx.nd.array(X)).result(30).asnumpy()
    assert out.shape == (3, CLASSES)
    for i in range(3):
        assert rows_match(out[i:i + 1], singles[i])


def test_pipelined_vs_sync_parity(pred):
    pred.warmup(mx.nd.array(rows(1)))
    X = rows(24, seed=9)

    def run(inflight):
        # every request fills a batch alone (4 rows, max_batch 4), so
        # both runs serve it through the bucket of 4 whatever the
        # linger and the machine's load do: one program, held to `==`
        with serving.DynamicBatcher(pred, max_batch=4, timeout_ms=2.0,
                                    inflight=inflight) as b:
            futs = [b.submit(mx.nd.array(X[i:i + 4]))
                    for i in range(0, 24, 4)]
            return [f.result(30).asnumpy() for f in futs]

    sync = run(0)       # window 0: every micro-batch retires eagerly
    piped = run(2)      # pipelined: host runs ahead of the device
    for a, c in zip(sync, piped):
        assert (a == c).all()


# ---------------------------------------------------------------------------
# guarded zero-sync hot loop
# ---------------------------------------------------------------------------

def test_guarded_serving_run_zero_unblessed_syncs(pred, monkeypatch):
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    pred.warmup(mx.nd.array(rows(1)))
    X = rows(8, seed=11)
    before = telemetry.value(telemetry.names.HOST_SYNCS,
                             "wait_to_read") or 0
    with serving.DynamicBatcher(pred, max_batch=8, timeout_ms=1.0) as b:
        futs = [b.submit(mx.nd.array(X[i:i + 1])) for i in range(8)]
        outs = [f.result(30) for f in futs]
    assert len(outs) == 8
    after = telemetry.value(telemetry.names.HOST_SYNCS,
                            "wait_to_read") or 0
    assert after - before == 0, \
        "serving hot loop performed an unblessed NDArray host sync"


def test_first_call_lowering_error_raises_untraceable_demotes():
    """CompiledPredictor demotes to the eager forward only for a forward
    that cannot be TRACED; one that traces but cannot lower or compile
    (a Pallas kernel compiled for real on a backend that only
    interprets — the shape of a Mosaic rejection on the chip) raises on
    the first call, from predict and from the AOT warmup alike."""
    from mxnet_tpu.gluon import HybridBlock
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops.kernels import norm as knorm

    class Unlowerable(HybridBlock):
        def forward(self, x):
            ones = jax.numpy.ones(x.shape[-1], x._data.dtype)
            return NDArray(knorm.layer_norm(x._data, ones, 0 * ones,
                                            interpret=False))

    class Untraceable(HybridBlock):
        def forward(self, x):
            return x * float(x.asnumpy().sum())

    x = mx.nd.array(rows(1))
    p = serving.CompiledPredictor(Unlowerable(), bucket_sizes=(1,))
    with pytest.raises(ValueError, match="interpret mode"):
        p.predict(x)
    assert p.mode == "fused"
    with pytest.raises(ValueError, match="interpret mode"):
        serving.CompiledPredictor(Unlowerable(),
                                  bucket_sizes=(1,)).warmup(x)

    p = serving.CompiledPredictor(Untraceable(), bucket_sizes=(1,))
    out = p.predict(x).asnumpy()
    assert p.mode == "eager"
    onp.testing.assert_allclose(out, rows(1) * rows(1).sum(), rtol=1e-6)


def test_guard_flushes_out_hidden_host_sync(monkeypatch):
    # a forward hiding a host materialization: the trace fails (tracer
    # has no concrete value), the eager fallback then trips the armed
    # transfer guard INSIDE the hot region instead of silently syncing
    # per request forever
    from mxnet_tpu.gluon import HybridBlock

    class Hostile(HybridBlock):
        def __init__(self):
            super().__init__()
            self.d = nn.Dense(4, in_units=IN)

        def forward(self, x):
            _ = x.asnumpy()            # the bug under test
            return self.d(x)

    net = Hostile()
    net.initialize()
    net(mx.nd.array(onp.zeros((1, IN), "float32")))
    p = serving.CompiledPredictor(net, bucket_sizes=(1,))
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    with pytest.raises(MXNetError, match="hot region"):
        p.predict(mx.nd.array(rows(1)))


# ---------------------------------------------------------------------------
# serving telemetry
# ---------------------------------------------------------------------------

def test_serving_metrics_flow(pred):
    reg = telemetry.registry()
    req0 = reg.value(telemetry.names.SERVING_REQUESTS) or 0
    bat0 = reg.value(telemetry.names.SERVING_BATCHES) or 0
    lat = reg.get(telemetry.names.SERVING_LATENCY)
    occ = reg.get(telemetry.names.SERVING_OCCUPANCY)
    lat0, occ0 = lat.count(), occ.count()
    with serving.DynamicBatcher(pred, max_batch=4, timeout_ms=1.0) as b:
        futs = [b.submit(mx.nd.array(rows(1, seed=i))) for i in range(6)]
        for f in futs:
            f.result(30)
    assert (reg.value(telemetry.names.SERVING_REQUESTS) or 0) - req0 == 6
    n_batches = (reg.value(telemetry.names.SERVING_BATCHES) or 0) - bat0
    assert n_batches >= 1
    assert lat.count() - lat0 == 6          # one latency per request
    assert occ.count() - occ0 == n_batches  # one occupancy per batch


# ---------------------------------------------------------------------------
# precision variants
# ---------------------------------------------------------------------------

def test_predictor_for_bf16_casts_params():
    net = make_net()
    p = serving.predictor_for(net, dtype="bf16", bucket_sizes=(1, 4))
    dtypes = {str(prm.data()._data.dtype)
              for prm in net.collect_params().values()}
    assert "bfloat16" in dtypes
    out = p.predict(mx.nd.array(rows(1)))
    assert out.shape == (1, CLASSES)


def test_predictor_for_int8_needs_calib():
    with pytest.raises(MXNetError, match="calib_data"):
        serving.predictor_for(make_net(), dtype="int8")


def test_predictor_for_int8_served_outputs_close():
    X = rows(32, seed=13)
    net = make_net()
    f32 = serving.CompiledPredictor(net, bucket_sizes=(1, 8))
    ref = f32.predict(mx.nd.array(X[:8])).asnumpy()
    # quantize the SAME net in place (the reference conversion
    # contract) and serve the int8 variant through the batcher
    calib = [mx.nd.array(X[i:i + 8]) for i in range(0, 32, 8)]
    p8 = serving.predictor_for(net, dtype="int8", calib_data=calib,
                               bucket_sizes=(1, 8))
    assert any(type(b).__name__ == "QuantizedDense" for b in net)
    with serving.DynamicBatcher(p8, max_batch=8, timeout_ms=5.0) as b:
        out = b.submit(mx.nd.array(X[:8])).result(30).asnumpy()
    # int8 quantization error is bounded, ranks mostly preserved
    assert out.shape == ref.shape
    assert onp.abs(out - ref).max() < 0.5
    agree = (out.argmax(1) == ref.argmax(1)).mean()
    assert agree >= 0.75


def test_predictor_for_unknown_dtype():
    with pytest.raises(MXNetError, match="unknown serving dtype"):
        serving.predictor_for(make_net(), dtype="fp8")


# ---------------------------------------------------------------------------
# load generator
# ---------------------------------------------------------------------------

def test_loadgen_percentiles_exact():
    from mxnet_tpu.serving import loadgen
    lat = [0.001 * i for i in range(1, 101)]     # 1..100 ms
    p = loadgen.percentiles(lat)
    assert p["p50_ms"] == pytest.approx(50.5, abs=1.0)
    assert p["p99_ms"] == pytest.approx(99.01, abs=1.0)
    assert loadgen.percentiles([])["p50_ms"] is None


def test_loadgen_closed_loop_counts():
    from mxnet_tpu.serving import loadgen
    seen = []
    rep = loadgen.run_closed_loop(lambda i: seen.append(i),
                                  concurrency=4, requests=40)
    assert rep["requests"] == 40 and rep["errors"] == 0
    assert len(seen) == 40 and rep["qps"] > 0
    assert rep["p50_ms"] is not None


def test_loadgen_open_loop_completes():
    from mxnet_tpu.serving import loadgen
    done = []

    def submit(i):
        return lambda *_: done.append(i)

    rep = loadgen.run_open_loop(submit, rate_qps=2000.0, requests=32)
    assert rep["requests"] == 32 and rep["errors"] == 0
    assert len(done) == 32


def test_loadgen_counts_errors():
    from mxnet_tpu.serving import loadgen

    def issue(i):
        if i % 2:
            raise RuntimeError("boom")

    rep = loadgen.run_closed_loop(issue, concurrency=2, requests=10)
    assert rep["errors"] == 5 and rep["requests"] == 5


# ---------------------------------------------------------------------------
# end-to-end: concurrent clients through the threaded batcher
# ---------------------------------------------------------------------------

def test_concurrent_clients_all_served(pred, rows_match):
    pred.warmup(mx.nd.array(rows(1)))
    X = rows(24, seed=17)
    singles = [pred.predict(mx.nd.array(X[i:i + 1])).asnumpy()
               for i in range(24)]
    results = [None] * 24
    with serving.DynamicBatcher(pred, max_batch=8, timeout_ms=2.0) as b:
        def client(i):
            results[i] = b.submit(
                mx.nd.array(X[i:i + 1])).result(30).asnumpy()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i in range(24):
        assert rows_match(results[i], singles[i])
    assert pred.n_traces == 4       # buckets only, never per-request
