"""Aux subsystems: profiler, AMP, runtime features, custom ops, control flow.

Reference analogs: tests/python/unittest/{test_profiler.py, test_operator.py
control-flow sections, test_contrib_amp-style checks}.
"""
import json
import os

import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------

def test_profiler_chrome_trace(tmp_path):
    fname = str(tmp_path / "trace.json")
    mx.profiler.set_config(filename=fname, aggregate_stats=True)
    mx.profiler.set_state("run")
    a = mx.nd.ones((8, 8))
    b = (a * 2 + 1).sum()
    b.wait_to_read()
    mx.profiler.set_state("stop")
    table = mx.profiler.dumps()
    assert "Calls" in table and len(table.splitlines()) > 1
    mx.profiler.dump()
    with open(fname) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert events and all(e["ph"] == "X" for e in events)
    names = {e["name"] for e in events}
    assert any("mul" in n or "add" in n or "sum" in n for n in names), names


def test_per_op_device_attribution_name_stack():
    """Framework op names must flow into the XLA name stack (via
    jax.named_scope in the invoke funnel) so XProf device traces attribute
    kernels inside a jitted CachedOp back to framework ops — the analog of
    the reference's __profiler_scope__/ProfileOperator device annotation
    (src/profiler/profiler.h:251-299)."""
    import jax
    from mxnet_tpu.ndarray.ndarray import NDArray

    def f(x):
        a = NDArray(x)
        b = mx.nd.add(a, a)
        return mx.nd.sigmoid(b)._data

    jaxpr = jax.make_jaxpr(f)(jnp.ones((2, 2)))
    stacks = [str(e.source_info.name_stack) for e in jaxpr.eqns]
    assert any("add" in s for s in stacks), stacks
    assert any("sigmoid" in s for s in stacks), stacks
    # a Gluon block traced inside jit funnels per-op through invoke_raw the
    # same way, so a cached computation carries per-op scopes for every layer
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4, in_units=3, activation="relu")
    net.initialize()

    def g(xj):
        return net(NDArray(xj))._data

    stacks = [str(e.source_info.name_stack)
              for e in jax.make_jaxpr(g)(jnp.ones((2, 3))).eqns]
    assert any("fully_connected" in s for s in stacks), stacks
    assert any("activation" in s for s in stacks), stacks


def test_profiler_scope_and_pause(tmp_path):
    fname = str(tmp_path / "trace2.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.set_state("run")
    with mx.profiler.scope("blockA"):
        (mx.nd.ones((4,)) + 1).wait_to_read()
    mx.profiler.pause()
    (mx.nd.ones((4,)) * 3).wait_to_read()
    mx.profiler.resume()
    mx.profiler.set_state("stop")
    mx.profiler.dump()
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    assert any(e["name"].startswith("blockA:") for e in events)
    assert not any("mul" in e["name"] for e in events)  # paused op absent


# ---------------------------------------------------------------------------
# AMP
# ---------------------------------------------------------------------------

def test_amp_matmul_runs_bf16():
    from mxnet_tpu import amp
    amp.init("bfloat16")
    try:
        assert amp.is_enabled()
        a = mx.nd.ones((4, 8))
        b = mx.nd.ones((8, 4))
        out = mx.nd.dot(a, b)
        # f32 in, bf16 OUT: the low dtype flows between MXU ops (reference
        # FP16_FUNCS semantics) so activations stay half-width in HBM
        assert onp.dtype(out.dtype).name == "bfloat16", out.dtype
        onp.testing.assert_allclose(out.asnumpy().astype("float32"),
                                    8 * onp.ones((4, 4)))
        # f32-pinned op casts UP: bf16 in, f32 out
        s = mx.nd.softmax(out)
        assert s.dtype == onp.float32
        # f32 input to a pinned op stays f32
        s2 = mx.nd.softmax(mx.nd.ones((2, 3)))
        assert s2.dtype == onp.float32
    finally:
        amp.uninit()
    assert not amp.is_enabled()


def test_amp_training_converges():
    from mxnet_tpu import amp
    from mxnet_tpu.gluon import nn
    amp.init("bfloat16")
    try:
        net = nn.Dense(1, in_units=4)
        net.initialize()
        tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.1})
        amp.init_trainer(tr)
        rng = onp.random.RandomState(0)
        x = mx.nd.array(rng.randn(64, 4).astype("float32"))
        w_true = onp.array([[1.0, -2.0, 0.5, 3.0]], "float32")
        y = mx.nd.array(rng.randn(64, 4).astype("float32").dot(w_true.T) * 0)
        y = mx.nd.array(x.asnumpy().dot(w_true.T))
        losses = []
        for _ in range(30):
            with mx.autograd.record():
                out = net(x)
                loss = ((out - y) ** 2).mean()
            with amp.scale_loss(loss, tr) as scaled:
                scaled.backward()
            tr.step(1)
            losses.append(float(loss.asnumpy()))
        assert losses[-1] < losses[0] * 0.2, losses[::10]
    finally:
        amp.uninit()


def test_amp_convert_hybrid_block():
    from mxnet_tpu import amp
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.BatchNorm(in_channels=8),
            nn.Dense(2, in_units=8))
    net.initialize()
    x = mx.nd.ones((2, 4))
    net(x)
    amp.convert_hybrid_block(net, "bfloat16")
    dtypes = {p.name: p.dtype for p in net.collect_params().values()}
    dense_dtypes = [d for n, d in dtypes.items() if "batchnorm" not in n.lower()
                    and "gamma" not in n and "beta" not in n
                    and "running" not in n]
    assert all(str(d) == "bfloat16" for d in dense_dtypes), dtypes


def test_loss_scaler_dynamics():
    from mxnet_tpu.amp import LossScaler
    s = LossScaler(init_scale=1024., scale_window=2)
    s.update_scale(overflow=True)
    assert s.loss_scale == 512.
    s.update_scale(False)
    s.update_scale(False)
    assert s.loss_scale == 1024.
    assert s.has_overflow([mx.nd.array(onp.array([onp.inf]))])
    assert not s.has_overflow([mx.nd.array(onp.array([1.0]))])


# ---------------------------------------------------------------------------
# runtime features
# ---------------------------------------------------------------------------

def test_runtime_feature_list():
    feats = mx.runtime.Features()
    assert feats.is_enabled("XLA") and feats.is_enabled("PALLAS")
    assert not feats.is_enabled("CUDA")
    fl = mx.runtime.feature_list()
    assert any(f.name == "RECORDIO" and f.enabled for f in fl)


# ---------------------------------------------------------------------------
# custom ops (mx.operator)
# ---------------------------------------------------------------------------

def test_custom_op_forward_backward():
    import mxnet_tpu.operator as mxop

    @mxop.register("mysquare")
    class SquareProp(mxop.CustomOpProp):
        def create_operator(self, ctx, in_shapes, in_dtypes):
            class Square(mxop.CustomOp):
                def forward(self, is_train, req, in_data, out_data, aux):
                    self.assign(out_data[0], req[0], in_data[0] * in_data[0])

                def backward(self, req, out_grad, in_data, out_data,
                             in_grad, aux):
                    self.assign(in_grad[0], req[0],
                                2 * in_data[0] * out_grad[0])
            return Square()

    x = mx.nd.array(onp.array([1., 2., 3.], "float32"))
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.Custom(x, op_type="mysquare")
        loss = y.sum()
    loss.backward()
    onp.testing.assert_allclose(y.asnumpy(), [1., 4., 9.])
    onp.testing.assert_allclose(x.grad.asnumpy(), [2., 4., 6.])


def test_custom_op_unregistered_errors():
    with pytest.raises(MXNetError, match="not registered"):
        mx.nd.Custom(mx.nd.ones((2,)), op_type="nope")


# ---------------------------------------------------------------------------
# control flow ops
# ---------------------------------------------------------------------------

def test_foreach_cumsum_and_grad():
    from mxnet_tpu.ndarray import contrib
    data = mx.nd.array(onp.arange(6, dtype="float32").reshape(6, 1))
    init = mx.nd.zeros((1,))
    init.attach_grad()
    with mx.autograd.record():
        outs, final = contrib.foreach(
            lambda x, st: (x + st[0], [x + st[0]]), data, [init])
        loss = outs.sum()
    loss.backward()
    onp.testing.assert_allclose(
        outs.asnumpy().ravel(), onp.cumsum(onp.arange(6.)))
    assert float(init.grad.asnumpy()) == 6.0  # d(sum cumsum)/d(init)


def test_while_loop():
    from mxnet_tpu.ndarray import contrib
    # double until > 100
    outs, states = contrib.while_loop(
        cond=lambda i, x: (x < 100).sum(),
        func=lambda i, x: (i, [i + 1, x * 2]),
        loop_vars=[mx.nd.zeros((1,)), mx.nd.ones((1,))],
        max_iterations=20)
    assert float(states[1].asnumpy()) == 128.0
    assert float(states[0].asnumpy()) == 7.0


def test_cond():
    from mxnet_tpu.ndarray import contrib
    x = mx.nd.array(onp.array([3.0], "float32"))
    out = contrib.cond(x.sum() > 2, lambda: x * 10, lambda: x - 1)
    out = out[0] if isinstance(out, (list, tuple)) else out
    onp.testing.assert_allclose(out.asnumpy(), [30.0])


# ---------------------------------------------------------------------------
# detection ops
# ---------------------------------------------------------------------------

def test_box_iou():
    from mxnet_tpu.ndarray import contrib
    a = mx.nd.array(onp.array([[0, 0, 2, 2], [1, 1, 3, 3]], "float32"))
    b = mx.nd.array(onp.array([[0, 0, 2, 2]], "float32"))
    iou = contrib.box_iou(a, b).asnumpy()
    onp.testing.assert_allclose(iou[:, 0], [1.0, 1.0 / 7.0], rtol=1e-5)


def test_box_nms():
    from mxnet_tpu.ndarray import contrib
    # [id, score, x1, y1, x2, y2]
    boxes = onp.array([
        [0, 0.9, 0, 0, 10, 10],
        [0, 0.8, 1, 1, 11, 11],    # big overlap with first -> suppressed
        [0, 0.7, 20, 20, 30, 30],  # far away -> kept
        [1, 0.6, 0, 0, 10, 10],    # other class -> kept
        [0, 0.0, 0, 0, 1, 1],      # below valid_thresh -> dropped
    ], "float32")
    out = contrib.box_nms(mx.nd.array(boxes), overlap_thresh=0.5,
                          valid_thresh=0.1, id_index=0).asnumpy()
    kept = out[out[:, 1] > 0]
    onp.testing.assert_allclose(sorted(kept[:, 1].tolist()),
                                [0.6, 0.7, 0.9], rtol=1e-6)


def test_roi_align():
    from mxnet_tpu.ndarray import contrib
    # constant image -> pooled output equals the constant
    data = mx.nd.ones((1, 2, 16, 16)) * 5.0
    rois = mx.nd.array(onp.array([[0, 2, 2, 10, 10]], "float32"))
    out = contrib.ROIAlign(data, rois, pooled_size=(4, 4), spatial_scale=1.0)
    assert out.shape == (1, 2, 4, 4)
    onp.testing.assert_allclose(out.asnumpy(), 5.0 * onp.ones((1, 2, 4, 4)),
                                rtol=1e-5)
    # gradient flows to data
    d = mx.nd.ones((1, 1, 8, 8))
    d.attach_grad()
    with mx.autograd.record():
        o = contrib.ROIAlign(d, mx.nd.array(onp.array([[0, 0, 0, 7, 7]],
                                                      "float32")),
                             pooled_size=2, spatial_scale=1.0)
        s = o.sum()
    s.backward()
    assert float(d.grad.asnumpy().sum()) > 0


def test_roi_align_padded_and_ps():
    from mxnet_tpu.ndarray import contrib
    data = mx.nd.ones((2, 8, 6, 6))
    # padded ROI (batch_idx -1) must be all zeros
    rois = mx.nd.array(onp.array([[0, 0, 0, 5, 5], [-1, 0, 0, 5, 5]],
                                 "float32"))
    out = contrib.ROIAlign(data, rois, pooled_size=2, spatial_scale=1.0)
    onp.testing.assert_allclose(out.asnumpy()[0], onp.ones((8, 2, 2)),
                                rtol=1e-5)
    onp.testing.assert_allclose(out.asnumpy()[1], onp.zeros((8, 2, 2)))
    # position-sensitive: C=8, PH*PW=4 -> out channel dim 2
    ps = contrib.ROIAlign(data, rois, pooled_size=2, spatial_scale=1.0,
                          position_sensitive=True)
    assert ps.shape == (2, 2, 2, 2)
    # adaptive sampling path (sample_ratio<=0) runs
    ad = contrib.ROIAlign(data, rois, pooled_size=2, spatial_scale=1.0,
                          sample_ratio=-1)
    onp.testing.assert_allclose(ad.asnumpy()[0], onp.ones((8, 2, 2)),
                                rtol=1e-5)


def test_box_nms_out_format():
    from mxnet_tpu.ndarray import contrib
    center = onp.array([[0, 0.9, 5, 5, 10, 10]], "float32")  # cx,cy,w,h
    out = contrib.box_nms(mx.nd.array(center), in_format="center",
                          out_format="corner").asnumpy()
    onp.testing.assert_allclose(out[0, 2:], [0, 0, 10, 10], rtol=1e-5)


def test_multibox_prior():
    from mxnet_tpu.ndarray import contrib
    data = mx.nd.ones((1, 8, 4, 4))
    anchors = contrib.MultiBoxPrior(data, sizes=(0.5, 0.25),
                                    ratios=(1.0, 2.0))
    # num_anchors = 2 + 2 - 1 = 3 per position
    assert anchors.shape == (1, 4 * 4 * 3, 4)
    a = anchors.asnumpy()[0]
    # first anchor at (0,0): center (0.125, 0.125), size 0.5
    onp.testing.assert_allclose(a[0], [0.125 - 0.25, 0.125 - 0.25,
                                       0.125 + 0.25, 0.125 + 0.25],
                                rtol=1e-5)
    # width/height of ratio-2 anchor: w = 0.5*sqrt(2), h = 0.5/sqrt(2)
    w = a[2, 2] - a[2, 0]
    h = a[2, 3] - a[2, 1]
    onp.testing.assert_allclose(w / h, 2.0, rtol=1e-5)


def test_multibox_target_and_detection_roundtrip():
    from mxnet_tpu.ndarray import contrib
    # 4 hand-built anchors; one gt box aligned with anchor 1
    anchors = onp.array([[0.0, 0.0, 0.3, 0.3],
                         [0.3, 0.3, 0.7, 0.7],
                         [0.6, 0.6, 1.0, 1.0],
                         [0.0, 0.6, 0.4, 1.0]], "float32")[None]
    gt = onp.array([[[1.0, 0.32, 0.28, 0.72, 0.68]]], "float32")  # cls 1
    cls_pred = onp.zeros((1, 3, 4), "float32")
    bt, mask, ct = contrib.MultiBoxTarget(
        mx.nd.array(anchors), mx.nd.array(gt), mx.nd.array(cls_pred))
    ct = ct.asnumpy()[0]
    assert ct[1] == 2.0          # gt cls 1 -> target 2 (0 is background)
    assert ct[0] == 0.0 and ct[2] == 0.0
    mask = mask.asnumpy().reshape(4, 4)
    assert mask[1].sum() == 4 and mask[0].sum() == 0

    # decode: feed perfect loc targets back -> recovered gt box
    bt = bt.asnumpy().reshape(1, -1)
    cls_prob = onp.zeros((1, 3, 4), "float32")
    cls_prob[0, 1, 1] = 0.9      # class 0 (fg) on anchor 1
    out = contrib.MultiBoxDetection(
        mx.nd.array(cls_prob), mx.nd.array(bt), mx.nd.array(anchors),
        threshold=0.5).asnumpy()[0]
    kept = out[out[:, 1] > 0]
    assert len(kept) == 1
    onp.testing.assert_allclose(kept[0, 2:], gt[0, 0, 1:], atol=1e-5)


def test_multibox_target_padded_labels_keep_forced_match():
    from mxnet_tpu.ndarray import contrib
    # low-IoU gt (only force-match applies) + a padding row whose argmax
    # would collide with the real gt's best anchor
    anchors = onp.array([[0.0, 0.0, 0.4, 0.4],
                         [0.5, 0.5, 0.9, 0.9]], "float32")[None]
    labels = onp.array([[[1.0, 0.0, 0.0, 0.2, 0.2],
                         [-1.0, 0.0, 0.0, 0.0, 0.0]]], "float32")
    cls_pred = onp.zeros((1, 3, 2), "float32")
    bt, mask, ct = contrib.MultiBoxTarget(
        mx.nd.array(anchors), mx.nd.array(labels), mx.nd.array(cls_pred))
    ct = ct.asnumpy()[0]
    assert ct[0] == 2.0, ct      # forced match survives the padding row
    assert mask.asnumpy().reshape(2, 4)[0].sum() == 4


def test_multibox_target_negative_mining_thresh():
    from mxnet_tpu.ndarray import contrib
    anchors = onp.array([[0.0, 0.0, 0.4, 0.4],     # matched (forced)
                         [0.02, 0.02, 0.42, 0.42],  # near-miss IoU>0.4
                         [0.6, 0.6, 0.9, 0.9]], "float32")[None]
    labels = onp.array([[[0.0, 0.0, 0.0, 0.4, 0.4]]], "float32")
    cls_pred = onp.zeros((1, 2, 3), "float32")
    _, _, ct = contrib.MultiBoxTarget(
        mx.nd.array(anchors), mx.nd.array(labels), mx.nd.array(cls_pred),
        overlap_threshold=0.9, negative_mining_ratio=1.0,
        negative_mining_thresh=0.4)
    ct = ct.asnumpy()[0]
    assert ct[0] == 1.0          # positive
    assert ct[1] == -1.0         # near-miss: excluded from negatives
    assert ct[2] == 0.0          # true negative kept


def test_profiler_memory_dump_and_summary(tmp_path):
    """Storage-profiler parity (reference src/profiler/storage_profiler.cc):
    pprof-format device memory snapshot + live-byte summary."""
    live = mx.nd.ones((512, 512))  # keep a buffer alive for the snapshot
    live.wait_to_read()
    p = mx.profiler.dump_memory(str(tmp_path / "mem.pprof"))
    assert os.path.getsize(p) > 0
    summary = mx.profiler.memory_summary()
    # routed through the telemetry catalog (mx_mem_device_* gauges):
    # every device reports, with its accounting source named —
    # allocator counters where the PjRt client has them, the documented
    # live-array fallback (XLA:CPU) otherwise — never silent Nones
    assert summary
    for dev, stats in summary.items():
        assert set(stats) == {"bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit", "source"}
        assert stats["source"] in ("allocator", "live_arrays")
        assert stats["bytes_in_use"] is not None
    # the live buffer above shows up somewhere (it sits on ONE of the
    # virtual mesh's devices; the others legitimately report 0)
    assert sum(s["bytes_in_use"] for s in summary.values()) > 0
    del live
