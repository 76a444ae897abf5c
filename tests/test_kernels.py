"""Pallas kernel layer (ops/kernels): dispatch gate, interpret-mode
parity sweep, grid-edge cases, fused optimizer bit-exactness, and the
guarded pipelined acceptance run.

The interpret tier (`pl.pallas_call(interpret=True)`) executes the
kernel BODIES as plain XLA ops on CPU — tier-1 exercises the kernels,
not just the XLA fallback. Parity contract (docs/PERF_NOTES.md
"Pallas kernel layer"): fp32 forwards are BIT-exact vs the references
for lane-aligned shapes; GRU/vanilla scan backwards and the optimizer
kernels are bit-exact too; the LSTM scan and norm backwards sit
within a few ulps (LLVM fp-contraction forms FMAs at different points
in structurally different programs); padded (unaligned) shapes get
tolerance-level parity because their reductions reassociate.
"""
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.analysis import guard as tguard
from mxnet_tpu.gluon import Trainer, TrainLoop, nn, rnn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.ops import kernels as K
from mxnet_tpu.ops import rnn as rnn_ops
from mxnet_tpu.ops.kernels import norm as knorm
from mxnet_tpu.ops.kernels import opt_update as kopt
from mxnet_tpu.ops.kernels import rnn_scan as krnn
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.telemetry import names as tnames

GATES = {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}


def _rnn_args(mode, T=7, N=8, H=128, dtype="float32", seed=0):
    g = GATES[mode]
    r = onp.random.RandomState(seed)
    xw = jnp.asarray((r.randn(T, N, g * H) * 0.5).astype(dtype))
    h0 = jnp.asarray((r.randn(N, H) * 0.5).astype(dtype))
    c0 = jnp.asarray((r.randn(N, H) * 0.5).astype(dtype)) \
        if mode == "lstm" else None
    w = jnp.asarray((r.randn(g * H, H) * 0.3).astype(dtype))
    b = jnp.asarray((r.randn(g * H) * 0.1).astype(dtype))
    return xw, h0, c0, w, b


def _grads(fn, mode, rev, args):
    def loss(xw, h0, c0, w, b):
        ys, h, c = fn(xw, h0, c0, w, b, mode, reverse=rev)
        s = jnp.sum(ys * 0.3) + jnp.sum(h * 1.3)
        if c is not None:
            s = s + jnp.sum(c * 0.7)
        return s
    argn = (0, 1, 2, 3, 4) if mode == "lstm" else (0, 1, 3, 4)
    return jax.grad(loss, argnums=argn)(*args)


# ---------------------------------------------------------------------------
# dispatch gate
# ---------------------------------------------------------------------------

def test_pallas_mode_parsing(monkeypatch):
    for raw, want in (("", "auto"), ("auto", "auto"), ("1", "on"),
                      ("ON", "on"), ("force", "on"), ("0", "off"),
                      ("off", "off"), ("garbage", "auto")):
        monkeypatch.setenv("MXNET_PALLAS", raw)
        assert K.pallas_mode() == want
    monkeypatch.delenv("MXNET_PALLAS")
    assert K.pallas_mode() == "auto"


def test_dispatch_tiers_on_cpu(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "off")
    assert K.dispatch("rnn_scan")[0] == "xla"
    monkeypatch.setenv("MXNET_PALLAS", "auto")
    path, reason = K.dispatch("rnn_scan")
    assert path == "xla" and "non-TPU" in reason
    monkeypatch.setenv("MXNET_PALLAS", "on")
    path, reason = K.dispatch("rnn_scan")
    assert path == "interpret" and "interpret" in reason
    # unsupported cases force the XLA tier with the caller's reason
    path, reason = K.dispatch("rnn_scan", supported=False,
                              reason="f64 not kernelized")
    assert path == "xla" and reason == "f64 not kernelized"
    assert K.decisions()["rnn_scan"] == (path, reason)


def test_tpu_tier_leaves_gspmd_partitioned_programs(monkeypatch):
    """Mosaic lowers into a single-device program or a shard_map body,
    never into one GSPMD partitions ("Mosaic kernels cannot be
    automatically partitioned", four v5e chips, PR 21): on a TPU the
    gate answers a trace under an active multi-device mesh with the XLA
    tier and says why; inside shard_map, and with no mesh, it compiles."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import shard_map
    monkeypatch.delenv("MXNET_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert K.dispatch("layernorm")[0] == "pallas"
    seen = []
    with make_mesh({"dp": 4}, jax.devices()[:4]) as mesh:
        path, reason = K.dispatch("layernorm")
        assert path == "xla" and "GSPMD" in reason and "4 devices" in reason

        def body(x):
            seen.append(K.dispatch("layernorm")[0])
            return x

        jax.jit(shard_map(body, mesh, (P("dp"),), P("dp"))).trace(
            jnp.zeros(8))
    assert seen == ["pallas"]
    with make_mesh({"dp": 1}, jax.devices()[:1]):
        assert K.dispatch("layernorm")[0] == "pallas"


def test_dispatch_covers_all_kernels(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "on")
    assert {K.dispatch(k)[0] for k in K.KERNELS} == {"interpret"}
    assert set(K.decisions()) >= set(K.KERNELS)
    monkeypatch.setenv("MXNET_PALLAS", "off")
    assert {K.dispatch(k)[0] for k in K.KERNELS} == {"xla"}


def test_dispatch_counts_in_telemetry(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "on")
    before = telemetry.value(tnames.KERNEL_DISPATCH, "interpret") or 0
    K.dispatch("layernorm")
    after = telemetry.value(tnames.KERNEL_DISPATCH, "interpret")
    assert after == before + 1


def test_scan_supported_reasons():
    xw, h0, c0, w, b = _rnn_args("lstm", T=3, N=4, H=16)
    assert krnn.scan_supported(xw, h0, c0, "lstm") is None
    assert "mode" in krnn.scan_supported(xw, h0, c0, "nope")
    assert "dtype" in krnn.scan_supported(
        xw.astype(jnp.float16), h0, c0, "lstm")


# ---------------------------------------------------------------------------
# RNN scan kernel: interpret-mode parity sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["lstm", "gru", "rnn_tanh", "rnn_relu"])
@pytest.mark.parametrize("rev", [False, True])
def test_scan_fwd_bit_exact_f32(monkeypatch, mode, rev):
    """fp32 forward is BIT-identical to the lax.scan reference (lane-
    aligned shapes) — ys, h_T and c_T."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    args = _rnn_args(mode)
    ys_r, h_r, c_r = rnn_ops.scan_reference(*args, mode, reverse=rev)
    ys_k, h_k, c_k = krnn.rnn_scan(*args, mode, reverse=rev)
    assert bool((ys_r == ys_k).all())
    assert bool((h_r == h_k).all())
    assert (c_r is None) == (c_k is None)
    if c_r is not None:
        assert bool((c_r == c_k).all())


@pytest.mark.parametrize("mode", ["gru", "rnn_tanh", "rnn_relu"])
@pytest.mark.parametrize("rev", [False, True])
def test_scan_bwd_bit_exact_f32(monkeypatch, mode, rev):
    """The vanilla backward is bit-identical too (the cotangent chain
    mirrors the scan transpose op for op). The GRU's is two differently
    structured programs with products to contract: whether LLVM fuses a
    multiply into the add that follows depends on the structure of the
    program around it, and each of the seven steps of the recurrence
    carries such a rounding into the next. Measured per gradient (PR 30,
    this XLA:CPU; bit-exact on the jax this test was written on), in
    units of ``2**-23 * max|reference|``: 4.9 to 6.5 forward in time,
    3.9 to 10.1 reversed (at most 1.34e-5 on gradients up to 28.5). Held
    to 16 of that unit, 1.9e-6 of the largest gradient, with no relative
    part: a term of the cotangent chain wrong at 1e-5 fails it."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    args = _rnn_args(mode)
    gr = _grads(rnn_ops.scan_reference, mode, rev, args)
    gk = _grads(krnn.rnn_scan, mode, rev, args)
    for a, b in zip(gr, gk):
        if mode == "gru":
            a = onp.asarray(a)
            onp.testing.assert_allclose(
                onp.asarray(b), a, rtol=0,
                atol=16 * 2.0 ** -23 * float(onp.abs(a).max()))
        else:
            assert bool((a == b).all())


@pytest.mark.parametrize("rev", [False, True])
def test_scan_bwd_lstm_ulp_parity(monkeypatch, rev):
    """The LSTM backward mirrors the scan transpose expression for
    expression, but LLVM fp-contraction differs across program
    structures — a few ulps, never more (docs/PERF_NOTES.md)."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    args = _rnn_args("lstm")
    gr = _grads(rnn_ops.scan_reference, "lstm", rev, args)
    gk = _grads(krnn.rnn_scan, "lstm", rev, args)
    for a, b in zip(gr, gk):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-4, atol=5e-5)


@pytest.mark.pallas
@pytest.mark.parametrize("mode", ["lstm", "gru"])
@pytest.mark.parametrize("shape", [(5, 6, 50), (9, 3, 130)])
def test_scan_grid_edge_unaligned(monkeypatch, mode, shape):
    """Hidden not a multiple of the 128-lane width / batch off the
    sublane tile: the padded h2h dot contracts over extra zero lanes,
    so its reduction may reassociate — tolerance-level parity."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    T, N, H = shape
    args = _rnn_args(mode, T=T, N=N, H=H)
    ys_r, h_r, c_r = rnn_ops.scan_reference(*args, mode)
    ys_k, h_k, c_k = krnn.rnn_scan(*args, mode)
    onp.testing.assert_allclose(onp.asarray(ys_r), onp.asarray(ys_k),
                                rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(onp.asarray(h_r), onp.asarray(h_k),
                                rtol=1e-4, atol=1e-5)
    gr = _grads(rnn_ops.scan_reference, mode, False, args)
    gk = _grads(krnn.rnn_scan, mode, False, args)
    for a, b in zip(gr, gk):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-4, atol=5e-5)


@pytest.mark.pallas
@pytest.mark.parametrize("mode,T", [("lstm", 10), ("gru", 10),
                                    ("lstm", 3), ("gru", 3)])
def test_scan_grid_edge_block_t(monkeypatch, mode, T):
    """Multi-timestep blocks with seq not divisible by (or smaller
    than) the block: the padded tail must contribute exact zeros."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    monkeypatch.setattr(krnn, "_FORCE_BLOCK_T", 4)
    args = _rnn_args(mode, T=T)
    ys_r, h_r, c_r = rnn_ops.scan_reference(*args, mode)
    ys_k, h_k, c_k = krnn.rnn_scan(*args, mode)
    onp.testing.assert_allclose(onp.asarray(ys_r), onp.asarray(ys_k),
                                rtol=1e-5, atol=1e-5)
    gr = _grads(rnn_ops.scan_reference, mode, False, args)
    gk = _grads(krnn.rnn_scan, mode, False, args)
    for a, b in zip(gr, gk):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-4, atol=5e-5)


@pytest.mark.parametrize("mode", ["lstm", "gru"])
def test_scan_bf16_tolerance(monkeypatch, mode):
    """bf16 operands: the kernel accumulates its dots and runs the gate
    math in f32 (Mosaic takes no narrower accumulator) and rounds once,
    on store; the lax.scan reference rounds every op to bf16. So the two
    no longer agree bit for bit — the pin is that the kernel is never
    FURTHER from the f32 oracle (same inputs upcast) than the all-bf16
    reference is, forward and backward."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    args = _rnn_args(mode, dtype="bfloat16")
    oracle = tuple(None if a is None else a.astype(jnp.float32)
                   for a in args)

    def err(got, want):
        return float(onp.abs(onp.asarray(got, onp.float32)
                             - onp.asarray(want)).max())

    ys_o = rnn_ops.scan_reference(*oracle, mode)[0]
    ys_r = rnn_ops.scan_reference(*args, mode)[0]
    ys_k = krnn.rnn_scan(*args, mode)[0]
    assert ys_k.dtype == jnp.bfloat16
    assert err(ys_k, ys_o) <= err(ys_r, ys_o)
    go = _grads(rnn_ops.scan_reference, mode, False, oracle)
    gr = _grads(rnn_ops.scan_reference, mode, False, args)
    gk = _grads(krnn.rnn_scan, mode, False, args)
    for o, r, k in zip(go, gr, gk):
        assert k.dtype == jnp.bfloat16
        assert err(k, o) <= err(r, o)


def test_fused_rnn_layer_parity_through_gate(monkeypatch):
    """The gluon LSTM layer end to end: MXNET_PALLAS=on output equals
    the off (reference) output bit for bit at aligned dims. One net —
    the dispatch decision is read per call."""
    r = onp.random.RandomState(0)
    x = r.randn(5, 4, 32).astype("float32")
    net = rnn.LSTM(128, num_layers=2, bidirectional=True,
                   input_size=32)
    net.initialize()
    outs = {}
    for env in ("off", "on"):
        monkeypatch.setenv("MXNET_PALLAS", env)
        outs[env] = net(mx.nd.array(x)).asnumpy()
    assert bool((outs["off"] == outs["on"]).all())


def test_scan_residual_bytes_ratchet(monkeypatch):
    """THE point of the kernel: the backward saves only the hidden
    (+cell) trajectory instead of the scan's per-step residual
    streams. Strictly fewer residual bytes at the LSTM-leg shape —
    the backend-independent form of 'fewer HBM round-trips' (the
    interpret-mode HLO's while-carries make raw boundary_bytes
    incomparable on CPU; see docs/PERF_NOTES.md)."""
    T, N, H, C = 35, 16, 128, 128
    r = onp.random.RandomState(0)
    x = jnp.asarray(r.randn(T, N, C).astype("f4"))
    h0 = jnp.asarray(r.randn(N, H).astype("f4"))
    c0 = jnp.asarray(r.randn(N, H).astype("f4"))
    wih = jnp.asarray((r.randn(4 * H, C) * 0.2).astype("f4"))
    whh = jnp.asarray((r.randn(4 * H, H) * 0.2).astype("f4"))
    bih = jnp.asarray((r.randn(4 * H) * 0.1).astype("f4"))
    bhh = jnp.asarray((r.randn(4 * H) * 0.1).astype("f4"))

    def measure(env):
        monkeypatch.setenv("MXNET_PALLAS", env)

        def f(x, h0, c0, wih, whh, bih, bhh):
            y, _, _ = rnn_ops._one_direction(
                x, h0, c0, wih, whh, bih, bhh, "lstm", False)
            return y
        _, vjp = jax.vjp(f, x, h0, c0, wih, whh, bih, bhh)
        return sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(vjp)
                   if hasattr(l, "nbytes"))

    ref, ker = measure("off"), measure("on")
    assert ker < ref, (ker, ref)
    assert ref / ker > 1.5          # ~13 streams -> ys+cs (+inputs)


# ---------------------------------------------------------------------------
# LayerNorm / bias-GELU kernels
# ---------------------------------------------------------------------------

def test_layernorm_fwd_bit_exact_aligned():
    r = onp.random.RandomState(0)
    x = jnp.asarray(r.randn(4, 16, 256).astype("f4"))
    g = jnp.asarray(r.randn(256).astype("f4"))
    b = jnp.asarray(r.randn(256).astype("f4"))

    def ref(x, g, b):
        from jax import lax
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return ((x - mean) * lax.rsqrt(var + 1e-5)
                * g.reshape(1, 1, -1) + b.reshape(1, 1, -1))

    a = jax.jit(ref)(x, g, b)
    k = jax.jit(lambda x, g, b: knorm.layer_norm(
        x, g, b, interpret=True))(x, g, b)
    assert bool((a == k).all())


@pytest.mark.pallas
@pytest.mark.parametrize("shape", [(8, 100), (3, 5, 130), (16, 256)])
def test_layernorm_fwd_bwd_tolerance(shape):
    c = shape[-1]
    r = onp.random.RandomState(1)
    x = jnp.asarray(r.randn(*shape).astype("f4"))
    g = jnp.asarray(r.randn(c).astype("f4"))
    b = jnp.asarray(r.randn(c).astype("f4"))
    from mxnet_tpu.ops import nn as FNN
    ref = FNN.layer_norm(x, g, b)          # default env: XLA reference
    ker = knorm.layer_norm(x, g, b, interpret=True)
    onp.testing.assert_allclose(onp.asarray(ref), onp.asarray(ker),
                                rtol=1e-5, atol=1e-5)
    gr = jax.grad(lambda *a: jnp.sum(jnp.cos(FNN.layer_norm(*a))),
                  argnums=(0, 1, 2))(x, g, b)
    gk = jax.grad(lambda *a: jnp.sum(jnp.cos(knorm.layer_norm(
        *a, interpret=True))), argnums=(0, 1, 2))(x, g, b)
    for a, bb in zip(gr, gk):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(bb),
                                    rtol=2e-3, atol=1e-4)


def test_layer_norm_op_dispatches(monkeypatch):
    """ops/nn.py layer_norm routes through the kernel under the gate
    (and the gluon LayerNorm block with it) — outputs stay equal."""
    from mxnet_tpu.ops import nn as FNN
    r = onp.random.RandomState(2)
    x = jnp.asarray(r.randn(6, 256).astype("f4"))
    g = jnp.asarray(r.randn(256).astype("f4"))
    b = jnp.asarray(r.randn(256).astype("f4"))
    monkeypatch.setenv("MXNET_PALLAS", "off")
    ref = FNN.layer_norm(x, g, b)
    monkeypatch.setenv("MXNET_PALLAS", "on")
    ker = FNN.layer_norm(x, g, b)
    assert K.decisions()["layernorm"][0] == "interpret"
    onp.testing.assert_allclose(onp.asarray(ref), onp.asarray(ker),
                                rtol=1e-6, atol=1e-6)
    # non-trailing axis stays on the reference path
    FNN.layer_norm(x, jnp.ones(6), jnp.zeros(6), axis=0)


def test_bias_gelu_fwd_error_bound_and_bwd():
    """The kernel builds erfc from exp (Mosaic lowers neither erf nor
    erfc): f32 GELU must sit within 1e-6 ABSOLUTE of the exact form
    (float64 erfc) over the whole active range, tails included — the
    same distance ``jax.nn.gelu`` itself keeps."""
    import math
    r = onp.random.RandomState(3)
    x = jnp.asarray((4.0 * r.randn(4, 16, 256)).astype("f4"))
    b = jnp.asarray(r.randn(256).astype("f4"))
    z = onp.asarray(x + b, "f8")
    exact = z * 0.5 * onp.vectorize(math.erfc)(-z / math.sqrt(2.0))
    ker = knorm.bias_gelu(x, b, interpret=True)
    assert onp.abs(onp.asarray(ker, "f8") - exact).max() <= 1e-6
    assert onp.abs(onp.asarray(ker) - onp.asarray(jax.nn.gelu(
        x + b, approximate=False))).max() <= 1e-6
    gr = jax.grad(lambda x, b: jnp.sum(jnp.cos(jax.nn.gelu(
        x + b, approximate=False))), argnums=(0, 1))(x, b)
    gk = jax.grad(lambda x, b: jnp.sum(jnp.cos(knorm.bias_gelu(
        x, b, interpret=True))), argnums=(0, 1))(x, b)
    for a, bb in zip(gr, gk):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(bb),
                                    rtol=2e-4, atol=1e-5)


def test_positionwise_ffn_bias_gelu_wiring(monkeypatch):
    """PositionwiseFFN takes the fused bias-GELU path under the gate,
    with output parity against the Dense→Activation reference."""
    from mxnet_tpu.gluon.nn.transformer import PositionwiseFFN
    r = onp.random.RandomState(4)
    x = r.randn(2, 6, 64).astype("f4")
    ffn = PositionwiseFFN(64, 256)
    ffn.initialize()
    outs = {}
    for env in ("off", "on"):
        monkeypatch.setenv("MXNET_PALLAS", env)
        assert (ffn._bias_gelu_path(mx.nd.array(x)) is not None) \
            == (env == "on")
        outs[env] = ffn(mx.nd.array(x)).asnumpy()
    onp.testing.assert_allclose(outs["off"], outs["on"],
                                rtol=1e-5, atol=1e-6)


def test_flash_attention_through_gate(monkeypatch):
    """flash_attention's default path obeys the shared gate: interpret
    kernels when forced on CPU, with parity vs the XLA blockwise path."""
    from mxnet_tpu.ops.attention import flash_attention
    r = onp.random.RandomState(5)
    q = jnp.asarray(r.randn(1, 2, 64, 64).astype("f4"))
    k = jnp.asarray(r.randn(1, 2, 64, 64).astype("f4"))
    v = jnp.asarray(r.randn(1, 2, 64, 64).astype("f4"))
    monkeypatch.setenv("MXNET_PALLAS", "off")
    ref = flash_attention(q, k, v, causal=True)
    monkeypatch.setenv("MXNET_PALLAS", "on")
    ker = flash_attention(q, k, v, causal=True)
    assert K.decisions()["flash_attention"][0] == "interpret"
    onp.testing.assert_allclose(onp.asarray(ref), onp.asarray(ker),
                                rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused optimizer-update kernel
# ---------------------------------------------------------------------------

def _opt_case(kind):
    if kind == "sgd":
        cfg = {"momentum": 0.9, "has_clip": False}

        def ref(w, g, lr, wd, t, states, rescale):
            g = g * rescale
            g = g + wd * w
            m = 0.9 * states[0] - lr * g
            return w + m, (m,)
        n_states = 1
    else:
        cfg = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
               "has_clip": False}

        def ref(w, g, lr, wd, t, states, rescale):
            b1, b2, eps = 0.9, 0.999, 1e-8
            m, v = states
            g = g * rescale
            g = g + wd * w
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            return w - lr * mhat / (jnp.sqrt(vhat) + eps), (m, v)
        n_states = 2
    return cfg, ref, n_states


#: The new moment is ``a * m + b * g``: two products and a sum, written
#: alike in the kernel body and in the XLA chain. XLA:CPU hands both to
#: LLVM, which contracts one product into the sum (an fma, one rounding
#: fewer) or not, by the structure of the program around it; the two
#: programs here are structured differently, so they may differ by one
#: rounding of the larger term. The terms stay under 0.5 (|m| < 0.45,
#: lr * |g| < 0.1), whose ulp is 2**-25. Measured (PR 30, this XLA:CPU,
#: single device and dp4 mesh alike): at most 2**-26 = 1.49e-8, 1.3 to
#: 1.6 roundings of the larger term, in under 1 % of the elements;
#: counted in ulp of the RESULT that reads 4 to 32, because the two terms
#: cancel. The weights, computed from either side's new moment by one
#: more such expression, agreed to the bit in every case.
_ONE_CONTRACTION = 2.0 ** -25


@pytest.mark.parametrize("kind", ["sgd", "adam"])
@pytest.mark.parametrize("hp", ["scalar", "vector"])
def test_opt_update_bit_exact(kind, hp):
    """The kernel applies the literal rule expressions on a reshaped
    lane layout — the weights bit-exact vs the XLA elementwise chain,
    the states to one rounding (``_ONE_CONTRACTION``), for scalar AND
    per-element (pack_shard_hparams bucket) hyperparameters."""
    cfg, ref, n_states = _opt_case(kind)
    r = onp.random.RandomState(0)
    P = 5000
    w = jnp.asarray(r.randn(P).astype("f4"))
    g = jnp.asarray(r.randn(P).astype("f4"))
    states = tuple(jnp.asarray(abs(r.randn(P)).astype("f4") * 0.1)
                   for _ in range(n_states))
    rescale = jnp.float32(0.25)
    if hp == "scalar":
        lr, wd, t = jnp.float32(0.05), jnp.float32(0.01), jnp.int32(3)
    else:
        lr = jnp.asarray(r.rand(P).astype("f4") * 0.1)
        wd = jnp.asarray(r.rand(P).astype("f4") * 0.01)
        t = jnp.asarray(r.randint(1, 5, P).astype("i4"))

    @jax.jit
    def both(w, g, lr, wd, t, states):
        a = ref(w, g, lr, wd, t, states, rescale)
        b = kopt.unit_update(kind, cfg, w, g, lr, wd, t, rescale,
                             jnp.float32(0.0), states, interpret=True)
        return a, b

    (wr, sr), (wk, sk) = both(w, g, lr, wd, t, states)
    assert bool((wr == wk).all())
    for a, b in zip(sr, sk):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=0, atol=_ONE_CONTRACTION)


@pytest.mark.parametrize("kind", ["sgd", "adam"])
def test_opt_update_bit_exact_dp4_sharded(kind):
    """The acceptance claim on REAL ZeRO layout: a NamedSharding'd
    flat 1/N-per-replica buffer at dp=4 (nonzero moments) updates
    through the kernel as through the XLA chain: the states to one
    rounding (``_ONE_CONTRACTION``), Adam's weights to the bit."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 virtual devices")
    cfg, ref, n_states = _opt_case(kind)
    mesh = Mesh(onp.array(jax.devices()[:4]), ("dp",))
    shard = NamedSharding(mesh, P("dp"))
    r = onp.random.RandomState(1)
    Pn = 4096
    w = jax.device_put(jnp.asarray(r.randn(Pn).astype("f4")), shard)
    g = jax.device_put(jnp.asarray(r.randn(Pn).astype("f4")), shard)
    states = tuple(jax.device_put(
        jnp.asarray(abs(r.randn(Pn)).astype("f4") * 0.1), shard)
        for _ in range(n_states))
    rescale = jnp.float32(0.25)

    @jax.jit
    def both(w, g, states):
        a = ref(w, g, jnp.float32(0.05), jnp.float32(0.01),
                jnp.int32(3), states, rescale)
        b = kopt.unit_update(kind, cfg, w, g, jnp.float32(0.05),
                             jnp.float32(0.01), jnp.int32(3), rescale,
                             jnp.float32(0.0), states, interpret=True)
        return a, b

    (wr, sr), (wk, sk) = both(w, g, states)
    for a, b in zip(sr, sk):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=0, atol=_ONE_CONTRACTION)
    if kind == "adam":
        assert bool((wr == wk).all())
    else:
        # sgd-mom at dp=4: XLA duplicates the momentum expression
        # into the weight fusion and fp-contracts the copy (it strips
        # optimization barriers on CPU, so this is not preventable
        # in-program) — the weight sits within 1 ulp of w + m
        onp.testing.assert_allclose(onp.asarray(wr), onp.asarray(wk),
                                    rtol=0, atol=1e-8)


def test_opt_kernel_kind_gating():
    from mxnet_tpu import optimizer as opt_mod
    assert kopt.opt_kernel_kind(opt_mod.SGD(momentum=0.9))[0] == "sgd"
    assert kopt.opt_kernel_kind(opt_mod.Adam())[0] == "adam"
    # LAMB is non-elementwise; subclass rules are not kernelized
    assert kopt.opt_kernel_kind(opt_mod.create("lamb")) is None
    assert kopt.opt_kernel_kind(opt_mod.create("nag")) is None


def test_kernel_step_fn_respects_gate(monkeypatch):
    from mxnet_tpu import optimizer as opt_mod
    monkeypatch.setenv("MXNET_PALLAS", "off")
    assert opt_mod.Adam().kernel_step_fn() is None
    monkeypatch.setenv("MXNET_PALLAS", "on")
    assert opt_mod.Adam().kernel_step_fn() is not None
    assert opt_mod.create("nag").kernel_step_fn() is None


def _zero_step(optname, kw, seed=0):
    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(8))
    net.initialize()
    r = onp.random.RandomState(seed)
    x = mx.nd.array(r.randn(16, 12).astype("float32"))
    y = mx.nd.array(r.randint(0, 8, size=(16,)).astype("int32"))
    net(x)
    loss = gloss.SoftmaxCrossEntropyLoss()
    tr = Trainer(net.collect_params(), optname, kw, kvstore=None)
    mesh = make_mesh({"dp": 4}, jax.devices()[:4])
    step = tr.compile_step(lambda a, b: loss(net(a), b), mesh=mesh,
                           zero_shard=True)
    return net, step, x, y


@pytest.mark.parametrize("optname,kw", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-3})])
def test_zero_train_step_kernel_vs_xla(monkeypatch, optname, kw):
    """The full zero-sharded train step at dp=4, kernel vs XLA update:
    bit-exact params and state after the first application, and
    ulp-level (the whole-program fp-contraction noise, ~1e-8
    relative) over a 4-step trajectory with equal losses."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 virtual devices")
    results = {}
    for env in ("off", "on"):
        monkeypatch.setenv("MXNET_PALLAS", env)
        net, step, x, y = _zero_step(optname, kw)
        losses = []
        snaps = []
        for _ in range(4):
            losses.append(float(step(x, y).asnumpy().sum()))
            snaps.append({k: p.data().asnumpy()
                          for k, p in net.collect_params().items()})
        results[env] = (losses, snaps)
    (l_off, s_off), (l_on, s_on) = results["off"], results["on"]
    for k in s_off[0]:
        if optname == "adam":
            assert bool((s_off[0][k] == s_on[0][k]).all()), k
        else:   # sgd-mom: ±1 ulp (see test_opt_update_bit_exact_dp4)
            onp.testing.assert_allclose(s_off[0][k], s_on[0][k],
                                        rtol=0, atol=1e-7)
    for a, b in zip(l_off, l_on):
        assert abs(a - b) < 1e-4
    for k in s_off[-1]:
        onp.testing.assert_allclose(s_off[-1][k], s_on[-1][k],
                                    rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the acceptance run: pipelined + guarded, kernels ON, zero unblessed syncs
# ---------------------------------------------------------------------------

def test_guarded_12step_pipelined_kernels_on(monkeypatch):
    """12 pipelined steps of an LSTM model with every kernel on the
    interpret tier under MXNET_TRANSFER_GUARD=raise: the kernel layer
    introduces no host syncs (interpret bodies are pure XLA ops)."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    onp.random.seed(0)

    class TinyLM(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(16, 8)
            self.lstm = rnn.LSTM(16, num_layers=1, layout="NTC")
            self.head = nn.Dense(16, flatten=False)

        def forward(self, tokens):
            return self.head(self.lstm(self.emb(tokens)))

    net = TinyLM()
    net.initialize()
    r = onp.random.RandomState(0)
    x = mx.nd.array(r.randint(0, 16, size=(4, 8)).astype("int32"))
    y = mx.nd.array(r.randint(0, 16, size=(4, 8)).astype("int32"))
    net(x)
    trainer = Trainer(net.collect_params(), "adam",
                      {"learning_rate": 5e-3})
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=2)
    loop.step(x, y)                  # compile outside the counted region
    loop.synchronize()
    tguard.reset_sync_counts()
    for bx, by in loop.prefetch((x, y) for _ in range(12)):
        loop.step(bx, by)            # raises on any unblessed sync
    loop.synchronize()
    counts = tguard.sync_counts()
    assert counts.get("wait_to_read", 0) == 0
    assert counts.get("window_retire", 0) == 12
    # the scan kernel actually took the interpret tier in this program
    assert K.decisions()["rnn_scan"][0] == "interpret"
