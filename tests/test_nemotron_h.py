"""Nemotron-H on the CPU at small sizes, float32 at ``highest``, seeded
weights: the selective scan in its three forms (chunked, the recurrence,
the quadratic form), the conv, the gated norm, what the mixer's backward
makes again, experts without a gate on both tiers, the LM through ``TrainLoop``
against the plain reference the benchmark keeps
(``benchmark/grid/configs/nemotron-3-nano-30b-a3b.py`` ``loss_sum``), the
test that ties one chip's share of the experts to the whole layer, and
the precision the comparison parts.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.nemotron_h import NemotronHLM
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import kernels
from mxnet_tpu.ops import moe as MOE
from mxnet_tpu.ops import ssm as SSM
from mxnet_tpu.telemetry import names as tnames

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRID = os.path.join(ROOT, "benchmark", "grid")
NAME = "nemotron-3-nano-30b-a3b"


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def grid_module(name):
    spec = importlib.util.spec_from_file_location(
        "nemotron_test_" + name.replace("/", "_").replace("-", "_")
        .replace(".", "_"), os.path.join(GRID, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    return grid_module(f"configs/{NAME}.py")


@pytest.fixture(scope="module")
def reference():
    return grid_module("reference.py")


def tiny_cfg(**over):
    with open(os.path.join(GRID, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["tiny"])
    cfg.update(over)
    return cfg


def int_nd(a):
    return mx.nd.array(a, dtype="int32")


def _counted(name, label):
    return telemetry.value(name, label) or 0


# ---------------------------------------------------------------------------
# the selective scan: chunked = the recurrence = the quadratic form
# ---------------------------------------------------------------------------

def _scan_inputs(seq, groups, batch=2, heads=4, width=8, state=16, seed=0):
    rng = onp.random.default_rng(seed)

    def f32(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    # step sizes and decay rates spread as the benchmark's weights spread
    # them: dt A from about 0.01 to 12 a token
    dt = jax.nn.softplus(f32(batch, seq, heads)
                         + jnp.asarray(rng.uniform(-1, 1, heads),
                                       jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.uniform(-1.39, 1.39, heads), jnp.float32))
    return (f32(batch, seq, heads, width), dt, A,
            f32(batch, seq, groups, state), f32(batch, seq, groups, state),
            f32(heads))


def _quadratic(x, dt, A, B, C, D):
    """``((C B^T) * L) (dt x) + D x`` with one S x S ``L`` a head."""
    heads, seq = x.shape[2], x.shape[1]
    Bh, Ch = (jnp.repeat(a, heads // a.shape[2], axis=2) for a in (B, C))
    total = jnp.cumsum(dt * A, axis=1)                       # (B, S, H)
    seen = jnp.tril(jnp.ones((seq, seq), bool))
    L = jnp.exp(jnp.where(seen[None, :, :, None],
                          total[:, :, None] - total[:, None, :], -jnp.inf))
    scores = jnp.einsum("bthn,bshn->btsh", Ch, Bh) * L
    return jnp.einsum("btsh,bshp->bthp", scores, dt[..., None] * x) \
        + D[:, None] * x


@pytest.mark.parametrize("groups", [1, 2, 4], ids=lambda g: f"groups{g}")
@pytest.mark.parametrize("seq,chunk", [(32, 8), (37, 8), (5, 8), (24, 24)],
                         ids=["whole_chunks", "ragged_tail",
                              "shorter_than_a_chunk", "one_chunk"])
def test_three_forms_of_the_scan_agree(seq, chunk, groups):
    """Forward and every gradient, the chunked form the program runs
    against the recurrence and the quadratic form: float32 sums in another
    order, so 2e-5 of the largest entry (bf16 products read 4e-3)."""
    args = _scan_inputs(seq, groups)
    weigh = jnp.asarray(onp.random.default_rng(9).normal(
        size=args[0].shape), jnp.float32)
    forms = {
        "chunked": lambda *a: SSM.ssd_scan(*a, chunk=chunk),
        "chunked_plain": lambda *a: SSM.ssd_scan(*a, chunk=chunk,
                                                 recompute=False),
        "recurrence": SSM.ssd_scan_reference,
        "quadratic": _quadratic}
    got = {name: jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * weigh), argnums=tuple(range(6)))(*args)
        for name, fn in forms.items()}
    out = {name: fn(*args) for name, fn in forms.items()}
    for name in ("chunked", "chunked_plain", "quadratic"):
        assert out[name].shape == out["recurrence"].shape
        scale = float(jnp.abs(out["recurrence"]).max())
        assert float(jnp.abs(out[name] - out["recurrence"]).max()) \
            < 2e-5 * scale, name
        for a, b in zip(got[name][1], got["recurrence"][1]):
            assert a.shape == b.shape
            assert float(jnp.abs(a - b).max()) \
                < 2e-5 * max(float(jnp.abs(b).max()), 1e-6), name
    # the same function with and without its own checkpoint
    for a, b in zip(got["chunked"][1], got["chunked_plain"][1]):
        assert float(jnp.abs(a - b).max()) <= 1e-6 * float(jnp.abs(b).max())


def test_scan_masks_before_the_exponential():
    """A fast head: dt A = -40 a token, so exp(cs_s - cs_t) above the
    diagonal of a chunk of 16 would be exp(600) = inf and inf * 0 NaN."""
    x, dt, A, B, C, D = _scan_inputs(32, 2)
    dt, A = jnp.full_like(dt, 4.0), jnp.full_like(A, -10.0)
    out, vjp = jax.vjp(lambda *a: SSM.ssd_scan(*a, D, chunk=16),
                       x, dt, A, B, C)
    grads = vjp(jnp.ones_like(out))
    assert all(bool(jnp.isfinite(a).all()) for a in (out,) + grads)
    want = SSM.ssd_scan_reference(x, dt, A, B, C, D)
    assert float(jnp.abs(out - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


def test_scan_keeps_its_operands_dtype_and_float32_decays():
    """bf16 x, B, C with float32 dt and A, as AMP hands them over: the
    result is bf16 and within bf16's rounding of the float32 scan (the
    decays were not rounded: a bf16 cumulative sum over 32 positions
    would be off by percents)."""
    x, dt, A, B, C, D = _scan_inputs(32, 2, seed=4)
    low = [a.astype(jnp.bfloat16) for a in (x, B, C)]
    got = SSM.ssd_scan(low[0], dt, A, low[1], low[2], D, chunk=8)
    assert got.dtype == jnp.bfloat16
    want = SSM.ssd_scan_reference(*(a.astype(jnp.float32) for a in (
        low[0], dt, A, low[1], low[2], D)))
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < 2.0 ** -6 * scale
    before = telemetry.value(tnames.SSD_SCAN_CHUNKS) or 0
    SSM.ssd_scan(x, dt, A, B, C, chunk=5)
    assert telemetry.value(tnames.SSD_SCAN_CHUNKS) == before + 7
    # the kernel layer's gate decides the tier and says why: chunks of 5
    # are not the kernels' (tests/test_ssd_scan_kernel.py has the rest)
    assert "ssd_scan" in kernels.KERNELS
    assert kernels.decisions()["ssd_scan"][0] == "xla"
    assert "chunks of 5" in kernels.decisions()["ssd_scan"][1]


@pytest.mark.parametrize("tier", ["xla", "interpret"])
def test_scan_backward_keeps_the_chunk_states_and_no_decay_matrix(
        tier, monkeypatch):
    """What ``ssd_scan`` saves from forward to backward on either tier: its
    operands and the chunk-boundary states, nothing of a chunk's (Q x Q)
    size. The XLA tier's ``jax.checkpoint`` sees to it, the kernel tier's
    custom VJP (heads of 64 lanes, state 128, chunks of 128: shapes the
    kernels take, their bodies under the interpreter)."""
    monkeypatch.setenv("MXNET_PALLAS", "on" if tier == "interpret" else "off")
    chunk, groups = (128, 2) if tier == "interpret" else (8, 2)
    args = _scan_inputs(32, 2) if tier == "xla" else _scan_inputs(
        256, groups, batch=1, heads=8, width=64, state=128)
    batch, seq, heads, width = args[0].shape
    state = args[3].shape[-1]
    def kept(fn):
        # the leaves of a vjp function are what the forward kept for it
        _, pull = jax.vjp(fn, *args)
        return [tuple(a.shape) for a in jax.tree_util.tree_leaves(pull)]
    shapes = kept(lambda *a: SSM.ssd_scan(*a, chunk=chunk))
    assert kernels.decisions()["ssd_scan"][0] == tier
    per_group = heads // groups
    entering = (batch, seq // chunk, groups, per_group, width, state) \
        if tier == "xla" else \
        (batch, seq // chunk, groups, state, per_group * width)
    assert entering in shapes
    assert not [s for s in shapes if s[-2:] == (chunk, chunk)]
    if tier == "interpret":
        # the operands as the kernels read them, the states, and no more
        operands = [(batch, seq, heads * width), (batch, seq, heads),
                    (heads,), (batch, seq, groups * state),
                    (batch, seq, groups * state), (heads,)]
        assert sorted(shapes) == sorted(operands + [entering])
    assert [s for s in kept(lambda *a: SSM._ssd_chunked(*a, chunk))
            if s[-2:] == (chunk, chunk)]


# ---------------------------------------------------------------------------
# conv and gated norm
# ---------------------------------------------------------------------------

def test_causal_conv_is_four_shifted_multiply_adds():
    rng = onp.random.default_rng(2)
    x = rng.normal(size=(2, 9, 6)).astype("float32")
    w = rng.normal(size=(6, 4)).astype("float32")
    b = rng.normal(size=(6,)).astype("float32")
    want = onp.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 3 + j]
    want += b
    got = SSM.causal_conv1d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    assert onp.allclose(onp.asarray(got), want, atol=1e-6)
    # causal: a later input moves no earlier output
    x2 = x.copy()
    x2[:, 5:] += 1.0
    got2 = SSM.causal_conv1d(jnp.asarray(x2), jnp.asarray(w), jnp.asarray(b))
    assert onp.array_equal(onp.asarray(got2)[:, :5], onp.asarray(got)[:, :5])
    low = SSM.causal_conv1d(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w))
    assert low.dtype == jnp.bfloat16


def test_gated_norm_gates_before_it_norms_each_group():
    rng = onp.random.default_rng(3)
    y, z = (rng.normal(size=(2, 5, 12)).astype("float32") for _ in range(2))
    gain = rng.normal(size=(12,)).astype("float32")
    v = (y * (z / (1 + onp.exp(-z)))).reshape(2, 5, 3, 4)
    want = (v / onp.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)) \
        .reshape(2, 5, 12) * gain
    got = SSM.gated_group_rms_norm(jnp.asarray(y), jnp.asarray(z),
                                   jnp.asarray(gain), 3, 1e-5)
    assert onp.allclose(onp.asarray(got), want, atol=1e-5)
    # one group over all lanes, or the gate behind the norm, is another
    # function
    one = SSM.gated_group_rms_norm(jnp.asarray(y), jnp.asarray(z),
                                   jnp.asarray(gain), 1, 1e-5)
    assert float(jnp.abs(one - got).max()) > 0.05


# ---------------------------------------------------------------------------
# the mixer block and what its backward makes again
# ---------------------------------------------------------------------------

def _mixer(seed=5):
    mixer = nn.Mamba2Mixer(32, 4, 8, 16, n_groups=2, chunk_size=8)
    rng = onp.random.default_rng(seed)
    for name, p in mixer.collect_params().items():
        scale = 0.5 if "conv" in name or "bias" in name or "A_log" in name \
            else 0.2
        value = rng.normal(size=p.shape) * scale
        if name in ("D", "norm_gamma"):
            value = 1 + value
        p.set_data(mx.nd.array(value.astype("float32")))
    return mixer


def test_mixer_is_its_equations():
    mixer = _mixer()
    w = {n: onp.asarray(p.data()._data)
         for n, p in mixer.collect_params().items()}
    assert sorted(w) == ["A_log", "D", "conv_bias", "conv_weight",
                         "dt_bias", "in_proj.weight", "norm_gamma",
                         "out_proj.weight"]
    assert w["in_proj.weight"].shape == (32 + (32 + 64) + 4, 32)
    u = onp.random.default_rng(6).normal(size=(2, 19, 32)).astype("float32")
    zxbcdt = jnp.asarray(u @ w["in_proj.weight"].T)
    z, xbc, dt = zxbcdt[..., :32], zxbcdt[..., 32:128], zxbcdt[..., 128:]
    xbc = jax.nn.silu(SSM.causal_conv1d(xbc, jnp.asarray(w["conv_weight"]),
                                        jnp.asarray(w["conv_bias"])))
    y = SSM.ssd_scan_reference(
        xbc[..., :32].reshape(2, 19, 4, 8),
        jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["A_log"]),
        xbc[..., 32:64].reshape(2, 19, 2, 16),
        xbc[..., 64:].reshape(2, 19, 2, 16), jnp.asarray(w["D"]))
    want = SSM.gated_group_rms_norm(
        y.reshape(2, 19, 32), z, jnp.asarray(w["norm_gamma"]), 2,
        1e-5) @ w["out_proj.weight"].T
    got = mixer(mx.nd.array(u))._data
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())
    with pytest.raises(mx.MXNetError):
        nn.Mamba2Mixer(32, 4, 8, 16, n_groups=3)


def test_tape_takes_no_segment_and_still_differentiates():
    """Under ``autograd.record()`` every op is a tape entry of its own: no
    checkpoint spans them, the layer says so, and gradients flow."""
    mixer = _mixer()
    for p in mixer.collect_params().values():
        p.grad_req = "write"
    u = mx.nd.array(onp.random.default_rng(7).normal(size=(1, 11, 32))
                    .astype("float32"))
    before = {r: _counted(tnames.MAMBA_RECOMPUTE, r)
              for r in ("segment", "none")}
    with autograd.record():
        out = mixer(u)
    out.backward()
    after = {r: _counted(tnames.MAMBA_RECOMPUTE, r) for r in before}
    assert after == dict(before, none=before["none"] + 1)
    for name, p in mixer.collect_params().items():
        assert float(jnp.abs(p.grad()._data).max()) > 0, name


# ---------------------------------------------------------------------------
# the LM through TrainLoop against the reference, recomputed and not
# ---------------------------------------------------------------------------

def seeded_net(cfg, model, reference, seed=3):
    net = NemotronHLM(cfg)
    spec = model.param_spec(cfg)
    params = net.collect_params()
    assert list(params) == [name for name, *_ in spec]
    weights = reference.make_weights(spec, seed)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p.set_data(NDArray(weights[name]))
    return net, weights


@pytest.fixture(scope="module")
def lm_case(model, reference):
    cfg = tiny_cfg()
    batch, seq = 4, 28                      # 3 chunks of 8 and a tail of 4
    (x, y), = model.batches(cfg, {"batch": batch, "seq": seq, "pool": 1}, 11)
    weights = reference.make_weights(model.param_spec(cfg), 3)
    loss, grads = jax.value_and_grad(model.loss_sum(
        cfg, reference.make_dot("f32")))(weights, x, y)
    return {"x": x, "y": y, "batch": batch, "loss": float(loss),
            "grads": grads}


GRADS = {}


@pytest.mark.parametrize("rung", ["kept", "segment"])
def test_lm_loss_and_every_gradient_through_trainloop(rung, lm_case, model,
                                                      reference, monkeypatch):
    """``segment``: the step as it runs, each mixer's conv, scan and norm
    one ``jax.checkpoint``; ``kept``: the same step with that checkpoint
    taken out, every intermediate kept."""
    if rung == "kept":
        monkeypatch.setattr(jax, "checkpoint", lambda fn, **kw: fn)
    cfg = tiny_cfg()
    assert cfg["hybrid_override_pattern"] == "MEM*E"
    net, _ = seeded_net(cfg, model, reference)
    x, y, batch = lm_case["x"], lm_case["y"], lm_case["batch"]
    logits = net(int_nd(x))._data
    assert logits.shape == (batch, x.shape[1], cfg["vocab_rows"])
    lr = 0.5
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr, "momentum": 0.9},
                            kvstore="tpu")
    loop = gluon.TrainLoop(net, trainer,
                           gluon.loss.SoftmaxCrossEntropyLoss())
    before = _counted(tnames.MAMBA_RECOMPUTE, "segment")
    losses = loop.step(int_nd(x), int_nd(y))
    loop.synchronize()
    step = loop.compiled_step
    assert step.mode == "fused" and step.n_traces == 1
    assert _counted(tnames.MAMBA_RECOMPUTE, "segment") >= before + 2
    assert float(jnp.sum(losses._data)) == pytest.approx(lm_case["loss"],
                                                         rel=2e-5)
    # SGD with momentum keeps m = -lr * g after one step
    names = sorted(net.collect_params())
    state = step.optimizer_state_buffers()
    assert len(state) == len(names)
    got = {}
    for name, m in zip(names, state):
        got[name] = onp.asarray(m) / -lr
        want = onp.asarray(lm_case["grads"][name]) / batch
        scale = max(float(onp.abs(want).max()), 1e-12)
        assert onp.abs(got[name] - want).max() / scale < 2e-4, name
        if name.endswith("router_bias"):
            assert not got[name].any() and not want.any()
        else:
            assert onp.abs(want).max() > 0, name
    GRADS[rung] = got
    if "kept" in GRADS and rung != "kept":
        # the checkpoint makes the forward again, it computes nothing else
        for name in names:
            scale = max(float(onp.abs(GRADS["kept"][name]).max()), 1e-12)
            assert onp.abs(got[name] - GRADS["kept"][name]).max() \
                <= 2e-6 * scale, name
    loop.step(int_nd(x), int_nd(y))
    loop.synchronize()
    assert step.n_traces == 1


def test_lm_counts_what_it_traces(model, reference):
    cfg = tiny_cfg()
    net, _ = seeded_net(cfg, model, reference)
    read = {"scans": lambda: _counted(tnames.MAMBA_RECOMPUTE, "segment"),
            "chunks": lambda: telemetry.value(tnames.SSD_SCAN_CHUNKS) or 0,
            "sigmoid": lambda: _counted(tnames.MOE_ROUTER, "sigmoid"),
            "grouped": lambda: _counted(tnames.MOE_DISPATCH, "grouped"),
            "products": lambda: _counted(tnames.MOE_GROUPED_DOT, "xla"),
            "causal": lambda: _counted(tnames.ATTENTION_MASK, "causal")}
    before = {k: f() for k, f in read.items()}
    net(int_nd(onp.zeros((2, 20))))
    counted = {k: f() - before[k] for k, f in read.items()}
    # MEM*E: two scans of ceil(20 / 8) chunks, two expert layers of two
    # products each (no gate), one attention layer
    assert counted == {"scans": 2, "chunks": 6, "sigmoid": 2, "grouped": 2,
                       "products": 4, "causal": 1}
    for name in (tnames.SSD_SCAN_CHUNKS, tnames.MAMBA_RECOMPUTE):
        assert name in tnames.CATALOG and name.startswith("mx_")
    assert net.pattern == "MEM*E"
    assert [type(l.mixer).__name__ for l in net.layers] == [
        "Mamba2Mixer", "SparseMoE", "Mamba2Mixer", "MultiHeadAttention",
        "SparseMoE"]
    # attention carries no position signal, the experts no gate
    assert net.layer3.mixer._rope_theta is None
    assert not [n for n in net.collect_params() if "gate" in n]
    with pytest.raises(ValueError):
        NemotronHLM(tiny_cfg(hybrid_override_pattern="MXM",
                             num_hidden_layers=3))
    with pytest.raises(ValueError):
        NemotronHLM(tiny_cfg(num_hidden_layers=9))


def test_amp_keeps_step_sizes_and_norm_in_float32():
    assert {"mamba_dt", "mamba_norm", "moe_route"} <= amp.FP32_OPS
    assert not {"ssd_scan", "mamba_conv"} & (amp.FP32_OPS
                                             | amp.TARGET_DTYPE_OPS)
    wrap = amp._make_wrapper(jnp.bfloat16)
    seen = {}

    def fn(*args):
        seen["dtypes"] = tuple(a.dtype for a in args)
        return args[0]
    low, full = jnp.ones((2, 4), jnp.bfloat16), jnp.ones((4,), jnp.float32)
    wrap("mamba_dt", fn)(low, full)
    assert seen["dtypes"] == (jnp.float32, jnp.float32)
    wrap("mamba_norm", fn)(low, low, full)
    assert seen["dtypes"] == (jnp.float32,) * 3
    # the scan: bf16 x, B, C beside float32 dt, A_log, D, each as it came
    wrap("ssd_scan", fn)(low, full, full, full)
    assert seen["dtypes"] == (jnp.bfloat16,) + (jnp.float32,) * 3


def test_mixer_under_amp_runs_products_in_bf16_and_decays_in_float32():
    mixer = _mixer()
    u = mx.nd.array(onp.random.default_rng(8).normal(size=(2, 16, 32))
                    .astype("float32"))
    want = mixer(u)._data
    amp.init()
    try:
        jaxpr = jax.make_jaxpr(lambda a: mixer(NDArray(a))._data)(u._data)
        got = mixer(u)._data
    finally:
        amp.uninit()
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.05 * scale
    text = str(jaxpr)
    assert "bf16" in text and "cumsum" in text
    # no cumulative sum, exponential or softplus ever sees bf16
    for line in text.splitlines():
        if "cumsum" in line or " exp " in line or "log1p" in line:
            assert "bf16" not in line.split("=", 1)[1], line


# ---------------------------------------------------------------------------
# experts without a gate
# ---------------------------------------------------------------------------

def _ungated_inputs(dtype, n=64, d=128, f=256, e=8, held=(2, 4), k=2,
                    seed=0):
    rng = onp.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), dtype)
    up = jnp.asarray(rng.normal(size=(held[1], f, d)) * d ** -0.5, dtype)
    down = jnp.asarray(rng.normal(size=(held[1], d, f)) * f ** -0.5, dtype)
    router = jnp.asarray(rng.normal(size=(e, d)) * d ** -0.5, jnp.float32)
    g = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    return x, up, down, router, g


def _ungated_layer(k, held):
    def layer(x, up, down, router):
        weights, order, place, sizes = MOE.moe_route(
            x, router, k, held, score="sigmoid", scale=2.5)
        y = MOE.moe_experts(x, order, place, sizes, None, up, down,
                            activation="relu2")
        return MOE.moe_combine(y, weights, order, place, sizes)
    return layer


def _dense_loop(x, up, down, router, k, held):
    """Every held expert on every token, kept by the router's weight."""
    x32 = x.astype(jnp.float32)
    scores = jax.nn.sigmoid(x32 @ router.T)
    _, idx = jax.lax.top_k(scores, k)
    chosen = jnp.take_along_axis(scores, idx, 1)
    weights = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    out = 0.0
    for e in range(held[1]):
        w_e = jnp.sum(jnp.where(idx == held[0] + e, weights, 0.0), -1)
        hidden = jnp.square(jax.nn.relu(x32 @ up[e].astype(jnp.float32).T))
        out = out + w_e[:, None] * (hidden @ down[e].astype(jnp.float32).T)
    return out


@pytest.mark.parametrize("tier,mode", [("xla", "off"), ("interpret", "on")])
@pytest.mark.parametrize("hidden", [256, 192],
                         ids=["whole_lane_tiles", "half_a_tile_over"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ungated_relu2_experts_equal_a_dense_loop(dtype, hidden, tier, mode,
                                                  monkeypatch):
    """``W_down relu(W_up x)^2`` over the held pairs, forward and every
    gradient, through ``lax.ragged_dot`` and through the grouped-product
    kernels in interpret mode: two products forward, three backward. A
    hidden width of 1.5 lane tiles (the cell's is 14.5) is zero-padded to
    2 on the kernel tier, and nothing of the result or of a gradient
    tells."""
    k, held = 2, (2, 4)
    x, up, down, router, g = _ungated_inputs(dtype, f=hidden)
    want_out, vjp = jax.vjp(
        lambda *a: _dense_loop(*a, k, held), x, up, down, router)
    want = (want_out,) + vjp(g)
    monkeypatch.setenv("MXNET_PALLAS", mode)
    before = _counted(tnames.MOE_GROUPED_DOT, tier)
    out, vjp = jax.vjp(_ungated_layer(k, held), x, up, down, router)
    got = (out,) + vjp(g)
    assert kernels.decisions()["grouped_dot"][0] == tier
    assert _counted(tnames.MOE_GROUPED_DOT, tier) - before == \
        {"xla": 2, "interpret": 5}[tier]
    tol = {"float32": 2e-5, "bfloat16": 2.0 ** -5}[dtype]
    for a, b in zip(got, want):
        assert a.shape == b.shape
        a, b = (onp.asarray(t, "float32") for t in (a, b))
        assert onp.isfinite(a).all()
        assert onp.abs(a - b).max() <= tol * max(1.0, onp.abs(b).max())


def test_the_cells_expert_width_is_padded_for_the_kernels(monkeypatch):
    """1856 = 14.5 x 128 lanes: the grouped-product kernels refuse that
    width as they did, so with the kernels on the layer WITHOUT a gate
    asks for 1920, zero-padded (the step's time then follows a seed's
    held pairs a fifth as much: PERF.md section 6, PR 35); the gated form
    at such a width stays ``lax.ragged_dot``'s, and so does every form
    with the kernels off."""
    from mxnet_tpu.ops.kernels import grouped_dot
    rows = 4096 * 6
    why = grouped_dot.supported(rows, 2688, 1856, jnp.bfloat16)
    assert why is not None and "128" in why
    assert grouped_dot.supported(rows, 2688, 1920, jnp.bfloat16) is None
    k, held = 2, (2, 4)
    x, up, down, router, _ = _ungated_inputs("float32", f=192)

    def tier_of(gate, mode):
        monkeypatch.setenv("MXNET_PALLAS", mode)
        _, order, place, sizes = MOE.moe_route(x, router, k, held,
                                               score="sigmoid")
        text = jax.jit(lambda *w: MOE.moe_experts(
            x, order, place, sizes, *w, activation="relu2")).lower(
                gate, up, down).as_text()
        return kernels.decisions()["grouped_dot"][0], text
    padded = "tensor<4x256x128xf32>"
    tier, text = tier_of(None, "on")
    assert tier == "interpret" and padded in text
    tier, text = tier_of(up, "on")
    assert tier == "xla" and padded not in text
    tier, text = tier_of(None, "off")
    assert tier == "xla" and padded not in text
    up, down = MOE._whole_lane_tiles(up, down, 256)
    assert up.shape == (4, 256, 128) and down.shape == (4, 128, 256)
    assert not up[:, 192:].any() and not down[:, :, 192:].any()
    assert sorted(MOE.ACTIVATIONS) == ["relu", "relu2", "silu"]
    x = jnp.asarray([-2.0, 0.0, 3.0])
    assert MOE.ACTIVATIONS["relu2"](x).tolist() == [0.0, 0.0, 9.0]


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _moe_share(held, whole=None, units=32, hidden=16, shared=24, e=128, k=6,
               seed=7):
    layer = nn.SparseMoE(units, hidden, e, k, held=held, score="sigmoid",
                         routed_scale=2.5, activation="relu2", gated=False,
                         shared_hidden=shared)
    if whole is None:
        rng = onp.random.default_rng(seed)
        whole = {
            "router_weight": rng.normal(size=(e, units)) * units ** -0.5,
            "router_bias": rng.normal(size=(e,)) * 0.05,
            "up_weight": rng.normal(size=(e, hidden, units)) * 0.3,
            "down_weight": rng.normal(size=(e, units, hidden)) * 0.3,
            "shared_up_weight": rng.normal(size=(shared, units)) * 0.3,
            "shared_down_weight": rng.normal(size=(units, shared)) * 0.3}
    first, count = held
    assert sorted(layer.collect_params()) == sorted(whole)
    for name, p in layer.collect_params().items():
        w = whole[name]
        if name in ("up_weight", "down_weight"):
            w = w[first:first + count]
        p.set_data(mx.nd.array(onp.asarray(w, "float32")))
    return layer, whole


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The cell's deployment at a small size: 16 chips hold 8 of the 128
    experts each and every one the shared expert. Their parts, the shared
    expert's term counted once, add up to the uncut layer, and the uncut
    layer is a loop over all 128 experts."""
    x = mx.nd.array(onp.random.default_rng(1).normal(size=(2, 24, 32))
                    .astype("float32"))
    full, whole = _moe_share((0, 128))
    want = full(x)._data
    parts, shared_term = [], None
    for chip in range(16):
        layer, _ = _moe_share((8 * chip, 8), whole)
        shared_term = layer.shared_expert(x)._data
        parts.append(layer(x)._data - shared_term)
    assert jnp.allclose(sum(parts) + shared_term, want, atol=2e-5)
    # 48 tokens x 6 choices over 16 chips: every chip was given something
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    assert float(jnp.abs(shared_term).max()) > 1e-3

    tokens = x._data.reshape(-1, 32)
    f32 = lambda name: jnp.asarray(whole[name], jnp.float32)
    s = jax.nn.sigmoid(tokens @ f32("router_weight").T)
    _, idx = jax.lax.top_k(s + f32("router_bias"), 6)
    chosen = jnp.take_along_axis(s, idx, 1)
    weights = 2.5 * chosen / chosen.sum(-1, keepdims=True)

    def expert(up, down):
        return jnp.square(jax.nn.relu(tokens @ up.T)) @ down.T
    by_hand = expert(f32("shared_up_weight"), f32("shared_down_weight")) \
        + sum(jnp.sum(jnp.where(idx == e, weights, 0.0), -1)[:, None]
              * expert(f32("up_weight")[e], f32("down_weight")[e])
              for e in range(128))
    assert jnp.allclose(by_hand.reshape(want.shape), want, atol=2e-5)


# ---------------------------------------------------------------------------
# what the comparison parts: bf16 products pass, fp8 products fail
# ---------------------------------------------------------------------------

def test_bf16_products_pass_and_fp8_products_fail(model, reference):
    """The plain reference with its products' operands rounded to bf16
    (the precision the configuration states) against the float32 one stays
    inside the tiny ``reference_limits``; rounded to fp8 it does not. The
    limits stand between the two (limits/<cell>.json says from what)."""
    cfg = tiny_cfg()
    with open(os.path.join(GRID, "limits",
                           f"{NAME}.train-b1-s4096.json")) as f:
        limits = json.load(f)["tiny"]["reference_limits"]
    traffic = {"batch": 4, "seq": 32, "pool": 3}
    optimizer = ("adam", {"name": "adam", "learning_rate": 1e-5})

    def follow(precision):
        return reference.follow(
            model.loss_sum(cfg, reference.make_dot(precision)),
            reference.make_weights(model.param_spec(cfg), 1),
            model.batches(cfg, traffic, 1), optimizer, steps=3,
            block_rows=2)
    exact = follow("f32")
    ok, compared = reference.compare(follow("bf16"), exact, limits)
    assert ok, compared
    ok, compared = reference.compare(follow("fp8"), exact, limits)
    assert not ok and any(c["value"] > c["limit"]
                          for c in compared.values()), compared
