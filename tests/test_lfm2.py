"""LFM2-MoE on the CPU at small sizes, float32 at ``highest``, seeded
weights: the gated short convolution against a loop over positions, the
per-head q/k norm against one written by hand, causality through conv and
attention, the LM through ``TrainLoop`` against the plain reference the
benchmark keeps (``benchmark/grid/configs/lfm2-24b-a2b.py``: its logits,
its loss and every leaf's gradient), the test that ties one chip's share
of the experts to the whole layer, and the router's epsilon.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, gluon, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.lfm2 import LFM2MoeLM
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import moe as MOE
from mxnet_tpu.ops import ssm as SSM
from mxnet_tpu.telemetry import names as tnames

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRID = os.path.join(ROOT, "benchmark", "grid")
NAME = "lfm2-24b-a2b"


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def grid_module(name):
    spec = importlib.util.spec_from_file_location(
        "lfm2_test_" + name.replace("/", "_").replace("-", "_")
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


def _counted(name, label=None):
    return telemetry.value(name, label) or 0


def _rel(got, want):
    got, want = (onp.asarray(a, "float32") for a in (got, want))
    return float(onp.abs(got - want).max()) / max(
        float(onp.abs(want).max()), 1e-12)


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------

def _loop(bcx, w):
    """``C[t] * sum_j w[:, j] (B * x)[t - K + 1 + j]`` position by
    position, zeros before the sequence starts."""
    c, taps = w.shape
    b, gate, x = bcx[..., :c], bcx[..., c:2 * c], bcx[..., 2 * c:]
    out = onp.zeros(b.shape, "float64")
    for t in range(bcx.shape[1]):
        for j in range(taps):
            s = t - taps + 1 + j
            if s >= 0:
                out[:, t] += w[:, j] * b[:, s] * x[:, s]
    return out * gate


@pytest.mark.parametrize("taps", [3, 4])
def test_gated_short_conv_is_a_loop_over_positions(taps):
    """Forward and both gradients against the loop above (autodiff of its
    jnp twin): float32 sums of at most four products in another order,
    so 1e-6 of the largest entry."""
    rng = onp.random.default_rng(taps)
    bcx = rng.normal(size=(2, 11, 3 * 6)).astype("float32")
    w = rng.normal(size=(6, taps)).astype("float32")
    got = SSM.gated_short_conv(jnp.asarray(bcx), jnp.asarray(w))
    assert got.shape == (2, 11, 6) and got.dtype == jnp.float32
    assert _rel(got, _loop(bcx.astype("float64"), w.astype("float64"))) \
        < 1e-6

    def twin(bcx_, w_):
        c = w_.shape[0]
        v = bcx_[..., :c] * bcx_[..., 2 * c:]
        conv = sum(w_[:, j] * jnp.pad(v, ((0, 0), (taps - 1 - j, 0), (0, 0)))
                   [:, :v.shape[1]] for j in range(taps))
        return bcx_[..., c:2 * c] * conv
    g = jnp.asarray(rng.normal(size=got.shape), jnp.float32)
    grads = jax.vjp(SSM.gated_short_conv, jnp.asarray(bcx),
                    jnp.asarray(w))[1](g)
    want = jax.vjp(twin, jnp.asarray(bcx), jnp.asarray(w))[1](g)
    for a, b in zip(grads, want):
        assert _rel(a, b) < 1e-6
    # bf16 in, bf16 out, the sums in float32 between: three inputs and
    # the output rounded to bf16 (2^-9 each) move an entry by a few of
    # its ulps, 2^-6 of the largest
    low = SSM.gated_short_conv(jnp.asarray(bcx, jnp.bfloat16),
                               jnp.asarray(w))
    assert low.dtype == jnp.bfloat16
    assert _rel(low, got) < 2.0 ** -6
    # a later input moves no earlier output
    later = bcx.copy()
    later[:, 7:] += 1.0
    moved = SSM.gated_short_conv(jnp.asarray(later), jnp.asarray(w))
    assert onp.array_equal(onp.asarray(moved)[:, :7], onp.asarray(got)[:, :7])


def test_gated_short_conv_runs_under_its_scope():
    """The gates, the conv and their backward under ``short_conv``; the
    op holds no projection."""
    def loss(bcx, w):
        return jnp.sum(SSM.gated_short_conv(bcx, w))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jnp.ones((1, 8, 12)), jnp.ones((4, 3))).as_text(debug_info=True)
    assert 'short_conv' in text
    assert tnames.SCOPE_SHORT_CONV == "short_conv"
    assert "dot_general" not in text


def test_short_conv_mixer_is_its_equations():
    mixer = nn.ShortConvMixer(16, 3)
    rng = onp.random.default_rng(5)
    for p in mixer.collect_params().values():
        p.set_data(mx.nd.array(rng.normal(size=p.shape).astype("float32")
                               * 0.3))
    w = {n: onp.asarray(p.data()._data, "float64")
         for n, p in mixer.collect_params().items()}
    assert list(w) == ["conv_weight", "in_proj.weight", "out_proj.weight"]
    assert w["in_proj.weight"].shape == (48, 16)
    u = rng.normal(size=(2, 9, 16))
    want = _loop(u @ w["in_proj.weight"].T, w["conv_weight"]) \
        @ w["out_proj.weight"].T
    before = _counted(tnames.SHORT_CONV)
    got = mixer(mx.nd.array(u.astype("float32")))._data
    assert _counted(tnames.SHORT_CONV) == before + 1
    # float32 at highest against float64: sums of 16 and 48 products
    assert _rel(got, want) < 2e-6
    # under AMP the projections and the op's output are bf16: two bf16
    # products and three roundings of activations, a few percent
    amp.init()
    try:
        low = mixer(mx.nd.array(u.astype("float32")))._data
    finally:
        amp.uninit()
    assert low.dtype == jnp.bfloat16 and _rel(low, want) < 0.03
    assert not {"gated_short_conv"} & (amp.FP32_OPS | amp.TARGET_DTYPE_OPS)


# ---------------------------------------------------------------------------
# the per-head q/k norm
# ---------------------------------------------------------------------------

def _attention_by_hand(u, w, heads, kv, d, theta, eps):
    """RMSNorm of each head of q and k, rotate-half RoPE, causal softmax
    over the key/value head each query head reads, in float64."""
    b, s, _ = u.shape

    def norm(x, gain):
        return x / onp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * gain

    def turn(x):
        inv = theta ** (-onp.arange(0, d, 2) / d)
        angle = onp.arange(s)[:, None] * inv[None]
        cos, sin = onp.cos(angle)[:, None], onp.sin(angle)[:, None]
        a, c = x[..., :d // 2], x[..., d // 2:]
        return onp.concatenate([a * cos - c * sin, c * cos + a * sin], -1)
    q = turn(norm((u @ w["query_proj.weight"].T).reshape(b, s, heads, d),
                  w["q_norm_gamma"]))
    k = turn(norm((u @ w["key_proj.weight"].T).reshape(b, s, kv, d),
                  w["k_norm_gamma"]))
    v = (u @ w["value_proj.weight"].T).reshape(b, s, kv, d)
    out = onp.zeros((b, s, heads, d))
    for h in range(heads):
        g = h // (heads // kv)
        scores = onp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, g]) \
            / onp.sqrt(d)
        scores = onp.where(onp.tril(onp.ones((s, s), bool)), scores,
                           -onp.inf)
        p = onp.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, :, h] = p @ v[:, :, g]
    return out.reshape(b, s, heads * d) @ w["out_proj.weight"].T


def test_qk_norm_is_a_norm_of_each_head():
    """float32 against float64 by hand: 2e-5 of the largest entry."""
    heads, kv, d, eps = 4, 2, 8, 1e-5
    att = nn.MultiHeadAttention(32, heads, use_bias=False, causal=True,
                                head_dim=d, num_kv_heads=kv,
                                rope_theta=1e6, qk_norm=eps)
    rng = onp.random.default_rng(3)
    for name, p in att.collect_params().items():
        value = rng.normal(size=p.shape) * (0.3 if "gamma" in name else
                                            32 ** -0.5)
        p.set_data(mx.nd.array((1 + value if "gamma" in name else value)
                               .astype("float32")))
    w = {n: onp.asarray(p.data()._data, "float64")
         for n, p in att.collect_params().items()}
    assert list(w)[:2] == ["q_norm_gamma", "k_norm_gamma"]
    assert w["q_norm_gamma"].shape == w["k_norm_gamma"].shape == (d,)
    u = rng.normal(size=(2, 13, 32)) * 3.0
    got = att(mx.nd.array(u.astype("float32")))._data
    assert _rel(got, _attention_by_hand(u, w, heads, kv, d, 1e6, eps)) \
        < 2e-5
    # without the norm the same weights give another function, and the
    # layer holds no gain
    plain = nn.MultiHeadAttention(32, heads, use_bias=False, causal=True,
                                  head_dim=d, num_kv_heads=kv,
                                  rope_theta=1e6)
    assert not [n for n in plain.collect_params() if "norm" in n]
    for name, p in plain.collect_params().items():
        p.set_data(mx.nd.array(w[name].astype("float32")))
    assert _rel(plain(mx.nd.array(u.astype("float32")))._data, got) > 0.05
    # the norm runs in float32 under AMP, inside its own scope
    assert "qk_norm" in amp.FP32_OPS and tnames.SCOPE_QK_NORM == "qk_norm"
    text = jax.jit(lambda a: att(NDArray(a))._data).lower(
        jnp.asarray(u, jnp.float32)).as_text(debug_info=True)
    assert "qk_norm" in text


# ---------------------------------------------------------------------------
# the LM through TrainLoop against the reference
# ---------------------------------------------------------------------------

def seeded_net(cfg, model, reference, seed=3):
    net = model.build_net(cfg, {})
    spec = model.param_spec(cfg)
    params = net.collect_params()
    assert list(params) == [name for name, *_ in spec]
    weights = reference.make_weights(spec, seed)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p.set_data(NDArray(weights[name]))
    return net, weights


def test_lm_logits_loss_and_every_gradient_through_trainloop(model,
                                                             reference):
    """The program's logits against the reference's forward (float32 sums
    in another order, over logits about 10 x sqrt(128) wide: 2e-5 of the
    largest), then ONE fused step: its loss against the reference's
    (2e-5) and, read back from SGD's momentum (m = -lr g after one
    step), every leaf's gradient against the reference's (2e-4 of the
    leaf's largest entry: a top-2 choice whose scores tie within float32
    rounding would part them, and none does at this seed)."""
    cfg = tiny_cfg()
    assert model.layer_types(cfg) == ["conv", "full_attention", "conv"]
    net, weights = seeded_net(cfg, model, reference)
    (x, y), = model.batches(cfg, {"batch": 4, "seq": 24, "pool": 1}, 11)
    logits = net(int_nd(x))._data
    assert logits.shape == (4, 24, cfg["vocab_rows"])
    dot = reference.make_dot("f32")
    want = model.forward(cfg, dot)(weights, jnp.asarray(x))
    assert _rel(logits, want) < 2e-5
    loss, grads = jax.value_and_grad(model.loss_sum(cfg, dot))(
        weights, jnp.asarray(x), jnp.asarray(y))
    lr = 0.5
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr, "momentum": 0.9},
                            kvstore="tpu")
    loop = gluon.TrainLoop(net, trainer,
                           gluon.loss.SoftmaxCrossEntropyLoss())
    losses = loop.step(int_nd(x), int_nd(y))
    loop.synchronize()
    step = loop.compiled_step
    assert step.mode == "fused" and step.n_traces == 1
    assert float(jnp.sum(losses._data)) == pytest.approx(float(loss),
                                                         rel=2e-5)
    names = sorted(net.collect_params())
    state = step.optimizer_state_buffers()
    assert len(state) == len(names)
    for name, m in zip(names, state):
        got = onp.asarray(m) / -lr
        want = onp.asarray(grads[name]) / 4
        assert _rel(got, want) < 2e-4, name
        if name.endswith("router_bias"):
            assert not got.any() and not want.any()
        else:
            assert onp.abs(want).max() > 0, name
    # the head is the embedding: no table of its own
    assert not [n for n in names if "head" in n]


def test_a_token_moves_no_earlier_logit(model, reference):
    """Changing token t leaves the logits before t as they were, through
    the short convs (3 taps back) and causal attention; it moves t and
    what follows."""
    cfg = tiny_cfg()
    net, _ = seeded_net(cfg, model, reference)
    x = onp.random.default_rng(4).integers(0, cfg["vocab_rows"], (2, 20))
    t = 9
    changed = x.copy()
    changed[:, t] = (changed[:, t] + 1) % cfg["vocab_rows"]
    a, b = (onp.asarray(net(int_nd(v))._data) for v in (x, changed))
    # the same sums at every earlier position; 1e-6 leaves room for a sum
    # XLA might tile differently when a later row moves
    assert onp.abs(a[:, :t] - b[:, :t]).max() <= 1e-6 * onp.abs(a).max()
    assert (onp.abs(a[:, t:] - b[:, t:]).max(-1) > 1e-3).all()


def test_lm_counts_what_it_traces(model, reference):
    cfg = tiny_cfg()
    net, _ = seeded_net(cfg, model, reference)
    read = {"convs": lambda: _counted(tnames.SHORT_CONV),
            "sigmoid": lambda: _counted(tnames.MOE_ROUTER, "sigmoid"),
            "grouped": lambda: _counted(tnames.MOE_DISPATCH, "grouped"),
            "causal": lambda: _counted(tnames.ATTENTION_MASK, "causal")}
    before = {k: f() for k, f in read.items()}
    net(int_nd(onp.zeros((2, 12))))
    counted = {k: f() - before[k] for k, f in read.items()}
    # conv (dense), attention (experts), conv (experts)
    assert counted == {"convs": 2, "sigmoid": 2, "grouped": 2, "causal": 1}
    assert tnames.SHORT_CONV in tnames.CATALOG
    assert [type(l.mixer).__name__ for l in net.layers] == [
        "ShortConvMixer", "MultiHeadAttention", "ShortConvMixer"]
    assert net.layer1.mixer._qk_norm == cfg["norm_eps"]
    assert net.layer1.mixer._rope_theta == 1e6
    assert net.layer_types == ["conv", "full_attention", "conv"]


def test_the_layers_come_from_layer_types():
    cfg = dict(tiny_cfg(), layer_types=["conv", "full_attention", "conv"])
    assert len(LFM2MoeLM(cfg).layers) == 3
    with pytest.raises(ValueError, match="mamba"):
        LFM2MoeLM(dict(cfg, layer_types=["conv", "mamba", "conv"]))
    with pytest.raises(ValueError):
        LFM2MoeLM(dict(cfg, num_hidden_layers=4))
    with pytest.raises(ValueError):
        LFM2MoeLM(dict(cfg, conv_bias=True))
    with pytest.raises(ValueError):
        LFM2MoeLM(dict(cfg, norm_topk_prob=False))


# ---------------------------------------------------------------------------
# the shares add up; the router's epsilon
# ---------------------------------------------------------------------------

def _moe_share(held, whole=None, units=32, hidden=16, e=64, k=4, seed=7):
    layer = nn.SparseMoE(units, hidden, e, k, held=held, score="sigmoid",
                         activation="silu", norm_eps=1e-6)
    if whole is None:
        rng = onp.random.default_rng(seed)
        whole = {
            "router_weight": rng.normal(size=(e, units)) * units ** -0.5,
            "router_bias": rng.normal(size=(e,)) * 0.05,
            "gate_weight": rng.normal(size=(e, hidden, units)) * 0.3,
            "up_weight": rng.normal(size=(e, hidden, units)) * 0.3,
            "down_weight": rng.normal(size=(e, units, hidden)) * 0.3}
    first, count = held
    assert sorted(layer.collect_params()) == sorted(whole)
    for name, p in layer.collect_params().items():
        w = whole[name]
        if name in ("gate_weight", "up_weight", "down_weight"):
            w = w[first:first + count]
        p.set_data(mx.nd.array(onp.asarray(w, "float32")))
    return layer, whole


def test_eight_shares_add_up_to_the_uncut_layer():
    """The cell's deployment at a small size: 8 chips hold 8 of the 64
    experts each, no shared expert. Their parts add up to the uncut
    layer, and the uncut layer is a loop over all 64 SwiGLU experts
    weighed by s / (sum of the chosen s + 1e-6) (float32 sums in another
    order: 2e-5)."""
    x = mx.nd.array(onp.random.default_rng(1).normal(size=(2, 24, 32))
                    .astype("float32"))
    full, whole = _moe_share((0, 64))
    want = full(x)._data
    parts = [_moe_share((8 * chip, 8), whole)[0](x)._data
             for chip in range(8)]
    assert jnp.allclose(sum(parts), want, atol=2e-5)
    # 48 tokens x 4 choices over 8 chips: every chip was given something
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)

    tokens = x._data.reshape(-1, 32)
    f32 = lambda name: jnp.asarray(whole[name], jnp.float32)
    s = jax.nn.sigmoid(tokens @ f32("router_weight").T)
    _, idx = jax.lax.top_k(s + f32("router_bias"), 4)
    chosen = jnp.take_along_axis(s, idx, 1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    by_hand = sum(
        jnp.sum(jnp.where(idx == e, weights, 0.0), -1)[:, None]
        * ((jax.nn.silu(tokens @ f32("gate_weight")[e].T)
            * (tokens @ f32("up_weight")[e].T)) @ f32("down_weight")[e].T)
        for e in range(64))
    assert jnp.allclose(by_hand.reshape(want.shape), want, atol=2e-5)


def test_the_routers_epsilon_is_added_only_where_asked():
    """Scores of about 1e-6 (logits near -14): with the family's 1e-6 the
    chosen weights sum to about s / (s + 1e-6), without it to 1; the
    default is without (the JoyAI and Nemotron cells' rule)."""
    rng = onp.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(8, 8)) * 0.01, jnp.float32)
    x, router = x.at[:, 0].set(1.0), router.at[:, 0].set(-14.0)
    plain, *_ = MOE.moe_route(x, router, 2, (0, 8), score="sigmoid")
    eps, *_ = MOE.moe_route(x, router, 2, (0, 8), score="sigmoid",
                            norm_eps=1e-6)
    assert onp.allclose(onp.asarray(plain).sum(-1), 1.0, atol=1e-6)
    s = onp.asarray(jax.nn.sigmoid(x @ router.T))
    chosen = onp.sort(s, -1)[:, -2:].sum(-1)
    # float32 scores of about 1e-6 against numpy's sort of the same: 1e-5
    assert onp.allclose(onp.asarray(eps).sum(-1), chosen / (chosen + 1e-6),
                        rtol=1e-5)
    assert onp.asarray(eps).sum(-1).max() < 0.9
    # a router given no epsilon lowers to the text it had before the
    # argument existed: no add of a zero
    text = jax.jit(lambda a, b: MOE.moe_route(a, b, 2, (0, 8),
                                              score="sigmoid")[0]) \
        .lower(x, router).as_text()
    text_eps = jax.jit(lambda a, b: MOE.moe_route(
        a, b, 2, (0, 8), score="sigmoid", norm_eps=1e-6)[0]) \
        .lower(x, router).as_text()
    assert text_eps.count("stablehlo.add") == text.count("stablehlo.add") + 1
