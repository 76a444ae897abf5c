"""JoyAI-LLM-Flash on the CPU at the tiny size, float32, seeded weights,
against the plain reference the benchmark keeps
(``benchmark/grid/configs/joyai-llm-flash.py`` ``loss_sum``, plain
``jax.numpy``): the LM with its MTP module through ``TrainLoop``, then
block by block: attention with keys wider than values through the XLA tier
and the Pallas kernels in interpret mode, RoPE over a slice in the
interleaved pairing, the sigmoid router with its selection bias, the shared
expert, and the test that ties one chip's share of the experts to the whole
layer. The older models' calls keep the tiling they had.
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
from mxnet_tpu.gluon.model_zoo.joyai import GatedFFN, JoyAILM
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import attention as ATT
from mxnet_tpu.ops import moe as MOE
from mxnet_tpu.telemetry import names as tnames

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRID = os.path.join(ROOT, "benchmark", "grid")
NAME = "joyai-llm-flash"


def grid_module(name):
    spec = importlib.util.spec_from_file_location(
        "joyai_test_" + name.replace("/", "_").replace("-", "_")
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


def seeded_net(cfg, model, reference, seed=3):
    net = JoyAILM(cfg)
    spec = model.param_spec(cfg)
    params = net.collect_params()
    assert list(params) == [name for name, *_ in spec]
    weights = reference.make_weights(spec, seed)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p.set_data(NDArray(weights[name]))
    return net, weights


def int_nd(a):
    return mx.nd.array(a, dtype="int32")


# ---------------------------------------------------------------------------
# the LM through TrainLoop against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mtp", [1, 0], ids=["with_mtp", "trunk_alone"])
def test_lm_logits_loss_and_every_gradient_through_trainloop(
        mtp, model, reference):
    cfg = tiny_cfg(num_nextn_predict_layers=mtp)
    net, weights = seeded_net(cfg, model, reference)
    batch, seq = 4, 32
    (x, y), = model.batches(cfg, {"batch": batch, "seq": seq, "pool": 1}, 11)
    if not mtp:
        x, y = x[:, :seq], y[:, :seq]
    dot = reference.make_dot("f32")
    f = model.loss_sum(cfg, dot)
    loss_ref, grads_ref = jax.value_and_grad(f)(weights, x, y)

    # the logits of each head: a head's rows of the output against the
    # reference's loss over that head alone (the trunk's is the reference
    # of the model without the module, on the same weights)
    logits = net(int_nd(x))._data
    assert logits.shape == (batch, (1 + mtp) * seq, cfg["vocab_rows"])
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, jnp.asarray(y)[..., None], -1)[..., 0]
    trunk_ref = float(model.loss_sum(
        dict(cfg, num_nextn_predict_layers=0), dot)(
            weights, x[:, :seq], y[:, :seq]))
    assert float(-jnp.sum(picked[:, :seq]) / seq) == pytest.approx(
        trunk_ref, rel=2e-5)
    if mtp:
        module_ref = 2 * float(loss_ref) - trunk_ref
        assert float(-jnp.sum(picked[:, seq:]) / seq) == pytest.approx(
            module_ref, rel=2e-5)
        assert abs(module_ref - trunk_ref) > 1e-4    # two heads, two losses

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
    assert float(jnp.sum(losses._data)) == pytest.approx(
        float(loss_ref), rel=2e-5)
    # SGD with momentum keeps m = -lr * g after one step: every leaf's
    # gradient (of the batch MEAN) reads off the state
    state = step.optimizer_state_buffers()
    names = sorted(net.collect_params())
    assert len(state) == len(names)
    for name, m in zip(names, state):
        got = onp.asarray(m) / -lr
        want = onp.asarray(grads_ref[name]) / batch
        scale = max(float(onp.abs(want).max()), 1e-12)
        assert onp.abs(got - want).max() / scale < 2e-3, name
        if name.endswith("router_bias"):
            # the selection bias picks and never weighs: no gradient
            assert not got.any() and not want.any()
    loop.step(int_nd(x), int_nd(y))
    loop.synchronize()
    assert step.n_traces == 1


def test_tied_tables_receive_the_gradients_of_both_heads(model, reference):
    """The MTP module's embedding and head are the trunk's parameters:
    one ``Parameter`` each, named once, and their gradient is the sum of
    the trunk's and the module's (the reference's total), not the
    trunk's alone."""
    cfg = tiny_cfg()
    net, weights = seeded_net(cfg, model, reference)
    params = net.collect_params()
    assert [n for n in params if n.endswith("embed.weight")] == \
        ["embed.weight"]
    assert [n for n in params if "head.weight" in n] == ["head.weight"]
    assert not any(n.startswith("mtp.") and ("embed." in n or "head." in n)
                   for n in params)
    (x, y), = model.batches(cfg, {"batch": 2, "seq": 16, "pool": 1}, 5)
    dot = reference.make_dot("f32")
    both = jax.grad(model.loss_sum(cfg, dot))(weights, x, y)
    trunk = jax.grad(model.loss_sum(
        dict(cfg, num_nextn_predict_layers=0), dot))(
            weights, x[:, :16], y[:, :16])
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 1.0, "momentum": 0.9},
                            kvstore="tpu")
    loop = gluon.TrainLoop(net, trainer,
                           gluon.loss.SoftmaxCrossEntropyLoss())
    loop.step(int_nd(x), int_nd(y))
    loop.synchronize()
    state = dict(zip(sorted(params),
                     loop.compiled_step.optimizer_state_buffers()))
    for name in ("embed.weight", "head.weight"):
        got = -onp.asarray(state[name]) * 2       # the mean over 2 rows
        want = onp.asarray(both[name])
        # the trunk's half alone is another gradient: the total is over
        # 2 x seq predictions, the trunk-only loss over seq
        alone = onp.asarray(trunk[name]) / 2
        scale = onp.abs(want).max()
        assert onp.abs(got - want).max() / scale < 2e-3, name
        assert onp.abs(alone - want).max() / scale > 0.05, name


def test_lm_counts_what_it_traces(model, reference):
    cfg = tiny_cfg()
    net, _ = seeded_net(cfg, model, reference)
    read = {"latent": lambda: telemetry.value(tnames.LATENT_ATTENTION,
                                              "expanded") or 0,
            "sigmoid": lambda: telemetry.value(tnames.MOE_ROUTER,
                                               "sigmoid") or 0,
            "softmax": lambda: telemetry.value(tnames.MOE_ROUTER,
                                               "softmax") or 0,
            "mtp": lambda: telemetry.value(tnames.MTP_MODULES) or 0,
            "grouped": lambda: telemetry.value(tnames.MOE_DISPATCH,
                                               "grouped") or 0,
            "causal": lambda: telemetry.value(tnames.ATTENTION_MASK,
                                              "causal") or 0}
    before = {k: f() for k, f in read.items()}
    net(int_nd(onp.zeros((2, 17))))
    counted = {k: f() - before[k] for k, f in read.items()}
    # two trunk layers (one dense, one with experts) and the module's
    assert counted == {"latent": 3, "sigmoid": 2, "softmax": 0, "mtp": 1,
                       "grouped": 2, "causal": 3}
    for name in (tnames.LATENT_ATTENTION, tnames.MOE_ROUTER,
                 tnames.MTP_MODULES):
        assert name in tnames.CATALOG and name.startswith("mx_")
    stats = net.layer1.experts.routing_stats(
        mx.nd.array(onp.random.default_rng(0).normal(size=(64, 64))))
    assert stats["pairs"].shape == (2,) and 0 < stats["held_share"] < 1


def test_amp_keeps_router_and_latent_norms_in_float32():
    assert {"moe_route", "latent_norm"} <= amp.FP32_OPS
    assert {"shared_expert", "moe_experts", "flash_attention"} <= \
        amp.TARGET_DTYPE_OPS
    wrap = amp._make_wrapper(jnp.bfloat16)
    seen = {}

    def fn(x, g):
        seen["dtypes"] = (x.dtype, g.dtype)
        return x
    x, g = jnp.ones((2, 4), jnp.bfloat16), jnp.ones((4,), jnp.float32)
    wrap("latent_norm", fn)(x, g)
    assert seen["dtypes"] == (jnp.float32, jnp.float32)
    wrap("shared_expert", fn)(x.astype(jnp.float32), g)
    assert seen["dtypes"] == (jnp.bfloat16, jnp.bfloat16)


# ---------------------------------------------------------------------------
# attention with keys wider than values
# ---------------------------------------------------------------------------

def _qkv(b, h, s, d, dv, hkv=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    hkv = hkv or h
    return (jax.random.normal(ks[0], (b, h, s, d)),
            jax.random.normal(ks[1], (b, hkv, s, d)),
            jax.random.normal(ks[2], (b, hkv, s, dv)),
            jax.random.normal(ks[3], (b, h, s, dv)))


def _close(got, want, tol=2e-4):
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < tol


@pytest.mark.parametrize("blocks", [16, 64], ids=["multi_block",
                                                  "one_block"])
@pytest.mark.parametrize("form", ["bsh", "bhsd"])
@pytest.mark.parametrize("widths", [(192, 128), (48, 32)],
                         ids=["192x128", "48x32"])
def test_pallas_kernels_take_two_widths(widths, form, blocks):
    """Forward, dq, dk/dv (several blocks) and the fused backward (one)
    in interpret mode against the unfused oracle, keys ``d`` wide beside
    values ``dv`` wide: from (B, S, H*D) the heads share whole lane tiles
    (two of 192 on 384 lanes, eight of 48), from (B, H, S, D) a head is
    padded or not as its widths fit."""
    (d, dv), h, s = widths, 8 if widths[0] == 48 else 4, 64
    q, k, v, do = _qkv(1, h, s, d, dv)
    scale = d ** -0.5
    want_o, vjp = jax.vjp(lambda *a: ATT.attention_reference(
        *a, causal=True, sm_scale=scale), q, k, v)
    want = (want_o,) + vjp(do)
    heads = h if form == "bsh" else None
    to = ATT._merge_heads if form == "bsh" else (lambda a: a)
    back = (lambda a: ATT._split_heads(a, h)) if form == "bsh" \
        else (lambda a: a)
    args = (to(q), to(k), to(v))
    o, lse = ATT._flash_fwd_pallas(*args, True, scale, blocks, blocks,
                                   True, heads)
    grads = ATT._flash_bwd_pallas(*args, o, lse, to(do), True, scale,
                                  blocks, blocks, True, heads)
    _close([back(a) for a in (o,) + tuple(grads)], want)
    t = ATT._tiles(args[0].shape, args[1].shape, blocks, blocks, heads,
                   None, args[2].shape)
    assert (t.head_dim, t.head_dim_v) == (d, dv)
    if form == "bsh":
        assert t.layout == "packed" and t.width % 128 == 0 \
            and t.width_v % 128 == 0 and t.width // t.heads == d
    else:
        # neither 192 nor 48 divides 128 or is a multiple of it
        assert t.layout == "padded" and t.width_v == 128
    assert (t.nq == 1) == (blocks == 64)


@pytest.mark.parametrize("case", ["192x128", "48x32", "grouped_256x128",
                                  "window_128x64", "full_192x128"])
def test_public_entry_points_take_two_widths(case, monkeypatch):
    """``flash_attention`` / ``flash_attention_bsh`` on the XLA tier (the
    CPU's) and, forced, through the kernels in interpret mode: the
    result has the values' width, the gradients their operands'."""
    d, dv, h, hkv, window, causal = {
        "192x128": (192, 128, 4, 4, None, True),
        "48x32": (48, 32, 8, 8, None, True),
        "grouped_256x128": (256, 128, 4, 2, None, True),
        "window_128x64": (128, 64, 4, 4, 24, True),
        "full_192x128": (192, 128, 2, 2, None, False)}[case]
    q, k, v, do = _qkv(2, h, 48, d, dv, hkv)
    want_o, vjp = jax.vjp(lambda *a: ATT.attention_reference(
        *a, causal=causal, window=window), q, k, v)
    want = (want_o,) + vjp(do)
    for tier in ("off", "on"):
        monkeypatch.setenv("MXNET_PALLAS", tier)
        o, vjp = jax.vjp(lambda *a: ATT.flash_attention(
            *a, causal=causal, window=window), q, k, v)
        _close((o,) + vjp(do), want)
        merged = [ATT._merge_heads(a) for a in (q, k, v, do)]
        o, vjp = jax.vjp(lambda *a: ATT.flash_attention_bsh(
            *a, h, causal=causal, num_kv_heads=hkv, window=window),
            *merged[:3])
        assert o.shape == (2, 48, h * dv)
        got = (o,) + vjp(merged[3])
        _close([ATT._split_heads(a, n) for a, n in
                zip(got, (h, h, hkv, hkv))], want)


@pytest.mark.parametrize("heads,width", [(2, 384), (4, 384), (8, 384),
                                         (2, 256), (2, 128), (1, 256),
                                         (16, 128)])
def test_lanes_cut_and_join_are_inverse(heads, width):
    """A head is reached through the lane tiles that hold it; joining
    every head's span back, each on its own lanes, gives the block."""
    lanes = ATT._Lanes(heads, width)
    x = jnp.arange(2 * 8 * width, dtype=jnp.float32).reshape(2, 8, width)
    masks = lanes.masks(x.shape)
    d = width // heads
    for i in range(heads):
        lo, hi = lanes.span(i)
        assert lo % 128 == 0 or lanes.whole
        assert lo <= i * d and (i + 1) * d <= hi
        own = ATT._only(lanes.cut(x, i), masks[i])
        # the head's lanes kept, every other lane of its span zeroed
        want = onp.zeros((2, 8, hi - lo), "float32")
        want[..., i * d - lo:(i + 1) * d - lo] = \
            onp.asarray(x)[..., i * d:(i + 1) * d]
        assert onp.array_equal(onp.asarray(own), want)
    # parts that hold garbage outside the head's lanes join to the block
    parts = [lanes.cut(x, i) + 0.0 for i in range(heads)]
    assert onp.array_equal(onp.asarray(lanes.join(parts, masks)),
                           onp.asarray(x))
    # per-row scalars spread over each head's lanes
    rows = [jnp.full((2, 8, 1), float(i)) for i in range(heads)]
    spread = onp.broadcast_to(onp.asarray(lanes.join(rows, masks)), x.shape)
    assert onp.array_equal(spread[0, 0], onp.repeat(onp.arange(heads), d))


TILINGS = {
    # BERT-base: 12 heads of 64 from the projections' (32, 512, 768)
    "bert": (((32, 512, 768), (32, 512, 768), 512, 512, 12), dict(
        layout="packed", rows=32, col_tiles=6, width=128, heads=2, group=1,
        block_q=512, block_k=512, nq=1, nk=1, head_dim_v=64, width_v=128)),
    # SmallThinker: 28 query heads over 4 key/value heads of 128, a window
    "smallthinker": (((1, 8192, 3584), (1, 8192, 512), 1024, 1024, 28,
                      4096), dict(
        layout="packed", rows=1, col_tiles=28, width=128, heads=1, group=7,
        window=4096, block_q=1024, block_k=1024, nq=8, nk=8,
        head_dim_v=128, width_v=128)),
    # JoyAI's latent attention: 32 heads, keys 192 wide, values 128
    "joyai": (((1, 4096, 6144), (1, 4096, 6144), 1024, 1024, 32, None,
               (1, 4096, 4096)), dict(
        layout="packed", rows=1, col_tiles=16, width=384, heads=2, group=1,
        block_q=1024, block_k=1024, nq=4, nk=4, head_dim=192,
        head_dim_v=128, width_v=256)),
    # the same heads as (B, H, S, D): folded, the keys padded in HBM
    "joyai_bhsd": (((1, 32, 4096, 192), (1, 32, 4096, 192), 1024, 1024,
                    None, None, (1, 32, 4096, 128)), dict(
        layout="padded", rows=32, col_tiles=1, width=256, heads=1,
        head_dim_v=128, width_v=128)),
    # widths of 80 keep their padded fold, of 32 their shared tile
    "d80": (((2, 256, 320), (2, 256, 320), 512, 512, 4), dict(
        layout="padded", width=128, heads=1, width_v=128)),
    "d32_bhsd": (((2, 4, 256, 32), (2, 4, 256, 32), 512, 512, None), dict(
        layout="unpadded", width=32, heads=1, width_v=32)),
}


@pytest.mark.parametrize("name", sorted(TILINGS))
def test_calls_keep_their_tiling(name):
    args, want = TILINGS[name]
    t = ATT._tiles(*args)
    assert {k: getattr(t, k) for k in want} == want
    if name in ("bert", "smallthinker"):
        # one width for q, k and v: the kernels' lanes are one object's
        assert t.lanes == t.lanes_v and t.lanes.whole
    if name == "joyai":
        assert t.lanes.span(0) == (0, 256) and t.lanes.span(1) == (128, 384)
        assert t.lanes_v.span(1) == (256 // 2, 256) and "192" in t.reason \
            or "values D=128" in t.reason


# ---------------------------------------------------------------------------
# RoPE over a slice, interleaved
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interleave", [True, False],
                         ids=["interleaved", "rotate_half"])
@pytest.mark.parametrize("lanes", [(16, 8), None], ids=["slice", "whole"])
def test_rope_keeps_scores_relative(lanes, interleave):
    """The same query and key content at every position: the score of
    (i, j) depends on i - j alone, the lanes outside the slice pass
    through, and the turn keeps every pair's length."""
    heads, d, s = 2, 24, 12
    rng = onp.random.default_rng(4)
    q1, k1 = (jnp.asarray(rng.normal(size=(1, 1, heads * d)), jnp.float32)
              for _ in range(2))
    q, k = (jnp.broadcast_to(a, (1, s, heads * d)) for a in (q1, k1))
    rq = ATT.rope(q, heads, 1e4, lanes=lanes, interleave=interleave)
    rk = ATT.rope(k, heads, 1e4, lanes=lanes, interleave=interleave)
    scores = jnp.einsum("bqhd,bkhd->hqk", rq.reshape(1, s, heads, d),
                        rk.reshape(1, s, heads, d))
    for shift in (1, 5):
        assert jnp.allclose(scores[:, shift:, shift:],
                            scores[:, :-shift, :-shift], atol=1e-4)
    assert not jnp.allclose(scores[:, 3, 0], scores[:, 0, 0], atol=1e-3)
    first, r = lanes or (0, d)
    per_head = rq.reshape(1, s, heads, d)
    kept = onp.ones(d, bool)
    kept[first:first + r] = False
    assert jnp.array_equal(per_head[..., kept],
                           q.reshape(1, s, heads, d)[..., kept])
    assert jnp.allclose(per_head[:, 0], q.reshape(1, s, heads, d)[:, 0])
    assert jnp.allclose(jnp.linalg.norm(per_head, axis=-1),
                        jnp.linalg.norm(q.reshape(1, s, heads, d), axis=-1),
                        atol=1e-5)


def test_rope_pairings_by_hand():
    """Position 1, theta 1: every pair turns by 1 radian; interleaved
    pairs are neighbours, rotate-half pairs are half a slice apart."""
    x = jnp.asarray([[[1.0, 0.0, 0.0, 1.0, 7.0, 9.0]] * 2])    # (1, 2, 6)
    c, s = onp.cos(1.0), onp.sin(1.0)
    inter = ATT.rope(x, 1, 1.0, lanes=(0, 4), interleave=True)[0, 1]
    assert onp.allclose(inter, [c, s, -s, c, 7.0, 9.0], atol=1e-6)
    half = ATT.rope(x, 1, 1.0, lanes=(0, 4))[0, 1]
    # pairs (x0, x2) and (x1, x3)
    assert onp.allclose(half, [c, -s, s, c, 7.0, 9.0], atol=1e-6)
    with pytest.raises(mx.MXNetError):
        ATT.rope(x, 1, 1.0, lanes=(4, 4))


# ---------------------------------------------------------------------------
# the router's score rules
# ---------------------------------------------------------------------------

def _router_inputs(n=64, d=32, e=16, seed=2):
    rng = onp.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(e, d)) * d ** -0.5, jnp.float32))


def test_sigmoid_bias_picks_and_never_weighs():
    x, rw = _router_inputs()
    k, e = 4, rw.shape[0]
    scores = jax.nn.sigmoid(jnp.einsum("nd,ed->ne", x, rw, precision="highest"))
    flat = MOE.moe_route(x, rw, k, (0, e), score="sigmoid", scale=2.5)
    bias = jnp.zeros(e).at[3].set(10.0).at[5].set(-10.0)
    tilted = MOE.moe_route(x, rw, k, (0, e), score="sigmoid", bias=bias,
                           scale=2.5)

    def chosen(route):
        # the experts of a token's pairs, from each pair's place in the
        # list sorted by expert
        weights, order, place, sizes = route
        expert_of_row = jnp.repeat(jnp.arange(e), sizes,
                                   total_repeat_length=order.shape[0])
        return expert_of_row[place]

    picked_flat, picked_tilted = chosen(flat), chosen(tilted)
    # b changes who is chosen: expert 3 by every token, expert 5 by none
    assert bool(jnp.all(jnp.any(picked_tilted == 3, axis=1)))
    assert not bool(jnp.any(picked_tilted == 5))
    assert bool(jnp.any(picked_flat != picked_tilted))
    assert not bool(jnp.all(jnp.any(picked_flat == 3, axis=1)))
    # ... and never a weight: each is 2.5 s / the chosen s's sum, s without b
    for route, picked in ((flat, picked_flat), (tilted, picked_tilted)):
        s_chosen = jnp.take_along_axis(scores, picked, axis=1)
        want = 2.5 * s_chosen / s_chosen.sum(-1, keepdims=True)
        assert jnp.allclose(route[0], want, atol=1e-6)
        assert jnp.allclose(route[0].sum(-1), 2.5, atol=1e-5)
    # no gradient reaches b; the router's weight gets one
    def total(b, w):
        weights = MOE.moe_route(x, w, k, (0, e), score="sigmoid", bias=b,
                                scale=2.5)[0]
        return jnp.sum(weights * jnp.arange(1, k + 1))
    gb, gw = jax.grad(total, argnums=(0, 1))(bias, rw)
    assert not bool(jnp.any(gb)) and bool(jnp.any(gw))
    with pytest.raises(ValueError):
        MOE.moe_route(x, rw, k, (0, e), score="tanh")


def test_softmax_rule_is_what_it_was():
    """SmallThinker's call, positional and without a rule: the top-k
    logits, weighed by the softmax over those k."""
    x, rw = _router_inputs()
    weights, order, place, sizes = MOE.moe_route(x, rw, 3, (4, 8))
    logits = jnp.einsum("nd,ed->ne", x, rw, precision="highest")
    vals, idx = jax.lax.top_k(logits, 3)
    assert jnp.allclose(weights, jax.nn.softmax(vals, -1), atol=1e-6)
    held = (idx >= 4) & (idx < 12)
    assert int(sizes.sum()) == int(held.sum())
    assert bool(jnp.all((place < sizes.sum()) == held))
    named = MOE.moe_route(x, rw, 3, (4, 8), score="softmax", bias=None)
    for a, b in zip(named, (weights, order, place, sizes)):
        assert jnp.array_equal(a, b)
    assert MOE.SCORES == ("softmax", "sigmoid")


# ---------------------------------------------------------------------------
# the expert layer: activation, shared expert, shares
# ---------------------------------------------------------------------------

def _moe_layer(held, shared, score, seed=7, units=32, hidden=16, e=8, k=3):
    layer = nn.SparseMoE(units, hidden, e, k, held=held, score=score,
                         routed_scale=2.5 if score == "sigmoid" else 1.0,
                         activation="silu", shared_hidden=shared)
    rng = onp.random.default_rng(seed)
    whole = {"router_weight": rng.normal(size=(e, units)) * units ** -0.5,
             "router_bias": rng.normal(size=(e,)) * 0.05,
             "gate_weight": rng.normal(size=(e, hidden, units)) * 0.2,
             "up_weight": rng.normal(size=(e, hidden, units)) * 0.2,
             "down_weight": rng.normal(size=(e, units, hidden)) * 0.2,
             "shared_gate_weight": rng.normal(size=(shared, units)) * 0.2,
             "shared_up_weight": rng.normal(size=(shared, units)) * 0.2,
             "shared_down_weight": rng.normal(size=(units, shared)) * 0.2}
    first, count = held
    for name, p in layer.collect_params().items():
        w = whole[name]
        if name in ("gate_weight", "up_weight", "down_weight"):
            w = w[first:first + count]
        p.set_data(mx.nd.array(w.astype("float32")))
    return layer, whole


@pytest.mark.parametrize("score,shared", [("sigmoid", 24), ("softmax", 0),
                                          ("sigmoid", 0)],
                         ids=["sigmoid_shared", "softmax_plain",
                              "sigmoid_plain"])
def test_shares_add_up_with_the_shared_expert_counted_once(score, shared):
    """Four chips hold two experts each and every one the shared expert:
    their parts, the shared expert's term counted once, add up to the
    uncut layer; the uncut layer is a loop over all experts."""
    x = mx.nd.array(onp.random.default_rng(1).normal(size=(2, 24, 32))
                    .astype("float32"))
    full, whole = _moe_layer((0, 8), shared, score)
    want = full(x)._data
    parts, shared_term = [], 0.0
    for chip in range(4):
        layer, _ = _moe_layer((2 * chip, 2), shared, score)
        assert ("router_bias" in layer.collect_params()) == \
            (score == "sigmoid")
        out = layer(x)._data
        if shared:
            shared_term = layer.shared_expert(x)._data
            out = out - shared_term
        parts.append(out)
    assert jnp.allclose(sum(parts) + shared_term, want, atol=2e-5)
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)

    # the uncut layer by hand: every expert on every token, kept by weight
    tokens = x._data.reshape(-1, 32)
    logits = tokens @ jnp.asarray(whole["router_weight"], jnp.float32).T
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + jnp.asarray(whole["router_bias"],
                                               jnp.float32), 3)
        chosen = jnp.take_along_axis(s, idx, 1)
        weights = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    else:
        vals, idx = jax.lax.top_k(logits, 3)
        weights = jax.nn.softmax(vals, -1)

    def expert(gate, up, down):
        gate, up, down = (jnp.asarray(a, jnp.float32)
                          for a in (gate, up, down))
        return (jax.nn.silu(tokens @ gate.T) * (tokens @ up.T)) @ down.T
    by_hand = sum(
        jnp.sum(jnp.where(idx == e, weights, 0.0), -1)[:, None]
        * expert(whole["gate_weight"][e], whole["up_weight"][e],
                 whole["down_weight"][e]) for e in range(8))
    if shared:
        by_hand = by_hand + expert(whole["shared_gate_weight"],
                                   whole["shared_up_weight"],
                                   whole["shared_down_weight"])
    assert jnp.allclose(by_hand.reshape(want.shape), want, atol=2e-5)


def test_sparse_moe_refuses_what_it_does_not_know():
    with pytest.raises(mx.MXNetError):
        nn.SparseMoE(8, 8, 4, 2, score="tanh")
    with pytest.raises(mx.MXNetError):
        nn.SparseMoE(8, 8, 4, 2, activation="gelu")
    relu = nn.SparseMoE(8, 8, 4, 2)               # SmallThinker's layer
    assert sorted(relu.collect_params()) == [
        "down_weight", "gate_weight", "router_weight", "up_weight"]
    assert sorted(MOE.ACTIVATIONS) == ["relu", "relu2", "silu"]


def test_gated_ffn_is_swiglu():
    ffn = GatedFFN(16, 24)
    rng = onp.random.default_rng(3)
    w = {n: rng.normal(size=p.shape).astype("float32") * 0.3
         for n, p in ffn.collect_params().items()}
    for n, p in ffn.collect_params().items():
        p.set_data(mx.nd.array(w[n]))
    x = rng.normal(size=(2, 5, 16)).astype("float32")
    want = (jax.nn.silu(x @ w["gate_proj.weight"].T)
            * (x @ w["up_proj.weight"].T)) @ w["down_proj.weight"].T
    assert jnp.allclose(ffn(mx.nd.array(x))._data, want, atol=1e-5)


def test_row_movers_take_the_cells_expert_layer():
    """h = 2048, top-8, 4096 tokens: a list of 32,768 rows of which about
    1,024 are live. The kernels' gate takes it (width a multiple of 128
    lanes, tokens of 16, order and weights within SMEM)."""
    from mxnet_tpu.ops.kernels import moe_rows
    n, k, held, d = 4096, 8, 8, 2048
    assert moe_rows.rows_supported(n, n * min(k, held), k, d,
                                   jnp.bfloat16) is None
    assert moe_rows.rows_supported(n, n * min(k, held), k, d, jnp.bfloat16,
                                   jnp.float32) is None
    # twice the tokens would not: 65,536 pairs fill SMEM's words exactly,
    # four times overflow them
    assert moe_rows.rows_supported(4 * n, 4 * n * 8, k, d,
                                   jnp.bfloat16) is not None
