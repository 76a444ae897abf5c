"""SmallThinker on the CPU at the tiny size, float32, seeded weights,
against the plain reference the benchmark keeps
(``benchmark/grid/configs/smallthinker-21b-a3b.py`` ``loss_sum``): the LM
through ``TrainLoop``, then block by block: the flash kernels' window and
grouped key/value heads against ``attention_reference``, RoPE, the dropless
expert layer against a masked loop over every expert, and the test that
ties one chip's share of the experts to the whole layer.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.smallthinker import SmallThinkerLM
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import attention as ATT
from mxnet_tpu.ops import moe as MOE
from mxnet_tpu.telemetry import names as tnames

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRID = os.path.join(ROOT, "benchmark", "grid")


def grid_module(name):
    spec = importlib.util.spec_from_file_location(
        "smallthinker_test_" + name.replace("/", "_").replace("-", "_")
        .replace(".", "_"), os.path.join(GRID, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    return grid_module("configs/smallthinker-21b-a3b.py")


@pytest.fixture(scope="module")
def reference():
    return grid_module("reference.py")


def tiny_cfg(**over):
    with open(os.path.join(GRID, "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["tiny"])
    cfg.update(over)
    return cfg


def seeded_net(cfg, model, reference, seed=3):
    net = SmallThinkerLM(cfg)
    spec = model.param_spec(cfg)
    params = net.collect_params()
    assert list(params) == [name for name, *_ in spec]
    weights = reference.make_weights(spec, seed)
    for name, p in params.items():
        p.set_data(NDArray(weights[name]))
    return net, weights


# ---------------------------------------------------------------------------
# the LM through TrainLoop against the reference
# ---------------------------------------------------------------------------

LAYOUTS = {"published": [0, 1, 1, 1], "all_full_nope": [0, 0, 0, 0],
           "all_window_rope": [1, 1, 1, 1]}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_lm_logits_loss_and_every_gradient_through_trainloop(
        layout, model, reference):
    cfg = tiny_cfg(rope_layout=LAYOUTS[layout],
                   sliding_window_layout=LAYOUTS[layout])
    net, weights = seeded_net(cfg, model, reference)
    traffic = {"batch": 4, "seq": 32, "pool": 1}
    (x, y), = model.batches(cfg, traffic, 11)
    f = model.loss_sum(cfg, reference.make_dot("f32"))

    # logits: the loss of one row picks them apart well enough, and the
    # net's own forward gives them whole
    logits = net(mx.nd.array(x, dtype="int32"))._data
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, jnp.asarray(y)[..., None], -1)
    loss_ref, grads_ref = jax.value_and_grad(f)(weights, x, y)
    assert logits.shape == (4, 32, cfg["vocab_rows"])
    assert float(-jnp.sum(picked) / 32) == pytest.approx(
        float(loss_ref), rel=2e-5)

    lr = 0.5
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr, "momentum": 0.9},
                            kvstore="tpu")
    loop = gluon.TrainLoop(net, trainer,
                           gluon.loss.SoftmaxCrossEntropyLoss())
    losses = loop.step(mx.nd.array(x, dtype="int32"),
                       mx.nd.array(y, dtype="int32"))
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
        want = onp.asarray(grads_ref[name]) / traffic["batch"]
        scale = max(float(onp.abs(want).max()), 1e-12)
        assert onp.abs(got - want).max() / scale < 2e-3, name
    # a second step of the same shapes does not retrace
    loop.step(mx.nd.array(x, dtype="int32"), mx.nd.array(y, dtype="int32"))
    loop.synchronize()
    assert step.n_traces == 1


def test_lm_counts_its_masks_and_its_expert_layers(model, reference):
    cfg = tiny_cfg()
    net, _ = seeded_net(cfg, model, reference)
    mask = lambda kind: telemetry.value(tnames.ATTENTION_MASK, kind) or 0
    path = lambda p: telemetry.value(tnames.MOE_DISPATCH, p) or 0
    before = (mask("full"), mask("causal"), mask("window"),
              path("grouped"), path("capacity"))
    net(mx.nd.array(onp.zeros((2, 16)), dtype="int32"))
    after = (mask("full"), mask("causal"), mask("window"),
             path("grouped"), path("capacity"))
    assert [a - b for a, b in zip(after, before)] == [0, 1, 3, 4, 0]
    stats = net.layer0.experts.routing_stats(
        mx.nd.array(onp.random.default_rng(0).normal(size=(64, 64))))
    assert stats["pairs"].shape == (2,) and 0 < stats["held_share"] < 1
    assert stats["pairs"].sum() == round(stats["held_share"] * 64 * 2)


# ---------------------------------------------------------------------------
# the flash kernels: window and grouped key/value heads
# ---------------------------------------------------------------------------

def _qkv(b, hq, hkv, s, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (b, h, s, d)
    return (jax.random.normal(ks[0], shape(hq)),
            jax.random.normal(ks[1], shape(hkv)),
            jax.random.normal(ks[2], shape(hkv)),
            jax.random.normal(ks[3], shape(hq)))


def _close(got, want, tol=2e-5):
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < tol


@pytest.mark.parametrize("hq,hkv,d", [(28, 4, 128), (28, 4, 64),
                                      (4, 2, 128), (4, 2, 64)])
@pytest.mark.parametrize("window", [None, 20])
def test_window_and_grouped_heads_in_the_pallas_kernels(hq, hkv, d, window):
    """Interpret mode, blocks of 16 over 72 positions so that the window
    skips whole blocks, cuts edge blocks, and the dk/dv kernel walks every
    query head of a group: forward and backward against the oracle, from
    (B, S, H*D) (packed at D = 128, folded at D = 64)."""
    q, k, v, do = _qkv(1, hq, hkv, 72, d)
    want, vjp = jax.vjp(lambda *a: ATT.attention_reference(
        *a, causal=True, window=window), q, k, v)
    wants = (want,) + vjp(do)
    bsh = [ATT._merge_heads(a) for a in (q, k, v, do)]
    scale = d ** -0.5
    o, lse = ATT._flash_fwd_pallas(*bsh[:3], True, scale, 16, 16, True,
                                   hq, window)
    grads = ATT._flash_bwd_pallas(*bsh[:3], o, lse, bsh[3], True, scale,
                                  16, 16, True, hq, window)
    got = [ATT._split_heads(o, hq), ATT._split_heads(grads[0], hq),
           ATT._split_heads(grads[1], hkv), ATT._split_heads(grads[2], hkv)]
    _close(got, wants)
    tiles = ATT._tiles(bsh[0].shape, bsh[1].shape, 16, 16, hq, window)
    assert tiles.layout == ("packed" if d == 128 else "unpadded")
    assert tiles.group == hq // hkv


@pytest.mark.parametrize("tier", ["xla", "interpret"])
@pytest.mark.parametrize("window", [None, 5, 200])
def test_public_flash_attention_takes_window_and_kv_heads(tier, window,
                                                          monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "on" if tier == "interpret" else "off")
    q, k, v, do = _qkv(2, 4, 2, 24, 16, seed=1)
    want, vjp = jax.vjp(lambda *a: ATT.attention_reference(
        *a, causal=True, window=window), q, k, v)
    got, vjp2 = jax.vjp(lambda *a: ATT.flash_attention(
        *a, causal=True, window=window, num_kv_heads=2), q, k, v)
    _close((got,) + vjp2(do), (want,) + vjp(do))
    bsh = [ATT._merge_heads(a) for a in (q, k, v)]
    out = ATT.flash_attention_bsh(*bsh, num_heads=4, causal=True,
                                  num_kv_heads=2, window=window)
    _close([ATT._split_heads(out, 4)], [want])


def test_attention_options_are_checked():
    q, k, v, _ = _qkv(1, 4, 2, 8, 16)
    with pytest.raises(mx.MXNetError, match="causal"):
        ATT.flash_attention(q, k, v, window=4)
    with pytest.raises(mx.MXNetError, match="num_kv_heads"):
        ATT.flash_attention(q, k, v, causal=True, num_kv_heads=4)
    with pytest.raises(mx.MXNetError, match="multiple"):
        ATT.flash_attention(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1))
    with pytest.raises(mx.MXNetError, match="window"):
        nn.MultiHeadAttention(32, 4, window=4)


def test_bert_shaped_call_keeps_its_tiling():
    """What BERT-base's layers pass (no window, equal head counts) tiles
    as before this file existed: packed, two heads a lane tile, the fused
    one-block backward."""
    t = ATT._tiles((32, 512, 768), (32, 512, 768), 512, 512, 12)
    assert (t.layout, t.heads, t.group, t.window, t.nq, t.nk) == \
        ("packed", 2, 1, None, 1, 1)
    assert t.row_group == t.col_group == 1


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def test_rope_turns_pairs_by_position_and_keeps_scores_relative():
    b, s, h, d, theta = 2, 12, 3, 16, 1.5e6
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, h * d))
    got = ATT.rope(x, h, theta).reshape(b, s, h, d)
    xr = onp.asarray(x).reshape(b, s, h, d).astype("float64")
    for i in (0, 3, d // 2 - 1):
        ang = onp.arange(s) * theta ** (-2.0 * i / d)
        want = xr[..., i] * onp.cos(ang)[None, :, None] \
            - xr[..., i + d // 2] * onp.sin(ang)[None, :, None]
        assert onp.abs(onp.asarray(got[..., i]) - want).max() < 1e-5
    assert onp.allclose(got[:, 0], xr[:, 0], atol=1e-6)    # position 0
    # q.k after RoPE depends on the distance alone: shift both by 5
    q = jnp.tile(x[:, :1], (1, s, 1))
    rq = ATT.rope(q, h, theta).reshape(b, s, h, d)
    dots = jnp.einsum("bshd,bthd->bhst", rq, rq)
    assert float(jnp.abs(dots[..., 1, 4] - dots[..., 6, 9]).max()) < 1e-4


def test_a_nope_layer_has_no_position_signal_and_a_rope_layer_has():
    """Full attention without RoPE is blind to the order of the keys it
    may see: the last query's output is the same when the earlier tokens
    swap places. With RoPE it is not."""
    onp.random.seed(0)
    x = onp.random.normal(size=(1, 10, 32)).astype("float32")
    swapped = x[:, [3, 1, 2, 0, 4, 5, 6, 7, 8, 9]]
    outs = {}
    for theta in (None, 1e4):
        att = nn.MultiHeadAttention(32, 4, use_bias=False, causal=True,
                                    num_kv_heads=2, rope_theta=theta)
        att.initialize()
        outs[theta] = [att(mx.nd.array(a)).asnumpy()[0, -1]
                       for a in (x, swapped)]
    assert onp.abs(outs[None][0] - outs[None][1]).max() < 1e-5
    assert onp.abs(outs[1e4][0] - outs[1e4][1]).max() > 1e-4


# ---------------------------------------------------------------------------
# the dropless expert layer
# ---------------------------------------------------------------------------

def _masked_loop(x, router_w, gate, up, down, top_k, first=0):
    """Every held expert on every token, kept by a 0/1 mask."""
    logits = jnp.einsum("nd,ed->ne", x, router_w, precision="highest")
    vals, idx = jax.lax.top_k(logits, top_k)
    w = jax.nn.softmax(vals, -1)
    out = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        chosen = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        y = (jax.nn.relu(x @ gate[e].T) * (x @ up[e].T)) @ down[e].T
        out = out + chosen[:, None] * y
    return out


def _grouped(x, router_w, gate, up, down, top_k, held):
    route = MOE.moe_route(x, router_w, top_k, held)
    y = MOE.moe_experts(x, *route[1:], gate, up, down)
    return MOE.moe_combine(y, *route)


def _expert_weights(n, d, f, experts, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (n, d)),
            0.3 * jax.random.normal(ks[1], (experts, d)),
            0.2 * jax.random.normal(ks[2], (experts, f, d)),
            0.2 * jax.random.normal(ks[3], (experts, f, d)),
            0.2 * jax.random.normal(ks[4], (experts, d, f)),
            jax.random.normal(ks[5], (n, d)))


@pytest.mark.parametrize("top_k", [1, 2])
def test_dropless_layer_under_skew_equals_the_masked_loop(top_k):
    """Routing so skewed that expert 0 takes EVERY token and expert 1
    none: nothing dropped, nothing NaN, forward and every gradient equal
    to the loop over all experts."""
    x, rw, gate, up, down, g = _expert_weights(96, 32, 16, 8)
    x = x.at[:, 0].set(4.0)
    rw = rw.at[0].set(0.0).at[1].set(0.0).at[0, 0].set(9.0).at[1, 0].set(-9.0)
    sizes, share = MOE.routing_counts(x, rw, top_k, (0, 8))
    assert int(sizes[0]) == 96 and int(sizes[1]) == 0 and float(share) == 1
    args = (x, rw, gate, up, down)
    want, vjp = jax.vjp(lambda *a: _masked_loop(*a, top_k), *args)
    got, vjp2 = jax.vjp(lambda *a: _grouped(*a, top_k, (0, 8)), *args)
    assert bool(jnp.all(jnp.isfinite(got)))
    for a, b in zip((got,) + vjp2(g), (want,) + vjp(g)):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.abs(a - b).max()) \
            < 2e-5 * max(1.0, float(jnp.abs(b).max()))


@pytest.mark.parametrize("count", [8, 16])
def test_the_shares_add_up_to_the_whole_layer(count, model, reference):
    """What ties one chip's share to the model: 64 experts, top-6; the 8
    parts from held=(8j, 8) add up to held=(0, 64), to the masked loop
    over all 64, and to the uncut reference's expert layer; each part
    computes only pairs of its own experts (and so do 4 parts of 16)."""
    x, rw, gate, up, down, _ = _expert_weights(80, 32, 16, 64, seed=6)
    whole = _grouped(x, rw, gate, up, down, 6, (0, 64))
    parts, pairs = [], 0
    for first in range(0, 64, count):
        sl = slice(first, first + count)
        parts.append(_grouped(x, rw, gate[sl], up[sl], down[sl], 6,
                              (first, count)))
        pairs += int(MOE.routing_counts(x, rw, 6, (first, count))[0].sum())
    assert pairs == 80 * 6                  # every pair on exactly one chip
    total = sum(parts)
    loop = _masked_loop(x, rw, gate, up, down, 6)
    tol = 2e-5 * float(jnp.abs(loop).max())
    assert float(jnp.abs(total - whole).max()) < tol
    assert float(jnp.abs(whole - loop).max()) < tol
    assert float(jnp.abs(parts[3]).max()) > 100 * tol      # a real part

    # the same through the Gluon block, and against the reference's layer:
    # a one-layer LM's logits move by exactly the block's output
    blocks = []
    for first in range(0, 64, count):
        blk = nn.SparseMoE(32, 16, 64, 6, held=(first, count))
        blk.initialize()
        sl = slice(first, first + count)
        for p, w in ((blk.router_weight, rw), (blk.gate_weight, gate[sl]),
                     (blk.up_weight, up[sl]), (blk.down_weight, down[sl])):
            p.set_data(NDArray(w))
        blocks.append(blk(NDArray(x))._data)
    assert float(jnp.abs(sum(blocks) - loop).max()) < tol


def test_one_grouped_product_in_every_dtype():
    """No kernel gate in front of the experts: ``lax.ragged_dot`` in bf16
    as in float32, and the two agree to bf16's rounding."""
    x, rw, gate, up, down, _ = _expert_weights(32, 32, 16, 8)
    gate, up, down = gate[:4], up[:4], down[:4]
    route = MOE.moe_route(x, rw, 2, (0, 4))
    want = MOE.moe_experts(x, *route[1:], gate, up, down)
    low = [a.astype(jnp.bfloat16) for a in (x, gate, up, down)]
    y = MOE.moe_experts(low[0], *route[1:], *low[1:])
    assert y.dtype == jnp.bfloat16 and want.dtype == jnp.float32
    total = int(route[3].sum())
    assert float(jnp.abs(y[:total].astype(jnp.float32) - want[:total]).max()) \
        < 0.05 * float(jnp.abs(want).max())
    assert not float(jnp.abs(want[total:]).max())      # zero past the groups


def test_sparse_moe_checks_what_it_holds():
    with pytest.raises(mx.MXNetError, match="held"):
        nn.SparseMoE(8, 4, 8, 2, held=(6, 4))
    with pytest.raises(mx.MXNetError, match="top_k"):
        nn.SparseMoE(8, 4, 2, 3)


def test_rows_of_the_sorted_list_are_bounded_by_what_can_be_held():
    x, rw, *_ = _expert_weights(40, 32, 16, 8)
    for held, rows in (((0, 8), 80), ((2, 1), 40), ((4, 2), 80)):
        w, order, place, sizes = MOE.moe_route(x, rw, 2, held)
        assert order.shape == (rows,) and place.shape == (40, 2)
        total = int(sizes.sum())
        # the first `total` rows are the held pairs, sorted by expert
        token, choice = order[:total] // 2, order[:total] % 2
        idx = jax.lax.top_k(jnp.einsum("nd,ed->ne", x, rw), 2)[1]
        experts = idx[token, choice]
        assert bool(jnp.all(jnp.diff(experts) >= 0))
        assert bool(jnp.all((experts >= held[0])
                            & (experts < held[0] + held[1])))
        assert bool(jnp.all(place[token, choice] == jnp.arange(total)))


def test_rows_past_the_last_group_may_hold_anything(monkeypatch):
    """Nothing that reads the sorted list may count on the rows past the
    last group (``ragged_dot`` leaves them zero; a kernel whose grid is as
    long as the groups would leave them as the buffer was found). Poison
    them with NaN after every grouped product: the layer's output and
    every gradient stay finite and equal to the masked loop."""
    product = MOE._grouped_dot

    def poisoned(lhs, rhs, sizes):
        out = product(lhs, rhs, sizes)
        keep = jnp.arange(out.shape[0]) < sizes.sum()
        return jnp.where(keep[:, None], out, jnp.nan)
    monkeypatch.setattr(MOE, "_grouped_dot", poisoned)
    x, rw, gate, up, down, g = _expert_weights(72, 32, 16, 8, seed=7)
    args = (x, rw, gate[2:5], up[2:5], down[2:5])
    want, vjp = jax.vjp(lambda *a: _masked_loop(*a, 2, first=2), *args)
    got, vjp2 = jax.vjp(lambda *a: _grouped(*a, 2, (2, 3)), *args)
    y = MOE.moe_experts(x, *MOE.moe_route(x, rw, 2, (2, 3))[1:],
                        gate[2:5], up[2:5], down[2:5])
    assert bool(jnp.isnan(y).any())             # the poison is there
    for a, b in zip((got,) + vjp2(g), (want,) + vjp(g)):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.abs(a - b).max()) \
            < 2e-5 * max(1.0, float(jnp.abs(b).max()))


def test_the_tape_carries_integer_outputs_between_ops():
    """Eager ``autograd.record()``: the router's integer outputs (order,
    place, sizes) flow from ``moe_route`` into ``moe_experts`` and
    ``moe_combine`` on the tape, and backward reaches x, the router and
    the experts."""
    from mxnet_tpu import autograd
    blk = nn.SparseMoE(32, 16, 8, 2, held=(2, 4))
    blk.initialize()
    x = mx.nd.array(onp.random.default_rng(1).normal(size=(12, 32)))
    x.attach_grad()
    with autograd.record():
        loss = (blk(x) ** 2).sum()
    loss.backward()
    w = [p.data()._data for p in (blk.router_weight, blk.gate_weight,
                                  blk.up_weight, blk.down_weight)]
    want = jax.grad(lambda x_, *w_: jnp.sum(
        _grouped(x_, *w_, 2, (2, 4)) ** 2), argnums=(0, 1, 2))(x._data, *w)
    for got, ref in zip((x.grad, blk.router_weight.grad(),
                         blk.gate_weight.grad()), want):
        assert float(jnp.abs(got._data - ref).max()) \
            < 1e-5 * max(1.0, float(jnp.abs(ref).max()))
