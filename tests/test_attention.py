"""Flash/ring attention + transformer/BERT tests.

Numeric oracle: unfused softmax(QK^T)V in f32 (attention_reference), the
same check style the reference uses for fused vs unfused ops (SURVEY §4).
Ring attention runs on the virtual 8-device CPU mesh — the TPU-world analog
of the reference's multi-process localhost collectives tests.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import attention as A


def _rand_qkv(b=2, h=4, s=64, d=32, seed=0):
    rng = onp.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d).astype("float32"))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    q, k, v = _rand_qkv()
    ref = A.attention_reference(q, k, v, causal=causal)
    out = A.flash_attention(q, k, v, causal=causal, use_pallas=False)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_pallas_interpret(causal):
    # The Pallas TPU kernel, run through the interpreter on CPU.
    q, k, v = _rand_qkv(s=96, d=24)  # odd sizes exercise padding
    ref = A.attention_reference(q, k, v, causal=causal)
    out = A._flash_fwd_pallas(q, k, v, causal, 24 ** -0.5, interpret=True)[0]
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def test_flash_attention_cross_length():
    q, k, v = _rand_qkv()
    q = q[:, :, :32]
    ref = A.attention_reference(q, k, v, causal=True)
    out = A.flash_attention(q, k, v, causal=True, use_pallas=False)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def test_flash_attention_grad():
    q, k, v = _rand_qkv(s=32, d=16)

    def loss_flash(q_, k_, v_):
        return jnp.sum(A.flash_attention(q_, k_, v_, causal=True,
                                         use_pallas=False) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(A.attention_reference(q_, k_, v_, causal=True) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_8dev(causal):
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = Mesh(onp.array(devs[:8]), ("sp",))
    q, k, v = _rand_qkv(s=64)
    ref = A.attention_reference(q, k, v, causal=causal)
    out = A.ring_attention_sharded(q, k, v, mesh, axis="sp", causal=causal)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def test_flash_attention_valid_length():
    # Per-sample key padding via the fused blockwise path must match an
    # explicitly-masked unfused reference.
    q, k, v = _rand_qkv(b=3, s=16, d=8)
    vl = jnp.asarray([16, 9, 4], jnp.float32)
    out = A.flash_attention(q, k, v, valid_length=vl)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (8 ** -0.5)
    keep = jnp.arange(16)[None, None, None, :] < vl[:, None, None, None]
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    ref = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)
    # and it is differentiable (vl gets a zero cotangent)
    g = jax.grad(lambda q_: jnp.sum(
        A.flash_attention(q_, k, v, valid_length=vl) ** 2))(q)
    assert onp.isfinite(onp.asarray(g)).all()


def test_masked_attention_respects_causal():
    # causal=True must still hold when an additive mask is supplied
    from mxnet_tpu.gluon.nn.transformer import _masked_attention
    q, k, v = _rand_qkv(s=12, d=8)
    zero_mask = jnp.zeros((1, 1, 1, 12), jnp.float32)
    out = _masked_attention(q, k, v, zero_mask, 8 ** -0.5, causal=True)
    ref = A.attention_reference(q, k, v, causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def test_multi_head_attention_layer():
    from mxnet_tpu.gluon import nn
    mha = nn.MultiHeadAttention(units=32, num_heads=4)
    mha.initialize()
    x = mx.nd.array(onp.random.randn(2, 10, 32).astype("float32"))
    out = mha(x)
    assert out.shape == (2, 10, 32)
    # padding mask changes masked positions' influence, not output shape
    mask = onp.zeros((2, 1, 1, 10), "float32")
    mask[:, :, :, 5:] = -1e30
    out_m = mha(x, mask=mx.nd.array(mask))
    assert out_m.shape == (2, 10, 32)
    assert not onp.allclose(out.asnumpy(), out_m.asnumpy())
    # valid_length (fused path) must agree with the equivalent additive mask
    out_vl = mha(x, valid_length=mx.nd.array(onp.array([5, 5], "float32")))
    onp.testing.assert_allclose(out_vl.asnumpy(), out_m.asnumpy(),
                                rtol=1e-5, atol=1e-5)


def test_transformer_encoder_grad_flows():
    from mxnet_tpu.gluon import nn
    enc = nn.TransformerEncoder(num_layers=2, units=16, hidden_size=32,
                                num_heads=2)
    enc.initialize()
    x = mx.nd.array(onp.random.randn(2, 8, 16).astype("float32"))
    with mx.autograd.record():
        out = enc(x)
        loss = (out * out).sum()
    loss.backward()
    params = enc.collect_params()
    grads = [p.grad() for p in params.values() if p.grad_req != "null"]
    assert any(float(onp.abs(g.asnumpy()).sum()) > 0 for g in grads)


def test_bert_forward_and_mlm():
    from mxnet_tpu.gluon.model_zoo import bert
    net = bert.bert_small_test(use_decoder=True)
    net.initialize()
    tokens = mx.nd.array(onp.random.randint(0, 128, (2, 12)), dtype="int32")
    vlen = mx.nd.array(onp.array([12, 7]), dtype="int32")
    seq, pooled, scores = net(tokens, None, vlen)
    assert seq.shape == (2, 12, 32)
    assert pooled.shape == (2, 32)
    assert scores.shape == (2, 12, 128)


@pytest.mark.slow
def test_bert_classifier_train_step():
    from mxnet_tpu.gluon.model_zoo import bert
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    net = bert.BERTClassifier(bert.bert_small_test(), num_classes=3)
    net.initialize()
    tokens = mx.nd.array(onp.random.randint(0, 128, (4, 10)), dtype="int32")
    y = mx.nd.array(onp.array([0, 1, 2, 1]), dtype="int32")
    loss_fn = SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 1e-3})
    with mx.autograd.record():
        logits = net(tokens)
        loss = loss_fn(logits, y)
    loss.backward()
    trainer.step(4)
    assert onp.isfinite(float(loss.mean().asnumpy()))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_pallas_backward(causal):
    # FlashAttention-2-style Pallas backward (interpret mode) vs the
    # unfused reference VJP
    q, k, v = _rand_qkv(b=2, h=2, s=48, d=16, seed=3)

    def loss_pallas(q_, k_, v_):
        return jnp.sum(A._flash_tpu(q_, k_, v_, causal, 16 ** -0.5,
                                    True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(A.attention_reference(q_, k_, v_,
                                             causal=causal) ** 2)

    g = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


def test_flash_attention_pallas_backward_cross_length():
    q, k, v = _rand_qkv(b=1, h=2, s=64, d=8, seed=4)
    q = q[:, :, :24]

    def loss_pallas(q_, k_, v_):
        return jnp.sum(A._flash_tpu(q_, k_, v_, True, 8 ** -0.5, True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(A.attention_reference(q_, k_, v_, causal=True) ** 2)

    g = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_pallas_backward_multiblock(causal):
    # Small explicit blocks force a 3x4 grid: exercises cross-block
    # accumulator init/+=/finalize and the causal block-skip predicate in
    # both backward kernels (not reachable with default 512 blocks on CI
    # sizes).
    q, k, v = _rand_qkv(b=1, h=2, s=48, d=8, seed=5)
    k = k[:, :, :64] if k.shape[2] >= 64 else k
    sm = 8 ** -0.5

    o, lse = A._flash_fwd_pallas(q, k, v, causal, sm, block_q=16,
                                 block_k=16, interpret=True)
    rng = onp.random.RandomState(9)
    do = jnp.asarray(rng.randn(*o.shape).astype("float32"))
    dq, dk, dv = A._flash_bwd_pallas(q, k, v, o, lse, do, causal, sm,
                                     block_q=16, block_k=16, interpret=True)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: A.attention_reference(q_, k_, v_, causal=causal,
                                                 sm_scale=sm), q, k, v)
    rq, rk, rv = vjp(do)
    for a, b in zip((dq, dk, dv), (rq, rk, rv)):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


def test_flash_pallas_bf16_interpret():
    """bf16 flash attention (interpret mode): the dtype the AMP path now
    feeds the Pallas kernels on TPU — fwd matches the reference, bwd
    grads are finite and keep the activation dtype."""
    rng = onp.random.RandomState(0)
    B, H, S, D = 2, 2, 64, 32
    q, k, v, do = (jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
                   for _ in range(4))
    out, lse = A._flash_fwd_pallas(q, k, v, causal=True,
                                   sm_scale=D ** -0.5, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = A.attention_reference(q, k, v, causal=True, sm_scale=D ** -0.5)
    onp.testing.assert_allclose(onp.asarray(out, "float32"),
                                onp.asarray(ref, "float32"),
                                rtol=3e-2, atol=3e-2)
    dq, dk, dv = A._flash_bwd_pallas(q, k, v, out, lse, do, causal=True,
                                     sm_scale=D ** -0.5, interpret=True)
    for g in (dq, dk, dv):
        assert g.dtype == jnp.bfloat16
        assert onp.isfinite(onp.asarray(g, "float32")).all()


# ---------------------------------------------------------------------------
# (B, S, H*D): the kernels at the model's own layout and head width
# ---------------------------------------------------------------------------

# head width -> the layout its shapes give the (B, S, H*D) entry
_BSH_LAYOUT = {32: "packed", 64: "packed", 128: "packed", 80: "padded"}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seqs", [(64, 64), (1024, 1024), (128, 256)],
                         ids=["s64-one-block", "s1024-multi-block",
                              "s128x256-cross"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d", [32, 64, 128, 80])
def test_flash_attention_bsh_parity(d, causal, seqs, dtype, monkeypatch):
    """Forward and the three gradients of the (B, S, H*D) entry against
    the unfused reference, kernel bodies interpreted: heads sharing a
    lane tile (32, 64), a head filling its own (128), a width that keeps
    the padded path (80); one block, several, and cross-length."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    sq, sk = seqs
    h = 128 // d if d < 128 else 2          # one 128-lane tile, or two
    if d == 80:
        h = 2
    rng = onp.random.RandomState(d + sq)
    q, k, v, do = (jnp.asarray(rng.randn(1, s, h * d), dtype)
                   for s in (sq, sk, sk, sq))

    def ref(q_, k_, v_):
        return A._merge_heads(A.attention_reference(
            *(A._split_heads(x.astype(jnp.float32), h)
              for x in (q_, k_, v_)),
            causal=causal))

    out, vjp = jax.vjp(lambda *a: A.flash_attention_bsh(*a, h, causal=causal),
                       q, k, v)
    want, ref_vjp = jax.vjp(ref, q, k, v)
    assert A._tiles(q.shape, k.shape, 512, 512, h).layout == _BSH_LAYOUT[d]
    tol = 2e-4 if dtype == "float32" else 4e-2
    for got, exp in zip((out,) + vjp(do),
                        (want,) + ref_vjp(do.astype(jnp.float32))):
        assert got.shape == exp.shape and got.dtype == q.dtype
        scale = float(jnp.max(jnp.abs(exp)))
        onp.testing.assert_allclose(onp.asarray(got, "float32") / scale,
                                    onp.asarray(exp, "float32") / scale,
                                    rtol=0, atol=tol)


def _walk_eqns(jaxpr, from_pallas=()):
    """(eqn, an operand is a pallas_call's result) over a jaxpr and the
    jaxprs its calls hold; a kernel's own body is not entered."""
    from jax.extend.core import Var
    from mxnet_tpu.analysis.program import _subjaxprs
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    tainted = set(from_pallas)

    def is_tainted(v):
        return isinstance(v, Var) and v in tainted

    for eqn in jaxpr.eqns:
        hit = any(map(is_tainted, eqn.invars))
        yield eqn, hit
        if eqn.primitive.name == "pallas_call":
            tainted.update(eqn.outvars)
            continue
        if hit and eqn.primitive.name in ("reshape", "transpose",
                                          "convert_element_type"):
            tainted.update(eqn.outvars)     # the same values, re-viewed
        for sub in (s for p in eqn.params.values() for s in _subjaxprs(p)):
            sub = getattr(sub, "jaxpr", sub)
            inner = [iv for iv, ov in zip(sub.invars, eqn.invars)
                     if is_tainted(ov)] \
                if len(sub.invars) == len(eqn.invars) else ()
            yield from _walk_eqns(sub, inner)


def test_multi_head_attention_program_moves_no_layout(monkeypatch):
    """BERT-base's attention layer, forward and backward, kernels on: the
    program holds the three Pallas calls and nothing that re-lays their
    operands out: no pad, no head transpose, no slice of a kernel's
    result."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import ParamBinding
    from mxnet_tpu.ndarray.ndarray import NDArray
    monkeypatch.setenv("MXNET_PALLAS", "on")
    mha = nn.MultiHeadAttention(units=768, num_heads=12)
    mha.initialize()
    params = list(mha.collect_params().values())
    datas = [p.data()._data for p in params]

    def loss(datas_, x_):
        with ParamBinding(params, datas_):
            out = mha(NDArray(x_))
        return jnp.sum(out._data ** 2)

    x = jnp.zeros((2, 512, 768), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(datas, x)
    seen = list(_walk_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e, _ in seen]
    assert names.count("pallas_call") == 2          # forward, fused backward
    assert "pad" not in names
    assert not [e for e, _ in seen if e.primitive.name == "transpose"
                and e.invars[0].aval.ndim >= 4]
    assert not [e for e, from_kernel in seen if from_kernel
                and e.primitive.name in ("slice", "dynamic_slice", "gather")]


def test_flash_layout_is_recorded_and_counted(monkeypatch):
    """The layout a call's shapes gave it is in the dispatch decision's
    reason and in ``mx_flash_attention_layout_total``: D = 80 keeps the
    padded path, D = 64 packs two heads into a lane tile, and the
    (B, H, S, D) form goes unpadded."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import kernels
    from mxnet_tpu.telemetry import names as tnames
    monkeypatch.setenv("MXNET_PALLAS", "on")

    def counts():
        return {lay: telemetry.value(tnames.FLASH_ATTENTION_LAYOUT, lay) or 0
                for lay in A.FLASH_LAYOUTS}

    x80 = jnp.ones((1, 16, 2 * 80), jnp.float32)
    before = counts()
    out = A.flash_attention_bsh(x80, x80, x80, 2)
    assert out.shape == x80.shape
    path, reason = kernels.decisions()["flash_attention"]
    assert path == "interpret" and "padded: D=80 → 128" in reason
    assert counts() == dict(before, padded=before["padded"] + 1)

    x64 = jnp.ones((1, 16, 2 * 64), jnp.float32)
    A.flash_attention_bsh(x64, x64, x64, 2)
    assert "packed: 2 heads per 128 lanes, no pad" in \
        kernels.decisions()["flash_attention"][1]
    A.flash_attention(*(A._split_heads(x64, 2),) * 3)
    assert "unpadded: D=64" in kernels.decisions()["flash_attention"][1]
    assert counts() == {"packed": before["packed"] + 1,
                        "unpadded": before["unpadded"] + 1,
                        "padded": before["padded"] + 1}
    # the XLA tier takes no layout: nothing is counted
    monkeypatch.setenv("MXNET_PALLAS", "off")
    A.flash_attention_bsh(x64, x64, x64, 2)
    assert counts()["packed"] == before["packed"] + 1
