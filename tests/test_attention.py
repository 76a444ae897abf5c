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


# multi-block cases of the three flash kernels, interpret mode: heads
# (q, k/v), head width, (seq_q, seq_k), (block_q, block_k), causal, window,
# and whether the call comes as (B, S, H*D) (packed at D = 128, folded
# below it) or as (B, H, S, D)
_MULTIBLOCK = {
    # 3x3 blocks: cross-block accumulator init / += / finalize
    "full": (2, 2, 8, (48, 48), (16, 16), False, None, False),
    "causal": (2, 2, 8, (48, 48), (16, 16), True, None, False),
    "causal-blocks-32x16": (2, 2, 8, (64, 64), (32, 16), True, None, False),
    "causal-blocks-16x32": (2, 2, 8, (64, 64), (16, 32), True, None, False),
    "window-inside-a-block": (2, 2, 8, (64, 64), (16, 16), True, 5, False),
    "window-of-two-blocks": (2, 2, 8, (64, 64), (16, 16), True, 32, False),
    "window-cuts-a-block": (2, 2, 8, (64, 64), (16, 16), True, 20, False),
    "window-over-the-sequence": (2, 2, 8, (64, 64), (16, 16), True, 100,
                                 False),
    # a padded tail: 40 = 2.5 blocks
    "causal-ragged-tail": (2, 2, 8, (40, 40), (16, 16), True, None, False),
    "window-ragged-tail": (2, 2, 8, (40, 40), (16, 16), True, 12, False),
    "full-ragged-tail": (2, 2, 8, (40, 56), (16, 16), False, None, False),
    # k blocks no query sees (their dk, dv are zeros) and q rows that see
    # no key (their o, dq are zeros)
    "window-longer-k": (2, 2, 8, (24, 64), (16, 16), True, 8, False),
    "causal-longer-q": (2, 2, 8, (64, 24), (16, 16), True, None, False),
    # grouped key/value heads, the projections' own layout
    "grouped-packed": (4, 2, 128, (48, 48), (16, 16), True, None, True),
    "grouped-packed-window": (4, 2, 128, (56, 56), (16, 16), True, 20, True),
    "grouped-folded": (4, 2, 32, (48, 48), (16, 16), True, None, True),
    "grouped-folded-window": (4, 2, 32, (56, 56), (16, 16), True, 20, True),
    "packed-two-heads-a-tile-window": (4, 4, 64, (64, 64), (16, 16), True,
                                       24, True),
}


def _bwd_forms():
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import names as tnames
    return {form: telemetry.value(tnames.FLASH_ATTENTION_BWD, form) or 0
            for form in ("one_block", "fused", "split")}


@pytest.mark.parametrize("form", ["fused", "split"])
@pytest.mark.parametrize("case", sorted(_MULTIBLOCK))
def test_flash_attention_pallas_backward_multiblock(case, form, monkeypatch):
    # Small explicit blocks force a multi-block grid: exercises the
    # accumulators across a row of live blocks and the table of live
    # blocks in the forward and in both forms of the backward (not
    # reachable with default 512 blocks on CI sizes), against the unfused
    # oracle: the one kernel with dq, dk, dv resident in VMEM, and the dq
    # and dk/dv kernels, which a call takes where no VMEM is left to ask
    # for them.
    if form == "split":
        monkeypatch.setattr(A, "_VMEM_ASK_BYTES", 0)
    hq, hkv, d, (sq, sk), (bq, bk), causal, window, bsh = _MULTIBLOCK[case]
    rng = onp.random.RandomState(5)
    q, k, v, do = (jnp.asarray(rng.randn(1, h, s, d).astype("float32"))
                   for h, s in ((hq, sq), (hkv, sk), (hkv, sk), (hq, sq)))
    sm = d ** -0.5
    want, vjp = jax.vjp(
        lambda q_, k_, v_: A.attention_reference(
            q_, k_, v_, causal=causal, sm_scale=sm, window=window), q, k, v)
    to = A._merge_heads if bsh else (lambda x: x)
    back = (lambda x, h: A._split_heads(x, h)) if bsh else (lambda x, h: x)
    heads = hq if bsh else None
    o, lse = A._flash_fwd_pallas(to(q), to(k), to(v), causal, sm, bq, bk,
                                 True, heads, window)
    before = _bwd_forms()
    dq, dk, dv = A._flash_bwd_pallas(to(q), to(k), to(v), o, lse, to(do),
                                     causal, sm, bq, bk, True, heads, window)
    assert {f: n - before[f] for f, n in _bwd_forms().items()} == \
        {"one_block": 0, "fused": 0, "split": 0, form: 1}
    t = A._tiles(to(q).shape, to(k).shape, bq, bk, heads, window)
    assert t.nq > 1 and t.nk > 1
    if bsh:
        assert t.layout == ("packed" if d >= 64 else "unpadded")
    got = (back(o, hq), back(dq, hq), back(dk, hkv), back(dv, hkv))
    for a, b in zip(got, (want,) + vjp(do)):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the table of live blocks: pure NumPy, no kernel
# ---------------------------------------------------------------------------

def _brute_live(t, causal):
    """(nq, nk) bool from the (seq_q, seq_k) element mask itself."""
    i = onp.arange(t.seq_q)[:, None] + (t.seq_k - t.seq_q)
    j = onp.arange(t.seq_k)[None, :]
    ok = onp.ones((t.seq_q, t.seq_k), bool)
    if causal:
        ok &= j <= i
    if t.window is not None:
        ok &= j > i - t.window
    padded = onp.zeros((t.sqp, t.skp), bool)
    padded[:t.seq_q, :t.seq_k] = ok
    return padded.reshape(t.nq, t.block_q, t.nk, t.block_k).any((1, 3))


def _check_rows(rows, heads, blocks, edge, live, group):
    """One walk of ``_live_steps`` against the live map it was built
    from: row-major; within a row head after head and blocks ascending;
    exactly the live blocks, once for each head; one first and one last
    step a row; a row with no live block keeps one step on block 0."""
    assert sorted(set(rows.tolist())) == list(range(len(live)))
    assert (onp.diff(rows) >= 0).all()
    for row, want in enumerate(live):
        at = onp.flatnonzero(rows == row)
        assert (edge[at] & A._FIRST != 0).tolist() == \
            [True] + [False] * (len(at) - 1)
        assert (edge[at] & A._LAST != 0).tolist() == \
            [False] * (len(at) - 1) + [True]
        steps = list(zip(heads[at].tolist(), blocks[at].tolist()))
        if not want.any():
            assert steps == [(0, 0)]
            continue
        assert steps == [(h, int(b)) for h in range(group)
                         for b in onp.flatnonzero(want)]


@pytest.mark.parametrize("group", [1, 7])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32)],
                         ids=["16x16", "32x16", "16x32"])
@pytest.mark.parametrize("seqs", [(96, 96), (96, 160), (160, 96), (90, 90),
                                  (70, 121)],
                         ids=["square", "longer-k", "longer-q", "ragged",
                              "ragged-longer-k"])
@pytest.mark.parametrize("mask", ["full", "causal", "window-1", "window-5",
                                  "window-17", "window-32", "window-40",
                                  "window-1000"])
def test_live_block_table_is_the_brute_force_mask(mask, seqs, blocks, group):
    """The flash kernels' grids list, block for block, the blocks in
    which the element mask has a true entry: forward and dq q-major,
    dk/dv k-major with every query head of the group in turn."""
    causal = mask != "full"
    window = int(mask.split("-")[1]) if "-" in mask else None
    (sq, sk), (bq, bk) = seqs, blocks
    t = A._tiles((1, sq, group * 128), (1, sk, 128), bq, bk, group, window)
    assert (t.group, t.seq_q, t.seq_k) == (group, sq, sk)
    live = A._live_blocks(t, causal)
    want = _brute_live(t, causal)
    assert live.shape == (t.nq, t.nk) and (live == want).all()
    qi, head, ki, edge = A._live_steps(live)
    assert all(x.dtype == onp.int32 for x in (qi, head, ki, edge))
    assert not head.any()
    _check_rows(qi, head, ki, edge, want, 1)
    ki, head, qi, edge = A._live_steps(live.T, group)
    _check_rows(ki, head, qi, edge, want.T, group)
    empty_q = int((~want.any(1)).sum())
    assert len(edge) == group * want.sum() + int((~want.any(0)).sum())
    if mask == "causal" and sq > sk:
        assert empty_q > 0           # rows the table must still write
    # the grid a kernel takes: the table where a block is dead, and the
    # dense grid, which is the table's own order, where none is
    for walk, lv, g in ((A._walk(live), live, 1),
                        (A._walk(live.T, group), live.T, group)):
        if mask != "full":
            assert not want.all() and len(walk.tables) == 4
            assert walk.axes == (len(walk.tables[0]),)
            at = [f(3, *walk.tables) for f in walk[2:]]
            assert at == [x[3] for x in walk.tables[:3]]
            continue
        assert empty_q == 0 and walk.tables == ()
        n_rows, n_blocks = lv.shape
        assert walk.axes == (n_rows, g * n_blocks)
        dense = [tuple(f(r, s) for f in walk[2:]) for r in range(n_rows)
                 for s in range(g * n_blocks)]
        assert dense == list(zip(*(x.tolist()
                                   for x in A._live_steps(lv, g)[:3])))
    # the fused backward's one walk: query head after query head, the
    # live (k block, q block) pairs k-major, each head's first and last
    # step marked
    walk = A._pair_walk(live, group)
    grid = [(s, *walk.tables) for s in range(walk.axes[0])] \
        if walk.tables else [(h, s) for h in range(walk.axes[0])
                             for s in range(walk.axes[1])]
    pairs = list(zip(*onp.nonzero(want.T)))
    assert [(walk.head(*at), walk.row(*at), walk.block(*at))
            for at in grid] == [(h, kb, qb) for h in range(group)
                                for kb, qb in pairs]
    if walk.tables:
        _check_rows(*walk.tables, onp.tile(want.T.reshape(1, -1),
                                           (group, 1)), 1)


def test_live_steps_of_the_smallthinker_cell():
    """28 q / 4 kv heads of 128 at 1 x 8192, 1024 blocks: 36 live blocks
    a head under the causal mask and 30 behind the 4096 window, of 64."""
    for window, n in ((None, 36), (4096, 30)):
        t = A._tiles((1, 8192, 28 * 128), (1, 8192, 4 * 128), 1024, 1024,
                     28, window)
        live = A._live_blocks(t, True)
        assert (t.nq, t.nk, int(live.sum())) == (8, 8, n)
        assert len(A._live_steps(live)[0]) == n
        assert len(A._live_steps(live.T, t.group)[0]) == 7 * n


def test_flash_grid_steps_are_counted_and_none_is_dead(monkeypatch):
    """``mx_flash_attention_grid_steps_total``: SmallThinker's attention
    shapes at half the sequence and half the window (one causal layer and
    three behind the window, traced only), then a one-block BERT call."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import names as tnames
    monkeypatch.setenv("MXNET_PALLAS", "on")

    def steps():
        return {kind: telemetry.value(tnames.FLASH_ATTENTION_GRID_STEPS,
                                      kind) or 0 for kind in ("live", "dead")}

    def trace(q, k, heads, **mask):
        before = steps()
        jax.eval_shape(
            lambda q_, k_, v_, do: jax.vjp(
                lambda *a: A.flash_attention_bsh(*a, heads, **mask),
                q_, k_, v_)[1](do), q, k, k, q)
        return {kind: n - before[kind] for kind, n in steps().items()}

    q = jax.ShapeDtypeStruct((1, 4096, 28 * 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 4096, 4 * 128), jnp.bfloat16)
    # 4 x 4 blocks of 1024 a head: 10 live when causal, 9 behind 2048;
    # the forward and the fused backward each walk them once for each of
    # 28 heads
    before = _bwd_forms()
    assert trace(q, k, 28, causal=True) == {"live": 2 * 28 * 10, "dead": 0}
    counted = [trace(q, k, 28, causal=True, window=2048) for _ in range(3)]
    assert counted == [{"live": 2 * 28 * 9, "dead": 0}] * 3
    assert _bwd_forms()["fused"] - before["fused"] == 4
    # BERT-base, 32 x 512: one block a sequence, two heads a lane tile
    # (6 column tiles), a few rows a program: one step a program in the
    # forward and in the one-block backward
    x = jax.ShapeDtypeStruct((32, 512, 768), jnp.bfloat16)
    programs = sum(32 // A._head_group(32, 512, 512, n_tiles=n,
                                       heads_per_block=2) * 6
                   for n in (1, 4))
    before = _bwd_forms()
    assert trace(x, x, 12) == {"live": programs, "dead": 0}
    assert {f: n - before[f] for f, n in _bwd_forms().items()} == \
        {"one_block": 1, "fused": 0, "split": 0}


# (batch, seq, query heads, key/value heads, head width, value width,
# causal, window) of the cells' attention calls, bf16, and the form of
# their backward
_BWD_FORMS = {
    "smallthinker-8192": ((1, 8192, 28, 4, 128, 128, True, None), "fused"),
    "smallthinker-8192-window": ((1, 8192, 28, 4, 128, 128, True, 4096),
                                 "fused"),
    "joyai-4096": ((1, 4096, 32, 32, 192, 128, True, None), "fused"),
    "nemotron-4096": ((1, 4096, 32, 2, 128, 128, True, None), "fused"),
    # the resident dq, dk, dv of a 32k sequence (100 MB) and the rest of
    # the kernel's VMEM are more than a core has: the dq and dk/dv kernels
    "smallthinker-32768": ((1, 32768, 28, 4, 128, 128, True, None),
                           "split"),
    "bert-512": ((32, 512, 12, 12, 64, 64, False, None), "one_block"),
}


@pytest.mark.parametrize("case", sorted(_BWD_FORMS))
def test_flash_backward_form_follows_what_fits(case, monkeypatch):
    """``mx_flash_attention_bwd_total{form}``: the fused multi-block
    backward wherever what it keeps for a whole sequence fits the VMEM a
    kernel may ask for, at the cells' own shapes (traced only)."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    (b, s, hq, hkv, d, dv, causal, window), form = _BWD_FORMS[case]
    q, k, v = (jax.ShapeDtypeStruct((b, s, h * w), jnp.bfloat16)
               for h, w in ((hq, d), (hkv, d), (hkv, dv)))
    o = jax.ShapeDtypeStruct((b, s, hq * dv), jnp.bfloat16)
    before = _bwd_forms()
    jax.eval_shape(lambda q_, k_, v_, do: jax.vjp(
        lambda *a: A.flash_attention_bsh(*a, hq, causal=causal,
                                         window=window, num_kv_heads=hkv),
        q_, k_, v_)[1](do), q, k, v, o)
    assert {f: n - before[f] for f, n in _bwd_forms().items()} == \
        {"one_block": 0, "fused": 0, "split": 0, form: 1}


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
