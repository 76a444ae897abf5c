"""An embedding table's gradient (ops/nn.py ``embedding``): on one chip
``moe_rows.scatter_sum`` sums the cotangent's rows into the ids' rows,
here its body in interpret mode against the transpose of XLA's gather,
which stays as the tier of every other backend and as the oracle. On the
chip the same comparison is ``chip_smoke.py``'s ``embedding_grad`` case.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.telemetry import names as tnames

ROWS, D = 64, 128


def _ids(case, rng):
    """A case's ids into ROWS rows: 300 of them unless said."""
    if case == "one_row":
        return onp.full((2, 150), 17)
    if case == "three_rows":
        ids = rng.integers(0, ROWS, size=300)
        ids[::2] = rng.choice([3, 40, 63], size=150)
        return ids.reshape(3, 100)
    if case == "clipped":
        return rng.integers(-20, ROWS + 20, size=(4, 75))
    if case == "ragged":            # 200 ids: a padded tail of 56
        return rng.integers(0, ROWS, size=200)
    raise ValueError(case)


def _loss(case, gather, ids, ct, h):
    if case == "tied":              # the table is the head's matrix too
        return lambda t: jnp.einsum("...d,vd->...v",
                                    gather(ids, t) + h.astype(t.dtype), t)
    return lambda t: gather(ids, t) * ct.astype(t.dtype)


def _grad(fn, table):
    _, pull = jax.vjp(fn, table)
    return onp.asarray(pull(jnp.ones_like(fn(table)))[0], "float64")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["one_row", "three_rows", "clipped",
                                  "ragged", "tied"])
def test_table_gradient_equals_the_gathers_transpose(case, dtype,
                                                     monkeypatch):
    rng = onp.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(ROWS, D)), dtype)
    ids = jnp.asarray(_ids("three_rows" if case == "tied" else case, rng),
                      jnp.int32)
    ct, h = (jnp.asarray(rng.normal(size=ids.shape + (D,)), dtype)
             for _ in range(2))
    fn = _loss(case, ops_nn.embedding, ids, ct, h)
    before = telemetry.value(tnames.EMBEDDING_GRAD, "interpret") or 0
    monkeypatch.setenv("MXNET_PALLAS", "on")
    got = _grad(fn, table)
    assert kernels.decisions()["embedding_grad"][0] == "interpret"
    assert telemetry.value(tnames.EMBEDDING_GRAD, "interpret") == before + 1
    monkeypatch.setenv("MXNET_PALLAS", "off")
    xla = _grad(fn, table)
    # the oracle: XLA's transpose of a plain gather, in float32
    want = _grad(_loss(case, lambda i, t: jnp.take(t, i, axis=0,
                                                   mode="clip"),
                       ids, ct.astype(jnp.float32), h),
                 table.astype(jnp.float32))
    scale = onp.abs(want).max()
    if dtype == "float32":
        # float32 sums in another order where ids repeat: rounding apart
        onp.testing.assert_allclose(got, xla, rtol=0, atol=2e-6 * scale)
        onp.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * scale)
    else:
        # the kernel sums in float32 and rounds once to bf16; XLA's bf16
        # scatter-add rounds at every add: the kernel is no further from
        # the float32 sum, and within a bf16 rounding of it
        assert onp.abs(got - want).max() <= \
            onp.abs(xla - want).max() + 2.0 ** -8 * scale
        onp.testing.assert_allclose(got, want, rtol=2.0 ** -7,
                                    atol=2.0 ** -8 * scale)
    if case == "clipped":
        hit = onp.zeros(ROWS, bool)
        hit[onp.clip(onp.asarray(ids).ravel(), 0, ROWS - 1)] = True
        assert not got[~hit].any() and hit[0] and hit[-1]


#: (ids a lookup, table rows, width) of every cell's tables, and the tier
#: one chip gives their gradient: kernel, or XLA with the reason's words
CELL_TABLES = {
    "smallthinker": (8192, 18992, 2560, None),
    "nemotron": (4096, 16384, 2688, None),
    "joyai": (4096, 16160, 2048, None),
    "lfm2": (4096, 8192, 2048, None),
    "bert_position": (512, 512, 768, None),
    "bert_word": (32 * 512, 30522, 768, "multiple of 16"),
    "bert_token_type": (32 * 512, 2, 768, "multiple of 16"),
    "lstm": (1024 * 35, 33278, 650, "650 is no multiple of 128"),
}


@pytest.mark.parametrize("cell", sorted(CELL_TABLES))
def test_each_cells_table_takes_its_tier_on_one_chip(cell, monkeypatch):
    """Traced at the cell's real size, nothing run: the gate reads shapes
    and dtypes alone."""
    n, rows, d, why = CELL_TABLES[cell]
    monkeypatch.delenv("MXNET_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def grad(ids, table, ct):
        _, pull = jax.vjp(lambda t: ops_nn.embedding(ids, t), table)
        return pull(ct)[0]
    jax.make_jaxpr(grad)(jax.ShapeDtypeStruct((n,), jnp.int32),
                         jax.ShapeDtypeStruct((rows, d), jnp.float32),
                         jax.ShapeDtypeStruct((n, d), jnp.float32))
    path, reason = kernels.decisions()["embedding_grad"]
    if why is None:
        assert path == "pallas"
    else:
        assert path == "xla" and why in reason


def test_counter_counts_each_traced_gradient_site(monkeypatch):
    """One count a table-gradient site by its tier, and none for a
    lookup whose gradient is not taken."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    rng = onp.random.default_rng(1)
    wide = jnp.asarray(rng.normal(size=(ROWS, D)), jnp.float32)
    narrow = jnp.asarray(rng.normal(size=(ROWS, 50)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, ROWS, size=(2, 64)), jnp.int32)

    def counts():
        return {t: telemetry.value(tnames.EMBEDDING_GRAD, t) or 0
                for t in ("pallas", "interpret", "xla")}

    def loss(w, n):
        return (ops_nn.embedding(ids, w).sum()
                + ops_nn.embedding(ids, w).mean()
                + ops_nn.embedding(ids, n).sum())

    before = counts()
    jax.make_jaxpr(loss)(wide, narrow)
    assert counts() == before
    jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(wide, narrow)
    after = counts()
    assert after["interpret"] - before["interpret"] == 2
    assert after["xla"] - before["xla"] == 1
    assert after["pallas"] == before["pallas"]


def test_gluon_embedding_trains_through_the_kernel(monkeypatch):
    """``gluon.nn.Embedding`` under ``autograd``: the weight's gradient
    through the kernel's body equals XLA's."""
    rng = onp.random.default_rng(2)
    ids = mx.nd.array(rng.integers(0, ROWS, size=(4, 40)).astype("int32"))
    grads = {}
    for mode in ("on", "off"):
        monkeypatch.setenv("MXNET_PALLAS", mode)
        layer = mx.gluon.nn.Embedding(ROWS, D)
        layer.initialize(mx.init.Normal(1.0))
        layer.weight.set_data(mx.nd.array(
            onp.random.default_rng(3).normal(size=(ROWS, D))
            .astype("float32")))
        with mx.autograd.record():
            out = (layer(ids) * layer(ids)).sum()
        out.backward()
        assert kernels.decisions()["embedding_grad"][0] == \
            {"on": "interpret", "off": "xla"}[mode]
        grads[mode] = layer.weight.grad().asnumpy()
    onp.testing.assert_allclose(grads["on"], grads["off"], rtol=1e-6,
                                atol=1e-5)
