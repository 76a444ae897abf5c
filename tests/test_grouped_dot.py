"""The grouped products of the dropless expert layer as kernels
(ops/kernels/grouped_dot.py): the kernel bodies in interpret mode against
``lax.ragged_dot``, which stays as the tier of every other backend and as
the oracle; float32 at HIGHEST on both sides (at the default precision a
float32 product is one bf16 pass and ReLU's mask flips on near-zeros). On
the chip the same comparison is ``chip_smoke.py``'s ``grouped_dot`` cases.
"""
import re

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels
from mxnet_tpu.ops import moe as MOE
from mxnet_tpu.ops.kernels import grouped_dot
from mxnet_tpu.telemetry import names as tnames

#: the cells' shapes cut small, (list rows, d, f): SmallThinker's 49,152 x
#: 2560 x 768 (widths that are 20 and 6 lane tiles) and JoyAI's 32,768 x
#: 2048 x 768
SHAPES = {"smallthinker": (768, 384, 256), "joyai": (512, 256, 128)}
#: group sizes as shares of the list's rows, by what they try: groups that
#: end inside a row tile, an empty group between two others and one at the
#: end, no pair at all, every row live, and groups that fill whole tiles
GROUPS = {
    "ragged": lambda rows: [rows // 8 + 3, 1, rows // 4 - 17, rows // 16],
    "empty": lambda rows: [rows // 4 + 5, 0, rows // 8, 0],
    "none": lambda rows: [0, 0, 0],
    "full": lambda rows: [rows // 2 - 1, 1, rows // 2],
    "tiles": lambda rows: [128, 0, 256],
}
TOL = {"float32": 2e-6, "bfloat16": 2.0 ** -7}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def small_tiles(monkeypatch):
    """A budget that cuts the contraction and the output into several
    tiles: the accumulator is carried over steps. The entry points are
    jitted by shape, not by budget."""
    monkeypatch.setattr(grouped_dot, "vmem_tile_budget", lambda: 320 * 1024)
    for fn in (grouped_dot.gmm, grouped_dot.tgmm):
        fn.clear_cache()
    yield
    for fn in (grouped_dot.gmm, grouped_dot.tgmm):
        fn.clear_cache()


def _operands(shape, groups, dtype, seed=0):
    rows, d, f = SHAPES[shape]
    sizes = onp.asarray(GROUPS[groups](rows), "int32")
    rng = onp.random.default_rng(seed)
    xs, dy = (rng.normal(size=(rows, d)) for _ in range(2))
    d_gate, d_up = (rng.normal(size=(rows, f)) for _ in range(2))
    w_gate, w_up = (rng.normal(size=(len(sizes), f, d)) * d ** -0.5
                    for _ in range(2))
    clean = [jnp.asarray(a, dtype) for a in (xs, dy, d_gate, d_up)]
    # what stands past the last group may be anything
    for a in (xs, dy, d_gate, d_up):
        a[sizes.sum():] = onp.nan
    dirty = [jnp.asarray(a, dtype) for a in (xs, dy, d_gate, d_up)]
    return (jnp.asarray(sizes), clean, dirty,
            [jnp.asarray(w, dtype) for w in (w_gate, w_up)])


def _ragged(lhs, rhs, sizes):
    return lax.ragged_dot(lhs.astype(jnp.float32), rhs.astype(jnp.float32),
                          sizes)


def _close(got, want, dtype, rows=None):
    got, want = (onp.asarray(a, "float32")[:rows] for a in (got, want))
    assert onp.isfinite(got).all()
    assert onp.abs(got - want).max(initial=0) <= TOL[dtype] * max(
        1.0, onp.abs(want).max(initial=0))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups", sorted(GROUPS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_three_products_equal_ragged_dot(shape, groups, dtype):
    rows, d, f = SHAPES[shape]
    sizes, (xs, dy, d_gate, d_up), dirty, (w_gate, w_up) = _operands(
        shape, groups, dtype)
    total = int(sizes.sum())
    walk = grouped_dot.group_metadata(sizes, rows, grouped_dot.row_tile(rows))
    xs_, dy_, d_gate_, d_up_ = dirty

    asked = {"precision": "highest", "interpret": True}
    got = grouped_dot.gmm(xs_, w_gate, walk, **asked)
    assert got.dtype == xs.dtype and got.shape == (rows, f)
    _close(got, _ragged(xs, w_gate.swapaxes(1, 2), sizes), dtype, total)

    # the rows' gradient, two cotangents in one accumulator
    got = grouped_dot.gmm((d_gate_, d_up_), (w_gate, w_up), walk,
                          transposed=True, **asked)
    assert got.dtype == xs.dtype and got.shape == (rows, d)
    _close(got, _ragged(d_gate, w_gate, sizes) + _ragged(d_up, w_up, sizes),
           dtype, total)

    # the matrices' gradient: ragged_dot's own transpose, of a cotangent
    # that is zero past the groups
    got = grouped_dot.tgmm(d_gate_, xs_, walk, **asked)
    assert got.dtype == xs.dtype and got.shape == w_gate.shape
    live = (jnp.arange(rows) < total)[:, None]
    _, vjp = jax.vjp(lambda w: _ragged(xs, w.swapaxes(1, 2), sizes),
                     w_gate.astype(jnp.float32))
    want, = vjp(jnp.where(live, d_gate, 0).astype(jnp.float32))
    _close(got, want, dtype)
    for g, size in enumerate(onp.asarray(sizes)):
        if size == 0:           # not stale memory, not a rounding: zero
            assert not onp.asarray(got[g], "float32").any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups", ["ragged", "empty"])
def test_the_accumulator_is_carried_over_contraction_tiles(groups, dtype,
                                                           small_tiles):
    rows, d, f = SHAPES["smallthinker"]
    tm = grouped_dot.row_tile(rows)
    assert grouped_dot.tiling(tm, d, f, dtype)[0] < d
    assert grouped_dot.tiling(tm, f, d, dtype, 2)[1] < d
    tp, tq = grouped_dot.tiling(tm, f, d, dtype, resident="out")
    assert tp * tq < f * d
    test_the_three_products_equal_ragged_dot("smallthinker", groups, dtype)


@pytest.mark.parametrize("seed", range(6))
def test_the_walk_visits_every_tile_a_group_touches_and_no_other(seed):
    rng = onp.random.default_rng(seed)
    tm, tiles_m, g = 128, 6, 5
    rows = tm * tiles_m
    sizes = rng.multinomial(rng.integers(0, rows + 1), [1 / g] * g)
    sizes[rng.integers(g)] = 0 if seed % 2 else sizes[0]
    sizes = onp.minimum(sizes, rows - (sizes.sum() - sizes))  # fits the list
    offsets, group, tile, visits = (onp.asarray(a) for a in
                                    grouped_dot.group_metadata(
                                        jnp.asarray(sizes, jnp.int32), rows,
                                        tm))
    assert offsets.tolist() == [0] + onp.cumsum(sizes).tolist()
    assert len(group) == len(tile) == tiles_m + g - 1 >= visits
    want = []
    for e, size in enumerate(sizes):
        lo, hi = offsets[e], offsets[e + 1]
        touched = range(lo // tm, (hi - 1) // tm + 1) if size else \
            [min(lo // tm, tiles_m - 1)]
        want += [(e, t) for t in touched]
    assert list(zip(group[:visits], tile[:visits])) == want
    # what follows repeats the last visit: an index map may read it
    assert (group[visits:] == group[visits - 1]).all()
    assert (tile[visits:] == tile[visits - 1]).all()
    assert (onp.diff(tile[:visits]) >= 0).all()


N, E = 128, 8
#: name -> (top_k, held): as tests/test_moe_rows.py's
SHARES = {"whole": (2, (0, E)), "three": (2, (2, 3)), "one": (4, (5, 1)),
          "none": (2, (6, 2))}


def _layer_inputs(dtype, held, d=256, f=128, seed=0):
    rng = onp.random.default_rng(seed)
    x = rng.normal(size=(N, d))
    rw = rng.normal(size=(E, d))
    if held == SHARES["none"][1]:
        x[:, 0] = 4.0               # no token chooses the held experts
        rw[held[0]:held[0] + held[1]] = 0.0
        rw[held[0]:held[0] + held[1], 0] = -50.0
    c = held[1]
    gate, up = (rng.normal(size=(c, f, d)) * d ** -0.5 for _ in range(2))
    down = rng.normal(size=(c, d, f)) * f ** -0.5
    g = rng.normal(size=(N, d))
    return (tuple(jnp.asarray(a, dtype) for a in (x, gate, up, down))
            + (jnp.asarray(rw, jnp.float32),), jnp.asarray(g, jnp.float32))


def _layer(k, held, activation="relu"):
    def layer(x, gate, up, down, rw):
        w, order, place, sizes = MOE.moe_route(x, rw, k, held)
        y = MOE.moe_experts(x, order, place, sizes, gate, up, down,
                            activation)
        return MOE.moe_combine(y, w, order, place, sizes)
    return layer


def _out_and_grads(monkeypatch, mode, layer, args, g):
    monkeypatch.setenv("MXNET_PALLAS", mode)
    out, vjp = jax.vjp(layer, *args)
    return (out,) + vjp(g)


def _products():
    return {t: telemetry.value(tnames.MOE_GROUPED_DOT, t) or 0
            for t in ("pallas", "interpret", "xla")}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("activation", sorted(MOE.ACTIVATIONS))
@pytest.mark.parametrize("share", sorted(SHARES))
def test_layer_and_every_gradient_equal_the_xla_forms(share, activation,
                                                      dtype, monkeypatch):
    k, held = SHARES[share]
    args, g = _layer_inputs(dtype, held)
    total = int(MOE.moe_route(args[0], args[-1], k, held)[3].sum())
    rows = N * min(k, held[1])
    assert {"whole": total == rows, "none": total == 0}.get(
        share, 0 < total < rows)
    layer = _layer(k, held, activation)
    before = _products()
    want = _out_and_grads(monkeypatch, "off", layer, args, g)
    assert kernels.decisions()["grouped_dot"] == ("xla", "MXNET_PALLAS=off")
    counted = _products()
    # off: three ragged_dot sites, their backward autodiff's
    assert (counted["xla"] - before["xla"], counted["interpret"]) == \
        (3, before["interpret"])
    got = _out_and_grads(monkeypatch, "on", layer, args, g)
    assert kernels.decisions()["grouped_dot"][0] == "interpret"
    # on: three forward and five backward sites, each counted once
    assert _products() == dict(counted,
                               interpret=counted["interpret"] + 8)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = (onp.asarray(t, "float32") for t in (a, b))
        assert onp.isfinite(a).all()
        assert onp.abs(a - b).max() <= TOL[dtype] * max(1.0, onp.abs(b).max())
    if share == "none":
        for grad in got[2:5]:       # exactly zero, not stale memory
            assert not onp.asarray(grad, "float32").any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_past_the_last_group_may_hold_anything(dtype, monkeypatch):
    """The kernels write nothing past the last group. Poison what they
    leave there, forward and backward, with NaN: the layer's output and
    every gradient stay finite and equal the clean XLA forms."""
    k, held = SHARES["three"]
    args, g = _layer_inputs(dtype, held, seed=3)
    layer = _layer(k, held)
    want = _out_and_grads(monkeypatch, "off", layer, args, g)

    def poisoned(fn):
        def product(lhs, rhs, walk, **kw):
            out = fn(lhs, rhs, walk, **kw)
            keep = jnp.arange(out.shape[0]) < walk[0][-1]
            return jnp.where(keep[:, None], out, jnp.nan)
        return product
    monkeypatch.setattr(grouped_dot, "gmm", poisoned(grouped_dot.gmm))
    monkeypatch.setenv("MXNET_PALLAS", "on")
    x, gate, up, down, rw = args
    route = MOE.moe_route(x, rw, k, held)
    y = MOE.moe_experts(x, *route[1:], gate, up, down)
    assert bool(jnp.isnan(y).any())                  # the poison is there
    got = _out_and_grads(monkeypatch, "on", layer, args, g)
    for a, b in zip(got, want):
        a, b = (onp.asarray(t, "float32") for t in (a, b))
        assert onp.isfinite(a).all()
        assert onp.abs(a - b).max() <= TOL[dtype] * max(1.0, onp.abs(b).max())


@pytest.mark.parametrize("why,n,d,f,dtypes", [
    ("no multiple of 128 lanes", N, 256, 64, ("float32",) * 4),
    ("no multiple of 128 lanes", N, 192, 128, ("float32",) * 4),
    ("no multiple of a 128-row tile", 72, 256, 128, ("float32",) * 4),
    ("not kernelized", N, 256, 128, ("float16",) * 4),
    ("one dtype wanted", N, 256, 128, ("bfloat16",) + ("float32",) * 3),
])
def test_what_the_kernels_do_not_take_goes_to_ragged_dot_and_says_why(
        why, n, d, f, dtypes, monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "on")
    k, held = SHARES["three"]
    assert why in grouped_dot.supported(n * k, d, f, *dtypes)
    rng = onp.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(n, d)), dtypes[0])
    rw = jnp.asarray(rng.normal(size=(E, d)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(held[1], f, d)) * d ** -0.5,
                            dtypes[1]) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(held[1], d, f)) * f ** -0.5,
                       dtypes[3])
    _, order, place, sizes = MOE.moe_route(x, rw, k, held)
    before = _products()
    y = MOE.moe_experts(x, order, place, sizes, gate, up, down)
    assert _products() == dict(before, xla=before["xla"] + 3)
    path, reason = kernels.decisions()["grouped_dot"]
    assert path == "xla" and why in reason
    xs = x[order // k]
    want = MOE._grouped_dot(
        jax.nn.relu(MOE._grouped_dot(xs, gate, sizes))
        * MOE._grouped_dot(xs, up, sizes), down, sizes)
    assert y.dtype == want.dtype and bool(jnp.all(y == want))


@pytest.mark.parametrize("asked,kernel_dots", [
    ("highest", "HIGHEST"), ("float32", "HIGHEST"), ("default", "DEFAULT"),
    ("high", None), ("F32_F32_F32", None)])
def test_float32_multiplies_as_asked_where_the_layer_was_called(
        asked, kernel_dots, monkeypatch):
    """The precision is read once, where ``moe_experts`` is called, and
    kept by the backward, which is traced after that ``with`` block has
    closed (``chip_smoke.py`` asks for ``highest`` around the forward
    alone): every product of the eight kernels multiplies at it. What
    Mosaic cannot multiply at (three passes, an algorithm's name) the gate
    gives to ``ragged_dot`` with the reason; bf16 is one pass always."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    k, held = SHARES["three"]
    (x, gate, up, down, rw), _ = _layer_inputs("float32", held)
    _, order, place, sizes = MOE.moe_route(x, rw, k, held)

    def layer(x, gate, up, down):
        with jax.default_matmul_precision(asked):
            return MOE.moe_experts(x, order, place, sizes, gate, up,
                                   down).sum()
    with jax.default_matmul_precision("default"):
        text = str(jax.make_jaxpr(jax.grad(layer, argnums=(0, 1, 2, 3)))(
            x, gate, up, down))
        path, reason = kernels.decisions()["grouped_dot"]
        if kernel_dots is None:
            assert path == "xla" and f"precision {asked!r}" in reason
            assert "pallas_call" not in text.split("_dispatch")[0]
            return
        assert path == "interpret"
        dots = re.findall(r"precision=\(Precision\.(\w+), ", text)
        # six kernel bodies at least (gate and up, and their matrices'
        # gradients, may share a trace)
        assert set(dots) == {kernel_dots} and len(dots) >= 6
        low = [a.astype(jnp.bfloat16) for a in (x, gate, up, down)]
        text = str(jax.make_jaxpr(jax.grad(layer, argnums=(0, 1, 2, 3)))(
            *low))
        assert set(re.findall(r"precision=\(Precision\.(\w+), ", text)) \
            == {"DEFAULT"}


def test_the_gate_switches_the_tier_and_off_is_the_xla_form(monkeypatch):
    """``off``, and ``auto`` off the chip, trace ``lax.ragged_dot`` and no
    kernel: three products forward, autodiff's six backward, and the two
    cotangents of the gathered rows added over the list. ``on`` traces
    eight kernels, no ``ragged_dot``, and adds nothing over the list (the
    input's gradient is made once)."""
    k, held = SHARES["three"]
    (x, gate, up, down, rw), _ = _layer_inputs("float32", held)
    _, order, place, sizes = MOE.moe_route(x, rw, k, held)

    def layer(x, gate, up, down):
        return MOE.moe_experts(x, order, place, sizes, gate, up, down).sum()
    for mode, tier in (("off", "xla"), ("auto", "xla"), ("on", "interpret")):
        monkeypatch.setenv("MXNET_PALLAS", mode)
        text = str(jax.make_jaxpr(jax.grad(layer, argnums=(0, 1, 2, 3)))(
            x, gate, up, down))
        assert kernels.decisions()["grouped_dot"][0] == tier
        found = {op: len(re.findall(rf"{op}\b", text)) for op in
                 ("= ragged_dot_general", "name=gmm", "name=tgmm",
                  "= add_any")}
        # the entry points are jitted: five calls of ``gmm``, three of
        # ``tgmm``, whatever traces they share
        assert list(found.values()) == ([9, 0, 0, 1] if tier == "xla"
                                        else [0, 5, 3, 0])


@pytest.mark.parametrize("cell,rows,d,f", [
    ("smallthinker", 49152, 2560, 768), ("joyai", 32768, 2048, 768)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_tiles_come_from_the_shapes_and_fit_the_budget(cell, rows, d, f,
                                                       dtype):
    """Both cells run one algorithm at their own sizes: the whole
    contraction a step where the budget allows (a group's matrix block is
    then fetched once for all its row tiles), every tile a divisor of its
    width in whole lane tiles, and what a step holds inside
    ``vmem_tile_budget()``."""
    assert grouped_dot.supported(rows, d, f, *[dtype] * 4) is None
    tm = grouped_dot.row_tile(rows)
    size = jnp.dtype(dtype).itemsize
    budget = kernels.vmem_tile_budget()
    for k, n, pairs in ((d, f, 1), (f, d, 1), (f, d, 2)):
        tk, tn = grouped_dot.tiling(tm, k, n, dtype, pairs)
        assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 == tn % 128
        assert tk == k or dtype == "float32"
        assert size * (pairs * (tm * tk + tk * tn) + tm * tn) \
            + 4 * tm * tn <= budget
    for p, q in ((f, d), (d, f)):
        tp, tq = grouped_dot.tiling(tm, p, q, dtype, resident="out")
        assert p % tp == 0 and q % tq == 0 and tp % 128 == 0 == tq % 128
        assert size * (tm * (tp + tq) + tp * tq) + 4 * tp * tq <= budget
    # the wider dtype takes the smaller tiles
    assert grouped_dot.tiling(tm, d, f, "float32") < \
        grouped_dot.tiling(tm, d, f, "bfloat16")
