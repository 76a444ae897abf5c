"""The row movers of the dropless expert layer (ops/kernels/moe_rows.py):
the kernel bodies in interpret mode against the XLA forms of ops/moe.py,
which stay as the tier of every other backend and as the oracle. On the
chip the same comparison is ``chip_smoke.py``'s ``moe_rows`` case.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels
from mxnet_tpu.ops import moe as MOE
from mxnet_tpu.ops.kernels import moe_rows
from mxnet_tpu.telemetry import names as tnames

N, D, F, E = 128, 128, 64, 8

#: name -> (top_k, held): the whole layer (every pair live), a share of
#: three, a share of ONE under top-2 (count < k: the list has N rows and
#: places run past it), and a share that is given no pair at all
SHARES = {"whole": (2, (0, E)), "three": (2, (2, 3)), "one": (2, (5, 1)),
          "none": (2, (6, 2))}


def _inputs(dtype, k, held, seed=0):
    rng = onp.random.default_rng(seed)
    x = rng.normal(size=(N, D))
    rw = rng.normal(size=(E, D))
    if held == SHARES["none"][1]:
        # a constant feature the held experts' routers read with a large
        # negative weight: no token chooses them
        x[:, 0] = 4.0
        rw[held[0]:held[0] + held[1]] = 0.0
        rw[held[0]:held[0] + held[1], 0] = -50.0
    c = held[1]
    gate, up = (rng.normal(size=(c, F, D)) * D ** -0.5 for _ in range(2))
    down = rng.normal(size=(c, D, F)) * F ** -0.5
    g = rng.normal(size=(N, D))
    return (tuple(jnp.asarray(a, dtype) for a in (x, gate, up, down))
            + (jnp.asarray(rw, jnp.float32),), jnp.asarray(g, jnp.float32))


def _layer(k, held):
    def layer(x, gate, up, down, rw):
        w, order, place, sizes = MOE.moe_route(x, rw, k, held)
        y = MOE.moe_experts(x, order, place, sizes, gate, up, down)
        return MOE.moe_combine(y, w, order, place, sizes)
    return layer


def _out_and_grads(monkeypatch, mode, k, held, args, g):
    monkeypatch.setenv("MXNET_PALLAS", mode)
    out, vjp = jax.vjp(_layer(k, held), *args)
    return (out,) + vjp(g)


def _movers():
    return {t: telemetry.value(tnames.MOE_ROW_MOVER, t) or 0
            for t in ("pallas", "interpret", "xla")}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_layer_and_every_gradient_equal_the_xla_forms(share, dtype,
                                                      monkeypatch):
    k, held = SHARES[share]
    args, g = _inputs(dtype, k, held)
    total = int(MOE.moe_route(args[0], args[-1], k, held)[3].sum())
    rows = N * min(k, held[1])
    assert {"whole": total == rows, "none": total == 0}.get(
        share, 0 < total < rows and total % 128)
    want = _out_and_grads(monkeypatch, "off", k, held, args, g)
    before = _movers()
    got = _out_and_grads(monkeypatch, "on", k, held, args, g)
    after = _movers()
    assert kernels.decisions()["moe_rows"][0] == "interpret"
    # the three call sites, each traced at least once, none by XLA
    assert after["interpret"] - before["interpret"] >= 3
    assert after["xla"] == before["xla"]
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = (onp.asarray(t, "float32") for t in (a, b))
        assert onp.isfinite(a).all()
        # the same rows in float32; a token's few terms summed in the
        # experts' order, not the choices'
        assert onp.abs(a - b).max() <= 2e-6 * max(1.0, onp.abs(b).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("total", [0, 1, 127, 128, 129, 300, 512])
def test_the_walk_stops_at_the_held_total(total, dtype, monkeypatch):
    """Four row blocks of 128 and two column blocks: a total inside a
    block, on its edge, none and all. Live rows are right, the rest of
    the last live block is zero, and a NaN anywhere past the total of the
    list a kernel reads reaches nothing."""
    monkeypatch.setattr(moe_rows, "_BLOCK_ROWS", 128)
    monkeypatch.setattr(moe_rows, "_RESIDENT_BYTES", 64 * 128 * 4)
    n, k, d, rows = 64, 8, 256, 512
    assert moe_rows._geometry(n, rows, d) == (128, 128, 2, 4)
    rng = onp.random.default_rng(total)
    order = rng.permutation(n * k)[:rows]
    token = order // k
    weights = rng.uniform(0.1, 1, size=(n, k)).astype("float32")
    w = weights.reshape(-1)[order].astype("float64")
    tokens = rng.normal(size=(n, d)).astype("float32")
    listed = rng.normal(size=(rows, d))
    listed[total:] = onp.nan
    listed = jnp.asarray(listed, dtype)
    live = onp.asarray(listed, "float64")[:total]
    args = (jnp.asarray(order, jnp.int32), jnp.asarray(total, jnp.int32), k)
    tol = 1e-6 if dtype == "float32" else 2.0 ** -8

    got, dots = moe_rows.gather_rows(jnp.asarray(tokens), *args,
                                     jnp.asarray(weights), listed,
                                     interpret=True)
    edge = -(-total // 128) * 128
    want = onp.zeros((edge, d))
    want[:total] = tokens[token[:total]] * w[:total, None]
    assert got.dtype == listed.dtype and dots.dtype == jnp.float32
    onp.testing.assert_allclose(onp.asarray(got, "float64")[:edge], want,
                                rtol=tol, atol=tol)
    onp.testing.assert_allclose(
        onp.asarray(dots, "float64")[:total],
        (live * tokens[token[:total]]).sum(-1), rtol=1e-5, atol=1e-5)

    got = moe_rows.scatter_sum(listed, *args, n, jnp.asarray(weights),
                               interpret=True)
    want = onp.zeros((n, d))
    onp.add.at(want, token[:total], live * w[:total, None])
    assert got.dtype == jnp.float32 and got.shape == (n, d)
    onp.testing.assert_allclose(onp.asarray(got, "float64"), want, rtol=1e-6,
                                atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_past_the_last_group_may_hold_anything(dtype, monkeypatch):
    """Every grouped product poisoned with NaN past the last group, so
    ``y`` and the cotangent of ``xs`` are: the kernel tier's output and
    gradients stay finite and equal the clean XLA forms."""
    k, held = SHARES["three"]
    args, g = _inputs(dtype, k, held, seed=3)
    want = _out_and_grads(monkeypatch, "off", k, held, args, g)

    def past_the_groups(fn, sizes_at):
        def poisoned(*a):
            out = fn(*a)
            keep = jnp.arange(out.shape[0]) < a[sizes_at].sum()
            return jnp.where(keep[:, None], out, jnp.nan)
        return poisoned
    monkeypatch.setattr(MOE, "_grouped_dot",
                        past_the_groups(MOE._grouped_dot, 2))
    monkeypatch.setenv("MXNET_PALLAS", "on")
    x, gate, up, down, rw = args
    route = MOE.moe_route(x, rw, k, held)
    y = MOE.moe_experts(x, *route[1:], gate, up, down)
    assert bool(jnp.isnan(y).any())                  # the poison is there
    got = _out_and_grads(monkeypatch, "on", k, held, args, g)
    for a, b in zip(got, want):
        a, b = (onp.asarray(t, "float32") for t in (a, b))
        assert onp.isfinite(a).all()
        assert onp.abs(a - b).max() <= 2e-6 * max(1.0, onp.abs(b).max())


@pytest.mark.parametrize("why,n,k,d,dtype", [
    ("no multiple of 128 lanes", N, 2, 96, "float32"),
    ("no multiple of 16 sublanes", 72, 2, D, "float32"),
    ("none of a 128-row block", 80, 2, D, "float32"),
    ("not kernelized", N, 2, D, "float16"),
    ("do not fit SMEM", 40000, 2, D, "bfloat16"),
])
def test_what_the_kernels_do_not_take_goes_to_xla_and_says_why(
        why, n, k, d, dtype, monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "on")
    assert why in moe_rows.rows_supported(n, n * k, k, d, jnp.dtype(dtype))
    if n > 1000:
        return
    rng = onp.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=(n * k, d)), dtype)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    rw = jnp.asarray(rng.normal(size=(E, d)), jnp.float32)
    weights, order, place, sizes = MOE.moe_route(x, rw, k, (0, E))
    before = _movers()
    out = MOE.moe_combine(y, weights, order, place, sizes)
    assert _movers()["xla"] == before["xla"] + 1
    path, reason = kernels.decisions()["moe_rows"]
    assert path == "xla" and why in reason
    want = (onp.asarray(y, "float32")[onp.asarray(place)]
            * onp.asarray(weights)[..., None]).sum(1)
    onp.testing.assert_allclose(onp.asarray(out), want, rtol=1e-5, atol=1e-5)


def test_the_gate_switches_the_tier_and_off_is_the_xla_form(monkeypatch):
    k, held = SHARES["three"]
    (x, _, _, _, rw), _ = _inputs("float32", k, held)
    weights, order, place, sizes = MOE.moe_route(x, rw, k, held)
    y = jnp.ones((order.shape[0], D), jnp.float32)
    for mode, tier in (("off", "xla"), ("auto", "xla"), ("on", "interpret")):
        monkeypatch.setenv("MXNET_PALLAS", mode)
        before = _movers()
        MOE.moe_combine(y, weights, order, place, sizes)
        assert kernels.decisions()["moe_rows"][0] == tier
        assert _movers()[tier] == before[tier] + 1
