"""The Pallas tier of ``ops.ssm.ssd_scan`` (ops/kernels/ssd_scan.py), its
kernel bodies under the interpreter on the CPU (``MXNET_PALLAS=on``), at
the Nemotron cell's geometry cut down: heads of width 64 in groups of 8
over a state of 128, chunks of 128, batch 2. Forward and every gradient
against the recurrence and against ``_ssd_chunked``; the mask before the
exponential; what the gate declines and why; the three gate modes and
``mx_ssd_scan_total{tier}``. (What the custom VJP keeps from forward to
backward is tests/test_nemotron_h.py's, beside the XLA tier's.)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels
from mxnet_tpu.ops import ssm as SSM
from mxnet_tpu.ops.kernels import ssd_scan as scan_kernels
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.telemetry import names as tnames

HEADS, WIDTH, GROUPS, STATE, CHUNK = 16, 64, 2, 128, 128
OPERANDS = ("x", "dt", "A", "B", "C", "D")


def scan_inputs(seq, dtype, batch=2, rate=None, seed=0):
    """Operands as the mixer hands them over: x, B, C in ``dtype``, the
    step sizes, decay rates and skip float32; ``dt A`` from about 0.01 to
    12 a token as the benchmark's weights spread them, or ``rate`` a token
    for every head."""
    rng = onp.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jax.nn.softplus(normal(batch, seq, HEADS) + jnp.asarray(
        rng.uniform(-1, 1, HEADS), jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.uniform(-1.39, 1.39, HEADS), jnp.float32))
    if rate is not None:
        dt, A = jnp.ones_like(dt), jnp.full_like(A, -rate)
    return (normal(batch, seq, HEADS, WIDTH).astype(dtype), dt, A,
            normal(batch, seq, GROUPS, STATE).astype(dtype),
            normal(batch, seq, GROUPS, STATE).astype(dtype), normal(HEADS))


def y_and_gradients(fn, args, weigh):
    """{"y": fn(*args), operand: gradient of sum(y * weigh)}, float32."""
    y, pull = jax.vjp(lambda *a: fn(*a).astype(jnp.float32), *args)
    out = dict(zip(OPERANDS, pull(weigh)), y=y)
    return {k: onp.asarray(v, "float32") for k, v in out.items()}


@functools.lru_cache(maxsize=None)
def three_forms(dtype, seq, through_state=False, rate=None):
    """The kernel tier, ``_ssd_chunked`` and the recurrence on one set of
    operands (the recurrence on their float32 copies), float32 products at
    HIGHEST on every side. ``through_state``: only the LAST chunk's y is
    weighed, so what reaches an earlier chunk's operands went through the
    carried state."""
    args = scan_inputs(seq, dtype, rate=rate)
    weigh = jnp.asarray(onp.random.default_rng(9).normal(
        size=args[0].shape), jnp.float32)
    if through_state:
        weigh = weigh.at[:, :-(seq % CHUNK or CHUNK)].set(0.0)
    env = pytest.MonkeyPatch()
    try:
        with jax.default_matmul_precision("highest"):
            env.setenv("MXNET_PALLAS", "on")
            got = y_and_gradients(SSM.ssd_scan, args, weigh)
            assert kernels.decisions()["ssd_scan"][0] == "interpret"
            env.setenv("MXNET_PALLAS", "off")
            chunked = y_and_gradients(SSM.ssd_scan, args, weigh)
            assert kernels.decisions()["ssd_scan"][0] == "xla"
            recurrence = y_and_gradients(
                SSM.ssd_scan_reference,
                tuple(a.astype(jnp.float32) for a in args), weigh)
    finally:
        env.undo()
    return got, chunked, recurrence


def gap(a, b):
    return float(onp.abs(a - b).max() / (onp.abs(b).max() + 1e-30))


# float32 sums in another order: 2e-5 of the largest entry, what
# tests/test_nemotron_h.py allows the chunked form; bf16 operands, rounded
# three times along a path on both tiers: 2 ** -6
TOLERANCE = {"float32": 2e-5, "bfloat16": 2.0 ** -6}


@pytest.mark.parametrize("tensor", ("y",) + OPERANDS)
@pytest.mark.parametrize("seq", [256, 300], ids=["whole_chunks",
                                                 "padded_tail"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_are_the_recurrence_and_the_chunked_form(dtype, seq, tensor):
    got, chunked, recurrence = three_forms(dtype, seq)
    assert got[tensor].shape == recurrence[tensor].shape
    assert onp.isfinite(got[tensor]).all()
    assert gap(got[tensor], recurrence[tensor]) < TOLERANCE[dtype]
    assert gap(got[tensor], chunked[tensor]) < TOLERANCE[dtype]
    # no further from the oracle than XLA's chunked form is, but for noise
    assert gap(got[tensor], recurrence[tensor]) \
        < 2 * gap(chunked[tensor], recurrence[tensor]) + 1e-6


@pytest.mark.parametrize("tensor", OPERANDS[:5])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gradient_through_the_carried_state(dtype, tensor):
    """Only the last of three chunks is weighed: the first two chunks' x,
    dt and B (and A) are reached through the state the grid carries, and
    its cotangent on the way back; their C, which only reads, not at all."""
    got, _, recurrence = three_forms(dtype, 300, through_state=True)
    early = (slice(None), slice(0, 2 * CHUNK)) if tensor != "A" else ()
    if tensor == "C":
        assert not got["C"][early].any() and not recurrence["C"][early].any()
        return
    assert onp.abs(recurrence[tensor][early]).max() > 0
    assert gap(got[tensor][early], recurrence[tensor][early]) \
        < TOLERANCE[dtype]


@pytest.mark.parametrize("tensor", ("y",) + OPERANDS)
def test_mask_stands_before_the_exponential(tensor):
    """``dt A`` of 5 a token: above the diagonal ``cs_t - cs_s`` reaches
    635 and its exponential is infinite; masked first it never is, in the
    forward kernel or in the backward's second making of ``L``."""
    got, chunked, recurrence = three_forms("float32", 256, rate=5.0)
    assert onp.isfinite(got[tensor]).all()
    # cs reaches -640 in float32, so dA, a sum over every position of
    # differences of such numbers, is 5e-4 off on BOTH chunked tiers
    assert gap(got[tensor], recurrence[tensor]) < max(
        TOLERANCE["float32"], 1.5 * gap(chunked[tensor], recurrence[tensor]))


def test_no_skip_is_a_skip_of_zero(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "on")
    args = scan_inputs(256, "float32", batch=1)
    with jax.default_matmul_precision("highest"):
        got = SSM.ssd_scan(*args[:5])
        want = SSM.ssd_scan_reference(*args[:5])
    assert gap(onp.asarray(got), onp.asarray(want)) < 2e-5


DECLINED = {
    "chunk_8": (dict(chunk=8), "chunks of 8"),
    "state_16": (dict(state=16), "state 16"),
    "head_lanes_192": (dict(heads=6, groups=2), "3 x 64"),
    "heads_of_96": (dict(width=96, heads=8, groups=2), "heads of 96"),
    "float16": (dict(dtype="float16"), "float16 not kernelized"),
    "float32_at_high": (dict(precision="high"), "precision 'high'"),
}


@pytest.mark.parametrize("case", DECLINED)
def test_gate_declines_by_what_it_sees_and_says_why(case, monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "on")
    change, why = DECLINED[case]
    shape = dict(heads=HEADS, width=WIDTH, groups=GROUPS, state=STATE,
                 chunk=CHUNK, dtype="float32", precision="highest")
    shape.update(change)
    rng = onp.random.default_rng(1)
    seq = 32

    def normal(*dims):
        return jnp.asarray(rng.normal(size=dims), shape["dtype"])
    x = normal(1, seq, shape["heads"], shape["width"])
    B, C = (normal(1, seq, shape["groups"], shape["state"])
            for _ in range(2))
    dt = jnp.full((1, seq, shape["heads"]), 0.1, jnp.float32)
    A = -jnp.ones((shape["heads"],), jnp.float32)
    with jax.default_matmul_precision(shape["precision"]):
        y = SSM.ssd_scan(x, dt, A, B, C, chunk=shape["chunk"])
    path, reason = kernels.decisions()["ssd_scan"]
    assert path == "xla" and why in reason, reason
    assert y.shape == x.shape
    assert scan_kernels.supported(
        HEADS, WIDTH, GROUPS, STATE, CHUNK, jnp.bfloat16) is None


def test_gate_declines_a_step_that_outgrows_the_tile_budget(monkeypatch):
    assert scan_kernels.supported(64, 64, 8, 128, 128, jnp.bfloat16) is None
    assert "tile budget" in scan_kernels.supported(
        64, 64, 1, 128, 128, jnp.bfloat16)
    monkeypatch.setenv("MXNET_VMEM_TILE_BUDGET", str(1 << 20))
    assert "tile budget" in scan_kernels.supported(
        64, 64, 8, 128, 128, jnp.bfloat16)


def test_gate_declines_a_mesh_on_the_chip(monkeypatch):
    """On a TPU under a multi-device mesh the scan is ``_ssd_chunked``'s
    (Mosaic kernels are not partitioned); without one it is the compiled
    kernels'."""
    monkeypatch.delenv("MXNET_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = scan_inputs(256, "bfloat16", batch=1)
    jax.eval_shape(lambda *a: SSM.ssd_scan(*a), *args)
    assert kernels.decisions()["ssd_scan"][0] == "pallas"
    with make_mesh({"dp": 4}, jax.devices()[:4]):
        jax.eval_shape(lambda *a: SSM.ssd_scan(*a), *args)
        path, reason = kernels.decisions()["ssd_scan"]
    assert path == "xla" and "GSPMD" in reason


@pytest.mark.parametrize("mode,tier", [("on", "interpret"), ("off", "xla"),
                                       ("auto", "xla")])
def test_gate_modes_and_the_tier_counter(mode, tier, monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", mode)
    before = {t: telemetry.value(tnames.SSD_SCAN, t) or 0
              for t in ("pallas", "interpret", "xla")}
    chunks = telemetry.value(tnames.SSD_SCAN_CHUNKS) or 0
    jax.eval_shape(lambda *a: SSM.ssd_scan(*a), *scan_inputs(300, "bfloat16", batch=1))
    assert kernels.decisions()["ssd_scan"][0] == tier
    after = {t: telemetry.value(tnames.SSD_SCAN, t) or 0 for t in before}
    assert {t: after[t] - before[t] for t in before if after[t] != before[t]
            } == {tier: 1}
    assert telemetry.value(tnames.SSD_SCAN_CHUNKS) == chunks + 3


def test_off_and_auto_lower_the_chunked_form_alone(monkeypatch):
    """Off the chip and switched off, the program is ``_ssd_chunked``'s,
    instruction for instruction: the gate adds nothing to the trace."""
    args = scan_inputs(256, "bfloat16", batch=1)

    def text(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(
            jnp.float32)), argnums=(0, 1, 2, 3, 4, 5))).lower(*args).as_text()
    plain = text(lambda *a: jax.checkpoint(
        functools.partial(SSM._ssd_chunked, chunk=CHUNK),
        policy=jax.checkpoint_policies.save_only_these_names(
            SSM.SSD_STATES))(*a))
    for mode in ("off", "auto"):
        monkeypatch.setenv("MXNET_PALLAS", mode)
        assert text(SSM.ssd_scan) == plain
    monkeypatch.setenv("MXNET_PALLAS", "on")
    assert "ssd_scan_fwd" not in plain and text(SSM.ssd_scan) != plain
