"""LayerNorm and bias-GELU forward+backward kernels (transformer/BERT).

The transformer block's normalization and activation epilogues are the
classic memory-bound kernels: XLA schedules LayerNorm as a multi-pass
reduce + elementwise chain and the FFN's bias-add + GELU as separate
fusions, each materializing a (tokens, hidden) intermediate to HBM.
These kernels stream a block of rows through VMEM once per pass:

- :func:`layer_norm` — f32 statistics over the trailing axis (same
  accumulation recipe as ops/nn.py ``layer_norm``), forward math
  mirrored expression-for-expression so the fp32 forward is bit-exact
  against the XLA reference for lane-aligned widths; custom-VJP
  backward computes dx in one kernel with dgamma/dbeta accumulated in
  VMEM across row blocks.
- :func:`bias_gelu` — exact (erf) GELU fused with the preceding bias
  add; the backward recomputes z = x + b and applies the closed-form
  dGELU(z) = Φ(z) + z·φ(z). Mosaic (jax 0.9.0) lowers neither erf nor
  erfc, so Φ is built in-kernel from exp (:func:`_norm_cdf`; f32 GELU
  within 1e-6 absolute of the exact form, which is also where
  ``jax.nn.gelu`` itself sits — tolerance-level parity).

Widths that are not a multiple of the 128-lane tile are zero-padded
and the statistics masked to the true width (tolerance-level parity —
a padded reduction reassociates). Dispatch: the shared MXNET_PALLAS
gate (ops/kernels/__init__.py); ops/nn.py ``layer_norm`` and
gluon/nn/transformer.py ``PositionwiseFFN`` route through here.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import dispatch, vmem_tile_budget

__all__ = ["layer_norm", "bias_gelu", "norm_supported"]

_LANES = 128
_BLOCK_ROWS = 256


def _pad_to(n, m):
    return -(-n // m) * m


def _budget_rows(cp: int, n_tiles: int = 4) -> int:
    """Row-block cap from the SHARED VMEM tile budget
    (ops/kernels.vmem_tile_budget — the same accessor rnn_scan and
    attention size against): ``n_tiles`` concurrent (rows, cp) f32
    tiles (x, dy, dx + the output) must fit. At the default 4 MiB
    budget this only binds for very wide feature axes — the 256-row
    Mosaic-program cap stays the usual limit."""
    rows = vmem_tile_budget() // max(1, n_tiles * cp * 4)
    return max(8, (rows // 8) * 8)


def norm_supported(x, c: int) -> "str | None":
    """None when the kernels cover this call, else the reason the XLA
    reference handles it."""
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {x.dtype} not kernelized (f32/bf16 only)"
    if x.ndim < 2:
        return "expects at least 2 dims (rows, features)"
    if c < 1:
        return "empty feature axis"
    return None


def _rows_layout(x, c):
    """(..., C) → padded (Rp, Cp) plus the geometry."""
    r = 1
    for d in x.shape[:-1]:
        r *= int(d)
    cp = _pad_to(c, _LANES)
    sub = 16 if x.dtype == jnp.bfloat16 else 8
    block_r = min(_BLOCK_ROWS, max(sub, _budget_rows(cp)),
                  _pad_to(max(r, 1), sub))
    rp = _pad_to(max(r, 1), block_r)
    x2 = jnp.pad(x.reshape(r, c), ((0, rp - r), (0, cp - c)))
    return x2, r, rp, cp, block_r


def _col_valid(c, cp):
    if c == cp:
        return None
    return lax.broadcasted_iota(jnp.int32, (1, cp), 1) < c


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_stats(xf, c, valid):
    """mean/var over the trailing axis; the aligned path is literally
    the reference's jnp.mean/jnp.var so the forward stays bit-exact."""
    if valid is None:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
    else:
        xm = jnp.where(valid, xf, 0.0)
        mean = jnp.sum(xm, axis=-1, keepdims=True) / c
        d = jnp.where(valid, xf - mean, 0.0)
        var = jnp.sum(d * d, axis=-1, keepdims=True) / c
    return mean, var


def _ln_fwd_kernel(eps, c, cp, x_ref, g_ref, b_ref, o_ref):
    xf = x_ref[...].astype(jnp.float32)
    mean, var = _ln_stats(xf, c, _col_valid(c, cp))
    out = (xf - mean) * lax.rsqrt(var + eps)
    out = out * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def _ln_bwd_kernel(eps, c, cp, x_ref, g_ref, dy_ref, dx_ref, dg_ref,
                   db_ref, dg_s, db_s):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_s[...] = jnp.zeros_like(dg_s)
        db_s[...] = jnp.zeros_like(db_s)

    valid = _col_valid(c, cp)
    xf = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean, var = _ln_stats(xf, c, valid)
    rstd = lax.rsqrt(var + eps)
    xhat = (xf - mean) * rstd
    if valid is not None:
        xhat = jnp.where(valid, xhat, 0.0)
        dy = jnp.where(valid, dy, 0.0)
    dg_s[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_s[...] += jnp.sum(dy, axis=0, keepdims=True)
    dxhat = dy * g_ref[...].astype(jnp.float32)
    m1 = jnp.sum(dxhat, axis=-1, keepdims=True) / c
    m2 = jnp.sum(dxhat * xhat, axis=-1, keepdims=True) / c
    dx = rstd * (dxhat - m1 - xhat * m2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dg_ref[...] = dg_s[...]
    db_ref[...] = db_s[...]


def _ln_call(x, gamma, beta, eps, interpret, bwd_dy=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    c = int(x.shape[-1])
    x2, r, rp, cp, block_r = _rows_layout(x, c)
    g2 = jnp.pad(gamma, (0, cp - c)).reshape(1, cp)
    blk = pl.BlockSpec((block_r, cp), lambda i: (i, 0))
    row1 = pl.BlockSpec((1, cp), lambda i: (0, 0))
    grid = (rp // block_r,)
    if bwd_dy is None:
        b2 = jnp.pad(beta, (0, cp - c)).reshape(1, cp)
        out = pl.pallas_call(
            functools.partial(_ln_fwd_kernel, eps, c, cp),
            grid=grid,
            in_specs=[blk, row1, row1],
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct((rp, cp), x.dtype),
            compiler_params=_params("parallel"),
            interpret=interpret,
        )(x2, g2, b2)
        return out[:r, :c].reshape(x.shape)
    dy2 = jnp.pad(bwd_dy.astype(x.dtype).reshape(r, c),
                  ((0, rp - r), (0, cp - c)))
    dx, dg, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps, c, cp),
        grid=grid,
        in_specs=[blk, row1, blk],
        out_specs=[blk, row1, row1],
        out_shape=[jax.ShapeDtypeStruct((rp, cp), x.dtype),
                   jax.ShapeDtypeStruct((1, cp), jnp.float32),
                   jax.ShapeDtypeStruct((1, cp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, cp), jnp.float32),
                        pltpu.VMEM((1, cp), jnp.float32)],
        compiler_params=_params("arbitrary"),
        interpret=interpret,
    )(x2, g2, dy2)
    return (dx[:r, :c].reshape(x.shape),
            dg[0, :c].astype(gamma.dtype),
            db[0, :c].astype(gamma.dtype))


def _params(sem):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(sem,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ln(eps, interpret, x, gamma, beta):
    return _ln_call(x, gamma, beta, eps, interpret)


def _ln_fwd(eps, interpret, x, gamma, beta):
    return _ln_call(x, gamma, beta, eps, interpret), (x, gamma)


def _ln_bwd(eps, interpret, res, dy):
    x, gamma = res
    return _ln_call(x, gamma, None, eps, interpret, bwd_dy=dy)


_ln.defvjp(_ln_fwd, _ln_bwd)


def layer_norm(x, gamma, beta, eps: float = 1e-5,
               interpret: bool = False):
    """Fused LayerNorm over the trailing axis (f32 statistics,
    activation-dtype output — the ops/nn.py recipe)."""
    return _ln(float(eps), interpret, x, gamma, beta)


# ---------------------------------------------------------------------------
# bias-GELU
# ---------------------------------------------------------------------------

_INV_SQRT2PI = 1.0 / math.sqrt(2.0 * math.pi)
_INV_SQRT2 = 1.0 / math.sqrt(2.0)
# Abramowitz & Stegun 7.1.26: erfc(x) = poly(t)·exp(-x²) + e(x) for
# x >= 0, t = 1/(1 + p·x), |e(x)| <= 1.5e-7
_ERFC_P = 0.3275911
_ERFC_A = (0.254829592, -0.284496736, 1.421413741, -1.453152027,
           1.061405429)


def _norm_cdf(z):
    """Φ(z) = erfc(-z/√2)/2 in f32 from primitives Mosaic lowers (exp,
    divide, select). Evaluating erfc at |z|/√2 and reflecting keeps the
    negative tail free of the 1 - erf cancellation. Absolute error
    3e-7 measured over [-12, 12] (half the A&S bound plus f32
    rounding); tests/test_kernels.py pins GELU within 1e-6."""
    x = jnp.abs(z) * _INV_SQRT2
    t = 1.0 / (1.0 + _ERFC_P * x)
    poly = t * (_ERFC_A[0] + t * (_ERFC_A[1] + t * (_ERFC_A[2] + t * (
        _ERFC_A[3] + t * _ERFC_A[4]))))
    half_erfc = 0.5 * poly * jnp.exp(-x * x)
    return jnp.where(z < 0, half_erfc, 1.0 - half_erfc)


def _bg_fwd_kernel(x_ref, b_ref, o_ref):
    z = (x_ref[...] + b_ref[...]).astype(jnp.float32)
    o_ref[...] = (z * _norm_cdf(z)).astype(o_ref.dtype)


def _bg_bwd_kernel(c, cp, x_ref, b_ref, dy_ref, dx_ref, db_ref, db_s):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        db_s[...] = jnp.zeros_like(db_s)

    z = (x_ref[...] + b_ref[...]).astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    # dGELU(z) = Phi(z) + z * phi(z) (exact-erf form)
    phi = jnp.exp(-0.5 * z * z) * _INV_SQRT2PI
    dx = dy * (_norm_cdf(z) + z * phi)
    valid = _col_valid(c, cp)
    if valid is not None:
        dx = jnp.where(valid, dx, 0.0)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    db_s[...] += jnp.sum(dx, axis=0, keepdims=True)
    db_ref[...] = db_s[...]


def _bg_call(x, b, interpret, bwd_dy=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    c = int(x.shape[-1])
    x2, r, rp, cp, block_r = _rows_layout(x, c)
    b2 = jnp.pad(b.astype(x.dtype), (0, cp - c)).reshape(1, cp)
    blk = pl.BlockSpec((block_r, cp), lambda i: (i, 0))
    row1 = pl.BlockSpec((1, cp), lambda i: (0, 0))
    grid = (rp // block_r,)
    if bwd_dy is None:
        out = pl.pallas_call(
            _bg_fwd_kernel,
            grid=grid,
            in_specs=[blk, row1],
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct((rp, cp), x.dtype),
            compiler_params=_params("parallel"),
            interpret=interpret,
        )(x2, b2)
        return out[:r, :c].reshape(x.shape)
    dy2 = jnp.pad(bwd_dy.astype(x.dtype).reshape(r, c),
                  ((0, rp - r), (0, cp - c)))
    dx, db = pl.pallas_call(
        functools.partial(_bg_bwd_kernel, c, cp),
        grid=grid,
        in_specs=[blk, row1, blk],
        out_specs=[blk, row1],
        out_shape=[jax.ShapeDtypeStruct((rp, cp), x.dtype),
                   jax.ShapeDtypeStruct((1, cp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, cp), jnp.float32)],
        compiler_params=_params("arbitrary"),
        interpret=interpret,
    )(x2, b2, dy2)
    return dx[:r, :c].reshape(x.shape), db[0, :c].astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bg(interpret, x, b):
    return _bg_call(x, b, interpret)


def _bg_fwd(interpret, x, b):
    return _bg_call(x, b, interpret), (x, b)


def _bg_bwd(interpret, res, dy):
    x, b = res
    return _bg_call(x, b, interpret, bwd_dy=dy)


_bg.defvjp(_bg_fwd, _bg_bwd)


def bias_gelu(x, b, interpret: bool = False):
    """Fused ``gelu(x + b)`` (exact erf form, matching
    ``F.Activation(act_type='gelu')``) over the trailing axis."""
    return _bg(interpret, x, b)
