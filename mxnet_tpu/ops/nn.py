"""Neural-network ops: convolution, pooling, normalization.

Reference analog: src/operator/nn/ (~31k LoC: conv via im2col/cuDNN, pooling
kernels, batch/layer/group/instance norm CPU+CUDA kernels). TPU-native design:
everything lowers to XLA's native conv/reduce-window/reduce emitters —
`lax.conv_general_dilated` maps directly onto the MXU, and XLA fuses the
normalization arithmetic into surrounding ops, absorbing what the reference's
cuDNN/MKLDNN vendor layers did by hand (SURVEY §2.2 note).

Layout: the public API is NCHW/NCW/NCDHW like the reference ops; XLA's TPU
layout assignment transposes internally to the MXU-friendly layout, so we keep
API parity without a perf tax.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError

__all__ = ["conv", "conv_transpose", "pool", "global_pool", "batch_norm_infer",
           "batch_norm_train", "embedding", "layer_norm", "group_norm",
           "instance_norm", "l2_norm", "lrn", "adaptive_avg_pool",
           "bilinear_resize"]


def _tup(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(v)
    return t if len(t) == n else t + t[-1:] * (n - len(t))


def _conv_dn(ndim: int):
    """NC+spatial dimension numbers for lax.conv_general_dilated."""
    sp = "DHW"[3 - ndim:]
    return lax.conv_dimension_numbers(
        (1, 1) + (1,) * ndim,  # dummy shapes; only layout strings matter
        (1, 1) + (1,) * ndim,
        ("NC" + sp, "OI" + sp, "NC" + sp))


def conv(x, w, b=None, stride=None, dilate=None, pad=None, num_group: int = 1):
    """N-d convolution, NC+spatial layout (reference Convolution op,
    src/operator/nn/convolution.cc). Lowers to one XLA conv → MXU."""
    ndim = x.ndim - 2
    stride = _tup(stride, ndim)
    dilate = _tup(dilate, ndim)
    pad = _tup(pad if pad is not None else 0, ndim)
    dn = _conv_dn(ndim)
    # NOTE: no preferred_element_type — the TPU MXU accumulates bf16 convs
    # in f32 internally regardless (one rounding at the output), and this
    # jax version's conv VJP mis-types the transposed conv when preferred
    # differs from the input dtype (bf16 primal vs f32 cotangent)
    out = lax.conv_general_dilated(
        x, w, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group)
    if b is not None:
        out = out + b.reshape((1, -1) + (1,) * ndim)
    return out


def conv_transpose(x, w, b=None, stride=None, dilate=None, pad=None,
                   adj=None, num_group: int = 1):
    """Transposed convolution (reference Deconvolution op). Implemented as
    the gradient of conv: lhs-dilated XLA conv."""
    ndim = x.ndim - 2
    stride = _tup(stride, ndim)
    dilate = _tup(dilate, ndim)
    pad = _tup(pad if pad is not None else 0, ndim)
    adj = _tup(adj if adj is not None else 0, ndim)
    dn = _conv_dn(ndim)
    k = w.shape[2:]
    # effective kernel extent with dilation
    eff = [(kk - 1) * dd + 1 for kk, dd in zip(k, dilate)]
    padding = [(e - 1 - p, e - 1 - p + a)
               for e, p, a in zip(eff, pad, adj)]
    # flip spatial dims and swap I/O channels for the gradient-conv form
    wt = jnp.flip(w, axis=tuple(range(2, 2 + ndim)))
    if num_group > 1:
        o, i = wt.shape[0], wt.shape[1]
        wt = wt.reshape((num_group, o // num_group, i) + k)
        wt = jnp.swapaxes(wt, 1, 2)
        wt = wt.reshape((num_group * i, o // num_group) + k)
    else:
        wt = jnp.swapaxes(wt, 0, 1)
    out = lax.conv_general_dilated(
        x, wt, window_strides=(1,) * ndim,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group)
    if b is not None:
        out = out + b.reshape((1, -1) + (1,) * ndim)
    return out


def pool(x, kernel, pool_type: str = "max", stride=None, pad=None,
         count_include_pad: bool = True, ceil_mode: bool = False,
         p_value: int = 2):
    """Max/avg/sum/lp pooling via XLA reduce_window (reference Pooling op).
    ceil_mode ≙ reference pooling_convention='full': extra right-padding so
    the output size uses ceil instead of floor (src/operator/nn/pooling.cc)."""
    ndim = x.ndim - 2
    kernel = _tup(kernel, ndim)
    stride = _tup(stride if stride is not None else kernel, ndim)
    pad = _tup(pad if pad is not None else 0, ndim)
    rpad = list(pad)
    if ceil_mode:
        for i in range(ndim):
            span = x.shape[2 + i] + 2 * pad[i] - kernel[i]
            rem = span % stride[i]
            if rem:
                rpad[i] = pad[i] + (stride[i] - rem)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    padding = ((0, 0), (0, 0)) + tuple(
        (p, r) for p, r in zip(pad, rpad))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        # Denominator semantics (reference src/operator/nn/pool.h): with
        # count_include_pad the window is clipped to the explicitly-padded
        # extent [0, H+2p) — ceil_mode's extra right-padding never counts;
        # without it only real elements count. Both reduce to a constant
        # that XLA folds when no clipping can occur.
        if count_include_pad:
            cnt_shape = (1, 1) + tuple(x.shape[2 + i] + 2 * pad[i]
                                       for i in range(ndim))
            cnt_pad = ((0, 0), (0, 0)) + tuple(
                (0, r - p) for p, r in zip(pad, rpad))
            ones = jnp.ones(cnt_shape, x.dtype)
        else:
            ones = jnp.ones((1, 1) + x.shape[2:], x.dtype)
            cnt_pad = padding
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, cnt_pad)
        return s / cnt
    if pool_type == "lp":
        s = lax.reduce_window(jnp.abs(x) ** p_value, 0.0, lax.add, window,
                              strides, padding)
        return s ** (1.0 / p_value)
    raise MXNetError(f"unknown pool_type {pool_type}")


def global_pool(x, pool_type: str = "max"):
    axes = tuple(range(2, x.ndim))
    if pool_type == "max":
        return jnp.max(x, axis=axes, keepdims=True)
    if pool_type == "avg":
        return jnp.mean(x, axis=axes, keepdims=True)
    return jnp.sum(x, axis=axes, keepdims=True)


def adaptive_avg_pool(x, output_size):
    """Reference contrib.AdaptiveAvgPooling2D."""
    n, c, h, w = x.shape
    oh, ow = (output_size, output_size) if isinstance(output_size, int) \
        else output_size
    if h % oh == 0 and w % ow == 0:
        x = x.reshape(n, c, oh, h // oh, ow, w // ow)
        return x.mean(axis=(3, 5))
    # general case: interp-style averaging via image resize of the integral
    return jax.image.resize(x, (n, c, oh, ow), method="linear")


def bilinear_resize(x, height: int, width: int, align_corners: bool = False):
    """Reference contrib.BilinearResize2D."""
    n, c, h, w = x.shape
    return jax.image.resize(x, (n, c, height, width), method="linear")


def _bcast_stats(ndim, v):
    return v.reshape((1, -1) + (1,) * (ndim - 2))


def _stat_dtype(x):
    """Normalization statistics accumulate in f32 even when activations
    flow bf16/fp16 (AMP): same recipe as every production TPU BN — the
    low-precision tensor is only the storage format, never the reduction
    accumulator. f64 inputs keep f64."""
    return jnp.promote_types(x.dtype, jnp.float32)


def batch_norm_infer(x, gamma, beta, moving_mean, moving_var, eps: float):
    """Inference-mode BN: normalize with running stats (f32 arithmetic,
    output in the activation dtype)."""
    dt = _stat_dtype(x)
    xf = x.astype(dt)
    mm = _bcast_stats(x.ndim, moving_mean).astype(dt)
    mv = _bcast_stats(x.ndim, moving_var).astype(dt)
    g = _bcast_stats(x.ndim, gamma).astype(dt)
    b = _bcast_stats(x.ndim, beta).astype(dt)
    inv = lax.rsqrt(mv + eps)
    return ((xf - mm) * inv * g + b).astype(x.dtype)


def batch_norm_train(x, gamma, beta, eps: float):
    """Training-mode BN: returns (out, batch_mean, batch_var) so the layer
    can fold the running-stat update into the same compiled step
    (reference batch_norm.cc saves mean/var as aux outputs). Stats are
    f32; out keeps the activation dtype."""
    dt = _stat_dtype(x)
    xf = x.astype(dt)
    axes = (0,) + tuple(range(2, x.ndim))
    mean = jnp.mean(xf, axis=axes)
    var = jnp.var(xf, axis=axes)
    m = _bcast_stats(x.ndim, mean)
    v = _bcast_stats(x.ndim, var)
    g = _bcast_stats(x.ndim, gamma).astype(dt)
    b = _bcast_stats(x.ndim, beta).astype(dt)
    out = ((xf - m) * lax.rsqrt(v + eps) * g + b).astype(x.dtype)
    return out, mean, var


def layer_norm(x, gamma, beta, axis: int = -1, eps: float = 1e-5):
    """Reference LayerNorm (src/operator/nn/layer_norm.cc). f32 stats,
    activation-dtype output.

    Trailing-axis calls dispatch through the Pallas kernel layer when
    the MXNET_PALLAS gate selects it (ops/kernels/norm.py: one VMEM
    pass per row block, fused forward+backward; fp32 forward bit-exact
    vs this reference for 128-lane-aligned widths)."""
    if axis == -1 or axis == x.ndim - 1:
        from .kernels import dispatch as _kdispatch
        from .kernels import norm as _knorm
        why = _knorm.norm_supported(x, int(x.shape[-1]))
        path, _ = _kdispatch("layernorm", supported=why is None,
                             reason=why)
        if path != "xla":
            return _knorm.layer_norm(x, gamma, beta, eps,
                                     interpret=(path == "interpret"))
    dt = _stat_dtype(x)
    xf = x.astype(dt)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.var(xf, axis=axis, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return (out * gamma.astype(dt).reshape(shape)
            + beta.astype(dt).reshape(shape)).astype(x.dtype)


def embedding(ids, table):
    """``table[ids]`` along the rows, an id past either end clamped to the
    nearest row (int32 ``ids`` of any shape). The table's gradient sums
    the cotangent's rows into the ids' rows; on one chip it is
    ``moe_rows.scatter_sum``, one pass over the ids with the table's
    column block resident in VMEM in float32 (8,192 ids into 18,992 x
    2,560 float32 rows: 0.65 ms on a TPU v5e, XLA's scatter-add 8.03)."""
    return _table_rows(table.shape[0], ids, table)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _table_rows(rows, ids, table):
    return jnp.take(table, ids, axis=0, mode="clip")


def _table_rows_fwd(rows, ids, table):
    # autodiff's own residuals, so that XLA's tier lowers as plain
    # autodiff of the gather does
    out, pull = jax.vjp(lambda t: jnp.take(t, ids, axis=0, mode="clip"),
                        table)
    return out, (ids, pull)


def _table_rows_bwd(rows, res, ct):
    ids, pull = res
    from .kernels import count_traced, dispatch, moe_rows
    d = ct.shape[-1]
    total = ids.size
    padded = total + -total % 128
    why = moe_rows.rows_supported(rows, padded, 1, d, ct.dtype) \
        if total else "no ids"
    path, _ = dispatch("embedding_grad", supported=why is None, reason=why)
    count_traced("EMBEDDING_GRAD", "tier", path)
    if path == "xla":
        return None, pull(ct)[0]
    # clipped as the forward's gather clipped: an id past the table would
    # be a row store outside the kernel's VMEM block
    flat = jnp.pad(jnp.clip(ids.reshape(-1), 0, rows - 1),
                   (0, padded - total))
    src = jnp.pad(ct.reshape(total, d), ((0, padded - total), (0, 0)))
    dw = moe_rows.scatter_sum(src, flat, jnp.int32(total), 1, rows,
                              jnp.ones((rows, 1), jnp.float32),
                              interpret=path == "interpret",
                              name="embedding_grad")
    return None, dw.astype(ct.dtype)


_table_rows.defvjp(_table_rows_fwd, _table_rows_bwd)


def rms_norm(x, gamma, eps: float = 1e-6):
    """Root-mean-square norm over the last axis: ``x / sqrt(mean(x^2) +
    eps) * gamma``, no mean taken out and no bias. f32 stats,
    activation-dtype output, as the other norms."""
    dt = _stat_dtype(x)
    xf = x.astype(dt)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * gamma.astype(dt)).astype(x.dtype)


def group_norm(x, gamma, beta, num_groups: int, eps: float = 1e-5):
    """Reference GroupNorm (src/operator/nn/group_norm.cc). x: (N, C, ...).
    f32 stats, activation-dtype output."""
    dt = _stat_dtype(x)
    n, c = x.shape[:2]
    sp = x.shape[2:]
    xg = x.astype(dt).reshape((n, num_groups, c // num_groups) + sp)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + eps)
    out = xg.reshape(x.shape)
    shape = (1, c) + (1,) * len(sp)
    return (out * gamma.astype(dt).reshape(shape)
            + beta.astype(dt).reshape(shape)).astype(x.dtype)


def instance_norm(x, gamma, beta, eps: float = 1e-5):
    """Reference InstanceNorm: normalize per (N, C) over spatial dims.
    f32 stats, activation-dtype output."""
    dt = _stat_dtype(x)
    xf = x.astype(dt)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return (out * gamma.astype(dt).reshape(shape)
            + beta.astype(dt).reshape(shape)).astype(x.dtype)


def l2_norm(x, axis=None, eps: float = 1e-10, mode: str = "instance"):
    """Reference L2Normalization."""
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, x.ndim))
    else:
        axes = axis
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return x / norm


def lrn(x, nsize: int, alpha: float = 1e-4, beta: float = 0.75,
        knorm: float = 2.0):
    """Local response normalization across channels (reference lrn.cc)."""
    sq = jnp.square(x)
    half = nsize // 2
    pad_cfg = [(0, 0)] * x.ndim
    pad_cfg[1] = (half, half)
    sqp = jnp.pad(sq, pad_cfg)
    window = [1] * x.ndim
    window[1] = nsize
    ssum = lax.reduce_window(sqp, 0.0, lax.add, tuple(window),
                             (1,) * x.ndim, "VALID")
    return x / jnp.power(knorm + alpha * ssum / nsize, beta)
