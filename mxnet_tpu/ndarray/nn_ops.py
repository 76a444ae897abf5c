"""Legacy NN op wrappers (``mx.nd.Convolution`` etc.) over ops/nn.py kernels.

Reference analog: the generated wrappers for src/operator/nn/ registrations.
Parameter names/semantics follow the reference ops so model code ports 1:1.
"""
from __future__ import annotations

import numpy as onp

import jax.numpy as jnp

from .. import _tape
from ..base import MXNetError
from ..ops import nn as K
from ..ops.registry import invoke_raw
from .ndarray import NDArray

__all__ = ["Convolution", "Deconvolution", "Pooling", "BatchNorm",
           "LayerNorm", "RMSNorm", "GroupNorm", "InstanceNorm",
           "L2Normalization",
           "LRN", "UpSampling", "BilinearResize2D", "RNN"]


def _wrap(x):
    return x if isinstance(x, NDArray) else NDArray(x)


def Convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None, **_ignored):
    data, weight = _wrap(data), _wrap(weight)
    if no_bias or bias is None:
        return invoke_raw(
            "convolution",
            lambda x, w: K.conv(x, w, None, stride, dilate, pad, num_group),
            [data, weight])
    return invoke_raw(
        "convolution",
        lambda x, w, b: K.conv(x, w, b, stride, dilate, pad, num_group),
        [data, weight, _wrap(bias)])


def Deconvolution(data, weight, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, num_filter=None,
                  num_group=1, no_bias=True, target_shape=None, **_ignored):
    data, weight = _wrap(data), _wrap(weight)
    if no_bias or bias is None:
        return invoke_raw(
            "deconvolution",
            lambda x, w: K.conv_transpose(x, w, None, stride, dilate, pad,
                                          adj, num_group),
            [data, weight])
    return invoke_raw(
        "deconvolution",
        lambda x, w, b: K.conv_transpose(x, w, b, stride, dilate, pad, adj,
                                         num_group),
        [data, weight, _wrap(bias)])


def Pooling(data, kernel=None, pool_type="max", stride=None, pad=None,
            global_pool=False, count_include_pad=True, pooling_convention=None,
            ceil_mode=False, p_value=2, **_ignored):
    data = _wrap(data)
    if global_pool:
        return invoke_raw("global_pool",
                          lambda x: K.global_pool(x, pool_type), [data])
    ceil = ceil_mode or pooling_convention == "full"
    return invoke_raw(
        "pooling",
        lambda x: K.pool(x, kernel, pool_type, stride, pad, count_include_pad,
                         ceil, p_value),
        [data])


def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
              momentum=0.9, fix_gamma=False, use_global_stats=False,
              output_mean_var=False, axis=1, **_ignored):
    """Imperative BatchNorm. In training mode returns normalized output and
    updates moving stats in place on the passed arrays (the Gluon layer calls
    the functional kernels directly for the hybridized path)."""
    data = _wrap(data)
    gamma, beta = _wrap(gamma), _wrap(beta)
    mm, mv = _wrap(moving_mean), _wrap(moving_var)
    training = _tape.is_training() and not use_global_stats
    if fix_gamma:
        gamma = NDArray(gamma._data * 0 + 1)
    if not training:
        return invoke_raw(
            "batch_norm",
            lambda x, g, b, m, v: K.batch_norm_infer(x, g, b, m, v, eps),
            [data, gamma, beta, mm, mv])
    out, bm, bv = invoke_raw(
        "batch_norm",
        lambda x, g, b: K.batch_norm_train(x, g, b, eps),
        [data, gamma, beta], n_outputs=3)
    # update running stats outside the recorded graph (stats reused from the
    # same kernel invocation; batch mean/var get zero cotangents)
    with _tape_paused():
        mm._data = momentum * mm._data + (1 - momentum) * bm._data
        mv._data = momentum * mv._data + (1 - momentum) * bv._data
    return out


class _tape_paused:
    def __enter__(self):
        self._old = _tape.set_recording(False)

    def __exit__(self, *exc):
        _tape.set_recording(self._old)


def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, **_ignored):
    return invoke_raw(
        "layer_norm",
        lambda x, g, b: K.layer_norm(x, g, b, axis, eps),
        [_wrap(data), _wrap(gamma), _wrap(beta)])


def RMSNorm(data, gamma, eps=1e-6, **_ignored):
    return invoke_raw("rms_norm", lambda x, g: K.rms_norm(x, g, eps),
                      [_wrap(data), _wrap(gamma)])


def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5, **_ignored):
    return invoke_raw(
        "group_norm",
        lambda x, g, b: K.group_norm(x, g, b, num_groups, eps),
        [_wrap(data), _wrap(gamma), _wrap(beta)])


def InstanceNorm(data, gamma, beta, eps=1e-5, **_ignored):
    return invoke_raw(
        "instance_norm",
        lambda x, g, b: K.instance_norm(x, g, b, eps),
        [_wrap(data), _wrap(gamma), _wrap(beta)])


def L2Normalization(data, eps=1e-10, mode="instance", **_ignored):
    return invoke_raw("l2_normalization",
                      lambda x: K.l2_norm(x, eps=eps, mode=mode), [_wrap(data)])


def LRN(data, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0, **_ignored):
    return invoke_raw("lrn",
                      lambda x: K.lrn(x, nsize, alpha, beta, knorm),
                      [_wrap(data)])


def UpSampling(data, scale=2, sample_type="nearest", num_args=1, **_ignored):
    import jax
    data = _wrap(data)

    def fn(x):
        n, c, h, w = x.shape
        method = "nearest" if sample_type == "nearest" else "linear"
        return jax.image.resize(x, (n, c, h * scale, w * scale), method=method)
    return invoke_raw("upsampling", fn, [data])


def BilinearResize2D(data, height=None, width=None, scale_height=None,
                     scale_width=None, mode="size", **_ignored):
    """Resize NCHW to an explicit (height, width) (``mode='size'``) or by
    scale factors (``mode='scale'``, output = floor(in * scale) — the
    ONNX Resize convention the importer maps onto); half-pixel linear
    interpolation via jax.image.resize (reference contrib
    BilinearResize2D, src/operator/contrib/bilinear_resize.cc)."""
    import math as _math
    data = _wrap(data)
    n, c, h, w = data.shape
    if mode == "size":
        if height is None or width is None:
            raise MXNetError(
                "BilinearResize2D mode='size' needs height and width")
    elif mode == "scale":
        if scale_height is None or scale_width is None:
            raise MXNetError("BilinearResize2D mode='scale' needs "
                             "scale_height and scale_width")
        height = int(_math.floor(h * scale_height))
        width = int(_math.floor(w * scale_width))
    else:
        raise MXNetError(f"BilinearResize2D mode {mode!r} unsupported "
                         "(size/scale)")
    return invoke_raw(
        "bilinear_resize",
        lambda x: K.bilinear_resize(x, int(height), int(width)), [data])


def _rnn_layout(mode, input_size, state_size, num_layers, bidirectional):
    """Slice table for the reference RNN op's packed parameter vector
    (rnn-inl.h: all weights layer/direction-major, then all biases):
    returns [(offset, shape)] in fused_rnn's [w_ih, w_hh, b_ih, b_hh]
    per-(layer, dir) order."""
    from ..ops.rnn import GATES
    g = GATES[mode]
    h = state_size
    dirs = 2 if bidirectional else 1
    w_slices, b_slices = [], []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else h * dirs
        for _ in range(dirs):
            w_slices.append((off, (g * h, in_sz)))
            off += g * h * in_sz
            w_slices.append((off, (g * h, h)))
            off += g * h * h
    for layer in range(num_layers):
        for _ in range(dirs):
            b_slices.append((off, (g * h,)))
            off += g * h
            b_slices.append((off, (g * h,)))
            off += g * h
    order = []
    for i in range(num_layers * dirs):
        order.append(w_slices[2 * i])       # w_ih
        order.append(w_slices[2 * i + 1])   # w_hh
        order.append(b_slices[2 * i])       # b_ih
        order.append(b_slices[2 * i + 1])   # b_hh
    return order, off


def _rnn_unpack(pv, order):
    """Slice a packed parameter vector by an _rnn_layout order table
    (single owner of the slice/reshape contract; used by the op kernel
    and the ONNX exporter)."""
    return [pv[o:o + int(onp.prod(s))].reshape(s) for o, s in order]


def RNN(data, parameters, state=None, state_cell=None, state_size=None,
        num_layers=1, bidirectional=False, mode="lstm", p=0.0,
        state_outputs=False, onnx_outputs=False, **_ignored):
    """Legacy fused RNN op over a single packed parameter vector
    (reference src/operator/rnn.cc; cuDNN packing: weights then biases,
    layer/direction-major). data: (T, N, C); state/state_cell:
    (L*D, N, H). Returns output (T, N, D*H), or
    ``[output, state_h(, state_cell)]`` with ``state_outputs=True``.
    ``onnx_outputs=True`` instead emits the ONNX recurrent-node layout
    ``[Y (T, D, N, H), Y_h(, Y_c)]`` (the importer's target)."""
    from ..ops import rnn as K_rnn
    if state_size is None:
        raise MXNetError("RNN requires state_size")
    data = _wrap(data)
    h = int(state_size)
    num_layers = int(num_layers)
    dirs = 2 if bidirectional else 1
    c_in = data.shape[-1]
    order, total = _rnn_layout(mode, c_in, h, num_layers, bidirectional)
    inputs = [data, _wrap(parameters)]
    have_h = state is not None
    have_c = state_cell is not None
    if have_c and not have_h:
        # positional symbol/executor binding would silently feed the cell
        # in as the hidden state — refuse the ambiguous form
        raise MXNetError("RNN: state_cell without state is unsupported; "
                         "pass both (in that order for symbolic calls)")
    if have_h:
        inputs.append(_wrap(state))
    if have_c:
        inputs.append(_wrap(state_cell))

    # inter-layer dropout (reference rnn-inl.h p): training-mode only,
    # keyed from the framework RNG stream (captured host-side)
    train = _tape.is_training() and float(p) > 0.0 and num_layers > 1
    if train:
        from .random import next_key
        drop_key = next_key()
    else:
        drop_key = None

    def fn(x, pv, *states):
        if pv.size != total:
            raise MXNetError(
                f"RNN packed parameter size {pv.size} != expected {total} "
                f"(mode={mode}, input={c_in}, hidden={h}, "
                f"layers={num_layers}, dirs={dirs})")
        flat = _rnn_unpack(pv, order)
        n = x.shape[1]
        zero = jnp.zeros((num_layers * dirs, n, h), x.dtype)
        si = 0
        if have_h:
            h0 = states[si]
            si += 1
        else:
            h0 = zero
        if mode == "lstm":
            c0 = states[si] if have_c else zero
        else:
            c0 = None
        y, h_out, c_out = K_rnn.fused_rnn(x, h0, c0, flat, mode,
                                          num_layers, bool(bidirectional),
                                          dropout=float(p), train=train,
                                          key=drop_key)
        if onnx_outputs:
            t = y.shape[0]
            y_onnx = y.reshape(t, n, dirs, h).transpose(0, 2, 1, 3)
            outs = [y_onnx, h_out]
            if mode == "lstm":
                outs.append(c_out)
            return tuple(outs)
        if state_outputs:
            outs = [y, h_out]
            if mode == "lstm":
                outs.append(c_out)
            return tuple(outs)
        return y

    n_out = 1
    if onnx_outputs or state_outputs:
        n_out = 3 if mode == "lstm" else 2
    res = invoke_raw("rnn_packed", fn, inputs, n_outputs=n_out)
    return res
