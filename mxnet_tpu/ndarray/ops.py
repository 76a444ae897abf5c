"""Foundation operator set for the legacy ``mx.nd`` namespace.

Reference analog: src/operator/tensor/ (elemwise/broadcast/reduce/dot/
indexing/ordering/matrix-manip, ~38k LoC of CPU/CUDA kernels) and the
generated Python wrappers in python/mxnet/ndarray/. Every op here is a thin
pure-JAX function: XLA emits the TPU kernel and handles fusion (the job the
reference's ``Kernel<OP,xpu>::Launch`` + pointwise-fusion JIT did by hand).
"""
from __future__ import annotations

import functools
from builtins import slice as builtins_slice
from typing import Optional

import numpy as onp

import jax
import jax.numpy as jnp

from ..base import MXNetError, jx_dtype
from ..ops import nn as ops_nn
from ..ops.registry import invoke_raw, register
from .ndarray import NDArray, _norm_axis

__all__: list = []  # populated by _export


def _export(fn):
    __all__.append(fn.__name__)
    return fn


def _wrap(x):
    return x if isinstance(x, NDArray) else NDArray(x)


def _unary(name, jfn):
    @register(name)
    def _kernel(x, **kw):
        return jfn(x, **kw) if kw else jfn(x)

    def op(data, **kwargs):
        f = functools.partial(jfn, **kwargs) if kwargs else jfn
        return invoke_raw(name, f, [_wrap(data)])
    op.__name__ = name
    return op


def _binary(name, jfn):
    @register(name)
    def _kernel(a, b):
        return jfn(a, b)

    def op(lhs, rhs, **kwargs):
        if isinstance(rhs, (int, float)):
            return invoke_raw(name + "_scalar",
                              lambda a, _s=rhs: jfn(a, _s), [_wrap(lhs)])
        if isinstance(lhs, (int, float)):
            return invoke_raw(name + "_scalar",
                              lambda b, _s=lhs: jfn(_s, b), [_wrap(rhs)])
        return invoke_raw(name, jfn, [_wrap(lhs), _wrap(rhs)])
    op.__name__ = name
    return op


# ---- elementwise unary (reference: src/operator/tensor/elemwise_unary_op*) ----
exp = _unary("exp", jnp.exp)
log = _unary("log", jnp.log)
log2 = _unary("log2", jnp.log2)
log10 = _unary("log10", jnp.log10)
log1p = _unary("log1p", jnp.log1p)
expm1 = _unary("expm1", jnp.expm1)
sqrt = _unary("sqrt", jnp.sqrt)
rsqrt = _unary("rsqrt", jax.lax.rsqrt)
cbrt = _unary("cbrt", jnp.cbrt)
rcbrt = _unary("rcbrt", lambda x: 1.0 / jnp.cbrt(x))
square = _unary("square", jnp.square)
reciprocal = _unary("reciprocal", lambda x: 1.0 / x)
negative = _unary("negative", jnp.negative)
abs = _unary("abs", jnp.abs)  # noqa: A001 — matches mx.nd.abs
sign = _unary("sign", jnp.sign)
ceil = _unary("ceil", jnp.ceil)
floor = _unary("floor", jnp.floor)
trunc = _unary("trunc", jnp.trunc)
rint = _unary("rint", jnp.rint)
round = _unary("round", jnp.round)  # noqa: A001
fix = _unary("fix", jnp.trunc)
sin = _unary("sin", jnp.sin)
cos = _unary("cos", jnp.cos)
tan = _unary("tan", jnp.tan)
arcsin = _unary("arcsin", jnp.arcsin)
arccos = _unary("arccos", jnp.arccos)
arctan = _unary("arctan", jnp.arctan)
sinh = _unary("sinh", jnp.sinh)
cosh = _unary("cosh", jnp.cosh)
tanh = _unary("tanh", jnp.tanh)
arcsinh = _unary("arcsinh", jnp.arcsinh)
arccosh = _unary("arccosh", jnp.arccosh)
arctanh = _unary("arctanh", jnp.arctanh)
degrees = _unary("degrees", jnp.degrees)
radians = _unary("radians", jnp.radians)
sigmoid = _unary("sigmoid", jax.nn.sigmoid)
log_sigmoid = _unary("log_sigmoid", jax.nn.log_sigmoid)
softsign = _unary("softsign", jax.nn.soft_sign)
erf = _unary("erf", jax.scipy.special.erf)
erfinv = _unary("erfinv", jax.scipy.special.erfinv)
gamma = _unary("gamma", lambda x: jnp.exp(jax.scipy.special.gammaln(x)))
gammaln = _unary("gammaln", jax.scipy.special.gammaln)
logical_not = _unary("logical_not", lambda x: jnp.logical_not(x).astype(x.dtype))
relu = _unary("relu", jax.nn.relu)
softrelu = _unary("softrelu", jax.nn.softplus)
gelu = _unary("gelu", jax.nn.gelu)
silu = _unary("silu", jax.nn.silu)
isnan = _unary("isnan", jnp.isnan)
isinf = _unary("isinf", jnp.isinf)
isfinite = _unary("isfinite", jnp.isfinite)

# ---- elementwise binary (+ broadcast; reference elemwise_binary_broadcast_op*) ----
add = _binary("add", jnp.add)
subtract = _binary("sub", jnp.subtract)
multiply = _binary("mul", jnp.multiply)
divide = _binary("div", jnp.divide)
modulo = _binary("mod", jnp.mod)
power = _binary("pow", jnp.power)
maximum = _binary("maximum", jnp.maximum)
minimum = _binary("minimum", jnp.minimum)
hypot = _binary("hypot", jnp.hypot)
arctan2 = _binary("arctan2", jnp.arctan2)
broadcast_add = add
broadcast_sub = subtract
broadcast_mul = multiply
broadcast_div = divide
broadcast_mod = modulo
broadcast_power = power
broadcast_maximum = maximum
broadcast_minimum = minimum
broadcast_hypot = hypot
__all__ += ["broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_div",
            "broadcast_mod", "broadcast_power", "broadcast_maximum",
            "broadcast_minimum", "broadcast_hypot"]


def _cmp(name, jfn):
    def op(lhs, rhs):
        if isinstance(rhs, (int, float)):
            return invoke_raw(name, lambda a, _s=rhs: jfn(a, _s).astype(a.dtype),
                              [_wrap(lhs)], record=False)
        return invoke_raw(name, lambda a, b: jfn(a, b).astype(a.dtype),
                          [_wrap(lhs), _wrap(rhs)], record=False)
    op.__name__ = name
    return op


equal = _cmp("equal", jnp.equal)
not_equal = _cmp("not_equal", jnp.not_equal)
greater = _cmp("greater", jnp.greater)
greater_equal = _cmp("greater_equal", jnp.greater_equal)
lesser = _cmp("lesser", jnp.less)
lesser_equal = _cmp("lesser_equal", jnp.less_equal)
broadcast_equal = equal
broadcast_not_equal = not_equal
broadcast_greater = greater
broadcast_greater_equal = greater_equal
broadcast_lesser = lesser
broadcast_lesser_equal = lesser_equal
logical_and = _cmp("logical_and", jnp.logical_and)
logical_or = _cmp("logical_or", jnp.logical_or)
logical_xor = _cmp("logical_xor", jnp.logical_xor)
broadcast_logical_and = logical_and
broadcast_logical_or = logical_or
broadcast_logical_xor = logical_xor
__all__ += ["broadcast_equal", "broadcast_not_equal", "broadcast_greater",
            "broadcast_greater_equal", "broadcast_lesser",
            "broadcast_lesser_equal", "broadcast_logical_and",
            "broadcast_logical_or", "broadcast_logical_xor"]


# ---- reductions (reference: src/operator/tensor/broadcast_reduce_op*) ----
def _reduction(name, jfn):
    def op(data, axis=None, keepdims=False, exclude=False, **kwargs):
        data = _wrap(data)
        ax = _norm_axis(axis)
        if exclude and ax is not None:
            axt = (ax,) if isinstance(ax, int) else tuple(ax)
            ax = tuple(i for i in range(data.ndim) if i not in axt)
        fn = lambda x: jfn(x, axis=ax, keepdims=keepdims)
        return invoke_raw(name, fn, [data])
    op.__name__ = name
    return op


sum = _reduction("sum", jnp.sum)  # noqa: A001
mean = _reduction("mean", jnp.mean)
prod = _reduction("prod", jnp.prod)
nansum = _reduction("nansum", jnp.nansum)
nanprod = _reduction("nanprod", jnp.nanprod)
max = _reduction("max", jnp.max)  # noqa: A001
min = _reduction("min", jnp.min)  # noqa: A001


@_export
def norm(data, ord=2, axis=None, keepdims=False):
    return _wrap(data).norm(ord=ord, axis=axis, keepdims=keepdims)


@_export
def argmax(data, axis=None, keepdims=False):
    return _wrap(data).argmax(axis=axis, keepdims=keepdims)


@_export
def argmin(data, axis=None, keepdims=False):
    return _wrap(data).argmin(axis=axis, keepdims=keepdims)


@_export
def sum_axis(data, axis=None, keepdims=False):
    return sum(data, axis=axis, keepdims=keepdims)


# ---- dot / linalg (reference: src/operator/tensor/dot*, la_op*) ----
@_export
def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """MXNet dot: contract last axis of lhs with first axis of rhs
    (reference dot-inl.h semantics, not numpy matmul)."""
    lhs, rhs = _wrap(lhs), _wrap(rhs)

    def fn(a, b):
        if transpose_a:
            a = jnp.transpose(a)
        if transpose_b:
            b = jnp.transpose(b)
        if a.ndim == 1 and b.ndim == 1:
            return jnp.dot(a, b)
        return jnp.tensordot(a, b, axes=([a.ndim - 1], [0]))
    return invoke_raw("dot", fn, [lhs, rhs])


@_export
def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False):
    lhs, rhs = _wrap(lhs), _wrap(rhs)

    def fn(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)
    return invoke_raw("batch_dot", fn, [lhs, rhs])


@_export
def linalg_gemm2(A, B, transpose_a=False, transpose_b=False, alpha=1.0):
    def fn(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return alpha * jnp.matmul(a, b)
    return invoke_raw("linalg_gemm2", fn, [_wrap(A), _wrap(B)])


@_export
def linalg_potrf(A):
    return invoke_raw("linalg_potrf", lambda a: jnp.linalg.cholesky(a), [_wrap(A)])


@_export
def linalg_syrk(A, transpose=False, alpha=1.0):
    def fn(a):
        at = jnp.swapaxes(a, -1, -2)
        return alpha * (jnp.matmul(at, a) if transpose else jnp.matmul(a, at))
    return invoke_raw("linalg_syrk", fn, [_wrap(A)])


@_export
def linalg_inverse(A):
    """Matrix inverse (reference la_op _linalg_inverse)."""
    return invoke_raw("linalg_inverse", jnp.linalg.inv, [_wrap(A)])


@_export
def linalg_det(A):
    return invoke_raw("linalg_det", jnp.linalg.det, [_wrap(A)])


@_export
def linalg_slogdet(A):
    return invoke_raw("linalg_slogdet",
                      lambda a: tuple(jnp.linalg.slogdet(a)), [_wrap(A)],
                      n_outputs=2)


@_export
def linalg_trsm(A, B, transpose=False, rightside=False, lower=True,
                alpha=1.0):
    """Triangular solve (reference la_op _linalg_trsm)."""
    def fn(a, b):
        import jax.scipy.linalg as jsl
        if rightside:
            # solve X A = alpha B  ->  A^T X^T = alpha B^T
            x = jsl.solve_triangular(jnp.swapaxes(a, -1, -2),
                                     jnp.swapaxes(alpha * b, -1, -2),
                                     lower=not lower, trans=1 if transpose
                                     else 0)
            return jnp.swapaxes(x, -1, -2)
        return jsl.solve_triangular(a, alpha * b, lower=lower,
                                    trans=1 if transpose else 0)
    return invoke_raw("linalg_trsm", fn, [_wrap(A), _wrap(B)])


@_export
def linalg_trmm(A, B, transpose=False, rightside=False, lower=True,
                alpha=1.0):
    """Triangular matmul (reference la_op _linalg_trmm)."""
    def fn(a, b):
        tri = jnp.tril(a) if lower else jnp.triu(a)
        if transpose:
            tri = jnp.swapaxes(tri, -1, -2)
        return alpha * (jnp.matmul(b, tri) if rightside
                        else jnp.matmul(tri, b))
    return invoke_raw("linalg_trmm", fn, [_wrap(A), _wrap(B)])


@_export
def linalg_syevd(A):
    """Symmetric eigendecomposition (reference la_op _linalg_syevd):
    returns (U, L) with rows of U the eigenvectors (A = U^T diag(L) U)."""
    def fn(a):
        l, u = jnp.linalg.eigh(a)
        return jnp.swapaxes(u, -1, -2), l
    return invoke_raw("linalg_syevd", fn, [_wrap(A)], n_outputs=2)


@_export
def linalg_sumlogdiag(A):
    """Sum of log of diagonal (reference la_op _linalg_sumlogdiag)."""
    def fn(a):
        return jnp.log(jnp.diagonal(a, axis1=-2, axis2=-1)).sum(-1)
    return invoke_raw("linalg_sumlogdiag", fn, [_wrap(A)])


# ---- shape / layout manipulation (reference: matrix_op*) ----
@_export
def reshape(data, shape, reverse=False):
    return _wrap(data).reshape(shape, reverse=reverse)


@_export
def reshape_like(lhs, rhs):
    return _wrap(lhs).reshape(_wrap(rhs).shape)


@_export
def transpose(data, axes=None):
    d = _wrap(data)
    return d.transpose(axes) if axes else d.transpose()


@_export
def swapaxes(data, dim1=0, dim2=0):
    return _wrap(data).swapaxes(dim1, dim2)


@_export
def flip(data, axis):
    return _wrap(data).flip(axis)


@_export
def reverse(data, axis):
    return _wrap(data).flip(axis)


@_export
def tile(data, reps):
    return _wrap(data).tile(reps)


@_export
def repeat(data, repeats, axis=None):
    return _wrap(data).repeat(repeats, axis)


@_export
def pad(data, mode="constant", pad_width=None, constant_value=0.0):
    """Reference Pad op: pad_width is flat (before, after) per axis."""
    data = _wrap(data)
    pw = list(zip(pad_width[0::2], pad_width[1::2]))
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
    if mode == "constant":
        fn = lambda x: jnp.pad(x, pw, mode="constant", constant_values=constant_value)
    else:
        fn = lambda x: jnp.pad(x, pw, mode=jmode)
    return invoke_raw("pad", fn, [data])


@_export
def expand_dims(data, axis):
    return _wrap(data).expand_dims(axis)


@_export
def squeeze(data, axis=None):
    return _wrap(data).squeeze(axis)


@_export
def broadcast_to(data, shape):
    return _wrap(data).broadcast_to(shape)


@_export
def broadcast_like(lhs, rhs):
    return _wrap(lhs).broadcast_to(_wrap(rhs).shape)


@_export
def broadcast_axis(data, axis, size):
    data = _wrap(data)
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    sizes = (size,) if isinstance(size, int) else tuple(size)
    tgt = list(data.shape)
    for a, s in zip(axes, sizes):
        tgt[a] = s
    return data.broadcast_to(tuple(tgt))


@_export
def concat(*data, dim=1):
    return invoke_raw("concat", lambda *xs: jnp.concatenate(xs, axis=dim),
                      [_wrap(d) for d in data])


@_export
def stack(*data, axis=0):
    return invoke_raw("stack", lambda *xs: jnp.stack(xs, axis=axis),
                      [_wrap(d) for d in data])


@_export
def split(data, num_outputs, axis=1, squeeze_axis=False):
    data = _wrap(data)

    def fn(x):
        parts = jnp.split(x, num_outputs, axis=axis)
        if squeeze_axis:
            parts = [jnp.squeeze(p, axis=axis) for p in parts]
        return tuple(parts)
    out = invoke_raw("split", fn, [data], n_outputs=num_outputs)
    return list(out) if isinstance(out, tuple) else [out]


slice_channel = split
__all__.append("slice_channel")


@_export
def slice(data, begin, end, step=None):  # noqa: A001 — mx.nd.slice
    data = _wrap(data)
    step = step or [1] * len(begin)
    key = tuple(builtins_slice(b, e, s) for b, e, s in zip(begin, end, step))
    return invoke_raw("slice", lambda x, _k=key: x[_k], [data])


@_export
def slice_axis(data, axis, begin, end):
    data = _wrap(data)
    if end is None:
        end = data.shape[axis]
    key = [builtins_slice(None)] * data.ndim
    key[axis] = builtins_slice(begin, end)
    key = tuple(key)
    return invoke_raw("slice_axis", lambda x, _k=key: x[_k], [data])


@_export
def slice_like(data, shape_like, axes=None):
    data, like = _wrap(data), _wrap(shape_like)
    tgt = list(data.shape)
    axes = axes if axes is not None else range(data.ndim)
    for a in axes:
        tgt[a] = like.shape[a]
    key = tuple(builtins_slice(0, t) for t in tgt)
    return invoke_raw("slice_like", lambda x, _k=key: x[_k], [data])


# ---- indexing (reference: indexing_op*) ----
@_export
def take(a, indices, axis=0, mode="clip"):
    a, indices = _wrap(a), _wrap(indices)

    def fn(x, idx):
        idx = idx.astype(jnp.int32)
        n = x.shape[axis]
        if mode == "clip":
            idx = jnp.clip(idx, 0, n - 1)
        elif mode == "wrap":
            idx = jnp.mod(idx, n)
        return jnp.take(x, idx, axis=axis)
    return invoke_raw("take", fn, [a, indices])


@_export
def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    """Reference Embedding op (src/operator/tensor/indexing_op.cc).

    ``sparse_grad=True`` (reference indexing_op.cc SparseEmbedding +
    FInferStorageType row_sparse grad): on the eager recording path the
    weight gradient is produced as a RowSparseNDArray whose values are
    segment-summed cotangent rows over the UNIQUE token ids — O(rows
    touched) gradient math instead of a dense scatter over the whole
    vocabulary, feeding the optimizer's lazy row update. Inside a jit trace
    (hybridized) gradients are dense by construction and the standard path
    is used."""
    data, weight = _wrap(data), _wrap(weight)
    if sparse_grad:
        from .. import _tape
        if _tape.is_recording() and not isinstance(data._data,
                                                   jax.core.Tracer):
            return _embedding_sparse_grad(data, weight)
    # mode='clip': out-of-range ids clamp to the nearest row. The reference
    # CPU kernel raises and its GPU kernel reads out of bounds
    # (indexing_op.h); neither is expressible under jit, and jnp.take's
    # default fill-with-NaN poisons gradients silently — clamping is the
    # deterministic TPU-native choice (documented deviation).
    return invoke_raw("embedding",
                      lambda idx, w: ops_nn.embedding(idx.astype(jnp.int32),
                                                      w),
                      [data, weight])


def _embedding_sparse_grad(data, weight):
    """Record an embedding lookup whose weight cotangent is row_sparse.

    The unique-id set and inverse map are computed on host at forward time
    (token ids are host-produced by the data pipeline, so this sync is
    effectively free); backward is then a pure XLA segment_sum over the
    looked-up rows."""
    from .. import _tape
    from .sparse import _make_row_sparse_lazy

    ids_host = onp.asarray(data._data).astype("int32").reshape(-1)
    uids, inv = onp.unique(ids_host, return_inverse=True)
    uids_j = jnp.asarray(uids, jnp.int32)
    inv_j = jnp.asarray(inv.astype("int32"))
    n_u = int(uids.shape[0])
    vocab, dim = weight._data.shape
    out_shape = tuple(data.shape) + (dim,)

    def fwd(idx, w):
        return jnp.take(w, idx.astype(jnp.int32), axis=0)

    out_data = jnp.take(weight._data, jnp.asarray(ids_host),
                        axis=0).reshape(out_shape)

    def vjp_fn(ct):
        ctd = ct._data if isinstance(ct, NDArray) else ct
        vals = ctd.reshape(-1, dim)
        summed = jax.ops.segment_sum(vals, inv_j, num_segments=n_u)
        # LAZY dense mirror: the O(vocab) scatter runs only if a dense
        # consumer reads it; the sparse path (lazy optimizer, kvstore
        # identity round-trip) stays O(rows) end-to-end
        thunk = (lambda s=summed: jnp.zeros((vocab, dim), s.dtype)
                 .at[uids_j].add(s))
        return (None, _make_row_sparse_lazy(thunk, uids_j, summed))

    node = _tape.TapeNode(
        "embedding_sparse", [data, weight], fwd, vjp_fn,
        [jax.ShapeDtypeStruct(out_data.shape, out_data.dtype)])
    out = NDArray(out_data)
    out._tape_entry = (node, 0)
    return out


@_export
def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    data, index = _wrap(data), _wrap(index)

    def fn(x, idx):
        idx = idx.astype(jnp.int32)
        n = x.shape[axis]
        idx = jnp.clip(idx, 0, n - 1) if mode == "clip" else jnp.mod(idx, n)
        out = jnp.take_along_axis(x, jnp.expand_dims(idx, axis), axis=axis)
        return out if keepdims else jnp.squeeze(out, axis=axis)
    return invoke_raw("pick", fn, [data, index])


@_export
def gather_nd(data, indices):
    data, indices = _wrap(data), _wrap(indices)

    def fn(x, idx):
        idx = idx.astype(jnp.int32)
        return x[tuple(idx[i] for i in range(idx.shape[0]))]
    return invoke_raw("gather_nd", fn, [data, indices])


@_export
def scatter_nd(data, indices, shape):
    data, indices = _wrap(data), _wrap(indices)

    def fn(d, idx):
        idx = idx.astype(jnp.int32)
        out = jnp.zeros(tuple(shape), d.dtype)
        return out.at[tuple(idx[i] for i in range(idx.shape[0]))].add(d)
    return invoke_raw("scatter_nd", fn, [data, indices])


@_export
def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    return _wrap(indices).one_hot(depth, on_value, off_value, dtype)


@_export
def where(condition, x, y):
    condition, x, y = _wrap(condition), _wrap(x), _wrap(y)
    return invoke_raw("where",
                      lambda c, a, b: jnp.where(c.astype(jnp.bool_), a, b),
                      [condition, x, y])


@_export
def boolean_mask(data, index, axis=0):
    data, index = _wrap(data), _wrap(index)
    idx = onp.asarray(index.asnumpy(), dtype=bool)
    sel = onp.nonzero(idx)[0]

    def fn(x, _sel=jnp.asarray(sel)):
        return jnp.take(x, _sel, axis=axis)
    return invoke_raw("boolean_mask", fn, [data])


# ---- ordering (reference: ordering_op*) ----
@_export
def sort(data, axis=-1, is_ascend=True):
    def fn(x):
        out = jnp.sort(x, axis=axis)
        return out if is_ascend else jnp.flip(out, axis=axis)
    return invoke_raw("sort", fn, [_wrap(data)])


@_export
def argsort(data, axis=-1, is_ascend=True, dtype="float32"):
    dt = jx_dtype(dtype)

    def fn(x):
        out = jnp.argsort(x, axis=axis)
        if not is_ascend:
            out = jnp.flip(out, axis=axis)
        return out.astype(dt)
    return invoke_raw("argsort", fn, [_wrap(data)], record=False)


@_export
def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    data = _wrap(data)
    dt = jx_dtype(dtype)

    if ret_typ not in ("value", "indices", "both", "mask"):
        raise MXNetError(f"unknown topk ret_typ {ret_typ!r}")

    def fn(x):
        xm = jnp.moveaxis(x, axis, -1)
        vals, idx = jax.lax.top_k(-xm if is_ascend else xm, k)
        if is_ascend:
            vals = -vals
        if ret_typ == "mask":
            onehots = jax.nn.one_hot(idx, xm.shape[-1], dtype=dt).sum(axis=-2)
            return jnp.moveaxis(onehots, -1, axis)
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis)
        if ret_typ == "value":
            return vals
        if ret_typ == "both":
            return vals, idx.astype(dt)
        return idx.astype(dt)
    n_out = 2 if ret_typ == "both" else 1
    return invoke_raw("topk", fn, [data], n_outputs=n_out,
                      record=(ret_typ == "value"))


# ---- casts / misc ----
@_export
def cast(data, dtype):
    return _wrap(data).astype(dtype)


@_export
def clip(data, a_min, a_max):
    return _wrap(data).clip(a_min, a_max)


@_export
def amp_cast(data, dtype):
    return cast(data, dtype)


@_export
def amp_multicast(*data, num_outputs=None):
    arrs = [_wrap(d) for d in data]
    widest = jnp.result_type(*[a._data.dtype for a in arrs])
    return [a.astype(widest) for a in arrs]


@_export
def zeros_like(data):
    return invoke_raw("zeros_like", jnp.zeros_like, [_wrap(data)], record=False)


@_export
def ones_like(data):
    return invoke_raw("ones_like", jnp.ones_like, [_wrap(data)], record=False)


@_export
def full_like(data, fill_value):
    return invoke_raw("full_like",
                      lambda x: jnp.full_like(x, fill_value), [_wrap(data)],
                      record=False)


@_export
def identity(data):
    return invoke_raw("identity", lambda x: x, [_wrap(data)])


@_export
def stop_gradient(data):
    return invoke_raw("stop_gradient", jax.lax.stop_gradient, [_wrap(data)])


BlockGrad = stop_gradient
__all__.append("BlockGrad")


@_export
def make_loss(data):
    return invoke_raw("make_loss", lambda x: x, [_wrap(data)])


@_export
def add_n(*args):
    return invoke_raw("add_n", lambda *xs: functools.reduce(jnp.add, xs),
                      [_wrap(a) for a in args])


ElementWiseSum = add_n
__all__.append("ElementWiseSum")


@_export
def unique(data):
    d = _wrap(data)
    arr = onp.unique(d.asnumpy())
    return NDArray(arr)


@_export
def histogram(data, bins=10, range=None):  # noqa: A002
    d = _wrap(data)
    cnt, edges = onp.histogram(d.asnumpy(), bins=bins, range=range)
    return NDArray(cnt), NDArray(edges)


@_export
def diag(data, k=0):
    return _wrap(data).diag(k)


@_export
def shape_array(data):
    return NDArray(onp.array(_wrap(data).shape, dtype=onp.int64))


@_export
def size_array(data):
    return NDArray(onp.array([_wrap(data).size], dtype=onp.int64))


@_export
def moments(data, axes=None, keepdims=False):
    data = _wrap(data)
    ax = _norm_axis(axes)

    def fn(x):
        m = jnp.mean(x, axis=ax, keepdims=keepdims)
        v = jnp.var(x, axis=ax, keepdims=keepdims)
        return m, v
    return invoke_raw("moments", fn, [data], n_outputs=2)


# ---- cumulative ----
@_export
def cumsum(data, axis=None, dtype=None):
    def fn(x):
        out = jnp.cumsum(x, axis=axis)
        return out.astype(jx_dtype(dtype)) if dtype else out
    return invoke_raw("cumsum", fn, [_wrap(data)])


# ---- sequence ops (reference: src/operator/sequence_*-inl.h) ----
@_export
def SequenceMask(data, sequence_length=None, use_sequence_length=False,
                 value=0.0, axis=0):
    data = _wrap(data)
    if not use_sequence_length or sequence_length is None:
        return identity(data)
    seq_len = _wrap(sequence_length)

    def fn(x, sl):
        T = x.shape[axis]
        pos = jnp.arange(T)
        shape = [1] * x.ndim
        shape[axis] = T
        pos = pos.reshape(shape)
        batch_axis = 1 - axis if axis in (0, 1) else 0
        slshape = [1] * x.ndim
        slshape[batch_axis] = x.shape[batch_axis]
        mask = pos < sl.reshape(slshape)
        return jnp.where(mask, x, jnp.asarray(value, x.dtype))
    return invoke_raw("sequence_mask", fn, [data, seq_len])


sequence_mask = SequenceMask
__all__ += ["SequenceMask", "sequence_mask"]


@_export
def SequenceLast(data, sequence_length=None, use_sequence_length=False, axis=0):
    data = _wrap(data)
    if not use_sequence_length or sequence_length is None:
        return invoke_raw("sequence_last",
                          lambda x: jnp.take(x, x.shape[axis] - 1, axis=axis),
                          [data])
    seq_len = _wrap(sequence_length)

    def fn(x, sl):
        idx = (sl.astype(jnp.int32) - 1)
        xm = jnp.moveaxis(x, axis, 0)  # (T, B, ...)
        return jnp.take_along_axis(
            xm, idx.reshape((1, -1) + (1,) * (xm.ndim - 2)), axis=0)[0]
    return invoke_raw("sequence_last", fn, [data, seq_len])


@_export
def SequenceReverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    data = _wrap(data)
    if not use_sequence_length or sequence_length is None:
        return flip(data, axis)
    seq_len = _wrap(sequence_length)

    def fn(x, sl):
        T = x.shape[0]
        pos = jnp.arange(T)[:, None]
        sl_i = sl.astype(jnp.int32)[None, :]
        rev_idx = jnp.where(pos < sl_i, sl_i - 1 - pos, pos)
        return jnp.take_along_axis(
            x, rev_idx.reshape(rev_idx.shape + (1,) * (x.ndim - 2)), axis=0)
    return invoke_raw("sequence_reverse", fn, [data, seq_len])


# ---- softmax family (reference: src/operator/nn/softmax*) ----
@_export
def softmax(data, axis=-1, temperature=None, length=None):
    data = _wrap(data)
    t = temperature or 1.0
    if length is not None:
        ln = _wrap(length)

        def fn(x, l):
            T = x.shape[axis]
            mask = jnp.arange(T) < l[..., None]
            x = jnp.where(mask, x / t, -jnp.inf)
            return jax.nn.softmax(x, axis=axis)
        return invoke_raw("softmax", fn, [data, ln])
    return invoke_raw("softmax", lambda x: jax.nn.softmax(x / t, axis=axis), [data])


@_export
def log_softmax(data, axis=-1, temperature=None):
    t = temperature or 1.0
    return invoke_raw("log_softmax",
                      lambda x: jax.nn.log_softmax(x / t, axis=axis), [_wrap(data)])


@_export
def softmin(data, axis=-1):
    return invoke_raw("softmin", lambda x: jax.nn.softmax(-x, axis=axis), [_wrap(data)])


@_export
def softmax_cross_entropy(data, label):
    data, label = _wrap(data), _wrap(label)

    def fn(x, y):
        logp = jax.nn.log_softmax(x, axis=-1)
        y = y.astype(jnp.int32)
        picked = jnp.take_along_axis(logp, y[:, None], axis=-1)
        return -jnp.sum(picked)
    return invoke_raw("softmax_cross_entropy", fn, [data, label])


@_export
def SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1,
                  use_ignore=False, multi_output=False, preserve_shape=False,
                  normalization="null", out_grad=False, smooth_alpha=0.0):
    """Legacy SoftmaxOutput: forward is softmax; backward injects CE grad
    (reference src/operator/softmax_output*). We model forward-only here; the
    gradient flows via softmax_cross_entropy in training loops."""
    return softmax(_wrap(data), axis=-1)


# ---- LeakyReLU/Activation op forms ----
@_export
def LeakyReLU(data, act_type="leaky", slope=0.25, gamma=None,
              lower_bound=0.125, upper_bound=0.334):
    data = _wrap(data)
    if act_type == "leaky":
        return invoke_raw("leaky_relu",
                          lambda x: jnp.where(x > 0, x, slope * x), [data])
    if act_type == "elu":
        return invoke_raw("elu", lambda x: jax.nn.elu(x, alpha=slope), [data])
    if act_type == "selu":
        return invoke_raw("selu", jax.nn.selu, [data])
    if act_type == "gelu":
        return invoke_raw("gelu", lambda x: jax.nn.gelu(x, approximate=False), [data])
    if act_type == "prelu":
        g = _wrap(gamma)
        return invoke_raw("prelu",
                          lambda x, gm: jnp.where(x > 0, x, gm * x), [data, g])
    if act_type == "rrelu":
        s = (lower_bound + upper_bound) / 2.0
        return invoke_raw("rrelu", lambda x: jnp.where(x > 0, x, s * x), [data])
    raise MXNetError(f"unknown LeakyReLU act_type {act_type}")


@_export
def Activation(data, act_type="relu"):
    fns = {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
           "softrelu": jax.nn.softplus, "softsign": jax.nn.soft_sign,
           "log_sigmoid": jax.nn.log_sigmoid,
           "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
           "gelu": lambda x: jax.nn.gelu(x, approximate=False),
           "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
           "silu": jax.nn.silu, "swish": jax.nn.silu}
    if act_type not in fns:
        raise MXNetError(f"unknown Activation act_type {act_type!r}")
    return invoke_raw(f"activation_{act_type}", fns[act_type], [_wrap(data)])


@_export
def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False,
                   flatten=True):
    """Reference FullyConnected (src/operator/nn/fully_connected.cc):
    out = X W^T + b; flatten collapses trailing axes."""
    data, weight = _wrap(data), _wrap(weight)

    if no_bias or bias is None:
        def fn(x, w):
            if flatten and x.ndim > 2:
                x = x.reshape(x.shape[0], -1)
            return jnp.dot(x, w.T)
        return invoke_raw("fully_connected", fn, [data, weight])

    bias = _wrap(bias)

    def fnb(x, w, b):
        if flatten and x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        return jnp.dot(x, w.T) + b
    return invoke_raw("fully_connected", fnb, [data, weight, bias])


@_export
def Dropout(data, p=0.5, mode="training", axes=None, cudnn_off=False):
    from .. import _tape as tape
    from . import random as nd_random
    data = _wrap(data)
    if not tape.is_training() and mode != "always":
        return identity(data)
    key = nd_random.next_key()
    axes = axes or ()

    def fn(x, _key=key):
        shape = list(x.shape)
        for a in axes:
            shape[a] = 1
        keep = jax.random.bernoulli(_key, 1.0 - p, tuple(shape))
        return jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))
    return invoke_raw("dropout", fn, [data])


@_export
def Embedding(data, weight, input_dim=None, output_dim=None, dtype="float32",
              sparse_grad=False):
    return embedding(data, weight, sparse_grad=sparse_grad)


@_export
def Flatten(data):
    return _wrap(data).flatten()


@_export
def Cast(data, dtype):
    return cast(data, dtype)


# ---- NN layer ops used by gluon (conv/pool/norm) live in ops/nn.py and are
# re-exported via ndarray/__init__ ----

# Rebuild __all__ from module globals so helper-created ops export under
# their bound python names (e.g. ``subtract = _binary("sub", ...)``).
__all__ = sorted({
    n for n, v in list(globals().items())
    if not n.startswith("_") and callable(v)
    and getattr(v, "__module__", __name__) in (__name__, None)
    and n not in ("NDArray", "invoke_raw", "register", "jx_dtype",
                  "MXNetError", "builtins_slice", "functools", "onp",
                  "jax", "jnp")
})
