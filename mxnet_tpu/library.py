"""Runtime-loadable out-of-tree operator libraries.

Reference analog: ``MXLoadLib`` + the header-only C ABI of
``include/mxnet/lib_api.h`` (CustomOp :903, REGISTER_OP :1157), which lets
users ship compiled operators in a standalone shared library loaded with
dlopen — no framework rebuild.

TPU-native re-design: the C contract is a minimal host-side kernel ABI
(float32 buffers + shapes); each loaded op registers into the normal op
registry and executes through ``jax.pure_callback``, so it works eagerly
AND inside jit/hybridized computations (the callback runs on host while
XLA treats it as an opaque custom call — the role the reference's
CustomOperator thread pool played, custom-inl.h:103). Device-side custom
kernels belong in Pallas (``mx.rtc.PallasModule``); this path is for host
ops (IO, CPU-only third-party code).

Required exports (C, extern "C"):

    int  mxt_lib_num_ops(void);
    const char* mxt_lib_op_name(int op);
    // fill out_shape/out_ndim from input shapes; return 0 on success
    int  mxt_lib_op_infer_shape(int op, const long* const* in_shapes,
                                const int* in_ndims, int n_in,
                                long* out_shape, int* out_ndim);
    // float32 kernel; return 0 on success
    int  mxt_lib_op_forward(int op, const float* const* ins,
                            const long* const* in_shapes,
                            const int* in_ndims, int n_in,
                            float* out, const long* out_shape, int out_ndim);

Example library + build line: tests/test_library.py.
"""
from __future__ import annotations

import ctypes
from typing import List

import numpy as onp

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ops import registry as _registry

__all__ = ["load"]

_MAX_NDIM = 8

_LOADED = {}


class _LibOp:
    def __init__(self, lib, idx: int, name: str):
        self._lib = lib
        self._idx = idx
        self.name = name

    def infer_shape(self, in_shapes) -> tuple:
        n = len(in_shapes)
        shape_arrs = [(ctypes.c_long * len(s))(*s) for s in in_shapes]
        shapes = (ctypes.POINTER(ctypes.c_long) * n)(
            *[ctypes.cast(a, ctypes.POINTER(ctypes.c_long))
              for a in shape_arrs])
        ndims = (ctypes.c_int * n)(*[len(s) for s in in_shapes])
        out_shape = (ctypes.c_long * _MAX_NDIM)()
        out_ndim = ctypes.c_int(0)
        rc = self._lib.mxt_lib_op_infer_shape(
            self._idx, shapes, ndims, n, out_shape,
            ctypes.byref(out_ndim))
        if rc != 0:
            raise MXNetError(
                f"library op {self.name!r}: infer_shape failed (rc={rc})")
        return tuple(out_shape[i] for i in range(out_ndim.value))

    def forward_host(self, *arrays: onp.ndarray) -> onp.ndarray:
        arrays = [onp.ascontiguousarray(a, dtype=onp.float32)
                  for a in arrays]
        in_shapes = [a.shape for a in arrays]
        out_shape = self.infer_shape(in_shapes)
        out = onp.zeros(out_shape, dtype=onp.float32)
        n = len(arrays)
        shape_arrs = [(ctypes.c_long * len(s))(*s) for s in in_shapes]
        shapes = (ctypes.POINTER(ctypes.c_long) * n)(
            *[ctypes.cast(a, ctypes.POINTER(ctypes.c_long))
              for a in shape_arrs])
        ndims = (ctypes.c_int * n)(*[len(s) for s in in_shapes])
        ins = (ctypes.POINTER(ctypes.c_float) * n)(
            *[a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
              for a in arrays])
        oshape = (ctypes.c_long * len(out_shape))(*out_shape)
        rc = self._lib.mxt_lib_op_forward(
            self._idx, ins, shapes, ndims, n,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            oshape, len(out_shape))
        if rc != 0:
            raise MXNetError(
                f"library op {self.name!r}: forward failed (rc={rc})")
        return out

    def kernel(self, *xs):
        """JAX-facing kernel. Eager calls run the C forward on host
        directly. Inside a trace the op lowers to ``jax.pure_callback``
        — an opaque host custom-call."""
        if not any(isinstance(x, jax.core.Tracer) for x in xs):
            return jnp.asarray(self.forward_host(
                *[onp.asarray(x) for x in xs]))
        out_shape = self.infer_shape([tuple(x.shape) for x in xs])
        cb = lambda *h: self.forward_host(*h)  # noqa: E731
        return jax.pure_callback(
            cb, jax.ShapeDtypeStruct(out_shape, jnp.float32),
            *[jnp.asarray(x, jnp.float32) for x in xs])


def load(path: str, verbose: bool = True) -> List[str]:
    """Load a compiled operator library; returns the op names registered.

    Reference MXLoadLib (python/mxnet/library.py): ops become callable as
    ``mx.nd.<name>(...)`` and through the op registry (``invoke``)."""
    if path in _LOADED:
        return _LOADED[path]
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        raise MXNetError(f"cannot dlopen {path!r}: {e}") from e
    for sym in ("mxt_lib_num_ops", "mxt_lib_op_name",
                "mxt_lib_op_infer_shape", "mxt_lib_op_forward"):
        if not hasattr(lib, sym):
            raise MXNetError(
                f"{path!r} is not an op library: missing symbol {sym}")
    lib.mxt_lib_op_name.restype = ctypes.c_char_p
    names = []
    from . import ndarray as nd_mod
    for i in range(int(lib.mxt_lib_num_ops())):
        name = lib.mxt_lib_op_name(i).decode()
        op = _LibOp(lib, i, name)
        _registry.register(name, differentiable=False)(op.kernel)

        def make_wrapper(o):
            def wrapper(*inputs, **_ignored):
                arrs = [x if isinstance(x, nd_mod.NDArray)
                        else nd_mod.array(x) for x in inputs]
                return _registry.invoke(o.name, *arrs)
            wrapper.__name__ = o.name
            wrapper.__doc__ = f"out-of-tree library op from {path}"
            return wrapper

        setattr(nd_mod, name, make_wrapper(op))
        names.append(name)
    if verbose:
        print(f"loaded library {path}: ops {names}")
    _LOADED[path] = names
    return names
