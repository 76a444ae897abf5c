"""Op registry + imperative invoke path.

Reference analog: the nnvm op registry plus ``Imperative::Invoke``
(src/imperative/imperative.cc:98) and ``PushFCompute``
(src/imperative/imperative_utils.h:448). The reference infers shape/type,
picks a DispatchMode, and pushes a closure to the threaded engine; here the
"kernel" is a pure JAX function dispatched through XLA's async runtime, and
the invoke layer's remaining jobs are (a) NDArray unwrap/wrap, (b) autograd
tape recording (see _tape.py), (c) NaiveEngine synchronous mode.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

from .. import _tape, engine
from ..base import MXNetError

__all__ = ["Op", "register", "get_op", "invoke", "invoke_raw", "list_ops",
           "set_np_ndarray_cls", "add_invoke_wrapper", "remove_invoke_wrapper"]

_OP_REGISTRY: Dict[str, "Op"] = {}

# Cross-cutting hooks on the imperative invoke funnel (profiler timing, AMP
# dtype casting). Each wrapper is fn(op_name, kernel) -> kernel'. The analog
# of the reference's engine-level profiler hooks (threaded_engine.h:85) and
# AMP op patching (contrib/amp/amp.py:282).
_INVOKE_WRAPPERS: List = []


def add_invoke_wrapper(wrapper):
    _INVOKE_WRAPPERS.append(wrapper)


def remove_invoke_wrapper(wrapper):
    if wrapper in _INVOKE_WRAPPERS:
        _INVOKE_WRAPPERS.remove(wrapper)

# The mx.np ndarray class, registered by mxnet_tpu.numpy at import. When any
# input to an op is an mx.np array, outputs are mx.np arrays — the analog of
# the reference's _set_np_ndarray_class hook (python/mxnet/ndarray/register.py).
_NP_CLS = None


def set_np_ndarray_cls(cls):
    global _NP_CLS
    _NP_CLS = cls


class Op:
    """A registered operator.

    ``fn(*jax_arrays, **attrs)`` is the pure functional kernel — everything
    XLA needs. Optional metadata mirrors the reference op attributes
    (include/mxnet/op_attr_types.h): num_outputs, differentiability.
    """

    def __init__(self, name: str, fn: Callable, num_outputs: int = 1,
                 differentiable: bool = True, ndarray_alias: Optional[str] = None):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs
        self.differentiable = differentiable
        self.ndarray_alias = ndarray_alias

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)

    def __repr__(self):
        return f"Op({self.name})"


def register(name: str, num_outputs: int = 1, differentiable: bool = True,
             alias: Optional[str] = None):
    """Decorator: register a JAX function as an operator."""
    def deco(fn):
        op = Op(name, fn, num_outputs, differentiable, alias)
        _OP_REGISTRY[name] = op
        if alias:
            _OP_REGISTRY[alias] = op
        return fn
    return deco


def get_op(name: str) -> Op:
    try:
        return _OP_REGISTRY[name]
    except KeyError as e:
        raise MXNetError(f"operator {name!r} is not registered") from e


def list_ops() -> List[str]:
    return sorted(_OP_REGISTRY)


try:
    from jax._src.core import trace_state_clean as _trace_state_clean
except ImportError:  # private symbol moved: annotate unconditionally
    def _trace_state_clean():
        return False


def _named_scope_kernel(name: str, fn: Callable) -> Callable:
    """Run the kernel under ``jax.named_scope(op_name)`` so the op name lands
    in the HLO metadata name stack: XProf device traces then attribute fused
    kernels back to framework op names even inside a single jitted CachedOp
    computation (reference __profiler_scope__ + ProfileOperator,
    src/profiler/profiler.h:251-299, c_api_ndarray.cc:104).

    Only applied while a trace is being built (hybridize/_build_cache, jit,
    vjp) — the metadata is meaningless on the eager hot path, so eager
    dispatch pays one thread-local check instead of a context manager."""
    if _trace_state_clean():
        return fn
    safe = name.replace(" ", "_")

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        import jax
        with jax.named_scope(safe):
            return fn(*args, **kwargs)
    return wrapped


@contextlib.contextmanager
def scope(name: str):
    """``jax.named_scope(name)`` around SEVERAL ops of a block (the
    projections in front of latent attention, a whole MTP module), so a
    device trace can sum them under one name; each op inside keeps its
    own. As ``_named_scope_kernel``: only while a trace is being built."""
    if _trace_state_clean():
        yield
        return
    import jax
    with jax.named_scope(name):
        yield


def invoke_raw(name: str, fn: Callable, inputs: Sequence[Any],
               n_outputs: int = 1, record: Optional[bool] = None,
               out_cls=None):
    """Invoke a pure function on NDArray inputs, returning NDArray outputs.

    This is the single funnel every imperative op goes through — the analog
    of MXImperativeInvokeEx → Imperative::Invoke (c_api_ndarray.cc:153).
    """
    from ..ndarray.ndarray import NDArray  # lazy to break import cycle

    cls = out_cls
    if cls is None:
        cls = NDArray
        if _NP_CLS is not None and any(isinstance(x, _NP_CLS) for x in inputs):
            cls = _NP_CLS
    fn = _named_scope_kernel(name, fn)
    for _w in _INVOKE_WRAPPERS:
        fn = _w(name, fn)
    in_datas = [x._data if isinstance(x, NDArray) else x for x in inputs]
    should_record = _tape.is_recording() if record is None else record

    if should_record:
        nd_inputs = [x if isinstance(x, NDArray) else NDArray(x) for x in inputs]
        # Allocate output handles; record_op fills data + tape entries.
        outs = [cls.__new__(cls) for _ in range(n_outputs)]
        for o in outs:
            o._init_empty()
        node = _tape.record_op(name, fn, nd_inputs, outs)
        del node
        result = outs[0] if n_outputs == 1 else tuple(outs)
    else:
        raw = fn(*in_datas)
        if n_outputs == 1 and not isinstance(raw, (tuple, list)):
            result = cls(raw)
        else:
            raw = raw if isinstance(raw, (tuple, list)) else (raw,)
            result = tuple(cls(r) for r in raw)

    eng = engine.get()
    if eng.is_naive:
        rs = result if isinstance(result, tuple) else (result,)
        eng.maybe_sync([r._data for r in rs])
    return result


def invoke(name: str, *inputs, **attrs):
    """Invoke a registered op by name with NDArray inputs + python attrs."""
    op = get_op(name)
    fn = functools.partial(op.fn, **attrs) if attrs else op.fn
    return invoke_raw(op.name, fn, list(inputs), n_outputs=op.num_outputs)
