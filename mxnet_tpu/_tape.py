"""Autograd tape: record-mode flags, tape nodes, backward engine.

TPU-native re-design of the reference's imperative autograd
(reference: src/imperative/imperative.cc:377-630 ``Imperative::Backward``,
include/mxnet/imperative.h:54-92 ``AGInfo``). The reference attaches an nnvm
node to every recorded array and later runs the ``MXGradient`` graph pass;
here each recorded op captures a ``jax.vjp`` closure, and ``backward`` walks
the tape in reverse record order, so XLA differentiates each op while the
tape supplies the cross-op chain rule.

Higher-order gradients (``create_graph=True``): instead of calling the saved
vjp closure, the backward of each node is re-invoked *through the tape* as a
fresh differentiable op (``jax.vjp`` of the stored primal fn), so the backward
computation is itself recorded — the analog of the reference re-recording
backward nodes when ``is_recording`` (imperative.cc:457 + RecordOp).
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from .base import MXNetError

__all__ = [
    "is_recording", "is_training", "set_recording", "set_training",
    "is_taping_suspended", "set_taping_suspended", "suspend_taping",
    "TapeNode", "record_op", "backward", "grad", "mark_variables",
]


class _State(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False
        # Hard override used by whole-graph functionalization (cached ops,
        # Trainer.compile_step): while suspended, is_recording() reports
        # False even if user code inside the traced region enters
        # autograd.record() — tape nodes must never be attached to tracers.
        self.suspended = False


_state = _State()
_node_counter = [0]


def is_recording() -> bool:
    return _state.recording and not _state.suspended


def is_training() -> bool:
    return _state.training


def set_recording(flag: bool) -> bool:
    old, _state.recording = _state.recording, flag
    return old


def set_training(flag: bool) -> bool:
    old, _state.training = _state.training, flag
    return old


def is_taping_suspended() -> bool:
    return _state.suspended


def set_taping_suspended(flag: bool) -> bool:
    old, _state.suspended = _state.suspended, flag
    return old


class suspend_taping:
    """Context manager: force is_recording() False for the duration, even
    across user calls to set_recording(True)/autograd.record() inside the
    scope. The functionalized-trace analog of the reference's
    Imperative::DCInfo scope (deferred compute forbids nested recording)."""

    def __enter__(self):
        self._prev = set_taping_suspended(True)
        return self

    def __exit__(self, *exc):
        set_taping_suspended(self._prev)
        return False


class TapeNode:
    """One recorded op: inputs (NDArray handles), primal fn, vjp closure.

    ``fn`` is a pure function jax arrays -> (tuple of) jax arrays with all
    non-tensor attrs already bound. ``vjp_fn`` is the fast-path closure from
    ``jax.vjp``; ``fn`` is retained for create_graph re-derivation.
    """

    __slots__ = ("id", "name", "inputs", "fn", "vjp_fn", "out_avals",
                 "n_outputs", "input_entries", "out_is_tuple")

    def __init__(self, name, inputs, fn, vjp_fn, out_avals, out_is_tuple=False):
        _node_counter[0] += 1
        self.id = _node_counter[0]
        self.name = name
        self.inputs = list(inputs)          # NDArray handles (strong refs = saved tensors)
        # Snapshot each input's tape entry NOW: later in-place mutation of an
        # input handle must not rewire this node's ancestry (write-after-read
        # ordering the reference engine enforces via versioned vars).
        self.input_entries = [getattr(x, "_tape_entry", None) for x in inputs]
        self.fn = fn
        self.vjp_fn = vjp_fn
        self.out_avals = out_avals          # list of jax.ShapeDtypeStruct
        self.n_outputs = len(out_avals)
        self.out_is_tuple = out_is_tuple    # fn returned a tuple (vjp wants one)


# Optional post-record hook on the concrete primal outputs — the tape-side
# attachment point for the inspector's NaN guard: under record the kernel
# runs inside jax.vjp tracing (invoke wrappers only see Tracers), while the
# primal values surfacing here are concrete (reference check_value through
# the engine's on-complete hook).
_output_check: Optional[Callable] = None


def set_output_check(fn: Optional[Callable]) -> Optional[Callable]:
    global _output_check
    old, _output_check = _output_check, fn
    return old


def record_op(name: str, fn: Callable, inputs: Sequence[Any],
              out_arrays: Sequence[Any]) -> None:
    """Attach a TapeNode to ``out_arrays``. ``out_arrays`` are the NDArray
    handles wrapping the outputs that ``fn(*input_datas)`` produced via vjp.
    Called by the op-invoke layer (ops/registry.py) when recording."""
    in_datas = [x._data for x in inputs]
    outs, vjp_fn = jax.vjp(fn, *in_datas)
    if _output_check is not None:
        _output_check(name, outs if isinstance(outs, (tuple, list))
                      else (outs,))
    out_is_tuple = isinstance(outs, (tuple, list))
    if not out_is_tuple:
        outs = (outs,)
    avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs]
    node = TapeNode(name, inputs, fn, vjp_fn, avals, out_is_tuple)
    for i, arr in enumerate(out_arrays):
        arr._data = outs[i]
        arr._tape_entry = (node, i)
    return node


def _zeros_like_aval(aval):
    """The cotangent of an output nobody differentiated through. An
    integer or boolean output (the sort indices and group sizes a router
    hands the expert layer) takes jax's ``float0``, as ``jax.vjp`` asks."""
    if not jnp.issubdtype(aval.dtype, jnp.inexact):
        return onp.zeros(aval.shape, jax.dtypes.float0)
    return jnp.zeros(aval.shape, aval.dtype)


def _collect_graph(heads) -> Tuple[List[TapeNode], Dict[int, TapeNode]]:
    """DFS from head arrays over snapshotted input entries; return reachable
    nodes sorted by record id (valid topological order)."""
    seen: Dict[int, TapeNode] = {}
    stack = [h._tape_entry[0] for h in heads
             if getattr(h, "_tape_entry", None) is not None]
    while stack:
        node = stack.pop()
        if node.id in seen:
            continue
        seen[node.id] = node
        for ent in node.input_entries:
            if ent is not None and ent[0].id not in seen:
                stack.append(ent[0])
    order = sorted(seen.values(), key=lambda n: n.id)
    return order, seen


def _ct_sum(a, b):
    """Sum two cotangents. Raw jax arrays and NDArray-typed cotangents
    (create_graph handles, row_sparse embedding grads) can meet on a shared
    input; a mixed pair densifies the NDArray side."""
    a_nd, b_nd = hasattr(a, "_data"), hasattr(b, "_data")
    if a_nd and not b_nd:
        return a._data + b
    if b_nd and not a_nd:
        return a + b._data
    return a + b


def _accumulate(store: Dict[Tuple[int, int], Any], key, val):
    if val is None:
        return
    if key in store:
        store[key] = _ct_sum(store[key], val)
    else:
        store[key] = val


def backward(heads, head_grads=None, retain_graph=False, create_graph=False,
             train_mode=True, variables=None):
    """Run reverse-mode through the tape.

    If ``variables`` is None: write into each reachable leaf's ``.grad``
    honoring grad_req write/add (reference Imperative::Backward semantics);
    returns None. Else: return the gradient arrays (jax arrays) w.r.t.
    ``variables`` without touching ``.grad`` (reference MXAutogradBackwardEx
    with var handles → autograd.grad).
    """
    heads = list(heads)
    if head_grads is None:
        head_grads = [None] * len(heads)
    head_grads = list(head_grads)
    if len(head_grads) != len(heads):
        raise MXNetError("heads and head_grads length mismatch")

    for h in heads:
        if getattr(h, "_tape_entry", None) is None and variables is None \
                and getattr(h, "_grad", None) is None:
            raise MXNetError(
                "cannot differentiate a head that is not in the recorded "
                "graph and has no grad attached")

    order, _ = _collect_graph(heads)

    # cotangent store keyed by (node_id, out_index); leaves handled separately
    ct: Dict[Tuple[int, int], Any] = {}
    # seed heads. In create_graph mode the cotangent store holds NDArray
    # handles (so accumulation itself is recorded); otherwise raw jax arrays.
    for h, hg in zip(heads, head_grads):
        ent = getattr(h, "_tape_entry", None)
        if hg is None:
            seed = jnp.ones(h._data.shape, h._data.dtype)
        else:
            seed = hg._data if hasattr(hg, "_data") else jnp.asarray(hg)
        if create_graph:
            from .ndarray.ndarray import NDArray  # lazy
            seed = NDArray(seed)
        if ent is not None:
            _accumulate(ct, (ent[0].id, ent[1]), seed)
        elif variables is None and getattr(h, "_grad", None) is not None:
            _write_leaf_grad(h, seed)

    leaf_grads: Dict[int, Any] = {}   # id(ndarray handle) -> jax array
    var_ids = {id(v): i for i, v in enumerate(variables)} if variables else None

    if create_graph:
        _backward_create_graph(order, ct, leaf_grads, var_ids, variables)
    else:
        prev = set_recording(False)
        prev_t = set_training(train_mode)
        try:
            for node in reversed(order):
                cts = [ct.pop((node.id, i), None) for i in range(node.n_outputs)]
                if all(c is None for c in cts):
                    continue
                if node.vjp_fn is None:
                    raise MXNetError(
                        "cannot run backward: the graph has already been "
                        "freed. Call backward(retain_graph=True) to backward "
                        "through the graph a second time")
                # unwrap NDArray-typed cotangents (row_sparse embedding
                # grads) to raw jax arrays before entering the vjp closure
                cts = [(c._data if hasattr(c, "_data") else c)
                       if c is not None else _zeros_like_aval(a)
                       for c, a in zip(cts, node.out_avals)]
                arg = tuple(cts) if node.out_is_tuple else cts[0]
                in_cts = node.vjp_fn(arg)
                _scatter_input_cts(node, in_cts, ct, leaf_grads, var_ids)
                if not retain_graph:
                    node.vjp_fn = None  # free residuals ASAP
        finally:
            set_recording(prev)
            set_training(prev_t)

    if variables is not None:
        out = []
        for v in variables:
            g = leaf_grads.get(id(v))
            if g is None:
                g = jnp.zeros(v._data.shape, v._data.dtype)
            out.append(g)
        return out

    # write leaf grads honoring grad_req
    for node in order:
        for x in node.inputs:
            gid = id(x)
            if gid in leaf_grads and getattr(x, "_grad", None) is not None:
                _write_leaf_grad(x, leaf_grads.pop(gid))
    return None


def _scatter_input_cts(node, in_cts, ct, leaf_grads, var_ids):
    # zip with snapshotted entries (handle duplicates positionally)
    for pos, g in enumerate(in_cts):
        # an integer input's cotangent is float0 (a numpy array; a lazy
        # row_sparse cotangent must not be asked its dtype: that
        # materializes its dense mirror): nothing to carry back
        if g is None or (isinstance(g, onp.ndarray)
                         and g.dtype == jax.dtypes.float0):
            continue
        x = node.inputs[pos]
        ent = node.input_entries[pos]
        if var_ids is not None and id(x) in var_ids:
            _accumulate_by_id(leaf_grads, id(x), g)
            continue
        if ent is not None:
            _accumulate(ct, (ent[0].id, ent[1]), g)
        else:
            _accumulate_by_id(leaf_grads, id(x), g)


def _accumulate_by_id(store: Dict[int, Any], key: int, val):
    if key in store:
        store[key] = _ct_sum(store[key], val)
    else:
        store[key] = val


def _write_leaf_grad(x, g):
    """Honor grad_req: 'write' overwrites, 'add' accumulates across backward
    calls, 'null' drops (reference grad_req handling, imperative.cc:490)."""
    req = getattr(x, "_grad_req", "write")
    if req == "null" or x._grad is None:
        return
    from .ndarray.sparse import RowSparseNDArray  # lazy: import cycle
    if isinstance(g, RowSparseNDArray) and req == "write":
        # keep the row_sparse structure on the leaf (reference grad_stype
        # row_sparse, FInferStorageType): the optimizer's lazy path reads
        # (indices, values); any dense consumer reads the dense mirror
        x._grad = g
        x._fresh_grad = True
        return
    if isinstance(x._grad, RowSparseNDArray):
        # dense gradient arriving on a leaf whose previous grad was sparse
        # (e.g. tied weights summed to dense this step): REPLACE the handle —
        # writing _data in place would leave the old (indices, values) aux
        # stale and the lazy optimizer would re-apply last step's rows
        from .ndarray.ndarray import NDArray
        gdata = g._data if hasattr(g, "_data") else g
        base = x._grad._data if req == "add" else None
        gdata = jnp.asarray(gdata, x._grad._data.dtype) \
            .reshape(x._grad._data.shape)
        x._grad = NDArray(gdata if base is None else base + gdata)
        x._fresh_grad = True
        return
    gdata = g._data if hasattr(g, "_data") else g
    gdata = jnp.asarray(gdata, x._grad._data.dtype)
    if gdata.shape != x._grad._data.shape:
        gdata = gdata.reshape(x._grad._data.shape)
    if req == "add":
        x._grad._data = x._grad._data + gdata
    else:
        x._grad._data = gdata
    x._fresh_grad = True


def _backward_create_graph(order, ct, leaf_grads, var_ids, variables):
    """Differentiable backward: each node's grad computation is re-invoked as
    a recorded op so second-order ``backward`` works."""
    from .ops.registry import invoke_raw  # lazy: avoids import cycle

    for node in reversed(order):
        cts = [ct.pop((node.id, i), None) for i in range(node.n_outputs)]
        if all(c is None for c in cts):
            continue
        n_in = len(node.inputs)
        fn = node.fn

        def grad_fn(*args, _fn=fn, _n_in=n_in, _tup=node.out_is_tuple):
            xs, gs = args[:_n_in], args[_n_in:]
            _, vjp_fn = jax.vjp(_fn, *xs)
            arg = tuple(gs) if _tup else gs[0]
            return tuple(vjp_fn(arg))

        ct_handles = []
        from .ndarray.ndarray import NDArray  # lazy
        for c, a in zip(cts, node.out_avals):
            if c is None:
                c = _zeros_like_aval(a)
            ct_handles.append(c if isinstance(c, NDArray) else NDArray(c))
        in_grads = invoke_raw(f"_backward_{node.name}", grad_fn,
                              list(node.inputs) + ct_handles, n_outputs=n_in)
        if not isinstance(in_grads, (list, tuple)):
            in_grads = [in_grads]
        _scatter_input_cts(node, list(in_grads), ct, leaf_grads, var_ids)


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    if retain_graph is None:
        retain_graph = create_graph
    return backward(heads, head_grads, retain_graph, create_graph,
                    train_mode, variables=variables)


def mark_variables(variables, gradients, grad_reqs="write"):
    """Reference autograd.mark_variables (python/mxnet/autograd.py:197):
    associate grads/reqs with arrays, making them tape leaves."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, r in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = r
        v._tape_entry = None
