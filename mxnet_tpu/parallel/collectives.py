"""Collective operations over mesh axes.

Reference analog: the Comm reduce paths (src/kvstore/comm.h), NCCL
collectives (kvstore_nccl.h), and tree reduction (comm_tree.h). On TPU every
one of these is an XLA collective over a mesh axis: psum/all_gather/
reduce_scatter/ppermute riding ICI. These helpers wrap shard_map so
imperative code can call collectives on sharded NDArrays.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .mesh import DeviceMesh, current_mesh

__all__ = ["allreduce", "allgather", "reduce_scatter", "broadcast_axis",
           "ppermute", "reduce_scatter_bucketed", "allgather_bucketed"]


def _get_mesh(mesh):
    mesh = mesh or current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh; wrap in `with make_mesh(...)`")
    return mesh


def shard_map(fn, mesh, in_spec, out_spec):
    """``jax.shard_map`` with the varying-axis check off (e.g. a tiled
    all_gather's output IS replicated over the axis but the inference
    can't prove it; numerics are asserted in tests/test_parallel.py
    instead). Accepts a DeviceMesh or a raw jax Mesh — the supported
    entry point for user/example code."""
    raw = mesh.mesh if isinstance(mesh, DeviceMesh) else mesh
    return jax.shard_map(fn, mesh=raw, in_specs=in_spec,
                         out_specs=out_spec, check_vma=False)


_shard_map = shard_map  # internal alias (pre-existing call sites)


def _on_mesh(x: NDArray, mesh: DeviceMesh, spec) -> jax.Array:
    """Place the operand on the mesh with the collective's input layout.
    Imperative callers usually hold single-device arrays (the reference's
    kvstore accepted plain NDArrays the same way); already-matching sharded
    arrays pass through without a copy."""
    from jax.sharding import NamedSharding
    return jax.device_put(x._data, NamedSharding(mesh.mesh, spec))


def allreduce(x: NDArray, axis: str = "dp",
              mesh: Optional[DeviceMesh] = None, op: str = "sum") -> NDArray:
    """psum over a mesh axis (the kvstore pushpull primitive)."""
    mesh = _get_mesh(mesh)

    def f(v):
        if op == "sum":
            return jax.lax.psum(v, axis)
        if op == "mean":
            return jax.lax.pmean(v, axis)
        if op == "max":
            return jax.lax.pmax(v, axis)
        raise MXNetError(f"unknown reduce op {op}")
    spec = _batch_spec(x, axis)
    out = _shard_map(f, mesh, (spec,), spec)(_on_mesh(x, mesh, spec))
    return NDArray(out)


def allgather(x: NDArray, axis: str = "dp",
              mesh: Optional[DeviceMesh] = None, tiled: bool = True) -> NDArray:
    mesh = _get_mesh(mesh)

    def f(v):
        return jax.lax.all_gather(v, axis, tiled=tiled)
    spec = _batch_spec(x, axis)
    out = _shard_map(f, mesh, (spec,), P())(_on_mesh(x, mesh, spec))
    return NDArray(out)


def reduce_scatter(x: NDArray, axis: str = "dp",
                   mesh: Optional[DeviceMesh] = None) -> NDArray:
    """psum_scatter over a mesh axis: each shard receives the reduced
    1/N tile of the leading dim — the first leg of the ZeRO-1 sharded
    weight update (reduce-scatter → shard-local update → all-gather,
    arXiv:2004.13336). A leading dim not divisible by the axis size is
    zero-padded before the scatter and sliced back after, so arbitrary
    parameter shapes ride the same collective."""
    mesh = _get_mesh(mesh)
    n = mesh.shape[axis]
    lead = int(x.shape[0]) if x.ndim >= 1 else 1
    if x.ndim == 0:
        raise MXNetError("reduce_scatter needs a >=1-d operand")
    pad = (-lead) % n
    data = x._data
    if pad:
        data = jnp.pad(data, ((0, pad),) + ((0, 0),) * (x.ndim - 1))

    def f(v):
        return jax.lax.psum_scatter(v, axis, tiled=True)
    out = _shard_map(f, mesh, (P(),),
                     _batch_spec_ndim(x.ndim, axis))(
                         _on_mesh(NDArray(data), mesh, P()))
    if pad:
        out = out[:lead]
    return NDArray(out)


def broadcast_axis(x: NDArray, axis: str = "dp",
                   mesh: Optional[DeviceMesh] = None, src: int = 0) -> NDArray:
    """Broadcast shard `src`'s value to all shards along the axis."""
    mesh = _get_mesh(mesh)
    n = mesh.shape[axis]

    def f(v):
        # psum of the src-masked value: every shard receives src's block
        # (ppermute can't fan out one source to many destinations)
        idx = jax.lax.axis_index(axis)
        masked = jnp.where(idx == src, v, jnp.zeros_like(v))
        return jax.lax.psum(masked, axis)
    spec = _batch_spec(x, axis)
    out = _shard_map(f, mesh, (spec,), spec)(_on_mesh(x, mesh, spec))
    return NDArray(out)


def ppermute(x: NDArray, perm, axis: str = "dp",
             mesh: Optional[DeviceMesh] = None) -> NDArray:
    mesh = _get_mesh(mesh)

    def f(v):
        return jax.lax.ppermute(v, axis, perm)
    spec = _batch_spec(x, axis)
    out = _shard_map(f, mesh, (spec,), spec)(_on_mesh(x, mesh, spec))
    return NDArray(out)


# ---------------------------------------------------------------------------
# bucketed flat-segment collectives (trace-level: jax arrays, usable
# inside jit — the ZeRO-1 fused step's communication bucketing rides
# these; gluon/fused_step.py)
# ---------------------------------------------------------------------------

def _bucket_rows(segs, num_shards: int):
    """Pad each flat segment to ``num_shards`` divisibility and view it
    as ``(num_shards, s_k)`` rows.  Returns ``(rows, cols)`` where
    ``cols[k]`` is the per-shard column count of segment ``k``."""
    rows, cols = [], []
    for g in segs:
        g = jnp.reshape(g, (-1,))
        n = int(g.shape[0])
        s = -(-n // num_shards)
        pad = s * num_shards - n
        if pad:
            g = jnp.pad(g, (0, pad))
        rows.append(g.reshape(num_shards, s))
        cols.append(s)
    return rows, cols


def reduce_scatter_bucketed(segs, num_shards: int, constrain=None):
    """One reduce-scatter per BUCKET instead of one per segment.

    ``segs`` is a list of flat gradient segments (arbitrary lengths;
    each is zero-padded to ``num_shards`` divisibility).  Every segment
    is viewed as ``(num_shards, s_k)`` and the views concatenate on the
    free axis into a single ``(num_shards, S)`` buffer, so ONE
    collective on the leading dim hands shard ``d`` exactly
    ``[seg_0[d*s_0:(d+1)*s_0], seg_1[...], ...]`` — per-segment shard
    extraction afterwards is a comm-free slice on the free axis.

    ``constrain`` maps the ``(num_shards, S)`` buffer to its sharded
    layout (e.g. ``lambda b: with_sharding_constraint(b,
    NamedSharding(mesh, P(axis, None)))``) and is where the collective
    actually materializes; ``None`` is the identity, which makes the
    routing itself unit-testable without a mesh.

    Returns a list of flat ``(num_shards * s_k,)`` padded segments in
    input order (values identical to padding + constraining each
    segment individually — the packing is pure routing).
    """
    rows, cols = _bucket_rows(segs, num_shards)
    buf = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
    if constrain is not None:
        buf = constrain(buf)
    outs, off = [], 0
    for s in cols:
        outs.append(buf[:, off:off + s].reshape(num_shards * s))
        off += s
    return outs


def allgather_bucketed(shards, num_shards: int, constrain=None,
                       orig_lens=None):
    """One all-gather per BUCKET: the inverse routing of
    :func:`reduce_scatter_bucketed`.

    ``shards`` is a list of flat sharded segments whose lengths are
    ``num_shards``-divisible (the reduce-scatter outputs, or the
    optimizer's new weights computed from them).  They concatenate into
    the same interleaved ``(num_shards, S)`` buffer, ``constrain``
    replicates it (the all-gather), and per-segment full values slice
    back out comm-free.  ``orig_lens`` (optional, per segment) strips
    the scatter padding; ``None`` keeps segments padded.

    Returns the list of flat replicated segments in input order.
    """
    rows = []
    for w in shards:
        w = jnp.reshape(w, (-1,))
        n = int(w.shape[0])
        if n % num_shards:
            raise MXNetError(
                "allgather_bucketed: segment length %d not divisible "
                "by num_shards=%d (pass reduce_scatter_bucketed "
                "outputs)" % (n, num_shards))
        rows.append(w.reshape(num_shards, n // num_shards))
    buf = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
    if constrain is not None:
        buf = constrain(buf)
    outs, off = [], 0
    for k, r in enumerate(rows):
        s = r.shape[1]
        full = buf[:, off:off + s].reshape(num_shards * s)
        if orig_lens is not None:
            full = full[:int(orig_lens[k])]
        outs.append(full)
        off += s
    return outs


def _batch_spec(x: NDArray, axis: str):
    return _batch_spec_ndim(x.ndim, axis)


def _batch_spec_ndim(ndim: int, axis: str):
    return P(axis, *([None] * (ndim - 1)))
