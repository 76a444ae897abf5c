"""Mamba-2's chunked selective scan (ops/ssm.py ``ssd_scan``) as two
kernels that keep a chunk's (Q x Q) matrices in VMEM and carry the state
along the grid.

Both walk a grid of (batch, group, chunk), the chunk axis innermost and
sequential. A step holds one chunk of the ``R = H / G`` heads that share
a group's ``B`` and ``C``: x as (Q, R P) from (B, S, H P), ``B`` and ``C``
as (Q, N) from (B, S, G N), the layouts the mixer holds, and the heads'
step sizes as (R, Q) from ``dt`` laid (B, G, R, S). The state of the group's
heads, (N, R P) float32, stays in VMEM scratch from chunk to chunk.

- :func:`forward` walks the chunks first to last: ``cs``, the in-chunk
  cumulative sum of ``dt A``, as a triangular product at HIGHEST (exact
  to float32); the group's scores ``C B^T`` once; a head's ``L[t, s] =
  exp(cs_t - cs_s)`` with the mask BEFORE the exponential; ``((C B^T) * L
  * dt) x``; the carried state read, ``exp(cs) * (C H_in)``, and
  updated, ``exp(cs_Q) H_in + B^T (x * to_end)``, one product each for
  all the group's heads. Writes y and the state ENTERING each chunk.
- :func:`backward` walks them last to first with the entering state's
  cotangent in scratch, makes ``cs``, ``L`` and the scores again from the
  operands and the kept entering states, and writes dx, dB and dC (summed
  over the group's heads in the kernel), d``dt`` laid like ``dt``, dA a
  head, and dD a head lane (the sum over a head's lanes is XLA's).

Nothing with a (Q, Q) face leaves VMEM. ``dt``, ``A``, ``cs``, ``L`` and
the states are float32; the products take their operands in x's dtype,
cast exactly where ``_ssd_chunked`` casts (the mixed matrix, ``x *
to_end``, the entering state), and accumulate in float32. Per-head
scalars of a chunk live as rows (R, Q) (``cs`` as columns (Q, R) too, the
in-kernel transpose); a row is spread over its head's P lanes of a (Q, R
P) matrix, and a head's lanes are summed into a row, by ONE bf16 pass of
a product with a 0 / 1 matrix over the three bf16 pieces that add up to
the float32 values, which is exact. A head narrower than a lane tile is reached
through the 128-lane tile that holds it, its neighbours' lanes masked on
one side of the product, so every slice and store is lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_SCOPED_DEFAULT_BYTES, vmem_tile_budget
from .grouped_dot import _precision

__all__ = ["forward", "backward", "supported"]

_LANES = 128
_CHUNK = 128
_F32 = jnp.float32
_EXACT = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _held_bytes(per_group: int, width: int, state: int, dtype) -> int:
    """What one step of the backward kernel, the larger of the two, holds
    at once: its blocks (x, dy, dx; B, C, dB, dC; the entering state),
    the carried cotangent and six float32 matrices as wide as the
    group's heads."""
    size = jnp.dtype(dtype).itemsize
    lanes = per_group * width
    return size * (3 * _CHUNK * lanes + 4 * _CHUNK * state) \
        + 4 * (2 * state * lanes + 6 * _CHUNK * lanes)


def supported(heads: int, width: int, groups: int, state: int, chunk: int,
              *dtypes, precision=None):
    """None when the kernels take a scan of ``heads`` heads of ``width``
    lanes in ``groups`` groups over a state of ``state`` lanes in chunks
    of ``chunk`` at ``precision`` (what ``jax.default_matmul_precision``
    asks for, None if nothing), else why ``_ssd_chunked`` does; from
    shapes, dtypes and that setting alone."""
    kinds = {jnp.dtype(dt) for dt in dtypes}
    if len(kinds) != 1:
        return f"operands of {sorted(map(str, kinds))}: one dtype wanted"
    kind = kinds.pop()
    if kind not in (jnp.float32, jnp.bfloat16):
        return f"dtype {kind} not kernelized (float32 / bfloat16 only)"
    if kind == jnp.float32 and _precision(kind, precision) is None:
        return (f"float32 at precision {precision!r}: Mosaic multiplies in "
                "one bf16 pass or at highest")
    if chunk != _CHUNK:
        return f"chunks of {chunk}: the kernels walk chunks of {_CHUNK}"
    per_group = heads // groups
    if state % _LANES or (per_group * width) % _LANES:
        return (f"state {state}, a group's head lanes {per_group} x "
                f"{width}: no multiple of {_LANES} lanes")
    if _LANES % width and width % _LANES:
        return (f"heads of {width} lanes neither fill nor evenly share a "
                f"{_LANES}-lane tile")
    held = _held_bytes(per_group, width, state, kind)
    if held > vmem_tile_budget():
        return (f"a step of {per_group} heads x {width} over state {state} "
                f"holds {held} bytes, over the tile budget "
                f"{vmem_tile_budget()}")
    return None


def _windows(per_group: int, width: int):
    """The lane windows of a group's heads: ``[(start, lanes, [(head, (lo,
    hi) | None)])]``, a window a 128-lane tile and the heads that share
    it with their lanes inside it, or one head that fills whole tiles."""
    if width % _LANES == 0:
        return [(r * width, width, [(r, None)]) for r in range(per_group)]
    share = _LANES // width
    return [(t * _LANES, _LANES,
             [(t * share + i, (i * width, (i + 1) * width))
              for i in range(share)])
            for t in range(per_group * width // _LANES)]


def _own_lanes(tile, bounds):
    """``tile`` (Q, 128) with the lanes outside ``bounds`` zeroed."""
    if bounds is None:
        return tile
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.where((lane >= bounds[0]) & (lane < bounds[1]), tile,
                     jnp.zeros_like(tile))


def _head_lanes(per_group: int, width: int, axis: int):
    """The 0 / 1 matrix (R, R P), one where lane l is head r's, three
    times over along ``axis``: against the three :func:`_pieces` of a
    float32 array laid along the same axis."""
    shape = (per_group, per_group * width)
    head = lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    own = ((lane >= head * width) & (lane < (head + 1) * width)) \
        .astype(_F32)
    return jnp.concatenate([own] * 3, axis=axis)


def _pieces(a):
    """Three float32 arrays of bf16 values that add up to ``a`` exactly: a
    product of their concatenation with a 0 / 1 matrix is ONE bf16 pass of
    the MXU and, summed in its float32 accumulator, exact."""
    def low(v):
        return v.astype(jnp.bfloat16).astype(_F32)
    first = low(a)
    second = low(a - first)
    return first, second, low(a - first - second)


def _one_pass(a, b, dims):
    return lax.dot_general(a, b, dims, precision=lax.Precision.DEFAULT,
                           preferred_element_type=_F32)


def _spread_rows(rows, tall):
    """``rows`` (R, Q), a scalar a head and position, as (Q, R P): every
    lane of head r in row t reads ``rows[r, t]``. Exact, one pass;
    ``tall`` is ``_head_lanes(.., axis=0)``."""
    return _one_pass(jnp.concatenate(_pieces(rows), axis=0), tall, _TN)


def _gather_rows(wide, long):
    """``wide`` (Q, R P) summed over each head's lanes, as rows (R, Q):
    float32 sums of the three pieces' products, one pass; ``long`` is
    ``_head_lanes(.., axis=1)``."""
    return _one_pass(long, jnp.concatenate(_pieces(wide), axis=1), _NT)


def _exact(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, precision=_EXACT,
                           preferred_element_type=_F32)


def _chunk_scalars(dt_ref, a_ref):
    """A chunk's per-head scalars from its step sizes (R, Q) and decay
    rates (R, 1): ``dt`` and ``cs`` as rows, ``cs`` as columns (Q, R), and
    the (t, s) mask ``s <= t``."""
    dt_row = dt_ref[...]
    q = dt_row.shape[1]
    t = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # cs_t = sum over s <= t of dt_s A: rows of (s, t), s <= t
    cs_row = _exact(dt_row * a_ref[...], (t <= s).astype(_F32))
    return dt_row, cs_row, cs_row.T, t >= s


def _decay(seen, cs_col, cs_row, r):
    """A head's ``L``: the mask stands before the exponential."""
    return jnp.exp(jnp.where(seen, cs_col[:, r:r + 1] - cs_row[r:r + 1, :],
                             -jnp.inf))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(VMEM_SCOPED_DEFAULT_BYTES,
                             4 * vmem_tile_budget()))


def _fwd_kernel(a_ref, skip_ref, dt_ref, x_ref, b_ref, c_ref, y_ref,
                entering_ref, h_ref, *, per_group, width, precision):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    dot = functools.partial(lax.dot_general, precision=precision,
                            preferred_element_type=_F32)
    dt_row, cs_row, cs_col, seen = _chunk_scalars(dt_ref, a_ref)
    q = dt_row.shape[1]
    x, bm, cm = x_ref[...], b_ref[...], c_ref[...]
    kind = x.dtype
    xf = x.astype(_F32)
    tall = _head_lanes(per_group, width, 0)
    entering = h_ref[...]
    entering_ref[...] = entering

    # the carried state read and the skip, all the group's heads at once
    decayed = _spread_rows(jnp.exp(cs_row), tall)           # (Q, R P)
    rest = dot(cm, entering.astype(kind), _NN) * decayed \
        + xf * skip_ref[...]
    # the state carried on: exp(cs_Q) H_in + B^T (x * to_end)
    to_end = _spread_rows(jnp.exp(cs_row[:, q - 1:q] - cs_row) * dt_row,
                          tall)
    weighed = (xf * to_end).astype(kind)
    h_ref[...] = entering * decayed[q - 1:q, :] + dot(bm, weighed, _TN)

    # inside the chunk: ((C B^T) * L * dt) x, a head at a time
    scores = dot(cm, bm, _NT)
    for start, lanes, members in _windows(per_group, width):
        acc = rest[:, start:start + lanes]
        tile = x[:, start:start + lanes]
        for r, bounds in members:
            mixed = scores * _decay(seen, cs_col, cs_row, r) \
                * dt_row[r:r + 1, :]
            acc += dot(mixed.astype(kind), _own_lanes(tile, bounds), _NN)
        y_ref[:, start:start + lanes] = acc.astype(kind)


def _specs(per_group, width, state, reverse, chunks):
    """Block specs of the operands both kernels read, (A, skip, dt, x, B,
    C), and of blocks laid like x, like B and like dt; ``reverse`` walks
    the chunks last to first."""
    lanes = per_group * width
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    like_x = pl.BlockSpec((None, _CHUNK, lanes),
                          lambda b, g, c: (b, at(c), g))
    like_b = pl.BlockSpec((None, _CHUNK, state),
                          lambda b, g, c: (b, at(c), g))
    like_dt = pl.BlockSpec((None, None, per_group, _CHUNK),
                           lambda b, g, c: (b, g, 0, at(c)))
    entering = pl.BlockSpec((None, None, None, state, lanes),
                            lambda b, g, c: (b, at(c), g, 0, 0))
    operands = [pl.BlockSpec((None, per_group, 1), lambda b, g, c: (g, 0, 0)),
                pl.BlockSpec((1, lanes), lambda b, g, c: (0, g)),
                like_dt, like_x, like_b, like_b]
    return operands, like_x, like_b, like_dt, entering


def _small(dt, A, D, groups, width):
    """The small operands as the kernels read them, a group's heads
    together: ``A`` (G, R, 1), the skip's weight spread over its head's
    lanes (1, H P), ``dt`` (B, G, R, S)."""
    batch, seq, heads = dt.shape
    return (A.astype(_F32).reshape(groups, heads // groups, 1),
            jnp.repeat(D.astype(_F32), width)[None, :],
            jnp.swapaxes(dt.astype(_F32), 1, 2).reshape(
                batch, groups, heads // groups, seq))


def _geometry(x, dt, B, groups):
    batch, seq, inner = x.shape
    heads = dt.shape[2]
    return (batch, seq // _CHUNK, heads // groups, inner // heads,
            B.shape[2] // groups)


@functools.partial(jax.jit, static_argnames=("groups", "precision",
                                             "interpret"))
def forward(x, dt, A, B, C, D, *, groups, precision=None, interpret=False):
    """``x`` (B, S, H P), ``dt`` (B, S, H), ``A`` and ``D`` (H,), ``B`` and
    ``C`` (B, S, G N), S a whole number of chunks, ``precision`` what
    :func:`supported` took. Returns y like x and the states entering each
    chunk, (B, S / Q, G, N, R P) float32."""
    batch, chunks, per_group, width, state = _geometry(x, dt, B, groups)
    operands, like_x, _, _, entering = _specs(per_group, width, state,
                                              False, chunks)
    lanes = per_group * width
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per_group=per_group, width=width,
                          precision=_precision(x.dtype, precision)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((batch, chunks, groups, state,
                                         lanes), _F32)),
        grid=(batch, groups, chunks), in_specs=operands,
        out_specs=(like_x, entering),
        scratch_shapes=[pltpu.VMEM((state, lanes), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_fwd")(*_small(dt, A, D, groups, width), x, B, C)


def _bwd_kernel(a_ref, skip_ref, dt_ref, x_ref, b_ref, c_ref, entering_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dskip_ref,
                dh_ref, cols_ref, rows_ref, *, per_group, width, precision):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    dot = functools.partial(lax.dot_general, precision=precision,
                            preferred_element_type=_F32)
    dt_row, cs_row, cs_col, seen = _chunk_scalars(dt_ref, a_ref)
    q = dt_row.shape[1]
    x, bm, cm, dy = x_ref[...], b_ref[...], c_ref[...], dy_ref[...]
    kind = x.dtype
    xf, dyf = x.astype(_F32), dy.astype(_F32)
    tall = _head_lanes(per_group, width, 0)
    long = _head_lanes(per_group, width, 1)
    entering = entering_ref[...]
    low = entering.astype(kind)
    dh = dh_ref[...]                 # of the state LEAVING this chunk
    dh_low = dh.astype(kind)

    # the carried state read: y_off = exp(cs) * (C H_in)
    decayed = _spread_rows(jnp.exp(cs_row), tall)
    dy_decayed = dyf * decayed
    dy_low = dy_decayed.astype(kind)
    dc = dot(dy_low, low, _NT)                              # (Q, N)
    dcs_row = _gather_rows(dy_decayed * dot(cm, low, _NN), long)
    # the state carried on: exp(cs_Q) H_in + B^T (x * to_end)
    gone = jnp.exp(cs_row[:, q - 1:q] - cs_row)             # (R, Q)
    to_end = gone * dt_row
    to_end_wide = _spread_rows(to_end, tall)
    db = dot((xf * to_end_wide).astype(kind), dh_low, _NT)  # (Q, N)
    d_weighed = dot(bm, dh_low, _NN)                        # (Q, R P)
    d_to_end = _gather_rows(d_weighed * xf, long)           # (R, Q)
    dcs_row -= d_to_end * to_end
    # what reaches cs at the chunk's end: through to_end and exp(cs_Q)
    at_end = jnp.sum(d_to_end * to_end, axis=1, keepdims=True) \
        + jnp.exp(cs_row[:, q - 1:q]) * jnp.sum(
            tall[:per_group] * jnp.sum(dh * entering, axis=0,
                                       keepdims=True),
            axis=1, keepdims=True)                          # (R, 1)
    dh_ref[...] = dh * decayed[q - 1:q, :] + dot(cm, dy_low, _TN)
    dskip_ref[...] += jnp.sum(dyf * xf, axis=0, keepdims=True)
    rest = d_weighed * to_end_wide + dyf * skip_ref[...]

    # inside the chunk, a head at a time
    scores = dot(cm, bm, _NT)
    d_scores = jnp.zeros_like(scores)
    for start, lanes, members in _windows(per_group, width):
        acc = rest[:, start:start + lanes]
        tile = x[:, start:start + lanes]
        dy_tile = dy[:, start:start + lanes]
        for r, bounds in members:
            step = dt_row[r:r + 1, :]
            decay = _decay(seen, cs_col, cs_row, r)
            own = _own_lanes(dy_tile, bounds)
            d_mixed = dot(own, tile, _NT)                   # (t, s)
            held = scores * decay
            acc += dot((held * step).astype(kind), own, _TN)
            d_step = d_mixed * held
            below = jnp.sum(d_step, axis=0, keepdims=True)  # (1, s)
            rows_ref[r:r + 1, :] = below
            cols_ref[:, r:r + 1] = jnp.sum(d_step * step, axis=1,
                                           keepdims=True)
            d_scores += d_mixed * (decay * step)
        dx_ref[:, start:start + lanes] = acc.astype(kind)
    d_low = d_scores.astype(kind)
    dc_ref[...] = (dc + dot(d_low, bm, _NN)).astype(dc_ref.dtype)
    db_ref[...] = (db + dot(d_low, cm, _TN)).astype(db_ref.dtype)

    # cs is a cumulative sum of dt A: its cotangent is summed from the end
    below = rows_ref[...]                                   # (R, s)
    last = lax.broadcasted_iota(jnp.int32, dcs_row.shape, 1) == q - 1
    dcs_row += cols_ref[...].T - below * dt_row + jnp.where(last, at_end,
                                                            0.0)
    d_rate = _exact(dcs_row, seen.astype(_F32))             # d(dt A)
    ddt_ref[...] = below + d_to_end * gone + d_rate * a_ref[...]
    da_ref[...] += jnp.sum(d_rate * dt_row, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("groups", "precision",
                                             "interpret"))
def backward(x, dt, A, B, C, D, entering, dy, *, groups, precision=None,
             interpret=False):
    """The cotangents ``(dx, ddt, dA, dB, dC, dD)`` of :func:`forward`'s
    operands for y's cotangent ``dy``, each shaped and typed like its
    operand (``ddt``, ``dA``, ``dD`` float32), from the operands and the
    ``entering`` states that :func:`forward` wrote."""
    batch, chunks, per_group, width, state = _geometry(x, dt, B, groups)
    heads = dt.shape[2]
    operands, like_x, like_b, like_dt, kept = _specs(
        per_group, width, state, True, chunks)
    lanes = per_group * width
    small = _small(dt, A, D, groups, width)
    dx, db, dc, ddt, da, dskip = pl.pallas_call(
        functools.partial(_bwd_kernel, per_group=per_group, width=width,
                          precision=_precision(x.dtype, precision)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype),
                   jax.ShapeDtypeStruct(small[2].shape, _F32),
                   jax.ShapeDtypeStruct((batch, groups, per_group, 1), _F32),
                   jax.ShapeDtypeStruct((batch, 1, heads * width), _F32)),
        grid=(batch, groups, chunks),
        in_specs=operands + [kept, like_x],
        out_specs=(like_x, like_b, like_b, like_dt,
                   pl.BlockSpec((None, None, per_group, 1),
                                lambda b, g, c: (b, g, 0, 0)),
                   pl.BlockSpec((None, 1, lanes),
                                lambda b, g, c: (b, 0, g))),
        scratch_shapes=[pltpu.VMEM((state, lanes), _F32),
                        pltpu.VMEM((_CHUNK, per_group), _F32),
                        pltpu.VMEM((per_group, _CHUNK), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_bwd")(*small, x, B, C, entering, dy)
    return (dx, jnp.swapaxes(ddt.reshape(batch, heads, -1), 1, 2),
            da.reshape(batch, heads).sum(0), db, dc,
            dskip.reshape(batch, heads, width).sum((0, 2)))
