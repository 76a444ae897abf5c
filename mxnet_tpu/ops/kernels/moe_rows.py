"""Row movers of the dropless expert layer (ops/moe.py): the passes behind
the grouped products, forward and backward, that XLA runs as gathers
through the pairs' places over the whole static list, as two kernels whose
work follows the pairs a layer HOLDS.

The sorted list has ``rows = N * min(k, count)`` rows, the most the held
experts can be given; the pairs they are given stand in its first
``total = sizes.sum()`` rows, a number only the device knows. These
kernels read ``total`` from SMEM and walk the live prefix:

- :func:`scatter_sum` (token-major result): ``out[n] = sum of w[r] *
  src[r]`` over the live rows r of token n, in float32; a token without
  a live row reads zero. ``moe_combine`` forward and, with weights of
  one, ``_dispatch`` backward. A token's pairs go to different experts,
  so its rows are added in the experts' order. It is also the gradient
  of an embedding table (ops/nn.py ``embedding``): ``k = 1``, unit
  weights, the ids as ``order`` and the table's rows as the tokens,
  under ``name="embedding_grad"``.
- :func:`gather_rows` (pair-major): ``out[r] = w[r] * src[token(r)]`` for
  ``r < total``, zero up to the end of the last live row block, and in
  the same pass ``<y[r], src[token(r)]>`` in float32, a pair's weight
  gradient. ``moe_combine`` backward.

(``_dispatch`` forward, ``x[order // k]``, stays XLA's: one gather of N-row
blocks at 0.4 ms a layer, which a third kernel beat by half and paid back
in set-up.)

Both walk a grid of (column blocks, row blocks). A row block past
``total`` does nothing (``pl.when``) and fetches nothing (its index map
repeats the last live block); a live block runs one scalar-loop step a
live row, four to a turn. The N-long side (the tokens: gather's source,
scatter's sum) stays in VMEM in float32 a column block at a time, single
buffered, so a row of it is one dynamic-sublane load or store of 32-bit
words: Mosaic (jax 0.9.0) takes no single-row DMA from a tiled HBM array
("slice shape along dimension 0 must be aligned to tiling (8)") and no
dynamic single-row access to packed bf16, so a bf16 block of the list is
widened to float32 in VMEM, densely, before its row loop. A turn of the
row loop costs what its scalar work costs, about 10 ns a row whatever the
block's width (v5e, PR 31), so the column block is as wide as VMEM allows:
1280 of 2560 columns at 8192 tokens.

``token(r) = order[r] // k`` and ``w[r] = weights.reshape(-1)[order[r]]``
are scalar reads (``order // k``, ``order`` and ``weights``
scalar-prefetched into SMEM). Each kernel has ONE form and its entry point
is jitted, so every call site of a model at one shape shares one trace
and one Mosaic lowering: lowering a kernel is a tenth of a second of
set-up on the chip's host, a call site, a lowering of the step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gather_rows", "scatter_sum", "rows_supported"]

_LANES = 128
#: rows of the sorted list a grid step holds: few steps (a dead one
#: costs under a microsecond), and a block of 1280 float32 columns is
#: 5 MB
_BLOCK_ROWS = 1024
#: rows a turn of the row loop moves: their scalar reads and address
#: arithmetic overlap (1 -> 4 is a fifth of a call's time; 8 and 16 add
#: nothing but operations to trace and lower)
_UNROLL = 4
#: what the token-long block of one call may keep in VMEM, and what the
#: kernels ask Mosaic for; one v5e core has 128 MiB
_RESIDENT_BYTES = 64 * 1024 * 1024
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
#: tokens, order and weights live in SMEM whole, this many words each
_SMEM_WORDS = 64 * 1024


def _column_block(n: int, d: int) -> int:
    """Widest column block (a multiple of 128 that divides d) whose
    token-long float32 block fits; 128 columns do for any n whose pairs
    fit SMEM."""
    lanes = d // _LANES
    return max(d // parts for parts in range(1, lanes + 1)
               if lanes % parts == 0 and (parts == lanes or n * (d // parts)
                                          * 4 <= _RESIDENT_BYTES))


def rows_supported(n: int, rows: int, k: int, d: int, *dtypes):
    """None when the kernels take a layer of ``n`` tokens, ``rows`` list
    rows and width ``d``, else why the XLA forms do; from shapes and
    dtypes alone."""
    for dt in dtypes:
        if dt not in (jnp.float32, jnp.bfloat16):
            return f"dtype {dt} not kernelized (float32 / bfloat16 only)"
    if d % _LANES:
        return f"row width {d} is no multiple of {_LANES} lanes"
    if n % 16 or rows % _LANES:
        return (f"{n} tokens are no multiple of 16 sublanes, or {rows} "
                f"list rows none of a {_LANES}-row block")
    if max(rows, n * k) > _SMEM_WORDS:
        return (f"{max(rows, n * k)} pairs: order and weights do not fit "
                f"SMEM ({_SMEM_WORDS} words each)")
    return None


def _geometry(n, rows, d):
    """(column block, row block, column blocks, row blocks): the row
    block the most 128s up to ``_BLOCK_ROWS`` that divide ``rows``."""
    dc = _column_block(n, d)
    r = max(m for m in range(_LANES, _BLOCK_ROWS + 1, _LANES)
            if rows % m == 0)
    return dc, r, d // dc, rows // r


def _live_block(c, i, held_ref, *_):
    """Index map of a row-blocked operand: block i while it is live, the
    last live block after (nothing is fetched for a dead step)."""
    return lax.min(i, held_ref[1]), c


def _scalars(total, order, k, weights, r):
    """What a kernel reads a number at a time: the held total with the
    last live row block, each list row's token and pair, every pair's
    weight."""
    total = total.astype(jnp.int32)
    held = jnp.stack([total, jnp.maximum((total + r - 1) // r - 1, 0)])
    return (held, order // k, order,
            weights.reshape(-1).astype(jnp.float32))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _token_long(n, dc):
    """A column block of the token-long operand: fetched or written once
    a column block, so one buffer."""
    return pl.BlockSpec((n, dc), lambda c, i, *_: (0, c),
                        pipeline_mode=pl.Buffered(1))


def _live_rows(i, r, total, row):
    """``row(j, i * r + j)`` for the rows j of block i up to the last
    live one, ``_UNROLL`` to a turn of the loop, so the last turn may run
    up to ``_UNROLL - 1`` rows past ``total``."""
    live = lax.min(r, total - i * r)

    def turn(t, carry):
        for u in range(_UNROLL):
            j = t * _UNROLL + u
            row(j, i * r + j)
        return carry
    # lax, not jnp: every jnp call in a kernel is a function to lower
    lax.fori_loop(0, lax.div(live + (_UNROLL - 1), _UNROLL), turn, 0)


def _gather_kernel(held_ref, token_ref, order_ref, w_ref, src_ref, y_ref,
                   out_ref, dots_ref, got_ref, col_ref, *, r):
    i = pl.program_id(1)
    total = held_ref[0]

    @pl.when(i * r < total)
    def _():
        def row(j, at):
            # a row past total in the last turn is some token's: harmless,
            # and masked below
            got_ref[pl.ds(j, 1), :] = src_ref[pl.ds(token_ref[at], 1), :]
            col_ref[pl.ds(j, 1), :] = lax.full(
                (1, _LANES), w_ref[order_ref[at]], jnp.float32)
        _live_rows(i, r, total, row)
        dc = got_ref.shape[1]
        live = i * r + lax.broadcasted_iota(jnp.int32, (r, dc), 0) < total
        got = got_ref[...]
        out = got * col_ref[:, :1]
        out_ref[...] = lax.select(live, out, jnp.zeros_like(out)) \
            .astype(out_ref.dtype)
        # (r,) sums to the (1, r) row the output keeps: through a
        # diagonal, 128 rows at a time (the weights' column is spent)
        dots = lax.reduce_sum(y_ref[...].astype(jnp.float32) * got, (1,))
        col_ref[...] = lax.broadcast_in_dim(dots, col_ref.shape, (0,))
        eye = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0) == \
            lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
        none = jnp.zeros((_LANES, _LANES), jnp.float32)

        def diagonal(s, carry):
            s = pl.multiple_of(s * _LANES, _LANES)
            dots_ref[0, pl.ds(s, _LANES)] = lax.reduce_sum(
                lax.select(eye, col_ref[pl.ds(s, _LANES), :], none), (0,))
            return carry
        lax.fori_loop(0, r // _LANES, diagonal, 0)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def gather_rows(src, order, total, k, weights, y, *, interpret=False):
    """``out[r] = w[r] * src[order[r] // k]`` in ``y``'s dtype and
    ``dots[r] = <y[r], src[order[r] // k]>`` in float32, for ``r <
    total``: ``src`` (N, d) float32, ``order`` (rows,) int32, ``total`` ()
    int32, ``weights`` (N, k) float32, ``y`` (rows, d). Rows of ``out``
    from ``total`` to the end of its row block are zero; later blocks,
    and ``dots`` from ``total`` on, are not written."""
    n, d = src.shape
    rows = order.shape[0]
    dc, r, n_col, n_blk = _geometry(n, rows, d)
    out, dots = pl.pallas_call(
        functools.partial(_gather_kernel, r=r),
        out_shape=[jax.ShapeDtypeStruct((rows, d), y.dtype),
                   jax.ShapeDtypeStruct((n_col, 1, rows), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n_col, n_blk),
            in_specs=[_token_long(n, dc),
                      pl.BlockSpec((r, dc), _live_block)],
            out_specs=[pl.BlockSpec((r, dc), _live_block),
                       pl.BlockSpec((None, 1, r), lambda c, i, *s:
                                    (c, 0, _live_block(c, i, *s)[0]))],
            scratch_shapes=[pltpu.VMEM((r, dc), jnp.float32),
                            pltpu.VMEM((r, _LANES), jnp.float32)]),
        compiler_params=_params(), interpret=interpret,
        name="moe_gather_rows",
    )(*_scalars(total, order, k, weights, r), src.astype(jnp.float32), y)
    return out, dots.sum(axis=0)[0]


def _scatter_kernel(held_ref, token_ref, order_ref, w_ref, src_ref, out_ref,
                    *stage, r):
    i = pl.program_id(1)
    total = held_ref[0]

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i * r < total)
    def _():
        rows_of = src_ref
        if stage:                   # no dynamic row of packed bf16
            rows_of, = stage
            rows_of[...] = src_ref[...].astype(jnp.float32)

        def row(j, at):
            # a select, not a product: past total the list holds anything
            got = rows_of[pl.ds(j, 1), :] * w_ref[order_ref[at]]
            out_ref[pl.ds(token_ref[at], 1), :] += lax.select(
                at < total, got, jnp.zeros_like(got))
        _live_rows(i, r, total, row)


@functools.partial(jax.jit, static_argnames=("k", "n", "interpret", "name"))
def scatter_sum(src, order, total, k, n, weights, *, interpret=False,
                name="moe_scatter_sum"):
    """``out[t] = sum over r < total with order[r] // k == t of w[r] *
    src[r]`` in float32: ``src`` (rows, d), ``order`` (rows,) int32,
    ``total`` () int32, ``weights`` (n, k) float32. Returns (n, d)
    float32. ``name`` is the kernel's in the program and its trace."""
    rows, d = src.shape
    dc, r, n_col, n_blk = _geometry(n, rows, d)
    stage = [] if src.dtype == jnp.float32 else \
        [pltpu.VMEM((r, dc), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_scatter_kernel, r=r),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n_col, n_blk),
            in_specs=[pl.BlockSpec((r, dc), _live_block)],
            out_specs=_token_long(n, dc),
            scratch_shapes=stage),
        compiler_params=_params(), interpret=interpret, name=name,
    )(*_scalars(total, order, k, weights, r), src)
