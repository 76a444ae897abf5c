"""Grouped products of the dropless expert layer (ops/moe.py): the rows of
the sorted list against the matrix of the group that holds each, as
kernels whose grid is as long as the GROUPS and not as the static list.

The list has ``rows`` rows; the pairs the held experts are given stand in
its first ``sizes.sum()`` rows, group after group. :func:`group_metadata`
turns ``sizes`` into the walk every product of a layer shares: one VISIT a
(row tile, group) that meet, in order, so a tile two groups share is
visited twice and a tile past the last group never. The visit count is the
middle (or last) axis of the grid, a number the device computes, and the
tile and group of a visit are scalar reads in the index maps. Three
products, one tiling family, float32 accumulation:

- :func:`gmm`: ``out[r] = lhs[r] @ rhs[g(r)].T`` for ``rhs`` (G, n, k), a
  matrix as the layer keeps it, or with ``transposed`` ``lhs[r] @
  rhs[g(r)]`` for ``rhs`` (G, k, n): the gradient of the rows. Several
  ``(lhs, rhs)`` pairs of one shape are summed into ONE output in the same
  accumulator (the two cotangents of the layer's input, never added over
  the list). A visit computes a whole tile and stores the rows of its own
  group, so the rows past the last group are NOT written: they hold what
  the buffer held.
- :func:`tgmm`: ``out[g] = lhs[rows of g].T @ rhs[rows of g]``, the
  gradient of the matrices: the rows outside the visit's group are masked
  on BOTH sides (what stands past the last group may be anything, NaN
  too), an accumulator a group, stored when the group ends; a group that
  was given no row is visited once and reads exactly zero.

:func:`row_tile` and :func:`tiling` pick ``(tm, tk, tn)`` from the shapes,
the dtype and ``vmem_tile_budget()``: the whole contraction in one tile
where it fits, because then a group's matrix block is fetched ONCE for all
its row tiles (a block whose index repeats is not fetched again), which is
what a layer of small groups pays for; then the widest output tile.

Each product has ONE form and its entry point is jitted, so the sites of
every layer of a model at one shape share one trace and one Mosaic
lowering (ops/kernels/moe_rows.py says what a site costs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_SCOPED_DEFAULT_BYTES, vmem_tile_budget

__all__ = ["gmm", "tgmm", "group_metadata", "row_tile", "tiling",
           "supported"]

_LANES = 128
#: row tiles tried, the first that divides the list. A visit computes a
#: whole tile, so a small one wastes least where a group ends inside it,
#: and a large one takes fewer steps and leaves less VMEM for (tk, tn): a
#: layer's eight products at the SmallThinker cell's shapes take 1.77 ms
#: at 256, 1.85 at 128, 2.35 at 512 (v5e, PR 33)
_ROW_TILES = (256, 128)


def supported(rows: int, k: int, n: int, *dtypes, precision=None):
    """None when the kernels take a product of ``rows`` list rows over
    widths ``k`` and ``n`` at ``precision`` (what
    ``jax.default_matmul_precision`` asks for, None if nothing), else why
    ``lax.ragged_dot`` does; from shapes, dtypes and that setting alone."""
    kinds = {jnp.dtype(dt) for dt in dtypes}
    if len(kinds) != 1:
        return f"operands of {sorted(map(str, kinds))}: one dtype wanted"
    kind = kinds.pop()
    if kind not in (jnp.float32, jnp.bfloat16):
        return f"dtype {kind} not kernelized (float32 / bfloat16 only)"
    if kind == jnp.float32 and _precision(kind, precision) is None:
        return (f"float32 at precision {precision!r}: Mosaic multiplies in "
                "one bf16 pass or at highest")
    if k % _LANES or n % _LANES:
        return f"widths {k}, {n}: no multiple of {_LANES} lanes"
    if rows % _ROW_TILES[-1]:
        return (f"{rows} list rows are no multiple of a "
                f"{_ROW_TILES[-1]}-row tile")
    return None


def row_tile(rows: int) -> int:
    """The row tile of every product of a layer (they share the walk)."""
    return next(t for t in _ROW_TILES if rows % t == 0)


def _widths(x: int):
    """Multiples of 128 that divide x, widest first."""
    return [x // p for p in range(1, x // _LANES + 1)
            if x % p == 0 and (x // p) % _LANES == 0]


def tiling(tm: int, k: int, n: int, dtype, pairs: int = 1,
           resident: str = "rhs"):
    """``(tk, tn)`` of a product whose row tile is ``tm``, by what one
    grid step holds against ``vmem_tile_budget()``: ``pairs`` lhs tiles
    (tm, tk) and matrix tiles (tk, tn), the output tile and its float32
    accumulator. ``resident="rhs"`` (:func:`gmm`): the widest tk first
    (the whole contraction keeps a group's matrix block in VMEM across
    its row tiles), then the widest tn. ``resident="out"`` (:func:`tgmm`,
    where the rows are contracted and (tk, tn) tile the OUTPUT): the
    largest tk * tn, the wider tn of equals."""
    size = jnp.dtype(dtype).itemsize
    budget = vmem_tile_budget()

    def held(tk, tn):
        if resident == "rhs":
            return size * (pairs * (tm * tk + tk * tn) + tm * tn) \
                + 4 * tm * tn
        return size * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    fits = [(tk, tn) for tk in _widths(k) for tn in _widths(n)
            if held(tk, tn) <= budget] or [(_LANES, _LANES)]
    if resident == "rhs":
        return max(fits)
    return max(fits, key=lambda t: (t[0] * t[1], t[1]))


def group_metadata(sizes, rows: int, tm: int):
    """The walk of a layer's products over ``rows`` list rows in tiles of
    ``tm``, from the groups' ``sizes`` (G,) int32: ``(offsets (G + 1,),
    group (L,), tile (L,), visits ())`` int32 with ``L = rows // tm + G -
    1``, the most visits there can be. Visit v < ``visits`` works on row
    tile ``tile[v]`` for group ``group[v]``; a group's visits are
    consecutive and so are a tile's; an empty group has one visit (its
    matrix's gradient must be written). Entries from ``visits`` on repeat
    the last visit."""
    g = sizes.shape[0]
    tiles_m = rows // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    per_group = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 1)
    upto = jnp.cumsum(per_group)
    visits = upto[-1]
    v = jnp.minimum(jnp.arange(tiles_m + g - 1, dtype=jnp.int32),
                    visits - 1)
    group = jnp.searchsorted(upto, v, side="right").astype(jnp.int32)
    tile = first[group] + v - (upto - per_group)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile.astype(jnp.int32), visits.astype(jnp.int32)


def _own_rows(off_ref, group, tile, tm, width):
    """(tm, width) bool: the rows of row tile ``tile`` that are
    ``group``'s."""
    row = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (row >= off_ref[group]) & (row < off_ref[group + 1])


def _precision(dtype, asked):
    """What a kernel's products multiply at: bf16 in one pass whatever is
    asked; float32 as ``asked`` (a ``jax.default_matmul_precision``
    setting, None if nothing) where Mosaic can, one bf16 pass or HIGHEST,
    else None. The entry points take ``asked`` as an argument and never
    read the setting themselves: a layer's backward is traced after the
    ``with`` block its forward ran in has closed."""
    if dtype != jnp.float32 or asked is None:
        return lax.Precision.DEFAULT
    try:
        asked = lax.Precision(asked)
    except ValueError:
        return None
    return asked if asked in (lax.Precision.DEFAULT,
                              lax.Precision.HIGHEST) else None


def _params(*semantics):
    # a step's tiles twice (Mosaic double-buffers what it streams) and
    # the accumulator: the scoped default at the default budget, more
    # only where the budget was raised
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=max(VMEM_SCOPED_DEFAULT_BYTES,
                             4 * vmem_tile_budget()))


def _gmm_kernel(off_ref, group_ref, tile_ref, *refs, pairs, dims, steps,
                precision):
    lhs, rhs, out_ref = refs[:pairs], refs[pairs:2 * pairs], refs[2 * pairs]
    v = pl.program_id(1)

    def products():
        return functools.reduce(lax.add, [lax.dot_general(
            l_ref[...], r_ref[...], dims, precision=precision,
            preferred_element_type=jnp.float32)
            for l_ref, r_ref in zip(lhs, rhs)])

    def store(acc):
        # the tile's other rows keep what an earlier visit stored (the
        # block stays in VMEM while its index repeats), or what the
        # buffer held
        mine = _own_rows(off_ref, group_ref[v], tile_ref[v], *acc.shape)
        out_ref[...] = lax.select(
            mine, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    if steps == 1:
        # the whole contraction in one step: no accumulator, and no
        # branch to lower (a branch is most of what lowering a kernel
        # costs the host, a call site a lowering of the step)
        store(products())
        return
    acc_ref = refs[2 * pairs + 1]
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += products()

    @pl.when(step == steps - 1)
    def _():
        store(acc_ref[...])


@functools.partial(jax.jit, static_argnames=("transposed", "precision",
                                             "interpret"))
def gmm(lhs, rhs, metadata, *, transposed=False, precision=None,
        interpret=False):
    """``out[r] = sum over the pairs of lhs[r] @ rhs[g(r)].T`` (or ``@
    rhs[g(r)]`` with ``transposed``) for the rows r of the groups, in
    lhs's dtype: ``lhs`` (rows, k) or a tuple of such, ``rhs`` (G, n, k)
    (``transposed``: (G, k, n)) or as many, ``metadata`` from
    :func:`group_metadata` at :func:`row_tile`, ``precision`` what
    :func:`supported` took. Rows past the last group are not written."""
    lhs = lhs if isinstance(lhs, (tuple, list)) else (lhs,)
    rhs = rhs if isinstance(rhs, (tuple, list)) else (rhs,)
    offsets, group, tile, visits = metadata
    rows, k = lhs[0].shape
    n = rhs[0].shape[2 if transposed else 1]
    tm = row_tile(rows)
    tk, tn = tiling(tm, k, n, lhs[0].dtype, len(lhs))
    if transposed:
        dims = (((1,), (0,)), ((), ()))
        matrix = pl.BlockSpec((None, tk, tn), lambda j, v, s, o, g, t:
                              (g[v], s, j))
    else:
        dims = (((1,), (1,)), ((), ()))
        matrix = pl.BlockSpec((None, tn, tk), lambda j, v, s, o, g, t:
                              (g[v], j, s))
    steps = k // tk
    return pl.pallas_call(
        functools.partial(_gmm_kernel, pairs=len(lhs), dims=dims,
                          steps=steps,
                          precision=_precision(lhs[0].dtype, precision)),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs[0].dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, visits, steps),
            in_specs=[pl.BlockSpec((tm, tk), lambda j, v, s, o, g, t:
                                   (t[v], s))] * len(lhs)
            + [matrix] * len(rhs),
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, s, o, g, t:
                                   (t[v], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if steps > 1 else []),
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret, name="moe_gmm_t" if transposed else "moe_gmm",
    )(offsets, group, tile, *lhs, *rhs)


def _tgmm_kernel(off_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                 acc_ref, *, tm, precision):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ref[v]

    @pl.when((v == 0) | (group_ref[lax.max(v - 1, 0)] != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def own(ref):
        # a select on both sides, not a product: past the last group the
        # list holds anything. (An empty group's one visit adds zeros: a
        # branch around it would cost more set-up than it saves.)
        x = ref[...].astype(jnp.float32)
        mine = _own_rows(off_ref, group, tile_ref[v], tm, x.shape[1])
        return lax.select(mine, x, jnp.zeros_like(x)).astype(ref.dtype)
    acc_ref[...] += lax.dot_general(
        own(lhs_ref), own(rhs_ref), (((0,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)

    @pl.when((v == last) | (group_ref[lax.min(v + 1, last)] != group))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("precision", "interpret"))
def tgmm(lhs, rhs, metadata, *, precision=None, interpret=False):
    """``out[g] = lhs[rows of g].T @ rhs[rows of g]`` in lhs's dtype:
    ``lhs`` (rows, p), ``rhs`` (rows, q), ``metadata`` from
    :func:`group_metadata` at :func:`row_tile`, ``precision`` what
    :func:`supported` took. Returns (G, p, q); a group without a row reads
    exactly zero."""
    offsets, group, tile, visits = metadata
    rows, p = lhs.shape
    q = rhs.shape[1]
    tm = row_tile(rows)
    tp, tq = tiling(tm, p, q, lhs.dtype, resident="out")
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm,
                          precision=_precision(lhs.dtype, precision)),
        out_shape=jax.ShapeDtypeStruct((offsets.shape[0] - 1, p, q),
                                       lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(p // tp, q // tq, visits),
            in_specs=[pl.BlockSpec((tm, tp), lambda i, j, v, o, g, t:
                                   (t[v], i)),
                      pl.BlockSpec((tm, tq), lambda i, j, v, o, g, t:
                                   (t[v], j))],
            out_specs=pl.BlockSpec((None, tp, tq), lambda i, j, v, o, g, t:
                                   (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tp, tq), jnp.float32)]),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="moe_tgmm",
    )(offsets, group, tile, lhs, rhs)
