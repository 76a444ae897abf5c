"""Attention ops: Pallas flash attention + ring attention (context parallel).

Reference parity note: the reference (Apache MXNet 2.0-dev) ships NO fused
attention and NO sequence/context parallelism (SURVEY.md §2.3, §5 "long-
context: none in the reference") — attention lived in gluon-nlp as unfused
batch_dot+softmax. This module is the TPU-idiomatic superset the build plan
(SURVEY.md §7 stage 10) calls for:

- ``flash_attention``: O(S) memory online-softmax attention. On TPU both
  the forward AND the backward are Pallas kernels (FlashAttention-2 style:
  the forward saves a per-row log-sum-exp residual; the backward rebuilds
  each softmax block from it once and makes dq, dk and dv there, or,
  where a sequence is too long for its buffers to stay in VMEM, in a dq
  and a dk/dv kernel — no S×S residual is ever materialized). Elsewhere a
  blockwise ``lax.scan`` XLA implementation with identical math and a
  recompute-based backward.
- ``flash_attention_bsh``: the same op on (batch, seq, heads*head_dim),
  what a projection emits and the output projection eats. The kernels
  address those arrays where they lie, at the head's own width (``_Tiles``:
  128 // D heads share one 128-lane block and are told apart by lane
  masks), so no transpose, pad or slice surrounds the calls;
  ``MultiHeadAttention`` calls this form.
- ``ring_attention``: context parallelism over a mesh axis. Each device
  holds a sequence shard of Q/K/V; K/V blocks rotate around the ring via
  ``lax.ppermute`` (ICI neighbor exchange) while online-softmax accumulators
  merge partial results — sequence length scales with the number of chips.

Math convention: inputs are (batch, heads, seq, head_dim) unless a
function says otherwise; softmax scale defaults to head_dim**-0.5; masking uses a large negative finite value so
fully-masked rows stay NaN-free through exp/renormalization.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

from ..base import MXNetError
from .kernels import VMEM_BYTES_PER_CORE

__all__ = ["flash_attention", "flash_attention_bsh", "rope",
           "paged_decode_attention", "ring_attention",
           "ring_attention_sharded", "attention_reference"]

_NEG_INF = -1e30  # finite mask value: keeps exp() NaN-free for masked rows


def _window_of(window, causal: bool) -> Optional[int]:
    """The sliding window as every path takes it: None, or a positive
    number of keys a query sees, itself included. A window is causal by
    definition here (key j is seen by query i iff 0 <= i - j < window)."""
    if window is None:
        return None
    if not causal:
        raise MXNetError("attention: window needs causal=True")
    if int(window) < 1:
        raise MXNetError(f"attention: window {window} < 1")
    return int(window)


def _kv_group(num_heads: int, num_kv_heads: int) -> int:
    """Query heads per key/value head (grouped-query attention): query
    head n reads key/value head n // group."""
    if num_kv_heads < 1 or num_heads % num_kv_heads:
        raise MXNetError(
            f"attention: query heads {num_heads} not a multiple of "
            f"K/V heads {num_kv_heads} (GQA needs integer groups)")
    return num_heads // num_kv_heads


def attention_reference(q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None, mask=None,
                        window: Optional[int] = None):
    """Unfused softmax(QK^T)V — the numeric oracle for tests and the
    arbitrary-additive-mask path (XLA fuses the softmax). ``mask`` is an
    additive float mask broadcastable to (B, H, Sq, Sk). ``window`` hides
    key j from query i unless 0 <= i - j < window. k and v may hold fewer
    heads than q (query head n reads head n // group), and v's heads
    another width than q's and k's (the result's). Convention shared
    by every attention path in this module: a query row with NO valid key
    outputs exactly zero (the flash-kernel convention)."""
    window = _window_of(window, causal)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape[1] != q.shape[1]:
        group = _kv_group(q.shape[1], k.shape[1])
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if mask is not None:
        s = s + mask.astype(jnp.float32)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        tri = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            tri = tri & ~jnp.tril(jnp.ones((sq, sk), bool),
                                  k=sk - sq - window)
        s = jnp.where(tri, s, _NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    out = out / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.where(m > _NEG_INF / 2, out, 0.0)  # fully-masked rows → 0
    return out.astype(q.dtype)


def rope(x, num_heads: int, theta: float = 10000.0, lanes=None,
         interleave: bool = False):
    """Rotary position embedding of (B, S, H*D) queries or keys, in
    front of the attention call. ``lanes = (first, count)`` is the slice
    of each head's D lanes that is turned (default: all of them; the
    rest pass through untouched). Position p turns a pair of the slice's
    R lanes by the angle p * theta^(-2i/R): the pair (x[i], x[i + R/2])
    ("rotate-half", the default), or with ``interleave`` the neighbours
    (x[2i], x[2i + 1]). Angles and the turn in float32, the result in
    the input's dtype. Either pairing keeps scores relative: <rope(q, p),
    rope(k, p')> depends on p - p' alone."""
    b, s, hd = x.shape
    d = hd // num_heads
    first, r = (0, d) if lanes is None else lanes
    if hd % num_heads or r % 2 or first < 0 or first + r > d:
        raise MXNetError(f"rope: width {hd} over {num_heads} heads, lanes "
                         f"{lanes}: no even slice of a head's width")
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]          # (1, S, 1, R/2)
    sin = jnp.sin(angle)[None, :, None, :]
    xf = x.astype(jnp.float32).reshape(b, s, num_heads, d)
    xr = xf if lanes is None else xf[..., first:first + r]
    if interleave:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        -1).reshape(b, s, num_heads, r)
    else:
        x1, x2 = xr[..., :r // 2], xr[..., r // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              -1)
    if lanes is not None:
        out = jnp.concatenate([xf[..., :first], out, xf[..., first + r:]],
                              -1)
    return out.reshape(b, s, hd).astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise XLA implementation (fallback forward + backward recompute target)
# ---------------------------------------------------------------------------

def _attention_xla(q, k, v, causal: bool, sm_scale: float,
                   block_k: int = 512, valid_length=None, window=None):
    """Online-softmax attention scanning over K/V blocks: O(Sq·block_k)
    live memory instead of O(Sq·Sk). Pure lax.scan — XLA pipelines the
    blocks and keeps the matmuls on the MXU. ``valid_length`` is an
    optional (B,) per-sample key length (padding mask); ``window`` the
    sliding window. k, v may hold fewer heads than q: the query heads of
    a group are folded into the query rows, so nothing is repeated. v's
    heads may have another width than q's and k's; the result has v's."""
    orig_dtype = q.dtype
    b, hq, sq, d = q.shape
    h, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = _kv_group(hq, h)
    if group > 1:       # (B, Hkv, group * Sq, D): row r is query r % Sq
        q = q.reshape(b, h, group * sq, d)
    block_k = min(block_k, sk)
    nk = -(-sk // block_k)
    pad = nk * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qf = q.astype(jnp.float32) * sm_scale
    kb = jnp.moveaxis(k.reshape(b, h, nk, block_k, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, nk, block_k, dv), 2, 0)
    # align causal diagonal to the end
    q_pos = jnp.tile(jnp.arange(sq) + (sk - sq), group)
    sq = group * sq

    def body(carry, inp):
        acc, m, l = carry
        kblk, vblk, ki = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32))
        k_pos = ki * block_k + jnp.arange(block_k)
        valid = (k_pos < sk)[None, None, None, :]
        if causal:
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            valid = valid & (k_pos[None, :] > q_pos[:, None] - window)
        if valid_length is not None:
            valid = valid & (k_pos[None, None, None, :]
                             < valid_length[:, None, None, None])
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = lax.scan(body, (acc0, m0, l0),
                              (kb, vb, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where((m > _NEG_INF / 2)[..., None], out, 0.0)  # no-key rows
    return out.reshape(b, hq, sq // group, dv).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernels: how they address the caller's arrays
# ---------------------------------------------------------------------------

#: the layouts ``_Tiles`` knows, as ``mx_flash_attention_layout_total``
#: labels them
FLASH_LAYOUTS = ("packed", "unpadded", "padded")
#: the blocks every kernel starts from; ``_tiles`` clamps them to the
#: sequence
_BLOCK_Q = _BLOCK_K = 512


def _default_block(head_dim: int) -> int:
    """The q and k block a call starts from when it names none. Heads
    that share a lane tile (D < 128) take 512: a program then works on
    128 // D heads' (bq, bk) score tiles. A head that fills a tile alone
    has one such tile a grid step, and at 512 the step's fixed cost is a
    large part of it: 1024 (v5e, bf16, 1 x 8192 causal, 28 q / 4 kv heads
    of 128, ms a call at 512 -> 1024, PR 28: forward 10.51 -> 5.21,
    backward 17.86 -> 15.68; behind a 4096 window 9.14 -> 4.83 and
    16.71 -> 14.59; on PR 29's grids of live blocks 9.16 -> 4.57 and
    14.04 -> 13.61, behind the window 7.36 -> 3.96 and 11.76 -> 12.11;
    the fused multi-block backward at 1024 on those grids, PR 38: 9.43,
    behind the window 8.40, where the dq and dk/dv kernels take 13.61 and
    12.11)."""
    return 1024 if head_dim >= 128 else _BLOCK_Q
#: narrowest head the kernels take without padding
_MIN_LANES = 8
#: most heads of two widths (q/k beside v) that share one packed block
_MAX_SHARE = 8


class _Tiles(NamedTuple):
    """How the three flash kernels address q, k, v, o and their
    gradients: as ``(rows, seq, col_tiles * width)`` arrays cut into
    ``(g, block, width)`` blocks at ``(row block, seq block, column
    tile)``, each block holding ``heads`` heads side by side on ``width
    // heads`` lanes apiece. The per-row statistics (log-sum-exp, delta) are
    ``(rows, col_tiles * heads, seq, 8)`` f32 (8 lanes: the narrowest
    block the TPU tiling takes for a row vector).

    q, k and their gradients have heads ``head_dim`` wide in blocks of
    ``width``; v, o and their gradients heads ``head_dim_v`` wide in
    blocks of ``width_v``. Most models have one width for both; latent
    attention (MLA) has keys of 192 = 128 + 64 rotary lanes beside
    values of 128. A block holds the same ``heads`` heads on both sides.

    - ``packed``: the projections' own (B, S, H*D). rows = B, width =
      128 lanes holding 128 // D heads (or D lanes and one head when D
      is a multiple of 128); nothing is moved around the call. With two
      widths a block holds the fewest heads that fill whole lane tiles
      on both sides (two heads of 192 on 384 lanes beside their 256
      lanes of values); the kernels reach a head through the lane tiles
      that hold it (``_Lanes``), so a width of 1.5 tiles is read where
      it lies, contracted over two tiles with the neighbour's half
      masked.
    - ``unpadded``: (B, H, S, D) folded to (B*H, S, D), one column tile
      of width D (a block's last dimension may be the array's own).
    - ``padded``: the same fold with D zero-padded to a multiple of 128
      in HBM: head widths that neither divide 128 nor are a multiple
      (80, 96). Zero lanes cancel in QK^T and are sliced off after.

    With fewer key/value heads than query heads (``group`` query heads
    read one of them) k, v and their gradients are the narrower arrays,
    never repeated in HBM: the block a query block meets is found by
    dividing its column tile (``packed``, which then takes one head a
    block) or its row (the folded layouts) by ``group``. ``window`` is
    the sliding window, None for none. Which (q block, k block) pairs
    the multi-block kernels visit is ``_walk``'s to say, from these
    fields: the grids hold the blocks with a valid pair alone.
    """
    layout: str
    batch: int
    num_heads: int
    head_dim: int
    seq_q: int
    seq_k: int
    rows: int
    col_tiles: int
    width: int
    heads: int          # heads side by side in one block
    group: int          # query heads per key/value head
    window: Optional[int]
    block_q: int
    block_k: int
    sqp: int
    skp: int
    nq: int
    nk: int
    head_dim_v: int
    width_v: int

    @property
    def reason(self) -> str:
        """The layout as the dispatch decision words it."""
        values = "" if self.head_dim_v == self.head_dim else \
            f" (values D={self.head_dim_v} on {self.width_v})"
        if self.layout == "packed":
            return (f"packed: {self.heads} head{'s' * (self.heads > 1)} "
                    f"per {self.width} lanes, no pad" + values)
        if self.layout == "unpadded":
            return (f"unpadded: D={self.head_dim}, one head per block"
                    + values)
        return f"padded: D={self.head_dim} → {self.width}" + values

    @property
    def lanes(self) -> "_Lanes":
        """The heads of a q or k block on its lanes."""
        return _Lanes(self.heads, self.width)

    @property
    def lanes_v(self) -> "_Lanes":
        """The heads of a v or o block on its lanes."""
        return _Lanes(self.heads, self.width_v)

    @property
    def row_group(self) -> int:
        """Query rows per key/value row (the folded layouts)."""
        return 1 if self.layout == "packed" else self.group

    @property
    def col_group(self) -> int:
        """Query column tiles per key/value column tile (``packed``)."""
        return self.group if self.layout == "packed" else 1


def _fits(d: int) -> bool:
    """A head width a block takes as it is: whole lane tiles, or a
    share of one."""
    return d % 128 == 0 or (128 % d == 0 and d >= _MIN_LANES)


def _tiles(q_shape, k_shape, block_q: int, block_k: int,
           num_heads: Optional[int], window: Optional[int] = None,
           v_shape=None) -> _Tiles:
    """The tiling for one call, from the shapes alone. ``num_heads``
    None: q, k, v are (B, H, S, D); else they are (B, S, H*D). The
    key/value head count is read off k's shape, the width of the values'
    heads off ``v_shape`` (None: the keys')."""
    if num_heads is None:
        b, h, sq, d = q_shape
        hkv, sk = k_shape[1], k_shape[2]
        dv = d if v_shape is None else v_shape[3]
    else:
        b, sq, hd = q_shape
        h, d, sk = num_heads, hd // num_heads, k_shape[1]
        hkv = k_shape[2] // d
        dv = d if v_shape is None else v_shape[2] // hkv
    group = _kv_group(h, hkv)
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    sqp = -(-sq // block_q) * block_q
    skp = -(-sk // block_k) * block_k
    # the fewest heads that fill whole lane tiles at both widths
    share = max(128 // math.gcd(d, 128), 128 // math.gcd(dv, 128))
    # heads that share a lane tile share its key/value tile too: with
    # groups, packed is for heads that fill a tile alone
    if dv == d and num_heads is not None and _fits(d) \
            and (h * d) % 128 == 0 and (group == 1 or d % 128 == 0):
        width = max(d, 128)
        layout, rows, cols, heads = "packed", b, h * d // width, width // d
    elif dv != d and num_heads is not None and h % share == 0 \
            and share <= _MAX_SHARE and (group == 1 or share == 1):
        layout, rows, cols, heads = "packed", b, h // share, share
        width = share * d
    elif _fits(d) and _fits(dv):
        layout, rows, cols, width, heads = "unpadded", b * h, 1, d, 1
    else:
        layout, rows, cols, width, heads = \
            "padded", b * h, 1, -(-d // 128) * 128, 1
    width_v = width if dv == d else heads * dv if layout == "packed" \
        else dv if layout == "unpadded" else -(-dv // 128) * 128
    return _Tiles(layout, b, h, d, sq, sk, rows, cols, width, heads, group,
                  window, block_q, block_k, sqp, skp, sqp // block_q,
                  skp // block_k, dv, width_v)


def _split_heads(x, num_heads: int):
    """(B, S, H*D) -> (B, H, S, D)."""
    b, s, hd = x.shape
    return x.reshape(b, s, num_heads, hd // num_heads).transpose(0, 2, 1, 3)


def _merge_heads(x):
    """(B, H, S, D) -> (B, S, H*D)."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _pad_for_blocks(q, k, v, block_q, block_k, num_heads=None,
                    window=None):
    """Shared fwd/bwd tiling preamble: clamp block sizes, choose the
    layout (:class:`_Tiles`) and bring q, k, v into it. Nothing is
    padded that the kernels can take as it is: the sequence only when
    it is not a multiple of its block, the head dimension only in the
    ``padded`` layout. The backward's exp(s - lse) recompute is only
    correct when it uses EXACTLY these conventions — keep this the
    single source. Returns ``(qt, kt, vt, to_tiles, from_tiles,
    tiles)``; ``to_tiles(x, seq_to)`` lays any further (…q- or k-shaped)
    array out the same way and ``from_tiles(y, seq)`` is its inverse;
    both take ``values=True`` for an array of v's kind (v, o, do, dv)."""
    default = _default_block(q.shape[-1] // (num_heads or 1))
    t = _tiles(q.shape, k.shape, block_q or default, block_k or default,
               num_heads, window, v.shape)
    b = t.batch

    def widths(values):
        """(a head's width, its block's) of an array of q's or v's kind."""
        return (t.head_dim_v, t.width_v) if values else \
            (t.head_dim, t.width)

    def to_tiles(x, seq_to, values=False):
        if t.layout == "packed":
            pad = seq_to - x.shape[1]
            return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
        d, width = widths(values)
        if num_heads is not None:
            x = _split_heads(x, x.shape[-1] // d)
        pad_s, pad_d = seq_to - x.shape[2], width - d
        if pad_s or pad_d:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_s), (0, pad_d)))
        return x.reshape(b * x.shape[1], seq_to, width)

    def from_tiles(y, seq, values=False):
        if t.layout == "packed":
            return y if y.shape[1] == seq else y[:, :seq]
        d, width = widths(values)
        y = y.reshape(b, y.shape[0] // b, y.shape[1], width)
        if y.shape[2] != seq or width != d:
            y = y[:, :, :seq, :d]
        return y if num_heads is None else _merge_heads(y)

    return (to_tiles(q, t.sqp), to_tiles(k, t.skp),
            to_tiles(v, t.skp, True), to_tiles, from_tiles, t)


def _head_group(bh: int, block_q: int, block_k: int,
                n_tiles: int = 1, heads_per_block: int = 1,
                row_group: int = 1) -> int:
    """Rows of the kernels' grid per Pallas program; a row is one block
    of ``heads_per_block`` heads (one head of one sample in the
    (B*H, S, D) layouts, the 128 // D heads that share a lane tile in
    the packed one), so a program holds g x heads_per_block heads.
    Per-program fixed overhead (~2-3 µs: launch + DMA setup) dominates
    short-seq attention when the grid has one program per (batch, head)
    — 384 programs for BERT-base bs=32. Batch up to 8 heads per program,
    bounded by the CONCURRENT (heads, bq, bk) f32 tiles' VMEM footprint
    (~16 MiB/core on v5e; the shared tile budget lives in ops/kernels —
    the rnn_scan timestep-block sizer accounts against the same
    number). ``n_tiles`` is how many such score-shaped tiles the kernel
    holds live at once: 1 for the forward (s; p overwrites it), 4 for
    every form of the backward (s, p, dp, ds) — budgeting the backward as
    a single tile oversizes G and fails Mosaic lowering at large
    blocks. The fused multi-block backward keeps, besides, g rows' whole
    sequences of dq, dk and dv in VMEM (``_resident_bytes``), which
    ``_flash_bwd_pallas`` weighs against ``_VMEM_ASK_BYTES`` with the G
    this gives. ``row_group`` > 1 (fewer key/value rows than query
    rows): one row a program, so that a program's rows read one
    key/value row."""
    from .kernels import vmem_tile_budget
    if row_group > 1:
        return 1
    budget = vmem_tile_budget()
    g = 1
    while (g * 2 * heads_per_block <= 8 and bh % (g * 2) == 0
           and g * 2 * heads_per_block * block_q * block_k * 4 * n_tiles
           <= budget):
        g *= 2
    return g


def _lane_tiles(width: int) -> int:
    """Lanes a block ``width`` wide fills in VMEM: whole tiles of 128."""
    return -(-width // 128) * 128


def _vmem_limit(g: int, t: _Tiles, itemsize: int, n_blocks: int,
                n_acc: int, n_tiles: int, n_rows: int,
                resident: int = 0) -> int:
    """``vmem_limit_bytes`` for one flash kernel, counted from what it
    keeps in VMEM: ``n_blocks`` (g, block, width) operand/result blocks
    in the input dtype (Pallas double-buffers each; a block narrower
    than 128 lanes still fills whole lane tiles there), ``n_acc`` f32
    values of that shape (scratch accumulators, the per-head statistics
    and partial results), ``n_tiles`` live f32 (g, bq, bk) score tiles
    for each head of the block (Mosaic gives every head of the unrolled
    loop its own), ``n_rows`` (g, heads, bq, 8 -> 128 lanes) f32
    statistic blocks and ``resident`` bytes of whole-sequence buffers
    (``_resident_bytes``), plus a quarter for Mosaic's own temporaries.
    ``_head_group`` budgets
    the score tiles only; at f32 the operand blocks alone double, and
    the BERT-shape forward asked for 16.42 MiB of the 16 MiB a kernel
    gets without a limit. Never below that default."""
    from .kernels import VMEM_SCOPED_DEFAULT_BYTES
    block = g * max(t.block_q, t.block_k) \
        * _lane_tiles(max(t.width, t.width_v))
    need = (2 * n_blocks * block * itemsize + n_acc * block * 4
            + n_tiles * g * t.heads * t.block_q * t.block_k * 4
            + 2 * n_rows * g * t.heads * t.block_q * 128 * 4 + resident)
    return max(VMEM_SCOPED_DEFAULT_BYTES, need + need // 4)


#: the most VMEM a flash kernel asks Mosaic for: the fused multi-block
#: backward is taken where its ``_vmem_limit`` is no more, the dq and
#: dk/dv kernels elsewhere
_VMEM_ASK_BYTES = VMEM_BYTES_PER_CORE


def _resident_bytes(g: int, t: _Tiles, itemsize: int) -> int:
    """What the fused multi-block backward keeps for a whole sequence:
    one query head's dq and the key/value head's dk, dv, each as an f32
    scratch accumulator and as a (double-buffered) output block in the
    input dtype."""
    w, wv = _lane_tiles(t.width), _lane_tiles(t.width_v)
    return g * (t.sqp * w + t.skp * (w + wv)) * (4 + 2 * itemsize)


def _head_masks(shape, heads: int):
    """One lane mask per head of a (g, rows, width) block: True on the
    head's own width // heads lanes. ``(None,)`` when one head fills
    the block."""
    if heads == 1:
        return (None,)
    d = shape[2] // heads
    lane = lax.broadcasted_iota(jnp.int32, shape, 2)
    return tuple((lane >= i * d) & (lane < (i + 1) * d)
                 for i in range(heads))


def _only(x, mask):
    """``x`` with the other heads' lanes zeroed: contracting it over the
    block's whole width then contracts over this head alone."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _by_head(parts, masks):
    """One (g, rows, width) value from one per head, each taken on its
    own lanes (the parts are per-row scalars (g, rows, 1), or products
    over the block's whole width of which only the head's lanes are
    this head's)."""
    out = parts[0]
    for part, mask in zip(parts[1:], masks[1:]):
        out = jnp.where(mask, part, out)
    return out


class _Lanes(NamedTuple):
    """Where the ``heads`` heads of one (g, rows, width) block lie: side
    by side, ``width // heads`` lanes apiece. A kernel reaches head i
    through its ``span``, the whole lane tiles that hold it: the block
    itself when that is one head or one tile (every width that divides
    128 or is a multiple of it), else a 128-aligned slice of it in which
    a neighbour's lanes are masked (a head of 192 lanes: two tiles, half
    of one the neighbour's)."""
    heads: int
    width: int

    @property
    def whole(self) -> bool:
        """Every head's span is the block itself."""
        return self.heads == 1 or self.width <= 128

    def span(self, i: int) -> tuple:
        if self.whole:
            return 0, self.width
        d = self.width // self.heads
        return i * d // 128 * 128, -(-(i + 1) * d // 128) * 128

    def cut(self, x, i: int):
        """Head i's span of a block ``x``."""
        lo, hi = self.span(i)
        return x if (lo, hi) == (0, self.width) else x[:, :, lo:hi]

    def masks(self, shape) -> tuple:
        """One mask per head over its span of a block of ``shape``: True
        on the head's own lanes, None where the span holds no other."""
        if self.whole:
            return _head_masks(shape, self.heads)
        d, out = self.width // self.heads, []
        for i in range(self.heads):
            lo, hi = self.span(i)
            if (lo, hi) == (i * d, (i + 1) * d):
                out.append(None)
                continue
            lane = lo + lax.broadcasted_iota(
                jnp.int32, (shape[0], shape[1], hi - lo), 2)
            out.append((lane >= i * d) & (lane < (i + 1) * d))
        return tuple(out)

    def join(self, parts, masks):
        """One (g, rows, width) value from one per head, each over its
        span (or a per-row scalar (g, rows, 1)), each taken on the
        head's own lanes; ``masks`` as ``masks()`` gave them."""
        if self.whole:
            return _by_head(parts, masks)
        # lane tile by lane tile: the part of the one head that fills it,
        # or of the heads that share it, each from its first lane on
        d, tiles = self.width // self.heads, []
        for lo_t in range(0, self.width, 128):
            out = None
            for i, part in enumerate(parts):
                if (i + 1) * d <= lo_t or i * d >= lo_t + 128:
                    continue
                at = lo_t - self.span(i)[0]
                if part.shape[2] != 1:
                    part = part[:, :, at:at + 128]
                if out is None:
                    out = jnp.broadcast_to(part, part.shape[:2] + (128,))
                    continue
                lane = lo_t + lax.broadcasted_iota(jnp.int32, out.shape, 2)
                out = jnp.where(lane >= i * d, part, out)
            tiles.append(out)
        return jnp.concatenate(tiles, axis=2)


def _causal_block_skip(qi, ki, block_q, block_k, seq_q, seq_k,
                       window=None):
    """True iff block (qi, ki) holds ANY valid causal entry — the ONE
    predicate by which the forward and both backward kernels leave a
    block out of their grids (a divergence here would desynchronize
    forward and backward masking). With a ``window`` the block's last
    key must also be seen by its first query. Exact for padded tails
    too: a block's first row and first key are always real."""
    first_q = qi * block_q + (seq_k - seq_q)
    run = ki * block_k <= first_q + block_q - 1
    if window is not None:
        run = run & (ki * block_k + block_k - 1 > first_q - window)
    return run


#: ``edge`` bits of a grid step: the first / the last of its row of blocks
_FIRST, _LAST = 1, 2


def _live_blocks(t: _Tiles, causal: bool):
    """(nq, nk) bool, on the host: the blocks that hold a valid pair
    under the call's mask. Without ``causal`` every one does."""
    live = True
    if causal:
        live = _causal_block_skip(onp.arange(t.nq)[:, None],
                                  onp.arange(t.nk)[None, :], t.block_q,
                                  t.block_k, t.seq_q, t.seq_k, t.window)
    return onp.broadcast_to(live, (t.nq, t.nk))


def _live_steps(live, group: int = 1):
    """The kernels' innermost grid axis: one step for each live block,
    and none for any other. ``live`` is (rows, blocks a row) bool, a
    row being what a kernel accumulates over: a q block's k blocks
    (forward, dq: ``_live_blocks``), or a k block's q blocks (dk/dv:
    its transpose) walked once for each of the ``group`` query heads
    that read the key/value head, head after head. Returns int32
    vectors ``(row, head, block, edge)``, row-major and ascending
    within a row as the dense grid ran them; ``edge`` has ``_FIRST`` on
    a row's first step (zero the scratch) and ``_LAST`` on its last
    (write the block out). A row with no live block keeps one step, on
    block 0: every pair of it is masked, so the row's result is written
    as zeros (the module's convention)."""
    steps = []
    for row, blocks in enumerate(live):
        walk = [(row, head, int(b), 0) for head in range(group)
                for b in onp.flatnonzero(blocks)] or [(row, 0, 0, 0)]
        walk[0] = walk[0][:3] + (_FIRST,)
        walk[-1] = walk[-1][:3] + (walk[-1][3] | _LAST,)
        steps += walk
    return tuple(onp.asarray(col, onp.int32) for col in zip(*steps))


class _Walk(NamedTuple):
    """How a multi-block kernel's grid walks its blocks, after the (row,
    column tile) axes: ``axes`` the grid's further axes, ``tables`` the
    scalar-prefetch operands the index maps and the kernel read, and
    ``row``, ``head``, ``block``, each of which turns what an index map
    is handed after (row, column tile) into the step's row of blocks,
    its query head of the group, or its block."""
    axes: tuple
    tables: tuple
    row: Callable
    head: Callable
    block: Callable


def _walk(live, group: int = 1) -> _Walk:
    """The walk over ``live`` (see ``_live_steps``), chosen by what the
    mask leaves: where a block holds no pair, one axis over the table of
    live steps; where every block is live (no mask, or one that cuts no
    whole block) the dense (rows, group * blocks) grid in the table's
    own order, which needs none: a step then reads no index from SMEM
    (v5e, 28 heads of 128 at 1 x 8192 unmasked, the table against the
    dense grid, PR 29: forward +2 %, backward +2 to +7 %)."""
    n_rows, n_blocks = live.shape
    if not live.all():
        tables = _live_steps(live, group)
        return _Walk((len(tables[0]),), tables,
                     lambda s, rows, head, blocks, edge: rows[s],
                     lambda s, rows, head, blocks, edge: head[s],
                     lambda s, rows, head, blocks, edge: blocks[s])
    if group == 1:
        return _Walk((n_rows, n_blocks), (), lambda row, s: row,
                     lambda row, s: 0, lambda row, s: s)
    return _Walk((n_rows, group * n_blocks), (), lambda row, s: row,
                 lambda row, s: s // n_blocks, lambda row, s: s % n_blocks)


def _pair_walk(live, group: int) -> _Walk:
    """The fused backward's walk: for each query head of the ``group``
    in turn, the (k block, q block) pairs of ``live`` (nq, nk) that hold
    a valid pair, k-major (``_walk`` over one row of the flattened pairs a
    head, so ``_grid_step`` in the kernel reads (head, pair, its head's
    first, its head's last)). ``row`` gives the step's k block, ``head``
    its query head, ``block`` its q block."""
    n_q = live.shape[0]
    walk = _walk(onp.tile(live.T.reshape(1, -1), (group, 1)))
    return _Walk(walk.axes, walk.tables,
                 lambda *at: walk.block(*at) // n_q, walk.row,
                 lambda *at: walk.block(*at) % n_q)


def _grid_step(tables, blocks_a_head=None):
    """Inside a kernel, this grid step as (row of blocks, block, first
    of its row, last of its row), from ``_walk``'s tables or, on the
    dense grid, from the grid's own indices (``blocks_a_head``: the
    row's blocks when the inner axis walks them once a head)."""
    from jax.experimental import pallas as pl
    if tables:
        rows, _, blocks, edge = tables
        s = pl.program_id(2)
        return (rows[s], blocks[s], (edge[s] & _FIRST) != 0,
                (edge[s] & _LAST) != 0)
    row, s = pl.program_id(2), pl.program_id(3)
    block = s if blocks_a_head is None else s % blocks_a_head
    return row, block, s == 0, s == pl.num_programs(3) - 1


def _count_grid_steps(grid) -> None:
    """``mx_flash_attention_grid_steps_total{kind}`` for one traced
    Pallas flash call. Every step of ``_walk``'s grids (and of the
    one-block backward's) computes, so all of ``grid`` counts as
    ``live``; ``dead``, the steps whose body is skipped, as the dense
    grids' were above the diagonal and behind the window, gets its 0 so
    that the series is there to read."""
    from .kernels import count_traced
    count_traced("FLASH_ATTENTION_GRID_STEPS", "kind", "live",
                 math.prod(grid))
    count_traced("FLASH_ATTENTION_GRID_STEPS", "kind", "dead", 0)


def _count_bwd_form(form: str) -> None:
    """``mx_flash_attention_bwd_total{form}`` for one traced Pallas flash
    backward: ``one_block``, ``fused`` or ``split``
    (``_flash_bwd_pallas``)."""
    from .kernels import count_traced
    count_traced("FLASH_ATTENTION_BWD", "form", form)


def _semantics(grid) -> tuple:
    """``dimension_semantics`` of a flash kernel's grid: its last axis
    accumulates, the others are independent."""
    return ("parallel",) * (len(grid) - 1) + ("arbitrary",)


def _q_index(t: _Tiles, walk: _Walk):
    """Index map of a q-side block on a grid over key/value rows and
    column tiles (the dk/dv kernel's, the fused backward's): the q block
    ``walk.block`` names, of the query head of the group ``walk.head``
    names."""
    rg, cg = t.row_group, t.col_group
    return lambda r, c, *at: (
        r * rg + (walk.head(*at) if rg > 1 else 0), walk.block(*at),
        c * cg + (walk.head(*at) if cg > 1 else 0))


def _kv_index(t: _Tiles, walk: _Walk):
    """Index map of a key/value block on the forward's and the dq
    kernel's grid (row, column tile, then ``walk``'s axes): the query
    block's own row and column tile, or with fewer key/value heads the
    ones its group reads."""
    if t.group == 1:
        return lambda r, c, *at: (r, walk.block(*at), c)
    rg, cg = t.row_group, t.col_group
    return lambda r, c, *at: (r // rg, walk.block(*at), c // cg)


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------

def _flash_kernel(*refs, sm_scale, causal, block_q, block_k, seq_q, seq_k,
                  need_mask, lanes, lanes_v, window=None):
    from jax.experimental import pallas as pl
    *tables, q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    # the grid walks the blocks that hold a pair alone (_walk): every
    # step computes
    qi, ki, first, last = _grid_step(tables)

    @pl.when(first)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # dots take the INPUT dtype (bf16 under AMP) with f32 accumulation —
    # an astype(f32) here would push the MXU onto its ~6x slower f32
    # passes
    q = q_ref[...]                                # (G, block_q, width)
    k = k_ref[...]                                # (G, block_k, width)
    v = v_ref[...]                                # (G, block_k, width_v)
    heads = lanes.heads
    masks = lanes.masks(q.shape)
    # one width for q, k and v: the same masks pick a head's lanes of o
    omasks = masks if lanes_v == lanes else lanes_v.masks(acc_s.shape)
    valid = None
    if need_mask or causal:
        # masking is real VPU work on a (bq, bk) tile — emitted only
        # when there is padding to hide or a causal wedge to cut
        k_pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < seq_k
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + (seq_k - seq_q)
            valid = valid & (k_pos <= q_pos)
            if window is not None:
                valid = valid & (k_pos > q_pos - window)
    alphas, pvs = [], []
    for i, mask in enumerate(masks):              # static: heads <= 16
        s = lax.dot_general(_only(lanes.cut(q, i), mask), lanes.cut(k, i),
                            (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
        if valid is not None:
            s = jnp.where(valid[None], s, _NEG_INF)
        m_prev = m_s[i, :, :, :1]                 # (G, block_q, 1)
        m_cur = s.max(axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_s[i, :, :, :1] * alpha + p.sum(axis=2, keepdims=True)
        m_s[i] = jnp.broadcast_to(m_new, m_s.shape[1:])
        l_s[i] = jnp.broadcast_to(l_new, l_s.shape[1:])
        alphas.append(alpha)
        pvs.append(lax.dot_general(
            p.astype(v.dtype), lanes_v.cut(v, i),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32))
    acc_s[...] = acc_s[...] * lanes_v.join(alphas, omasks) \
        + lanes_v.join(pvs, omasks)

    @pl.when(last)
    def _finalize():
        masks = lanes_v.masks(acc_s.shape)
        ms = [m_s[i, :, :, :1] for i in range(heads)]
        ls = [jnp.maximum(l_s[i, :, :, :1], 1e-30) for i in range(heads)]
        out = acc_s[...] / lanes_v.join(ls, masks)
        # rows that never saw a valid key (m still at init) output zero —
        # the shared convention across every path in this module
        out = jnp.where(lanes_v.join(ms, masks) > _NEG_INF / 2, out, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)
        for i, (m, l) in enumerate(zip(ms, ls)):
            # log-sum-exp per row: the residual the backward kernels need
            # (p = exp(s - lse) reconstructs softmax without the S×S
            # matrix)
            lse = jnp.where(m > _NEG_INF / 2, m + jnp.log(l), _NEG_INF)
            # 8-lane replication: narrowest layout the TPU tiling allows
            lse_ref[:, i] = jnp.broadcast_to(
                lse, (lse.shape[0], lse.shape[1], lse_ref.shape[-1]))


def _flash_fwd_pallas(q, k, v, causal: bool, sm_scale: float,
                      block_q: int = None, block_k: int = None,
                      interpret: bool = False, num_heads=None, window=None):
    # 512x512 blocks measured 2.2x faster than 128x128 on an earlier
    # chip (8x12x2048x64 causal: 4.5ms vs 13ms; XLA blockwise scan:
    # 9.7ms). The same shape on a v5e, bf16, ms a call (PR 27): forward
    # 2.33 with heads padded to 128 lanes in HBM, 2.28 at the head's own
    # width, 2.25 from (B, S, H*D); forward + backward 8.25, 7.80, 6.17.
    """Pallas flash attention forward → (out, lse). q, k, v are
    (B, H, S, D), or (B, S, H*D) with ``num_heads``; ``out`` comes back
    in the same form and ``lse`` as the kernel wrote it, (rows, heads,
    padded seq, 8) f32, for ``_flash_bwd_pallas`` alone. Tiling via
    ``_pad_for_blocks``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qt, kt, vt, _, from_tiles, t = _pad_for_blocks(
        q, k, v, block_q, block_k, num_heads, window)
    g = _head_group(t.rows, t.block_q, t.block_k, heads_per_block=t.heads,
                    row_group=t.row_group)
    w, wv = t.width, t.width_v

    walk = _walk(_live_blocks(t, causal))
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, block_q=t.block_q,
        block_k=t.block_k, seq_q=t.seq_q, seq_k=t.seq_k,
        need_mask=(t.skp != t.seq_k), lanes=t.lanes, lanes_v=t.lanes_v,
        window=t.window)
    grid = (t.rows // g, t.col_tiles) + walk.axes
    _count_grid_steps(grid)
    q_at = lambda r, c, *at: (r, walk.row(*at), c)
    q_spec = pl.BlockSpec((g, t.block_q, w), q_at)
    o_spec = q_spec if wv == w else pl.BlockSpec((g, t.block_q, wv), q_at)
    k_spec = pl.BlockSpec((g, t.block_k, w), _kv_index(t, walk))
    v_spec = k_spec if wv == w else \
        pl.BlockSpec((g, t.block_k, wv), _kv_index(t, walk))
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.tables),
            grid=grid,
            in_specs=[q_spec, k_spec, v_spec],
            out_specs=[
                o_spec,
                pl.BlockSpec((g, t.heads, t.block_q, 8),
                             lambda r, c, *at: (r, c, walk.row(*at), 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((t.heads, g, t.block_q, 128), jnp.float32),
                pltpu.VMEM((t.heads, g, t.block_q, 128), jnp.float32),
                pltpu.VMEM((g, t.block_q, wv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape[:2] + (t.col_tiles * wv,),
                                 q.dtype),
            jax.ShapeDtypeStruct(
                (t.rows, t.col_tiles * t.heads, t.sqp, 8), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_semantics(grid),
            # q, k, v, o blocks; acc scratch, m and l and one product a
            # head; s and p tiles; the lse block
            vmem_limit_bytes=_vmem_limit(g, t, q.dtype.itemsize, 4,
                                         1 + 3 * t.heads, 2, 1)),
        interpret=interpret,
    )(*walk.tables, qt, kt, vt)
    return from_tiles(out, t.seq_q, True), lse


# ---------------------------------------------------------------------------
# Pallas TPU backward kernels (FlashAttention-2 style: recompute p from the
# saved per-row log-sum-exp; no S×S residual is ever materialized)
# ---------------------------------------------------------------------------

def _bwd_mask(qi, ki, block_q, block_k, causal, seq_q, seq_k, window=None):
    k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
    valid = (k_pos < seq_k) & (q_pos < seq_q)
    if causal:
        valid = valid & (k_pos <= q_pos + (seq_k - seq_q))
    if window is not None:
        valid = valid & (k_pos > q_pos + (seq_k - seq_q) - window)
    return valid


def _bwd_head(q, k, v, do, lse, delta, qmask, omask, valid, sm_scale):
    """One head's softmax block rebuilt from its log-sum-exp, and the
    score gradient: (p, ds), both f32 (G, bq, bk). The operands are the
    head's spans of their blocks (``_Lanes.cut``); ``qmask`` and
    ``omask`` pick the head's lanes of the q-side operands (q; do); k
    and v need none, the zeroed lanes of the other side cancel theirs."""
    # operands keep the input dtype (bf16 under AMP), f32 accumulate
    # — see the forward kernel's MXU-pass note
    s = lax.dot_general(_only(q, qmask), k, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32) * sm_scale
    p = jnp.exp(s - lse)                            # (G, bq, bk)
    if valid is not None:
        p = jnp.where(valid[None], p, 0.0)
    dp = lax.dot_general(_only(do, omask), v, (((2,), (2,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


def _bwd_masks(lanes, lanes_v, q, k, v, do):
    """The heads' lane masks of the backward kernels' four kinds of
    block, (q, do, k, v): with one width on both sides the q block's
    masks serve do and the k block's serve v."""
    qmasks, kmasks = lanes.masks(q.shape), lanes.masks(k.shape)
    if lanes_v == lanes:
        return qmasks, qmasks, kmasks, kmasks
    return qmasks, lanes_v.masks(do.shape), kmasks, lanes_v.masks(v.shape)


def _flash_bwd_dkv_kernel(*refs, blocks_a_head, sm_scale, causal, block_q,
                          block_k, seq_q, seq_k, need_mask, lanes, lanes_v,
                          window=None):
    from jax.experimental import pallas as pl
    (*tables, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
     dv_ref, dk_s, dv_s) = refs
    # the grid walks, k block after k block, the q blocks that hold a
    # pair with it, once for every query head that reads this key/value
    # head, one head after another (_walk)
    ki, qi, first, last = _grid_step(tables, blocks_a_head)

    @pl.when(first)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q = q_ref[...]                                  # (G, bq, width)
    k = k_ref[...]                                  # (G, bk, width)
    v = v_ref[...]                                  # (G, bk, width_v)
    do = do_ref[...]                                # (G, bq, width_v)
    qmasks, omasks, kmasks, vmasks = _bwd_masks(lanes, lanes_v, q, k, v, do)
    valid = _bwd_mask(qi, ki, block_q, block_k, causal, seq_q, seq_k,
                      window) if need_mask or causal else None
    dks, dvs = [], []
    for i, (qmask, omask) in enumerate(zip(qmasks, omasks)):
        q_i, do_i = lanes.cut(q, i), lanes_v.cut(do, i)
        p, ds = _bwd_head(q_i, lanes.cut(k, i), lanes_v.cut(v, i), do_i,
                          lse_ref[:, i][:, :, :1],
                          delta_ref[:, i][:, :, :1], qmask, omask, valid,
                          sm_scale)
        dvs.append(lax.dot_general(p.astype(do.dtype), do_i,
                                   (((1,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32))
        dks.append(lax.dot_general(ds.astype(q.dtype), q_i,
                                   (((1,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32))
    dv_s[...] += lanes_v.join(dvs, vmasks)
    dk_s[...] += lanes.join(dks, kmasks)

    @pl.when(last)
    def _finalize():
        dk_ref[...] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_s[...].astype(dv_ref.dtype)


def _bwd_products(q, k, v, do, lse_ref, deltas, masks, valid, lanes,
                  lanes_v, sm_scale):
    """The three gradients of one visit to a (q block, k block) pair,
    each head's softmax block rebuilt ONCE: lists of one f32 product a
    head, (dqs, dks, dvs), each over the head's span of its block.
    ``deltas(i, omask)`` is head i's (G, bq, 1) delta and ``masks`` what
    ``_bwd_masks`` gave."""
    qmasks, omasks = masks[:2]
    dqs, dks, dvs = [], [], []
    for i, (qmask, omask) in enumerate(zip(qmasks, omasks)):
        q_i, k_i, do_i = lanes.cut(q, i), lanes.cut(k, i), \
            lanes_v.cut(do, i)
        delta = deltas(i, omask)
        p, ds = _bwd_head(q_i, k_i, lanes_v.cut(v, i), do_i,
                          lse_ref[:, i][:, :, :1], delta, qmask, omask,
                          valid, sm_scale)
        ds = ds.astype(k.dtype)
        dvs.append(lax.dot_general(p.astype(do.dtype), do_i,
                                   (((1,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32))
        dqs.append(lax.dot_general(ds, k_i, (((2,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32))
        dks.append(lax.dot_general(ds, q_i, (((1,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32))
    return dqs, dks, dvs


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                            dq_ref, dk_ref, dv_ref, *, sm_scale, causal,
                            block_q, block_k, seq_q, seq_k, need_mask,
                            lanes, lanes_v, window=None):
    """Single-block backward (nq == nk == 1, no shared key/value head:
    the short-seq fast path): one program computes dq, dk AND dv from
    the softmax block rebuilt ONCE, as ``_flash_bwd_walk_kernel`` does a
    visit. delta_i = rowsum(dO_i * O_i) is taken here from the o block,
    per head, so no XLA pass over dO and O precedes the call."""
    q = q_ref[...]                                  # (G, bq, width)
    k = k_ref[...]                                  # (G, bk, width)
    v = v_ref[...]                                  # (G, bk, width_v)
    do = do_ref[...]                                # (G, bq, width_v)
    do_o = do.astype(jnp.float32) * o_ref[...].astype(jnp.float32)
    masks = _bwd_masks(lanes, lanes_v, q, k, v, do)
    qmasks, _, kmasks, vmasks = masks
    valid = _bwd_mask(0, 0, block_q, block_k, causal, seq_q, seq_k,
                      window) if need_mask or causal else None
    dqs, dks, dvs = _bwd_products(
        q, k, v, do, lse_ref,
        lambda i, omask: _only(lanes_v.cut(do_o, i), omask).sum(
            axis=2, keepdims=True),
        masks, valid, lanes, lanes_v, sm_scale)
    dq_ref[...] = lanes.join(dqs, qmasks).astype(dq_ref.dtype)
    dk_ref[...] = lanes.join(dks, kmasks).astype(dk_ref.dtype)
    dv_ref[...] = lanes_v.join(dvs, vmasks).astype(dv_ref.dtype)


def _flash_bwd_walk_kernel(*refs, group, n_q, sm_scale, causal, block_q,
                           block_k, seq_q, seq_k, need_mask, lanes, lanes_v,
                           window=None):
    """The multi-block backward as ONE kernel: the grid walks, for each
    query head of the group in turn, k block after k block, the q blocks
    that hold a pair with it (``_walk`` over the flattened (k block, q
    block) pairs, one row a head), and every visit rebuilds the softmax
    block once and makes dq, dk and dv from it. dk and dv accumulate for
    the key/value head's whole sequence, summed over the group, dq for
    the query head's, all three in f32 VMEM scratch: dq is written when
    its head's last visit is made, dk and dv after the group's."""
    from jax.experimental import pallas as pl
    (*tables, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
     dk_ref, dv_ref, dq_s, dk_s, dv_s) = refs
    head, pair, first, last = _grid_step(tables)
    ki, qi = pair // n_q, pair % n_q

    @pl.when(first & (head == 0))
    def _init_kv():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when(first)
    def _init_q():
        dq_s[...] = jnp.zeros_like(dq_s)

    q = q_ref[...]                                  # (G, bq, width)
    k = k_ref[...]                                  # (G, bk, width)
    v = v_ref[...]                                  # (G, bk, width_v)
    do = do_ref[...]                                # (G, bq, width_v)
    masks = _bwd_masks(lanes, lanes_v, q, k, v, do)
    qmasks, _, kmasks, vmasks = masks
    valid = _bwd_mask(qi, ki, block_q, block_k, causal, seq_q, seq_k,
                      window) if need_mask or causal else None
    dqs, dks, dvs = _bwd_products(
        q, k, v, do, lse_ref,
        lambda i, omask: delta_ref[:, i][:, :, :1],
        masks, valid, lanes, lanes_v, sm_scale)
    at_q = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    at_k = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
    dv_s[:, at_k] += lanes_v.join(dvs, vmasks)
    dk_s[:, at_k] += lanes.join(dks, kmasks)
    dq_s[:, at_q] += lanes.join(dqs, qmasks)

    @pl.when(last)
    def _write_q():
        dq_ref[...] = dq_s[...].astype(dq_ref.dtype)

    @pl.when(last & (head == group - 1))
    def _write_kv():
        dk_ref[...] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_s[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, seq_q,
                         seq_k, need_mask, lanes, lanes_v, window=None):
    from jax.experimental import pallas as pl
    (*tables, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
     dq_s) = refs
    qi, ki, first, last = _grid_step(tables)        # as the forward

    @pl.when(first)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    q = q_ref[...]                                  # (G, bq, width)
    k = k_ref[...]                                  # (G, bk, width)
    v = v_ref[...]                                  # (G, bk, width_v)
    do = do_ref[...]                                # (G, bq, width_v)
    qmasks = lanes.masks(q.shape)
    omasks = qmasks if lanes_v == lanes else lanes_v.masks(do.shape)
    valid = _bwd_mask(qi, ki, block_q, block_k, causal, seq_q, seq_k,
                      window) if need_mask or causal else None
    dqs = []
    for i, (qmask, omask) in enumerate(zip(qmasks, omasks)):
        k_i = lanes.cut(k, i)
        _, ds = _bwd_head(lanes.cut(q, i), k_i, lanes_v.cut(v, i),
                          lanes_v.cut(do, i), lse_ref[:, i][:, :, :1],
                          delta_ref[:, i][:, :, :1], qmask, omask, valid,
                          sm_scale)
        dqs.append(lax.dot_general(ds.astype(k.dtype), k_i,
                                   (((2,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32))
    dq_s[...] += lanes.join(dqs, qmasks)

    @pl.when(last)
    def _finalize():
        dq_ref[...] = dq_s[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal: bool, sm_scale: float,
                      block_q: int = None, block_k: int = None,
                      interpret: bool = False, num_heads=None, window=None):
    """Pallas flash attention backward, in one of three forms
    (``mx_flash_attention_bwd_total{form}``):

    - ``one_block``: the whole sequence is one block and no key/value
      head is shared: one kernel, one step a program, delta taken inside;
    - ``fused``: one kernel (``_flash_bwd_walk_kernel``) walks the live
      blocks (``_walk``: a block with no valid pair is not in the grid)
      head by head of the group, k block by k block, and makes dq, dk and
      dv from each visit's ONE rebuilt softmax block. dq accumulates for
      the query head's whole sequence and dk, dv for the key/value head's
      in VMEM, so the call takes this form where those buffers fit the
      VMEM a kernel may ask for (``_resident_bytes``, ``_VMEM_ASK_BYTES``);
    - ``split``: longer sequences: dq via a kernel that walks each q
      block's live k blocks and dk/dv via one that walks each k block's
      live q blocks, once for each query head of the group, so a
      key/value head's gradient is summed where it is made. Each rebuilds
      the softmax block: seven products a visit where the fused form
      makes five.

    ``lse`` is what ``_flash_fwd_pallas`` returned for the same blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qt, kt, vt, to_tiles, from_tiles, t = _pad_for_blocks(
        q, k, v, block_q, block_k, num_heads, window)
    sq, sk = t.seq_q, t.seq_k
    do = do.astype(q.dtype)
    dot = to_tiles(do, t.sqp, True)
    g = _head_group(t.rows, t.block_q, t.block_k, n_tiles=4,
                    heads_per_block=t.heads, row_group=t.row_group)
    need_mask = (t.skp != sk) or (t.sqp != sq)
    w, wv, heads = t.width, t.width_v, t.heads
    static = dict(sm_scale=sm_scale, causal=causal, block_q=t.block_q,
                  block_k=t.block_k, seq_q=sq, seq_k=sk,
                  need_mask=need_mask, lanes=t.lanes, lanes_v=t.lanes_v,
                  window=t.window)
    q_shape = jax.ShapeDtypeStruct(qt.shape, q.dtype)
    k_shape = jax.ShapeDtypeStruct(kt.shape, k.dtype)
    v_shape = jax.ShapeDtypeStruct(vt.shape, v.dtype)

    if t.nq == 1 and t.nk == 1 and t.group == 1:
        bspec = lambda blk, width=w: pl.BlockSpec((g, blk, width),
                                                  lambda r, c: (r, 0, c))
        rspec = pl.BlockSpec((g, heads, t.block_q, 8),
                             lambda r, c: (r, c, 0, 0))
        _count_grid_steps((t.rows // g, t.col_tiles))
        _count_bwd_form("one_block")
        dq, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_fused_kernel, **static),
            grid=(t.rows // g, t.col_tiles),
            in_specs=[bspec(t.block_q), bspec(t.block_k),
                      bspec(t.block_k, wv), bspec(t.block_q, wv),
                      bspec(t.block_q, wv), rspec],
            out_specs=[bspec(t.block_q), bspec(t.block_k),
                       bspec(t.block_k, wv)],
            out_shape=[q_shape, k_shape, v_shape],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                # q, k, v, o, do in, dq, dk, dv out; dO*O and three
                # products a head; s, p, dp, ds tiles; the lse block
                vmem_limit_bytes=_vmem_limit(g, t, q.dtype.itemsize, 8,
                                             1 + 3 * heads, 4, 1)),
            interpret=interpret,
        )(qt, kt, vt, to_tiles(o, t.sqp, True), dot, lse)
        return (from_tiles(dq, sq), from_tiles(dk, sk),
                from_tiles(dv, sk, True))

    # delta_i = rowsum(dO_i * O_i), per head, laid out as the lse is
    # (cheap; XLA fuses it into one pass over dO and O)
    delta = do.astype(jnp.float32) * o.astype(jnp.float32)
    if num_heads is None:
        delta = delta.sum(-1)
    else:
        # reduce first: the transpose then moves one number a head
        delta = delta.reshape(t.batch, sq, t.num_heads,
                              t.head_dim_v).sum(-1).transpose(0, 2, 1)
    delta = delta.reshape(lse.shape[0], lse.shape[1], sq)
    if t.sqp != sq:
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, t.sqp - sq)))
    # 8-lane replication (TPU block tiling minimum for a row vector)
    delta = jnp.broadcast_to(delta[..., None], lse.shape)

    live = _live_blocks(t, causal)
    kv_grid = (kt.shape[0] // g, kt.shape[2] // w)

    def kv_specs(walk):
        """The k and v blocks of a grid over key/value rows and column
        tiles, at the walk's k block."""
        at = lambda r, c, *a: (r, walk.row(*a), c)
        k_spec = pl.BlockSpec((g, t.block_k, w), at)
        return k_spec, (k_spec if wv == w else
                        pl.BlockSpec((g, t.block_k, wv), at))

    def q_specs(walk):
        """The q, do, lse and delta blocks of such a grid: the walk's q
        block of the query head of the group it names."""
        at = _q_index(t, walk)

        def stat_at(*a):
            row, qi, col = at(*a)
            return row, col, qi, 0
        q_spec = pl.BlockSpec((g, t.block_q, w), at)
        return (q_spec, q_spec if wv == w else
                pl.BlockSpec((g, t.block_q, wv), at),
                pl.BlockSpec((g, heads, t.block_q, 8), stat_at))

    resident = _resident_bytes(g, t, q.dtype.itemsize)
    # q, k, v, do in; three products a head; 4 score tiles; lse and
    # delta blocks; dq, dk, dv whole
    limit = _vmem_limit(g, t, q.dtype.itemsize, 4, 3 * heads, 4, 2,
                        resident)
    if limit <= _VMEM_ASK_BYTES:
        _count_bwd_form("fused")
        walk = _pair_walk(live, t.group)
        grid = kv_grid + walk.axes
        _count_grid_steps(grid)
        q_in, do_in, row_in = q_specs(walk)
        k_in, v_in = kv_specs(walk)

        def whole_q(r, c, *at):
            row, _, col = _q_index(t, walk)(r, c, *at)
            return row, 0, col
        whole_kv = lambda r, c, *at: (r, 0, c)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_walk_kernel, **static,
                              group=t.group, n_q=t.nq),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(walk.tables),
                grid=grid,
                in_specs=[q_in, k_in, v_in, do_in, row_in, row_in],
                out_specs=[pl.BlockSpec((g, t.sqp, w), whole_q),
                           pl.BlockSpec((g, t.skp, w), whole_kv),
                           pl.BlockSpec((g, t.skp, wv), whole_kv)],
                scratch_shapes=[pltpu.VMEM((g, t.sqp, w), jnp.float32),
                                pltpu.VMEM((g, t.skp, w), jnp.float32),
                                pltpu.VMEM((g, t.skp, wv), jnp.float32)]),
            out_shape=[q_shape, k_shape, v_shape],
            compiler_params=pltpu.CompilerParams(
                # every step of a (row, column tile) adds to its buffers
                dimension_semantics=("parallel", "parallel")
                + ("arbitrary",) * len(walk.axes),
                vmem_limit_bytes=limit),
            interpret=interpret,
        )(*walk.tables, qt, kt, vt, dot, lse, delta)
        return (from_tiles(dq, sq), from_tiles(dk, sk),
                from_tiles(dv, sk, True))

    _count_bwd_form("split")
    walk = _walk(live)
    grid = (t.rows // g, t.col_tiles) + walk.axes
    _count_grid_steps(grid)
    at_q = lambda r, c, *at: (r, walk.row(*at), c)
    q_in = pl.BlockSpec((g, t.block_q, w), at_q)
    do_in = q_in if wv == w else pl.BlockSpec((g, t.block_q, wv), at_q)
    row_in = pl.BlockSpec((g, heads, t.block_q, 8),
                          lambda r, c, *at: (r, c, walk.row(*at), 0))
    k_in = pl.BlockSpec((g, t.block_k, w), _kv_index(t, walk))
    v_in = k_in if wv == w else \
        pl.BlockSpec((g, t.block_k, wv), _kv_index(t, walk))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.tables),
            grid=grid,
            in_specs=[q_in, k_in, v_in, do_in, row_in, row_in],
            out_specs=q_in,
            scratch_shapes=[pltpu.VMEM((g, t.block_q, w), jnp.float32)]),
        out_shape=q_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_semantics(grid),
            # q, k, v, do in, dq out; dq scratch and one product a head;
            # s, p, dp, ds tiles; lse and delta blocks
            vmem_limit_bytes=_vmem_limit(g, t, q.dtype.itemsize, 5,
                                         1 + heads, 4, 2)),
        interpret=interpret,
    )(*walk.tables, qt, kt, vt, dot, lse, delta)

    walk = _walk(live.T, t.group)
    grid = kv_grid + walk.axes
    _count_grid_steps(grid)
    q_in, do_in, row_in = q_specs(walk)
    k_spec, v_spec = kv_specs(walk)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **static,
                          blocks_a_head=t.nq if t.group > 1 else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.tables),
            grid=grid,
            in_specs=[q_in, k_spec, v_spec, do_in, row_in, row_in],
            out_specs=[k_spec, v_spec],
            scratch_shapes=[pltpu.VMEM((g, t.block_k, w), jnp.float32),
                            pltpu.VMEM((g, t.block_k, wv), jnp.float32)]),
        out_shape=[k_shape, v_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_semantics(grid),
            # q, k, v, do in, dk, dv out; dk, dv scratch and two
            # products a head; 4 score tiles; lse and delta blocks
            vmem_limit_bytes=_vmem_limit(g, t, q.dtype.itemsize, 6,
                                         2 + 2 * heads, 4, 2)),
        interpret=interpret,
    )(*walk.tables, qt, kt, vt, dot, lse, delta)

    return (from_tiles(dq, sq), from_tiles(dk, sk),
            from_tiles(dv, sk, True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_tpu(q, k, v, causal, sm_scale, interpret, num_heads=None,
               window=None):
    return _flash_fwd_pallas(q, k, v, causal, sm_scale, interpret=interpret,
                             num_heads=num_heads, window=window)[0]


def _flash_tpu_fwd(q, k, v, causal, sm_scale, interpret, num_heads, window):
    o, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale,
                               interpret=interpret, num_heads=num_heads,
                               window=window)
    return o, (q, k, v, o, lse)


def _flash_tpu_bwd(causal, sm_scale, interpret, num_heads, window, res, g):
    q, k, v, o, lse = res
    return _flash_bwd_pallas(q, k, v, o, lse, g, causal, sm_scale,
                             interpret=interpret, num_heads=num_heads,
                             window=window)


_flash_tpu.defvjp(_flash_tpu_fwd, _flash_tpu_bwd)


# ---------------------------------------------------------------------------
# Public flash_attention with recompute backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, window=None):
    """XLA (non-Pallas) flash path: blockwise scan forward, recompute
    backward. The TPU default goes through _flash_tpu instead."""
    return _attention_xla(q, k, v, causal, sm_scale, window=window)


def _flash_fwd(q, k, v, causal, sm_scale, window):
    return _flash(q, k, v, causal, sm_scale, window), (q, k, v)


def _flash_bwd(causal, sm_scale, window, res, g):
    q, k, v = res
    # Flash-style backward: recompute attention blockwise (no S×S residual).
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _attention_xla(q_, k_, v_, causal, sm_scale,
                                          window=window),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_vl(q, k, v, vl, causal, sm_scale):
    return _attention_xla(q, k, v, causal, sm_scale, valid_length=vl)


def _flash_vl_fwd(q, k, v, vl, causal, sm_scale):
    return _flash_vl(q, k, v, vl, causal, sm_scale), (q, k, v, vl)


def _flash_vl_bwd(causal, sm_scale, res, g):
    q, k, v, vl = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _attention_xla(q_, k_, v_, causal, sm_scale,
                                          valid_length=vl), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, jnp.zeros_like(vl)


_flash_vl.defvjp(_flash_vl_fwd, _flash_vl_bwd)


def _kernel_tier(q, k, v, num_heads, use_pallas,
                 window=None) -> Optional[bool]:
    """Which tier takes this call: None for the XLA reference, else
    whether the kernel bodies run interpreted. ``use_pallas`` None asks
    the shared MXNET_PALLAS three-tier gate (ops/kernels): compiled
    kernels on TPU, interpret-mode bodies when forced on other backends,
    blockwise-XLA reference otherwise. A call the kernels take is
    counted by the layout its shapes gave it, and the gate's recorded
    reason says which."""
    tiles = _tiles(q.shape, k.shape, _BLOCK_Q, _BLOCK_K, num_heads, window,
                   v.shape)
    if use_pallas is None:
        from .kernels import dispatch as _kdispatch
        path, _ = _kdispatch("flash_attention", detail=tiles.reason)
    else:
        path = "pallas" if use_pallas else "xla"
    if path == "xla":
        return None
    from .kernels import count_traced
    count_traced("FLASH_ATTENTION_LAYOUT", "layout", tiles.layout)
    return path == "interpret"


def _count_mask(causal: bool, window) -> None:
    """``mx_attention_mask_total{kind}``: which mask a traced attention
    call asked for, whatever tier then took it."""
    from .kernels import count_traced
    count_traced("ATTENTION_MASK", "kind",
                 "window" if window is not None else
                 "causal" if causal else "full")


def _check_kv_heads(num_kv_heads, found: int) -> None:
    if num_kv_heads is not None and num_kv_heads != found:
        raise MXNetError(f"attention: num_kv_heads={num_kv_heads} but k "
                         f"holds {found} heads")


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    use_pallas: Optional[bool] = None,
                    valid_length=None, num_kv_heads: Optional[int] = None,
                    window: Optional[int] = None):
    """Fused memory-efficient attention on (B, H, S, D) tensors.

    On TPU forward and backward run as Pallas kernels (_flash_tpu:
    FlashAttention-2 dq/dkv kernels off the saved log-sum-exp); elsewhere
    a blockwise lax.scan implementation with identical online-softmax math
    and a recompute-based backward. ``valid_length`` (B,) masks padded
    keys; that path uses the blockwise implementation (still O(S·block)
    memory, never an S×S score matrix).

    k and v may hold fewer heads than q (``num_kv_heads``, read off their
    shape when not given): query head n reads head n // (H // Hkv), and
    no tier repeats k or v in memory. v's heads may have another width
    than q's and k's (latent attention: 192 beside 128); the result has
    v's, and no tier pads v to q's. ``window`` (with ``causal``) hides
    key j from query i unless 0 <= i - j < window; the kernels' grids
    hold neither the blocks wholly outside it nor those above the
    diagonal (``_walk``).
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise MXNetError("flash_attention expects (batch, heads, seq, dim)")
    _check_kv_heads(num_kv_heads, k.shape[1])
    window = _window_of(window, causal)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    _count_mask(causal, window)
    if valid_length is not None:
        if window is not None or k.shape[1] != q.shape[1]:
            raise MXNetError("flash_attention: valid_length goes with "
                             "neither a window nor grouped K/V heads")
        vl = jnp.asarray(valid_length, jnp.float32)
        return _flash_vl(q, k, v, vl, causal, float(sm_scale))
    interpret = _kernel_tier(q, k, v, None, use_pallas, window)
    if interpret is None:
        return _flash(q, k, v, causal, float(sm_scale), window)
    # full-Pallas path: flash forward AND FlashAttention-2-style
    # backward kernels off the saved log-sum-exp
    return _flash_tpu(q, k, v, causal, float(sm_scale), interpret, None,
                      window)


def flash_attention_bsh(q, k, v, num_heads: int, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        num_kv_heads: Optional[int] = None,
                        window: Optional[int] = None):
    """:func:`flash_attention` on (B, S, H*D) tensors, heads side by
    side on the last axis as a projection emits them and as the output
    projection eats them; returns (B, S, H*D). k and v are
    (B, S, Hkv*D), Hkv = ``num_kv_heads`` (``num_heads`` when None);
    v may be (B, S, Hkv*Dv) with heads of another width, and the result
    is then (B, S, H*Dv).

    When the head width divides 128 (and H*D is a multiple of 128) or
    is a multiple of it, the Pallas kernels read and write these arrays
    where they lie: no head transpose, no padding (``_Tiles``,
    "packed"; with Hkv < H only heads that fill a lane tile alone). Any
    other width, and the XLA tier, transposes to (B, H, S, D) here,
    inside the op, and back.
    """
    if q.ndim != 3 or k.ndim != 3 or v.ndim != 3:
        raise MXNetError("flash_attention_bsh expects (batch, seq, "
                         "heads * dim)")
    if q.shape[-1] % num_heads:
        raise MXNetError(f"flash_attention_bsh: width {q.shape[-1]} not "
                         f"divisible by heads {num_heads}")
    d = q.shape[-1] // num_heads
    if k.shape[-1] % d:
        raise MXNetError(f"flash_attention_bsh: K/V width {k.shape[-1]} "
                         f"not a multiple of the head width {d}")
    kv_heads = k.shape[-1] // d
    _check_kv_heads(num_kv_heads, kv_heads)
    if v.shape[-1] % kv_heads:
        raise MXNetError(f"flash_attention_bsh: V width {v.shape[-1]} not "
                         f"divisible by its {kv_heads} heads")
    window = _window_of(window, causal)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    _count_mask(causal, window)
    interpret = _kernel_tier(q, k, v, num_heads, None, window)
    if interpret is not None:
        return _flash_tpu(q, k, v, causal, float(sm_scale), interpret,
                          num_heads, window)
    return _merge_heads(_flash(_split_heads(q, num_heads),
                               _split_heads(k, kv_heads),
                               _split_heads(v, kv_heads),
                               causal, float(sm_scale), window))


# ---------------------------------------------------------------------------
# Paged decode attention: the single-token serving read path
# ---------------------------------------------------------------------------

def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           sm_scale: Optional[float] = None):
    """One query token per batch slot attending over K/V held in a
    paged cache (serving/kvcache.py) — the decode path through the
    flash-attention kernel, reading keys through page-table
    indirection.

    - ``q``: (S, H, D) — the current token's query per slot;
    - ``k_pages``/``v_pages``: (P, page_size, Hkv, D) — the pooled page
      arrays of one layer. ``Hkv`` may DIVIDE the query head count H
      (grouped-query attention): each stored K/V head is broadcast
      across its group of ``H // Hkv`` query heads, so a GQA decoder
      pays the KV-cache bytes of ``Hkv`` heads while attending with H;
    - ``page_table``: (S, max_pages) int32 — slot → page ids, padded
      with the null page 0 past each slot's allocation;
    - ``lengths``: (S,) — valid key count per slot (the token just
      written included).

    The page gather is a shape-stable XLA gather (the compiled program
    never depends on which pages a slot holds), and the attention runs
    as ``flash_attention(..., valid_length=lengths)`` so padding pages
    and unwritten tail positions are masked exactly (never a NaN, never
    a contribution from another request's freed pages). Returns
    (S, H, D).
    """
    s, h, d = q.shape
    hkv = k_pages.shape[2]
    if h != hkv and (hkv < 1 or h % hkv):
        raise MXNetError(
            f"paged_decode_attention: query heads {h} not a multiple "
            f"of K/V heads {hkv} (GQA needs integer groups)")
    ps = k_pages.shape[1]
    t = page_table.shape[1] * ps
    # (S, max_pages, page_size, Hkv, D) -> (S, Hkv, T, D): slot s's key
    # at position p lives at flat index p because pages fill in order
    k = k_pages[page_table].reshape(s, t, hkv, d).transpose(0, 2, 1, 3)
    v = v_pages[page_table].reshape(s, t, hkv, d).transpose(0, 2, 1, 3)
    if h != hkv:
        # GQA broadcast: repeat each stored head over its query group
        # (head j serves query heads [j*g, (j+1)*g))
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    out = flash_attention(q[:, :, None, :], k, v, causal=False,
                          sm_scale=sm_scale, valid_length=lengths)
    return out[:, :, 0, :]


# ---------------------------------------------------------------------------
# Ring attention: context parallelism over a mesh axis
# ---------------------------------------------------------------------------

def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Per-shard ring attention body — call under shard_map with the
    sequence dimension sharded over ``axis_name``.

    Each of the N devices holds S/N of the sequence. K/V shards rotate
    around the ring (lax.ppermute = ICI neighbor exchange, overlapping with
    the local attention block), and online-softmax stats merge the partial
    results — the TPU-native form of sequence/context parallelism.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    axis_size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s, d = q.shape
    orig_dtype = q.dtype
    qf = q.astype(jnp.float32) * sm_scale
    q_pos = idx * s + jnp.arange(s)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def _merge(acc, m, l, kc, vc, src):
        """Online-softmax merge of one K/V chunk (chunk id ``src``)."""
        s_ij = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(jnp.float32))
        if causal:
            k_pos = src * s + jnp.arange(s)
            mask = k_pos[None, :] <= q_pos[:, None]
            s_ij = jnp.where(mask, s_ij, _NEG_INF)
        m_new = jnp.maximum(m, s_ij.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_ij - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
        return acc, m_new, l

    def body(carry, i):
        acc, m, l, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)   # rotate, then merge: the
        vc = lax.ppermute(vc, axis_name, perm)   # local chunk was step 0
        acc, m, l = _merge(acc, m, l, kc, vc, (idx - i) % axis_size)
        return (acc, m, l, kc, vc), None

    acc0 = jnp.zeros((b, h, s, d), jnp.float32)
    m0 = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    # Step 0 = local chunk; steps 1..N-1 rotate first, so exactly N-1
    # neighbor exchanges happen in total.
    acc0, m0, l0 = _merge(acc0, m0, l0, k, v, idx)
    (acc, m, l, _, _), _ = lax.scan(body, (acc0, m0, l0, k, v),
                                    jnp.arange(1, axis_size))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where((m > _NEG_INF / 2)[..., None], out, 0.0)  # no-key rows
    return out.astype(orig_dtype)


def ring_attention_sharded(q, k, v, mesh, axis: str = "sp",
                           causal: bool = False,
                           sm_scale: Optional[float] = None):
    """shard_map wrapper: jax arrays in, sequence dim sharded over ``axis``
    of ``mesh`` (a jax.sharding.Mesh or mxnet_tpu DeviceMesh)."""
    from jax.sharding import PartitionSpec as P
    m = getattr(mesh, "mesh", mesh)
    spec = P(None, None, axis, None)
    fn = functools.partial(ring_attention, axis_name=axis, causal=causal,
                           sm_scale=sm_scale)
    from ..parallel.collectives import shard_map as _shard_map
    return _shard_map(lambda a, b_, c: fn(a, b_, c), m,
                      (spec, spec, spec), spec)(q, k, v)


# ---------------------------------------------------------------------------
# sharding spec packs (analysis/sharding.py expect_spec)
# ---------------------------------------------------------------------------
# The invariant packs for the two attention parallelism paths, declared
# NEXT TO the implementations they describe so a change to the
# collective pattern and its contract land in the same review:
#
# - tensor-parallel attention ("tp-attention"): per-head QKV projections
#   column-sharded over 'tp', the output projection row-sharded — the
#   Megatron signature is exactly ONE all-reduce (the output psum) per
#   application; any all-gather above the floor means an activation
#   silently left the head-sharded layout.
# - sequence-parallel ring attention ("sp-ring-attention"): K and V
#   shards rotate the ring with lax.ppermute — >= 2 collective-permutes
#   (K and V; the backward adds reverse hops) and NOTHING ELSE: a
#   gather here means the sequence dimension was materialized on one
#   device, the exact failure ring attention exists to avoid.
try:
    from ..analysis import sharding as _asharding

    TP_ATTENTION_SPEC_PACK = _asharding.register_spec_pack(
        _asharding.SpecPack(
            name="tp-attention",
            description="tensor-parallel attention (Megatron split: "
                        "column-sharded QKV, row-sharded output proj, "
                        "one output all-reduce)",
            axes=("tp",),
            rules=(_asharding.CollectiveRule(
                "all_reduce", axis="tp", min_count=1),),
            declared=(_asharding.CollectiveRule(
                "reduce_scatter", axis="tp"),),
            state_axis="tp"))

    RING_ATTENTION_SPEC_PACK = _asharding.register_spec_pack(
        _asharding.SpecPack(
            name="sp-ring-attention",
            description="sequence-parallel ring attention (K/V shards "
                        "rotate via ppermute, online-softmax merge)",
            axes=("sp",),
            rules=(_asharding.CollectiveRule(
                "collective_permute", axis="sp", min_count=2),),
            declared=()))
except Exception:                        # pragma: no cover - defensive
    pass
