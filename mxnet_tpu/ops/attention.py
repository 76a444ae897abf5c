"""Attention ops: Pallas flash attention + ring attention (context parallel).

Reference parity note: the reference (Apache MXNet 2.0-dev) ships NO fused
attention and NO sequence/context parallelism (SURVEY.md §2.3, §5 "long-
context: none in the reference") — attention lived in gluon-nlp as unfused
batch_dot+softmax. This module is the TPU-idiomatic superset the build plan
(SURVEY.md §7 stage 10) calls for:

- ``flash_attention``: O(S) memory online-softmax attention. On TPU both
  the forward AND the backward are Pallas kernels (FlashAttention-2 style:
  the forward saves a per-row log-sum-exp residual; the backward's dq and
  dk/dv kernels reconstruct softmax blocks from it — no S×S residual is
  ever materialized). Elsewhere a blockwise ``lax.scan`` XLA implementation
  with identical math and a recompute-based backward.
- ``ring_attention``: context parallelism over a mesh axis. Each device
  holds a sequence shard of Q/K/V; K/V blocks rotate around the ring via
  ``lax.ppermute`` (ICI neighbor exchange) while online-softmax accumulators
  merge partial results — sequence length scales with the number of chips.

Math convention: inputs are (batch, heads, seq, head_dim); softmax scale
defaults to head_dim**-0.5; masking uses a large negative finite value so
fully-masked rows stay NaN-free through exp/renormalization.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError

__all__ = ["flash_attention", "paged_decode_attention", "ring_attention",
           "ring_attention_sharded", "attention_reference"]

_NEG_INF = -1e30  # finite mask value: keeps exp() NaN-free for masked rows


def attention_reference(q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None, mask=None):
    """Unfused softmax(QK^T)V — the numeric oracle for tests and the
    arbitrary-additive-mask path (XLA fuses the softmax). ``mask`` is an
    additive float mask broadcastable to (B, H, Sq, Sk). Convention shared
    by every attention path in this module: a query row with NO valid key
    outputs exactly zero (the flash-kernel convention)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if mask is not None:
        s = s + mask.astype(jnp.float32)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        tri = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(tri, s, _NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    out = out / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.where(m > _NEG_INF / 2, out, 0.0)  # fully-masked rows → 0
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Blockwise XLA implementation (fallback forward + backward recompute target)
# ---------------------------------------------------------------------------

def _attention_xla(q, k, v, causal: bool, sm_scale: float,
                   block_k: int = 512, valid_length=None):
    """Online-softmax attention scanning over K/V blocks: O(Sq·block_k)
    live memory instead of O(Sq·Sk). Pure lax.scan — XLA pipelines the
    blocks and keeps the matmuls on the MXU. ``valid_length`` is an
    optional (B,) per-sample key length (padding mask)."""
    orig_dtype = q.dtype
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_k = min(block_k, sk)
    nk = -(-sk // block_k)
    pad = nk * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qf = q.astype(jnp.float32) * sm_scale
    kb = jnp.moveaxis(k.reshape(b, h, nk, block_k, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, nk, block_k, d), 2, 0)
    q_pos = jnp.arange(sq) + (sk - sq)  # align causal diagonal to the end

    def body(carry, inp):
        acc, m, l = carry
        kblk, vblk, ki = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32))
        k_pos = ki * block_k + jnp.arange(block_k)
        valid = (k_pos < sk)[None, None, None, :]
        if causal:
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
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

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = lax.scan(body, (acc0, m0, l0),
                              (kb, vb, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where((m > _NEG_INF / 2)[..., None], out, 0.0)  # no-key rows
    return out.astype(orig_dtype)


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------

def _head_group(bh: int, block_q: int, block_k: int,
                n_tiles: int = 1) -> int:
    """Heads per Pallas program. Per-program fixed overhead (~2-3 µs:
    launch + DMA setup) dominates short-seq attention when the grid has
    one program per (batch, head) — 384 programs for BERT-base bs=32.
    Batch G heads per program, bounded by the CONCURRENT (G, bq, bk) f32
    tiles' VMEM footprint (~16 MiB/core on v5e; the shared tile budget
    lives in ops/kernels — the rnn_scan timestep-block sizer accounts
    against the same number). ``n_tiles`` is how many such score-shaped
    tiles the kernel holds live at once: 1 for the forward (s; p
    overwrites it), 4 for the fused backward (s, p, dp, ds) — budgeting
    the backward as a single tile oversizes G and fails Mosaic lowering
    at large blocks."""
    from .kernels import vmem_tile_budget
    budget = vmem_tile_budget()
    g = 1
    while (g * 2 <= 8 and bh % (g * 2) == 0
           and g * 2 * block_q * block_k * 4 * n_tiles
           <= budget):
        g *= 2
    return g


def _vmem_limit(g: int, block_q: int, block_k: int, dp: int,
                itemsize: int, n_blocks: int, n_acc: int,
                n_tiles: int) -> int:
    """``vmem_limit_bytes`` for one flash kernel, counted from what it
    keeps in VMEM: ``n_blocks`` (G, block, dp) operand/result blocks in
    the input dtype (Pallas double-buffers each), ``n_acc`` f32
    (G, block, dp) scratch accumulators and ``n_tiles`` live f32
    (G, bq, bk) score tiles, plus a quarter for Mosaic's own temporaries.
    ``_head_group`` budgets the score tiles only; at f32 the operand
    blocks alone double, and the BERT-shape forward asked for 16.42 MiB
    of the 16 MiB a kernel gets without a limit. Never below that
    default."""
    from .kernels import VMEM_SCOPED_DEFAULT_BYTES
    block = g * max(block_q, block_k) * dp
    need = (2 * n_blocks * block * itemsize + n_acc * block * 4
            + n_tiles * g * block_q * block_k * 4)
    return max(VMEM_SCOPED_DEFAULT_BYTES, need + need // 4)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                  sm_scale, causal, block_q, block_k, nk, seq_q, seq_k,
                  need_mask):
    from jax.experimental import pallas as pl
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Causal: skip blocks strictly above the diagonal (no valid entries).
    diag_off = seq_k - seq_q
    run = True
    if causal:
        run = _causal_block_skip(qi, ki, block_q, block_k, seq_q, seq_k)

    @pl.when(run)
    def _compute():
        # dots take the INPUT dtype (bf16 under AMP) with f32
        # accumulation — an astype(f32) here would push the MXU onto its
        # ~6x slower f32 passes
        q = q_ref[...]                            # (G, block_q, d)
        k = k_ref[...]                            # (G, block_k, d)
        s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
        if need_mask or causal:
            # masking is real VPU work on a (bq, bk) tile — emitted only
            # when there is padding to hide or a causal wedge to cut
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = k_pos < seq_k
            if causal:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0) + diag_off
                valid = valid & (k_pos <= q_pos)
            s = jnp.where(valid[None], s, _NEG_INF)

        m_prev = m_s[:, :, :1]                    # (G, block_q, 1)
        m_cur = s.max(axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_s[:, :, :1] * alpha + p.sum(axis=2, keepdims=True)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
        acc_s[...] = acc_s[...] * alpha + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_s[:, :, :1], 1e-30)
        out = acc_s[...] / l
        # rows that never saw a valid key (m still at init) output zero —
        # the shared convention across every path in this module
        out = jnp.where(m_s[:, :, :1] > _NEG_INF / 2, out, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)
        # log-sum-exp per row: the residual the backward kernels need
        # (p = exp(s - lse) reconstructs softmax without the S×S matrix)
        lse = jnp.where(m_s[:, :, :1] > _NEG_INF / 2,
                        m_s[:, :, :1] + jnp.log(l), _NEG_INF)
        # 8-lane replication: narrowest layout the TPU tiling rules allow
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _pad_for_blocks(q, k, v, block_q, block_k):
    """Shared fwd/bwd tiling preamble: clamp block sizes, pad seq dims to
    block multiples and head_dim to the 128-lane tile, fold (B, H) →
    batch-of-heads. The backward's exp(s - lse) recompute is only correct
    when it uses EXACTLY these conventions — keep this the single source."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    dp = max(128, -(-d // 128) * 128)
    sqp = -(-sq // block_q) * block_q
    skp = -(-sk // block_k) * block_k

    def pad3(x, s_to, d_to):
        return jnp.pad(x, ((0, 0), (0, 0), (0, s_to - x.shape[2]),
                           (0, d_to - x.shape[3])))

    qp = pad3(q, sqp, dp).reshape(b * h, sqp, dp)
    kp = pad3(k, skp, dp).reshape(b * h, skp, dp)
    vp = pad3(v, skp, dp).reshape(b * h, skp, dp)
    return (qp, kp, vp, pad3, block_q, block_k, dp, sqp, skp,
            sqp // block_q, skp // block_k)


def _flash_fwd_pallas(q, k, v, causal: bool, sm_scale: float,
                      block_q: int = 512, block_k: int = 512,
                      interpret: bool = False):
    # 512x512 blocks measured 2.2x faster than 128x128 on one TPU chip
    # (8x12x2048x64 causal: 4.5ms vs 13ms; XLA blockwise scan: 9.7ms)
    """Pallas flash attention forward → (out, lse). Padding/tiling via
    _pad_for_blocks; zero-padded head dims cancel in QK^T and are sliced
    off the output."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    (qp, kp, vp, _, block_q, block_k, dp, sqp, skp, nq, nk) = \
        _pad_for_blocks(q, k, v, block_q, block_k)
    g = _head_group(b * h, block_q, block_k)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, nk=nk, seq_q=sq, seq_k=sk,
        need_mask=(skp != sk))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h // g, nq, nk),
        in_specs=[
            pl.BlockSpec((g, block_q, dp), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((g, block_k, dp), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((g, block_k, dp), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, block_q, dp), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((g, block_q, 8), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sqp, dp), q.dtype),
            jax.ShapeDtypeStruct((b * h, sqp, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, block_q, 128), jnp.float32),
            pltpu.VMEM((g, block_q, 128), jnp.float32),
            pltpu.VMEM((g, block_q, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # q, k, v, o blocks; m, l, acc scratch; s and p tiles
            vmem_limit_bytes=_vmem_limit(g, block_q, block_k, dp,
                                         q.dtype.itemsize, 4, 3, 2)),
        interpret=interpret,
    )(qp, kp, vp)
    return (out.reshape(b, h, sqp, dp)[:, :, :sq, :d],
            lse[:, :, 0].reshape(b, h, sqp)[:, :, :sq])


# ---------------------------------------------------------------------------
# Pallas TPU backward kernels (FlashAttention-2 style: recompute p from the
# saved per-row log-sum-exp; no S×S residual is ever materialized)
# ---------------------------------------------------------------------------

def _causal_block_skip(qi, ki, block_q, block_k, seq_q, seq_k):
    """True iff block (qi, ki) holds ANY valid causal entry — the shared
    skip predicate for the forward and both backward kernels (a divergence
    here would desynchronize forward and backward masking)."""
    return ki * block_k <= qi * block_q + block_q - 1 + (seq_k - seq_q)


def _bwd_mask(qi, ki, block_q, block_k, causal, seq_q, seq_k):
    k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
    valid = (k_pos < seq_k) & (q_pos < seq_q)
    if causal:
        valid = valid & (k_pos <= q_pos + (seq_k - seq_q))
    return valid


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_s, dv_s, *, sm_scale, causal,
                          block_q, block_k, nq, seq_q, seq_k, need_mask):
    from jax.experimental import pallas as pl
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    run = True
    if causal:  # this k block only touches q rows at/after the diagonal
        run = _causal_block_skip(qi, ki, block_q, block_k, seq_q, seq_k)

    @pl.when(run)
    def _compute():
        # operands keep the input dtype (bf16 under AMP), f32 accumulate
        # — see the forward kernel's MXU-pass note
        q = q_ref[...]                              # (G, bq, d)
        k = k_ref[...]                              # (G, bk, d)
        v = v_ref[...]
        do = do_ref[...]                            # (G, bq, d)
        lse = lse_ref[...][:, :, :1]                # (G, bq, 1)
        delta = delta_ref[...][:, :, :1]            # (G, bq, 1)
        s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)                        # (G, bq, bk)
        if need_mask or causal:
            valid = _bwd_mask(qi, ki, block_q, block_k, causal,
                              seq_q, seq_k)
            p = jnp.where(valid[None], p, 0.0)
        dv_s[...] += lax.dot_general(p.astype(do.dtype), do,
                                     (((1,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale)
        dk_s[...] += lax.dot_general(ds.astype(q.dtype), q,
                                     (((1,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[...] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_s[...].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, *, sm_scale, causal,
                            block_q, block_k, seq_q, seq_k, need_mask):
    """Single-block backward (nq == nk == 1, the short-seq fast path):
    one program computes dq, dk AND dv, reconstructing the softmax block
    ONCE — the two-kernel general path pays the s = qk^T + exp recompute
    twice, and that VPU work dominates short-seq attention (r5)."""
    q = q_ref[...]                                  # (G, bq, d)
    k = k_ref[...]                                  # (G, bk, d)
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[...][:, :, :1]
    delta = delta_ref[...][:, :, :1]
    s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32) * sm_scale
    p = jnp.exp(s - lse)                            # (G, bq, bk)
    if need_mask or causal:
        valid = _bwd_mask(0, 0, block_q, block_k, causal, seq_q, seq_k)
        p = jnp.where(valid[None], p, 0.0)
    pb = p.astype(do.dtype)
    dv_ref[...] = lax.dot_general(
        pb, do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
    dq_ref[...] = lax.dot_general(
        ds, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[...] = lax.dot_general(
        ds, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_s, *, sm_scale, causal, block_q,
                         block_k, nk, seq_q, seq_k, need_mask):
    from jax.experimental import pallas as pl
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    run = True
    if causal:
        run = _causal_block_skip(qi, ki, block_q, block_k, seq_q, seq_k)

    @pl.when(run)
    def _compute():
        # operands keep the input dtype (bf16 under AMP), f32 accumulate
        q = q_ref[...]                              # (G, bq, d)
        k = k_ref[...]                              # (G, bk, d)
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][:, :, :1]
        delta = delta_ref[...][:, :, :1]
        s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)
        if need_mask or causal:
            valid = _bwd_mask(qi, ki, block_q, block_k, causal,
                              seq_q, seq_k)
            p = jnp.where(valid[None], p, 0.0)
        dp = lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_s[...] += lax.dot_general(ds.astype(k.dtype), k,
                                     (((2,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[...] = dq_s[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal: bool, sm_scale: float,
                      block_q: int = 512, block_k: int = 512,
                      interpret: bool = False):
    """Pallas flash attention backward: dq via a (q-parallel, k-inner)
    kernel, dk/dv via a (k-parallel, q-inner) kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    (qp, kp, vp, pad3, block_q, block_k, dp, sqp, skp, nq, nk) = \
        _pad_for_blocks(q, k, v, block_q, block_k)
    dop = pad3(do.astype(q.dtype), sqp, dp).reshape(b * h, sqp, dp)
    # delta_i = rowsum(dO_i * O_i) (cheap; XLA fuses into the pad)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    dl = jnp.pad(delta.reshape(b * h, sq), ((0, 0), (0, sqp - sq)))
    lsep = jnp.pad(lse.reshape(b * h, sq), ((0, 0), (0, sqp - sq)))
    # 8-lane replication (TPU block tiling minimum for a row vector)
    dl = jnp.broadcast_to(dl[..., None], dl.shape + (8,))
    lsep = jnp.broadcast_to(lsep[..., None], lsep.shape + (8,))
    g = _head_group(b * h, block_q, block_k, n_tiles=4)
    need_mask = (skp != sk) or (sqp != sq)

    if nq == 1 and nk == 1:
        bspec = lambda blk: pl.BlockSpec((g, blk, dp),
                                         lambda bh: (bh, 0, 0))
        rspec = pl.BlockSpec((g, block_q, 8), lambda bh: (bh, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
                block_q=block_q, block_k=block_k, seq_q=sq, seq_k=sk,
                need_mask=need_mask),
            grid=(b * h // g,),
            in_specs=[bspec(block_q), bspec(block_k), bspec(block_k),
                      bspec(block_q), rspec, rspec],
            out_specs=[bspec(block_q), bspec(block_k), bspec(block_k)],
            out_shape=[jax.ShapeDtypeStruct((b * h, sqp, dp), q.dtype),
                       jax.ShapeDtypeStruct((b * h, skp, dp), k.dtype),
                       jax.ShapeDtypeStruct((b * h, skp, dp), v.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                # q, k, v, do in, dq, dk, dv out; s, p, dp, ds tiles
                vmem_limit_bytes=_vmem_limit(g, block_q, block_k, dp,
                                             q.dtype.itemsize, 7, 0, 4)),
            interpret=interpret,
        )(qp, kp, vp, dop, lsep, dl)
        return (dq.reshape(b, h, sqp, dp)[:, :, :sq, :d],
                dk.reshape(b, h, skp, dp)[:, :, :sk, :d],
                dv.reshape(b, h, skp, dp)[:, :, :sk, :d])

    q_spec = pl.BlockSpec((g, block_q, dp), lambda bh, a, c: (bh, a, 0))
    row_spec = pl.BlockSpec((g, block_q, 8), lambda bh, a, c: (bh, a, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          nk=nk, seq_q=sq, seq_k=sk, need_mask=need_mask),
        grid=(b * h // g, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec((g, block_k, dp), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((g, block_k, dp), lambda bh, qi, ki: (bh, ki, 0)),
            q_spec, row_spec, row_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, block_q, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # q, k, v, do in, dq out; dq scratch; s, p, dp, ds tiles
            vmem_limit_bytes=_vmem_limit(g, block_q, block_k, dp,
                                         q.dtype.itemsize, 5, 1, 4)),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dl)

    k_spec = pl.BlockSpec((g, block_k, dp), lambda bh, ki, qi: (bh, ki, 0))
    qrow = pl.BlockSpec((g, block_q, dp), lambda bh, ki, qi: (bh, qi, 0))
    rrow = pl.BlockSpec((g, block_q, 8), lambda bh, ki, qi: (bh, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          nq=nq, seq_q=sq, seq_k=sk, need_mask=need_mask),
        grid=(b * h // g, nk, nq),
        in_specs=[qrow, k_spec, k_spec, qrow, rrow, rrow],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, skp, dp), k.dtype),
                   jax.ShapeDtypeStruct((b * h, skp, dp), v.dtype)],
        scratch_shapes=[pltpu.VMEM((g, block_k, dp), jnp.float32),
                        pltpu.VMEM((g, block_k, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # q, k, v, do in, dk, dv out; dk, dv scratch; 4 score tiles
            vmem_limit_bytes=_vmem_limit(g, block_q, block_k, dp,
                                         q.dtype.itemsize, 6, 2, 4)),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dl)

    return (dq.reshape(b, h, sqp, dp)[:, :, :sq, :d],
            dk.reshape(b, h, skp, dp)[:, :, :sk, :d],
            dv.reshape(b, h, skp, dp)[:, :, :sk, :d])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_tpu(q, k, v, causal, sm_scale, interpret):
    return _flash_fwd_pallas(q, k, v, causal, sm_scale,
                             interpret=interpret)[0]


def _flash_tpu_fwd(q, k, v, causal, sm_scale, interpret):
    o, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale,
                               interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_tpu_bwd(causal, sm_scale, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_bwd_pallas(q, k, v, o, lse, g, causal, sm_scale,
                             interpret=interpret)


_flash_tpu.defvjp(_flash_tpu_fwd, _flash_tpu_bwd)


# ---------------------------------------------------------------------------
# Public flash_attention with recompute backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, sm_scale):
    """XLA (non-Pallas) flash path: blockwise scan forward, recompute
    backward. The TPU default goes through _flash_tpu instead."""
    return _attention_xla(q, k, v, causal, sm_scale)


def _flash_fwd(q, k, v, causal, sm_scale):
    return _flash(q, k, v, causal, sm_scale), (q, k, v)


def _flash_bwd(causal, sm_scale, res, g):
    q, k, v = res
    # Flash-style backward: recompute attention blockwise (no S×S residual).
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _attention_xla(q_, k_, v_, causal, sm_scale),
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


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    use_pallas: Optional[bool] = None,
                    valid_length=None):
    """Fused memory-efficient attention on (B, H, S, D) tensors.

    On TPU forward and backward run as Pallas kernels (_flash_tpu:
    FlashAttention-2 dq/dkv kernels off the saved log-sum-exp); elsewhere
    a blockwise lax.scan implementation with identical online-softmax math
    and a recompute-based backward. ``valid_length`` (B,) masks padded
    keys; that path uses the blockwise implementation (still O(S·block)
    memory, never an S×S score matrix).
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise MXNetError("flash_attention expects (batch, heads, seq, dim)")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if valid_length is not None:
        vl = jnp.asarray(valid_length, jnp.float32)
        return _flash_vl(q, k, v, vl, causal, float(sm_scale))
    if use_pallas is None:
        # the shared MXNET_PALLAS three-tier gate (ops/kernels):
        # compiled kernels on TPU, interpret-mode bodies when forced
        # on other backends, blockwise-XLA reference otherwise
        from .kernels import dispatch as _kdispatch
        path, _ = _kdispatch("flash_attention")
        if path != "xla":
            return _flash_tpu(q, k, v, causal, float(sm_scale),
                              path == "interpret")
        return _flash(q, k, v, causal, float(sm_scale))
    if use_pallas:
        # full-Pallas path: flash forward AND FlashAttention-2-style
        # backward kernels (dq + dkv) off the saved log-sum-exp
        return _flash_tpu(q, k, v, causal, float(sm_scale), False)
    return _flash(q, k, v, causal, float(sm_scale))


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
