"""The core of a state-space mixer (Mamba-2, "SSD": Dao & Gu 2024,
arXiv:2405.21060): a causal depthwise conv, the selective scan in its
chunked form, and the gated group RMSNorm behind it; and the gated short
convolution of LFM2's conv mixers (:func:`gated_short_conv`), the same
conv between two multiplicative gates and nothing else.

No reference analog (the reference's one recurrence is the fused RNN of
ops/rnn.py). A head h of width P carries a state ``H`` (P x N) along the
sequence::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T        A < 0, dt_t > 0
    y_t = H_t C_t + D x_t

with ``B_t``, ``C_t`` (N,) shared by the heads of a group. Three forms of
the same function live here and in the tests:

- :func:`ssd_scan_reference`: the recurrence, a ``lax.scan`` over time in
  float32. The oracle; O(S) steps, never on a step's path.
- :func:`ssd_scan`: the CHUNKED form the program runs. Inside a chunk of
  ``Q`` positions, with ``cs`` the in-chunk cumulative sum of ``dt A``,

      Y_diag = ((C B^T) * L) (dt x),   L[t, s] = exp(cs_t - cs_s), s <= t

  (the mask stands BEFORE the exponential: above the diagonal ``cs_t -
  cs_s`` is positive and may overflow); a chunk's own state ``sum_s
  exp(cs_Q - cs_s) dt_s x_s B_s^T``; the state carried INTO chunk c + 1,
  ``exp(cs_Q(c)) H_in(c) + state(c)``, a short ``lax.scan`` over the
  chunks; and ``Y_off[t] = exp(cs_t) H_in C_t``. Matrix products inside
  chunks, S / Q sequential steps. ``dt``, ``A``, ``cs``, ``L`` and the
  states are float32 whatever the operands are; the products take their
  operands in x's dtype (bf16 under AMP) and accumulate in float32.
- the quadratic form (one S x S ``L`` a head), the benchmark's plain
  reference (benchmark/grid/configs/nemotron-3-nano-30b-a3b.py).

Two tiers run the chunked form, picked by the kernel layer's gate
(``kernels.dispatch("ssd_scan")``; ``mx_ssd_scan_total{tier}`` counts the
traced calls by tier, ``mx_ssd_scan_chunks_total`` their chunks):

- the kernels of ops/kernels/ssd_scan.py on one TPU chip (their bodies
  under the interpreter with ``MXNET_PALLAS=on`` elsewhere) at chunk 128
  with the state and a group's head lanes whole multiples of 128 lanes,
  bf16 or float32: a grid of (batch, group, chunk) that keeps a chunk's
  (Q x Q) matrices in VMEM and carries the group's state along the chunk
  axis, forward and, last chunk to first, backward. A ``jax.custom_vjp``
  keeps the operands and the states ``H_in`` entering the chunks (named
  :data:`SSD_STATES`) and makes ``cs``, ``L``, ``C B^T`` again in VMEM; y
  is named :data:`SSD_OUTPUT`;
- :func:`_ssd_chunked`, XLA's, everywhere else (other backends, a GSPMD
  mesh, ``MXNET_PALLAS=off``, shapes the kernels decline, with the reason
  in ``kernels.decisions()``) and as the oracle beside the recurrence: a
  ``jax.checkpoint`` that keeps its operands and ``H_in`` (the same name)
  and makes ``cs``, ``L``, ``C B^T`` again, so the (Q x Q) decay matrices
  of every chunk and head are never kept from forward to backward, though
  XLA writes them to HBM inside a pass. A caller whose own
  ``jax.checkpoint`` spans the call (``gluon.nn.Mamba2Mixer``) passes
  ``recompute=False`` and names :data:`SSD_STATES` (and
  :data:`SSD_OUTPUT`) in its policy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

__all__ = ["causal_conv1d", "gated_short_conv", "ssd_scan",
           "ssd_scan_reference", "gated_group_rms_norm", "SSD_STATES",
           "SSD_OUTPUT"]

#: the name the chunk-boundary states carry for a ``jax.checkpoint``
#: policy (``jax.checkpoint_policies.save_only_these_names``)
SSD_STATES = "ssd_chunk_states"
#: the name the kernel tier's y carries: a caller's checkpoint that keeps
#: it with the states launches the forward kernel once (the two are its
#: only outputs); the XLA tier names no such value
SSD_OUTPUT = "ssd_scan_output"

_F32 = jnp.float32


def causal_conv1d(x, weight, bias=None):
    """Causal depthwise conv along the sequence: ``out[t, c] = bias[c] +
    sum_j weight[c, j] x[t - K + 1 + j, c]``, zeros before the sequence
    starts. ``x`` (B, S, C); ``weight`` (C, K); ``bias`` (C,) or None.
    Summed in float32, returned in x's dtype."""
    taps, s = weight.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(_F32)
    out = sum(padded[:, j:j + s].astype(_F32) * w[:, j]
              for j in range(taps))
    if bias is not None:
        out = out + bias.astype(_F32)
    return out.astype(x.dtype)


def gated_short_conv(bcx, weight):
    """The gated short convolution of LFM2's conv mixers, between their
    two projections: ``bcx`` (B, S, 3C) is ``[B | C | x]``, ``weight``
    (C, K) a causal depthwise conv without bias; returns ``C *
    causal_conv1d(B * x, weight)`` (B, S, C). No state and no activation.
    Gates and conv summed in float32, returned in bcx's dtype, all of it
    (and its backward) under the ``short_conv`` scope."""
    from ..telemetry.names import SCOPE_SHORT_CONV
    with jax.named_scope(SCOPE_SHORT_CONV):
        c = bcx.shape[-1] // 3
        b, gate, x = (bcx[..., i * c:(i + 1) * c].astype(_F32)
                      for i in range(3))
        return (gate * causal_conv1d(b * x, weight)).astype(bcx.dtype)


def gated_group_rms_norm(y, z, gain, groups: int, eps: float = 1e-5):
    """``RMSNorm_groups(y * silu(z)) * gain``: the gate BEFORE the norm,
    the mean square taken over each of the ``groups`` equal slices of the
    last axis. float32 inside, y's dtype out."""
    v = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    grouped = v.reshape(v.shape[:-1] + (groups, v.shape[-1] // groups))
    grouped = grouped * lax.rsqrt(
        jnp.mean(jnp.square(grouped), -1, keepdims=True) + eps)
    return (grouped.reshape(v.shape) * gain.astype(_F32)).astype(y.dtype)


def _per_head(a, heads: int):
    """(B, S, G, N) -> (B, S, H, N): head h reads group h // (H / G)."""
    return jnp.repeat(a, heads // a.shape[2], axis=2)


def ssd_scan_reference(x, dt, A, B, C, D=None):
    """The recurrence, step by step in float32: ``x`` (B, S, H, P), ``dt``
    (B, S, H) after its softplus, ``A`` (H,) negative, ``B``, ``C`` (B, S,
    G, N), ``D`` (H,) or None. Returns y (B, S, H, P) float32."""
    heads = x.shape[2]
    x, dt, A = x.astype(_F32), dt.astype(_F32), A.astype(_F32)
    Bh, Ch = (_per_head(a.astype(_F32), heads) for a in (B, C))

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], -1)

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + (B.shape[-1],), _F32)
    _, y = lax.scan(step, start, tuple(jnp.moveaxis(a, 1, 0)
                                       for a in (x, dt, Bh, Ch)))
    y = jnp.moveaxis(y, 0, 1)
    return y if D is None else y + D.astype(_F32)[:, None] * x


def _product(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def _ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """The chunked form (the module's docstring); shapes as
    :func:`ssd_scan`. Axes: b batch, c chunk, t / s position in the chunk
    (read / written), g group, r head in its group, p head lane, n state
    lane."""
    batch, seq, heads, width = x.shape
    groups, n = B.shape[2:]
    pad = -seq % chunk
    if pad:
        # dt = 0 past the end: the state neither decays nor is written
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                               * (a.ndim - 2)) for a in (x, dt, B, C))
    chunks = (seq + pad) // chunk
    per_group = heads // groups
    xc = x.reshape(batch, chunks, chunk, groups, per_group, width)
    Bc = B.reshape(batch, chunks, chunk, groups, n)
    Cc = C.reshape(batch, chunks, chunk, groups, n)
    # (b, c, g, r, position): a head's scalars of one chunk lie together
    dtc = jnp.moveaxis(dt.astype(_F32).reshape(
        batch, chunks, chunk, groups, per_group), 2, -1)
    cs = jnp.cumsum(dtc * A.astype(_F32).reshape(groups, per_group, 1), -1)

    # inside a chunk: ((C B^T) * L) (dt x)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))
    scores = _product("bctgn,bcsgn->bcgts", Cc, Bc)
    mixed = (scores[:, :, :, None] * decay * dtc[..., None, :])
    y = _product("bcgrts,bcsgrp->bctgrp", mixed.astype(x.dtype), xc)

    # a chunk's own state, then the state carried into each chunk
    to_end = jnp.moveaxis(jnp.exp(cs[..., -1:] - cs) * dtc, -1, 2)
    states = _product("bcsgn,bcsgrp->bcgrpn", Bc,
                      (xc * to_end[..., None]).astype(x.dtype))

    def carry(state, inputs):
        through, own = inputs
        return through[..., None, None] * state + own, state

    _, entering = lax.scan(
        carry, jnp.zeros(states.shape[:1] + states.shape[2:], _F32),
        (jnp.moveaxis(jnp.exp(cs[..., -1]), 1, 0),
         jnp.moveaxis(states, 1, 0)))
    entering = checkpoint_name(jnp.moveaxis(entering, 0, 1), SSD_STATES)
    y = y + _product("bctgn,bcgrpn->bctgrp", Cc, entering.astype(x.dtype)) \
        * jnp.moveaxis(jnp.exp(cs), -1, 2)[..., None]
    y = y.reshape(batch, seq + pad, heads, width)[:, :seq]
    if D is not None:
        y = y + D.astype(_F32)[:, None] * x[:, :seq]
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ssd_kernels(groups, kernel, x, dt, A, B, C, D):
    """The chunked form on a kernel tier (ops/kernels/ssd_scan.py): ``x``
    (B, S, H P), ``B`` and ``C`` (B, S, G N), S whole chunks. ``kernel =
    (tier, precision)`` as ``ops.moe._experts`` has it: the matmul
    precision asked for where the scan was called, which its backward
    keeps though it is traced after that ``with`` block has closed."""
    return _ssd_kernels_fwd(groups, kernel, x, dt, A, B, C, D)[0]


def _kernel_keywords(groups, kernel):
    tier, precision = kernel
    return {"groups": groups, "precision": precision,
            "interpret": tier == "interpret"}


def _ssd_kernels_fwd(groups, kernel, x, dt, A, B, C, D):
    from .kernels import ssd_scan as kernels
    y, entering = kernels.forward(x, dt, A, B, C, D,
                                  **_kernel_keywords(groups, kernel))
    # kept from forward to backward: the operands and the states entering
    # the chunks, the latter by the name a caller's checkpoint knows
    return (checkpoint_name(y, SSD_OUTPUT),
            (x, dt, A, B, C, D, checkpoint_name(entering, SSD_STATES)))


def _ssd_kernels_bwd(groups, kernel, kept, dy):
    from .kernels import ssd_scan as kernels
    operands = kept[:6]
    grads = kernels.backward(*kept, dy, **_kernel_keywords(groups, kernel))
    return tuple(g.astype(a.dtype) for g, a in zip(grads, operands))


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def _ssd_on_kernels(x, dt, A, B, C, D, chunk, kernel):
    """:func:`_ssd_kernels` behind what lays its operands out: the heads'
    and groups' lanes side by side (no copy), the tail padded to a whole
    chunk with ``dt = 0``, a skip of zero where there is none."""
    batch, seq, heads, width = x.shape
    groups = B.shape[2]
    flat = [a.reshape(batch, seq, -1) for a in (x, B, C)] + [dt]
    pad = -seq % chunk
    if pad:
        flat = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in flat]
    x_, B_, C_, dt_ = flat
    y = _ssd_kernels(groups, kernel, x_, dt_, A, B_, C_,
                     jnp.zeros_like(A) if D is None else D)
    return y[:, :seq].reshape(x.shape)


def ssd_scan(x, dt, A, B, C, D=None, chunk: int = 128,
             recompute: bool = True):
    """The selective scan of a state-space mixer, chunked. ``x`` (B, S,
    H, P); ``dt`` (B, S, H) after its softplus and ``A`` (H,) negative,
    both float32; ``B``, ``C`` (B, S, G, N), head h reading group h //
    (H / G); ``D`` (H,) the skip's weight or None; S need be no multiple
    of ``chunk``. Returns y (B, S, H, P) in x's dtype.

    The kernel layer's gate picks the tier (``"ssd_scan"`` in
    ``kernels.decisions()``, ``mx_ssd_scan_total{tier}``): the kernels of
    ops/kernels/ssd_scan.py on one TPU chip at shapes they take,
    :func:`_ssd_chunked` everywhere else.

    ``recompute`` (default): the XLA tier is its own ``jax.checkpoint``
    that keeps the operands and the chunk-boundary states. False where the
    caller's checkpoint spans the call. The kernel tier's custom VJP keeps
    just those either way."""
    from .kernels import count_traced, dispatch
    from .kernels import ssd_scan as kernels
    count_traced("SSD_SCAN_CHUNKS", n=-(-x.shape[1] // chunk))
    precision = jax.config.jax_default_matmul_precision
    why = kernels.supported(x.shape[2], x.shape[3], B.shape[2], B.shape[3],
                            chunk, x.dtype, B.dtype, C.dtype,
                            precision=precision)
    tier = dispatch("ssd_scan", supported=why is None, reason=why)[0]
    count_traced("SSD_SCAN", "tier", tier)
    if tier != "xla":
        return _ssd_on_kernels(x, dt, A, B, C, D, chunk, (tier, precision))
    scan = functools.partial(_ssd_chunked, chunk=chunk)
    if recompute:
        scan = jax.checkpoint(
            scan, policy=jax.checkpoint_policies.save_only_these_names(
                SSD_STATES))
    return scan(x, dt, A, B, C, D)
