"""Time-fused recurrent scan kernel (LSTM / GRU / vanilla RNN).

The ``lax.scan`` reference in ops/rnn.py compiles into a while loop
whose body is scheduled as separate kernels: the h2h matmul, the gate
fusion and the carry update each round-trip (N, G*H) intermediates
through HBM every timestep, and the backward additionally saves the
per-step linearization residuals — stacked (T, N, G*H) tensors the
fusion census ranks as the worst boundary materializations of the LSTM
leg. This kernel is the whole-program-ownership move for the
recurrence: ONE Pallas program walks a block of timesteps with h (and
c) pinned in VMEM, the weights resident, and only x-projections in /
hidden states out touching HBM; the custom VJP re-derives the gates in
the backward from the saved hidden trajectory (one extra matmul per
step, FlashAttention-style recompute) instead of materializing
residuals.

Gate-order parity with ops/rnn.py (and src/operator/rnn_impl.h):
LSTM [i, f, g, o], GRU [r, z, n] — converted checkpoints drop in, and
the fp32 forward/backward are BIT-exact against the scan reference
(the gate math mirrors the reference expression for expression,
including the cotangent groupings jax's autodiff emits).

Layout: hidden padded to the 128-lane tile, batch to the dtype's
sublane tile, time to the block; gate blocks pad INDEPENDENTLY so gate
g still lives at rows ``[g*Hp, (g+1)*Hp)``. Padded tail timesteps need
no masking: zero-padded inputs keep the tail finite in the forward
(those rows are sliced off), and the reverse-time backward visits the
tail first with zero cotangents, so every tail contribution is an
exact zero.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import (VMEM_BYTES_PER_CORE, VMEM_SCOPED_DEFAULT_BYTES, dispatch,
               vmem_tile_budget)

__all__ = ["rnn_scan", "rnn_decode_step", "rnn_verify_scan",
           "scan_supported"]

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}
_MAX_BLOCK_T = 16      # unrolled in-kernel; bounds Mosaic program size

#: test hook: force a timestep-block size (None = auto). The grid-edge
#: tests use it to exercise multi-step blocks with tail padding under
#: interpret mode.
_FORCE_BLOCK_T = None


def _sublane(dtype) -> int:
    return 16 if dtype == jnp.bfloat16 else 8


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_plan(seq: int, np_: int, g: int, hp: int, itemsize: int,
               interpret: bool):
    """→ ``(block_t, vmem_limit_bytes)``, or None when the weights plus
    ONE timestep of tiles do not fit VMEM (``scan_supported`` turns that
    into the XLA tier with the reason).

    Sized once, from the BACKWARD call — the forward streams a subset
    of its operands, and both must agree on block_t because the saved
    trajectories are padded to it. Counted, all resident for the whole
    grid: ``w_hh`` in and ``dW`` out (Pallas double-buffers every
    blocked operand, constant index map or not), the f32 ``dW``
    scratch, the (N, H) carry blocks, and the f32 gate temporaries of
    one unrolled step. What is left of three quarters of the physical
    VMEM (the rest is Mosaic's own scratch) goes to the double-buffered
    per-timestep tiles — xw in, dxw out, hprev/cprev/cs/dys, budgeted
    as 2 gate-wide + 6 hidden-wide — capped by the shared tile budget
    (``vmem_tile_budget()``; ops.attention._head_group sizes against
    the same accessor). In interpret mode: block 1, so the grid loop
    mirrors the lax.scan reference's one-step body structure — that is
    what makes the fp32 forward BIT-identical (XLA re-fuses a multi-step
    unrolled body differently, which costs a ulp)."""
    if interpret:
        bt = _FORCE_BLOCK_T if _FORCE_BLOCK_T is not None else 1
        return int(min(bt, max(1, seq))), None
    per_step = np_ * (2 * g * hp + 6 * hp) * itemsize
    resident = ((4 * itemsize + 4) * g * hp * hp
                + 16 * np_ * hp * 4 + 8 * np_ * g * hp * 4)
    fit = (VMEM_BYTES_PER_CORE * 3 // 4 - resident) // (2 * per_step)
    if fit < 1:
        return None
    bt = (_FORCE_BLOCK_T if _FORCE_BLOCK_T is not None
          else max(1, vmem_tile_budget() // per_step))
    bt = int(min(bt, fit, _MAX_BLOCK_T, max(1, seq)))
    limit = resident + 2 * bt * per_step + 4 * 1024 * 1024
    return bt, max(VMEM_SCOPED_DEFAULT_BYTES, limit)


def _geometry(n: int, gh: int, mode: str, dtype):
    """(g, h, hp, np_) — the padded layout of one call."""
    g = _GATES[mode]
    h = gh // g
    return g, h, _pad_to(h, 128), _pad_to(n, _sublane(dtype))


def scan_supported(xw, h0, c0, mode: str) -> Optional[str]:
    """None when the kernel covers this call, else the fallback reason
    (the dispatch gate reports it; the XLA reference handles the call)."""
    if mode not in _GATES:
        return f"unknown mode {mode!r}"
    if xw.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {xw.dtype} not kernelized (f32/bf16 only)"
    if xw.ndim != 3 or xw.shape[0] < 1:
        return "expects (T, N, G*H) with T >= 1"
    t, n, gh = xw.shape
    g, _, hp, np_ = _geometry(n, gh, mode, xw.dtype)
    if _vmem_plan(t, np_, g, hp, jnp.dtype(xw.dtype).itemsize,
                  False) is None:
        return (f"w_hh ({g}x{hp}x{hp}) with its dW accumulators and one "
                "timestep of tiles exceed VMEM")
    return None


def _pad_gated(a, g: int, h: int, hp: int, axis: int):
    """Pad the gate-blocked axis (size g*h) to g*hp keeping gate g's
    block at [g*hp, (g+1)*hp)."""
    shape = a.shape
    split = shape[:axis] + (g, h) + shape[axis + 1:]
    pad = [(0, 0)] * (len(shape) + 1)
    pad[axis + 1] = (0, hp - h)
    out = jnp.pad(a.reshape(split), pad)
    return out.reshape(shape[:axis] + (g * hp,) + shape[axis + 1:])


# ---------------------------------------------------------------------------
# gate math — expression-for-expression mirror of ops/rnn.py _step_fns
# (forward) and of the cotangent chains jax emits for them (backward);
# any re-grouping here breaks fp32 bit parity with the scan reference
# ---------------------------------------------------------------------------

def _fwd_step(mode, xw_t, h, c, hw, b):
    """One timestep from precomputed hw = h @ w_hh.T. Returns (h, c)."""
    if mode == "lstm":
        gates = xw_t + hw + b
        hp = gates.shape[-1] // 4
        gi, gf, gg, go = (gates[:, k * hp:(k + 1) * hp] for k in range(4))
        i, f, o = (jax.nn.sigmoid(gi), jax.nn.sigmoid(gf),
                   jax.nn.sigmoid(go))
        g = jnp.tanh(gg)
        c_new = f * c + i * g
        return o * jnp.tanh(c_new), c_new
    if mode == "gru":
        hwb = hw + b
        hp = hwb.shape[-1] // 3
        xr, xz, xn = (xw_t[:, k * hp:(k + 1) * hp] for k in range(3))
        hr, hz, hn = (hwb[:, k * hp:(k + 1) * hp] for k in range(3))
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        return (1.0 - z) * n + z * h, None
    act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu
    return act(xw_t + hw + b), None


def _dtanh(t, y):
    """Cotangent through tanh with saved output y, in the exact form
    jax's tanh rule emits — u = t·(1−y); u + u·y — NOT t·(1−y²):
    the two differ in the last ulp and would break bit parity."""
    u = t * (1.0 - y)
    return u + u * y


def _dsigmoid(t, s):
    """Cotangent through logistic with saved output s (jax's form:
    t · (s·(1−s)))."""
    return t * (s * (1.0 - s))


def _bwd_step(mode, xw_t, h_prev, c_prev, c_new, y, hw, b, dy,
              dh_carry, dc_carry):
    """One reverse timestep. Returns (dgates→dxw, dhw-for-weight-grads,
    dh_carry', dc_carry')."""
    dh = dy + dh_carry
    if mode == "lstm":
        gates = xw_t + hw + b
        hp = gates.shape[-1] // 4
        gi, gf, gg, go = (gates[:, k * hp:(k + 1) * hp] for k in range(4))
        i, f, o = (jax.nn.sigmoid(gi), jax.nn.sigmoid(gf),
                   jax.nn.sigmoid(go))
        g = jnp.tanh(gg)
        tc = jnp.tanh(c_new)
        # the scan transpose interleaves the carry add INSIDE the tanh
        # chain: dc = (dc_carry + u) + u*tc — associativity is not
        # bit-free, so mirror the grouping exactly
        u = (dh * o) * (1.0 - tc)
        dc = dc_carry + u + u * tc
        dgi = _dsigmoid(dc * g, i)
        dgf = _dsigmoid(dc * c_prev, f)
        dgg = _dtanh(dc * i, g)
        dgo = _dsigmoid(dh * tc, o)
        dgates = jnp.concatenate([dgi, dgf, dgg, dgo], axis=-1)
        return dgates, dgates, None, dc * f
    if mode == "gru":
        hwb = hw + b
        hp = hwb.shape[-1] // 3
        xr, xz, xn = (xw_t[:, k * hp:(k + 1) * hp] for k in range(3))
        hr, hz, hn = (hwb[:, k * hp:(k + 1) * hp] for k in range(3))
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        dz = dh * h_prev - dh * n
        dn_pre = _dtanh(dh * (1.0 - z), n)
        dr = dn_pre * hn
        dhn = dn_pre * r
        dr_pre = _dsigmoid(dr, r)
        dz_pre = _dsigmoid(dz, z)
        dxw = jnp.concatenate([dr_pre, dz_pre, dn_pre], axis=-1)
        dhw = jnp.concatenate([dr_pre, dz_pre, dhn], axis=-1)
        return dxw, dhw, dh * z, None
    if mode == "rnn_tanh":
        dpre = _dtanh(dh, y)
    else:
        dpre = jnp.where(y > 0, dh, jnp.zeros_like(dh))
    return dpre, dpre, None, None


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _dot_f32(a, b, ca: int, cb: int):
    """MXU dot contracting ``a`` dim ``ca`` with ``b`` dim ``cb``:
    operands in the weight dtype (bf16 under AMP), f32 accumulation —
    Mosaic rejects a narrower accumulator, and the gate math downstream
    then runs in f32 and is cast once, on store. For f32 operands this
    is the plain dot, so fp32 bit parity with the reference holds."""
    return lax.dot_general(a.astype(b.dtype), b, (((ca,), (cb,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _fwd_kernel(mode, block_t, *refs):
    from jax.experimental import pallas as pl
    lstm = mode == "lstm"
    if lstm:
        (xw_ref, h0_ref, c0_ref, w_ref, b_ref, ys_ref, cs_ref,
         h_s, c_s) = refs
    else:
        xw_ref, h0_ref, w_ref, b_ref, ys_ref, h_s = refs
        c0_ref = cs_ref = c_s = None

    @pl.when(pl.program_id(0) == 0)
    def _init():
        h_s[...] = h0_ref[...]
        if lstm:
            c_s[...] = c0_ref[...]

    w = w_ref[...]                          # (G*Hp, Hp), resident
    b = b_ref[...]                          # (1, G*Hp)
    for i in range(block_t):
        h = h_s[...]
        hw = _dot_f32(h, w, 1, 1)
        h_new, c_new = _fwd_step(mode, xw_ref[i], h,
                                 c_s[...] if lstm else None, hw, b)
        h_new = h_new.astype(h_s.dtype)
        h_s[...] = h_new
        ys_ref[i] = h_new
        if lstm:
            c_new = c_new.astype(c_s.dtype)
            c_s[...] = c_new
            cs_ref[i] = c_new


def _bwd_kernel(mode, block_t, nt, seq, *refs):
    from jax.experimental import pallas as pl
    lstm = mode == "lstm"
    if lstm:
        (xw_ref, hp_ref, cp_ref, cs_ref, w_ref, b_ref, dy_ref, dct_ref,
         dxw_ref, dh0_ref, dc0_ref, dw_ref, db_ref,
         dh_s, dc_s, dw_s, db_s) = refs
        ys_ref = None
    else:
        (xw_ref, hp_ref, ys_ref, w_ref, b_ref, dy_ref,
         dxw_ref, dh0_ref, dw_ref, db_ref, dh_s, dw_s, db_s) = refs
        cp_ref = cs_ref = dct_ref = dc0_ref = dc_s = None

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dh_s[...] = jnp.zeros_like(dh_s)
        dw_s[...] = jnp.zeros_like(dw_s)
        db_s[...] = jnp.zeros_like(db_s)
        if lstm:
            dc_s[...] = jnp.zeros_like(dc_s)

    w = w_ref[...]
    b = b_ref[...]
    for i in reversed(range(block_t)):
        h_prev = hp_ref[i]
        hw = _dot_f32(h_prev, w, 1, 1)
        dc_in = None
        if lstm:
            # c_T's cotangent seeds the reverse carry exactly at step
            # seq-1 (the scan transpose's init carry); padded tail
            # steps (t >= seq, walked first) keep the zero carry so
            # every tail contribution stays an exact zero
            t_idx = (nt - 1 - pl.program_id(0)) * block_t + i
            dc_in = jnp.where(t_idx == seq - 1, dct_ref[...],
                              dc_s[...])
        dxw, dhw, dh_dir, dc_new = _bwd_step(
            mode, xw_ref[i], h_prev,
            cp_ref[i] if lstm else None,
            cs_ref[i] if lstm else None,
            ys_ref[i] if ys_ref is not None else None,
            hw, b, dy_ref[i],
            dh_s[...], dc_in)
        dxw_ref[i] = dxw.astype(dxw_ref.dtype)
        # dh through the h2h matmul: dgates @ W (contract gate dim)
        dh_mat = _dot_f32(dhw, w, 1, 0)
        dh_s[...] = dh_dir + dh_mat if dh_dir is not None else dh_mat
        if lstm:
            dc_s[...] = dc_new.astype(dc_s.dtype)
        dw_s[...] += _dot_f32(dhw, h_prev, 0, 0)
        db_s[...] += jnp.sum(dhw, axis=0, keepdims=True)

    dh0_ref[...] = dh_s[...].astype(dh0_ref.dtype)
    dw_ref[...] = dw_s[...].astype(dw_ref.dtype)
    db_ref[...] = db_s[...].astype(db_ref.dtype)
    if lstm:
        dc0_ref[...] = dc_s[...].astype(dc0_ref.dtype)


def _compiler_params(vmem_limit):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=vmem_limit)


def _padded_operands(xw, h0, c0, w_hh, b_hh, mode, interpret):
    t, n, gh = xw.shape
    g, h, hp, np_ = _geometry(n, gh, mode, xw.dtype)
    bt, vmem_limit = _vmem_plan(t, np_, g, hp,
                                jnp.dtype(xw.dtype).itemsize, interpret)
    tp = _pad_to(t, bt)
    xw_p = _pad_gated(jnp.pad(xw, ((0, tp - t), (0, np_ - n), (0, 0))),
                      g, h, hp, axis=2)
    w_p = jnp.pad(w_hh.reshape(g, h, h),
                  ((0, 0), (0, hp - h), (0, hp - h))).reshape(g * hp, hp)
    b_p = _pad_gated(b_hh, g, h, hp, axis=0).reshape(1, g * hp)
    h0_p = jnp.pad(h0, ((0, np_ - n), (0, hp - h)))
    c0_p = jnp.pad(c0, ((0, np_ - n), (0, hp - h))) \
        if c0 is not None else None
    return xw_p, h0_p, c0_p, w_p, b_p, (t, n, g, h, hp, np_, bt, tp,
                                        vmem_limit)


def _scan_fwd_pallas(xw, h0, c0, w_hh, b_hh, mode, interpret):
    """→ padded (ys_p[, cs_p]) plus the geometry; callers slice."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    xw_p, h0_p, c0_p, w_p, b_p, geo = _padded_operands(
        xw, h0, c0, w_hh, b_hh, mode, interpret)
    t, n, g, h, hp, np_, bt, tp, vmem_limit = geo
    lstm = mode == "lstm"
    dt = xw.dtype

    tspec = pl.BlockSpec((bt, np_, g * hp), lambda k: (k, 0, 0))
    ospec = pl.BlockSpec((bt, np_, hp), lambda k: (k, 0, 0))
    full2 = lambda shape: pl.BlockSpec(shape, lambda k: (0, 0))
    in_specs = [tspec, full2((np_, hp))]
    operands = [xw_p, h0_p]
    if lstm:
        in_specs.append(full2((np_, hp)))
        operands.append(c0_p)
    in_specs += [full2((g * hp, hp)), full2((1, g * hp))]
    operands += [w_p, b_p]
    out_specs = [ospec] + ([ospec] if lstm else [])
    out_shape = [jax.ShapeDtypeStruct((tp, np_, hp), dt)] * (
        2 if lstm else 1)
    scratch = [pltpu.VMEM((np_, hp), dt)] + \
        ([pltpu.VMEM((np_, hp), dt)] if lstm else [])
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, mode, bt),
        grid=(tp // bt,),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
    )(*operands)
    return list(outs), geo


def _scan_bwd_pallas(res, dys, dct, mode, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    xw, h0, c0, w_hh, b_hh, ys_p, cs_p = res
    xw_p, h0_p, c0_p, w_p, b_p, geo = _padded_operands(
        xw, h0, c0, w_hh, b_hh, mode, interpret)
    t, n, g, h, hp, np_, bt, tp, vmem_limit = geo
    lstm = mode == "lstm"
    dt = xw.dtype
    nt = tp // bt

    # hidden/cell trajectories shifted one step: hprev[t] = h_{t-1}
    hp_arr = jnp.concatenate([h0_p[None], ys_p[:-1]], axis=0)
    dys_p = jnp.pad(dys.astype(dt),
                    ((0, tp - t), (0, np_ - n), (0, hp - h)))
    if lstm:
        cp_arr = jnp.concatenate([c0_p[None], cs_p[:-1]], axis=0)
        dct_p = jnp.pad(dct.astype(dt), ((0, np_ - n), (0, hp - h)))

    # reverse-time grid: grid step k walks time block nt-1-k
    rts = pl.BlockSpec((bt, np_, g * hp), lambda k: (nt - 1 - k, 0, 0))
    rhs = pl.BlockSpec((bt, np_, hp), lambda k: (nt - 1 - k, 0, 0))
    full2 = lambda shape: pl.BlockSpec(shape, lambda k: (0, 0))

    if lstm:
        in_specs = [rts, rhs, rhs, rhs, full2((g * hp, hp)),
                    full2((1, g * hp)), rhs, full2((np_, hp))]
        operands = [xw_p, hp_arr, cp_arr, cs_p, w_p, b_p, dys_p, dct_p]
    else:
        in_specs = [rts, rhs, rhs, full2((g * hp, hp)),
                    full2((1, g * hp)), rhs]
        operands = [xw_p, hp_arr, ys_p, w_p, b_p, dys_p]
    out_specs = [rts, full2((np_, hp))] + \
        ([full2((np_, hp))] if lstm else []) + \
        [full2((g * hp, hp)), full2((1, g * hp))]
    out_shape = [jax.ShapeDtypeStruct((tp, np_, g * hp), dt),
                 jax.ShapeDtypeStruct((np_, hp), dt)] + \
        ([jax.ShapeDtypeStruct((np_, hp), dt)] if lstm else []) + \
        [jax.ShapeDtypeStruct((g * hp, hp), w_hh.dtype),
         jax.ShapeDtypeStruct((1, g * hp), b_hh.dtype)]
    scratch = [pltpu.VMEM((np_, hp), jnp.float32)] + \
        ([pltpu.VMEM((np_, hp), jnp.float32)] if lstm else []) + \
        [pltpu.VMEM((g * hp, hp), jnp.float32),
         pltpu.VMEM((1, g * hp), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, mode, bt, nt, t),
        grid=(nt,),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
    )(*operands)
    if lstm:
        dxw_p, dh0_p, dc0_p, dw_p, db_p = outs
    else:
        dxw_p, dh0_p, dw_p, db_p = outs
        dc0_p = None
    dxw = dxw_p.reshape(tp, np_, g, hp)[:t, :n, :, :h].reshape(
        t, n, g * h)
    dh0 = dh0_p[:n, :h]
    dc0 = dc0_p[:n, :h] if dc0_p is not None else None
    dw = dw_p.reshape(g, hp, hp)[:, :h, :h].reshape(g * h, h)
    db = db_p.reshape(g, hp)[:, :h].reshape(g * h)
    return dxw, dh0, dc0, dw, db


# ---------------------------------------------------------------------------
# custom-VJP wrappers (one per carry family)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan_lstm(mode, interpret, xw, h0, c0, w_hh, b_hh):
    """→ (ys, c_T). Returning the FINAL cell state (not the full cell
    trajectory) keeps the backward's dc chain structurally identical to
    the scan transpose's carry — the full trajectory stays an internal
    residual only."""
    outs, geo = _scan_fwd_pallas(xw, h0, c0, w_hh, b_hh, mode, interpret)
    t, n, h = geo[0], geo[1], geo[3]
    return outs[0][:t, :n, :h], outs[1][t - 1, :n, :h]


def _scan_lstm_fwd(mode, interpret, xw, h0, c0, w_hh, b_hh):
    outs, geo = _scan_fwd_pallas(xw, h0, c0, w_hh, b_hh, mode, interpret)
    t, n, h = geo[0], geo[1], geo[3]
    ys_p, cs_p = outs
    return ((ys_p[:t, :n, :h], cs_p[t - 1, :n, :h]),
            (xw, h0, c0, w_hh, b_hh, ys_p, cs_p))


def _scan_lstm_bwd(mode, interpret, res, cots):
    dys, dct = cots
    return _scan_bwd_pallas(res, dys, dct, mode, interpret)


_scan_lstm.defvjp(_scan_lstm_fwd, _scan_lstm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan_noc(mode, interpret, xw, h0, w_hh, b_hh):
    outs, geo = _scan_fwd_pallas(xw, h0, None, w_hh, b_hh, mode,
                                 interpret)
    t, n, h = geo[0], geo[1], geo[3]
    return outs[0][:t, :n, :h]


def _scan_noc_fwd(mode, interpret, xw, h0, w_hh, b_hh):
    outs, geo = _scan_fwd_pallas(xw, h0, None, w_hh, b_hh, mode,
                                 interpret)
    t, n, h = geo[0], geo[1], geo[3]
    return outs[0][:t, :n, :h], (xw, h0, None, w_hh, b_hh, outs[0],
                                 None)


def _scan_noc_bwd(mode, interpret, res, dys):
    dxw, dh0, _, dw, db = _scan_bwd_pallas(res, dys, None, mode,
                                           interpret)
    return dxw, dh0, dw, db


_scan_noc.defvjp(_scan_noc_fwd, _scan_noc_bwd)


# ---------------------------------------------------------------------------
# single-step decode kernel (the T=1 / block_t=1 variant)
# ---------------------------------------------------------------------------

def _decode_kernel(mode, *refs):
    lstm = mode == "lstm"
    if lstm:
        xw_ref, h0_ref, c0_ref, w_ref, b_ref, hy_ref, cy_ref = refs
    else:
        xw_ref, h0_ref, w_ref, b_ref, hy_ref = refs
        c0_ref = cy_ref = None
    # everything VMEM-resident for the whole call: h (and c), the h2h
    # weights and bias — one matmul + gate fusion, zero HBM round-trips
    # between them (the per-token analogue of the scan kernel's block)
    h = h0_ref[...]
    w = w_ref[...]
    b = b_ref[...]
    hw = _dot_f32(h, w, 1, 1)
    h_new, c_new = _fwd_step(mode, xw_ref[...], h,
                             c0_ref[...] if lstm else None, hw, b)
    hy_ref[...] = h_new.astype(hy_ref.dtype)
    if lstm:
        cy_ref[...] = c_new.astype(cy_ref.dtype)


def _decode_pallas(xw, h, c, w_hh, b_hh, mode, interpret):
    from jax.experimental import pallas as pl
    n, gh = xw.shape
    g = _GATES[mode]
    hdim = gh // g
    hp = _pad_to(hdim, 128)
    np_ = _pad_to(n, _sublane(xw.dtype))
    xw_p = _pad_gated(jnp.pad(xw, ((0, np_ - n), (0, 0))),
                      g, hdim, hp, axis=1)
    w_p = jnp.pad(w_hh.reshape(g, hdim, hdim),
                  ((0, 0), (0, hp - hdim),
                   (0, hp - hdim))).reshape(g * hp, hp)
    b_p = _pad_gated(b_hh, g, hdim, hp, axis=0).reshape(1, g * hp)
    h_p = jnp.pad(h, ((0, np_ - n), (0, hp - hdim)))
    lstm = mode == "lstm"
    dt = xw.dtype
    full = lambda shape: pl.BlockSpec(shape, lambda: (0, 0))
    in_specs = [full((np_, g * hp)), full((np_, hp))]
    operands = [xw_p, h_p]
    if lstm:
        in_specs.append(full((np_, hp)))
        operands.append(jnp.pad(c, ((0, np_ - n), (0, hp - hdim))))
    in_specs += [full((g * hp, hp)), full((1, g * hp))]
    operands += [w_p, b_p]
    out_specs = [full((np_, hp))] + ([full((np_, hp))] if lstm else [])
    out_shape = [jax.ShapeDtypeStruct((np_, hp), dt)] * (2 if lstm
                                                         else 1)
    outs = pl.pallas_call(
        functools.partial(_decode_kernel, mode),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    h_new = outs[0][:n, :hdim]
    c_new = outs[1][:n, :hdim] if lstm else None
    return h_new, c_new


def decode_supported(xw, h, c, mode: str) -> Optional[str]:
    """None when the decode-step kernel covers this call, else the
    fallback reason."""
    if mode not in _GATES:
        return f"unknown mode {mode!r}"
    if xw.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {xw.dtype} not kernelized (f32/bf16 only)"
    if xw.ndim != 2:
        return "expects (N, G*H) — one timestep per call"
    return None


def rnn_decode_step(xw, h, c, w_hh, b_hh, mode: str):
    """ONE recurrence step from a precomputed input projection ``xw``
    (N, G*H) — the autoregressive-decode variant of :func:`rnn_scan`
    (T = 1, block_t = 1): h (and c for LSTM) plus the h2h weights live
    in VMEM for the whole call, so a decode iteration costs one fused
    kernel instead of a scan prologue over a length-1 sequence.

    Dispatches through the shared MXNET_PALLAS gate; the XLA reference
    path is the SAME ``_fwd_step`` gate math the scan reference uses,
    so a token decoded step-by-step is bit-identical to the same token
    position inside a full :func:`rnn_scan` (tier-1 pins it). Returns
    ``(h_new, c_new|None)``; no VJP — decode is inference-only.
    """
    why = decode_supported(xw, h, c, mode)
    path, _ = dispatch("rnn_decode_step", supported=why is None,
                       reason=why)
    if path == "xla":
        hw = lax.dot_general(h, w_hh, (((1,), (1,)), ((), ())))
        return _fwd_step(mode, xw, h, c, hw, b_hh)
    return _decode_pallas(xw, h, c, w_hh, b_hh, mode,
                          path == "interpret")


def rnn_verify_scan(xw, h, c, w_hh, b_hh, mode: str, valid):
    """Masked multi-position scan for speculative-decode verification
    (serving/decode.py): run the SAME single-step cell as
    :func:`rnn_decode_step` over K candidate positions ``xw`` (K, N,
    G*H), bit-preserving the carry wherever ``valid`` (K, N) is False,
    and return the full per-position state TRAJECTORIES ``(hs, cs)``
    (each (K, N, H); ``cs`` None for non-LSTM modes) — the verifier
    needs the state AT EVERY position so acceptance can roll the carry
    back to the last accepted draft. The dispatch decision (Pallas
    decode kernel vs the XLA ``_fwd_step`` reference) is made ONCE and
    the chosen single-step body scans, so each position's math is
    bit-identical to the step :func:`rnn_decode_step` would run —
    parity with plain decode is by construction.
    """
    why = decode_supported(xw[0], h, c, mode)
    path, _ = dispatch("rnn_decode_step", supported=why is None,
                       reason=why)
    lstm = mode == "lstm"
    valid = jnp.asarray(valid)

    def body(carry, inp):
        h, c = carry
        xw_t, v_t = inp
        if path == "xla":
            hw = lax.dot_general(h, w_hh, (((1,), (1,)), ((), ())))
            h2, c2 = _fwd_step(mode, xw_t, h, c, hw, b_hh)
        else:
            h2, c2 = _decode_pallas(xw_t, h, c, w_hh, b_hh, mode,
                                    path == "interpret")
        vm = v_t[:, None]
        h = jnp.where(vm, h2, h)
        c = jnp.where(vm, c2, c) if lstm else None
        return (h, c), (h, c)

    (_, _), (hs, cs) = lax.scan(body, (h, c if lstm else None),
                                (xw, valid))
    return hs, cs


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def rnn_scan(xw, h0, c0, w_hh, b_hh, mode: str, reverse: bool = False):
    """The recurrence of one RNN direction from precomputed input
    projections: ``xw`` (T, N, G*H) = x @ W_ih^T + b_ih.

    Dispatches through the MXNET_PALLAS gate: Pallas kernel on TPU,
    interpret-mode kernel when forced on non-TPU backends, else the
    ``lax.scan`` XLA reference (ops/rnn.py ``scan_reference``) — the
    two paths are fp32 bit-identical by construction (tests pin it).
    Returns ``(ys, h_T, c_T|None)`` with ys in forward time order.
    """
    why = scan_supported(xw, h0, c0, mode)
    path, _ = dispatch("rnn_scan", supported=why is None, reason=why)
    if path == "xla":
        from ..rnn import scan_reference
        return scan_reference(xw, h0, c0, w_hh, b_hh, mode,
                              reverse=reverse)
    interpret = path == "interpret"
    if reverse:
        # flip-scan-flip ≡ lax.scan(reverse=True): identical op
        # sequence, pure data movement around it
        xw = jnp.flip(xw, axis=0)
    if mode == "lstm":
        ys, c_t = _scan_lstm(mode, interpret, xw, h0, c0, w_hh, b_hh)
        h_t = ys[-1]
        if reverse:
            ys = jnp.flip(ys, axis=0)
        return ys, h_t, c_t
    ys = _scan_noc(mode, interpret, xw, h0, w_hh, b_hh)
    h_t = ys[-1]
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return ys, h_t, None
