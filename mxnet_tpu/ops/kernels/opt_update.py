"""Fused multi-tensor optimizer-update kernel (SGD-momentum, Adam).

Under the ZeRO-1 sharded weight update (gluon/fused_step.py) every
parameter update is an ELEMENTWISE rule over a flat padded 1/N shard —
a bucket unit already fuses many small parameters into one buffer with
per-element lr/wd/t vectors (``Optimizer.pack_shard_hparams``). XLA
schedules that update as a chain of small elementwise kernels
interleaved with the state buffers' HBM traffic; this kernel instead
streams ``w, g, m[, v]`` through VMEM ONCE per block and applies the
whole rule (rescale → clip → wd → moments → bias correction → step) in
registers — the reference's multi-tensor ``multi_sgd_mom_update`` /
``multi_adam_update`` discipline (src/operator/optimizer_op.cc) on the
TPU.

The rule bodies mirror ``optimizer.py``'s ``_rule()`` expressions
term for term, and the flat buffers are only reshaped to the (rows,
128) lane layout — elementwise math is shape-independent, so the
kernel path is BIT-exact against the XLA elementwise update
(tests/test_kernels.py pins sgd-mom and adam at dp=4).

Dispatch: the shared MXNET_PALLAS gate (see ops/kernels/__init__.py).
Only exact SGD/Adam instances kernelize — subclasses may override the
rule, so they (and every other optimizer) keep the XLA path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import dispatch, vmem_tile_budget

__all__ = ["kernel_step_fn", "unit_update", "opt_kernel_kind"]

_LANES = 128
_BLOCK_ROWS = 256            # (256, 128) f32 blocks = 128 KiB per ref


def _block_rows_cap() -> int:
    """Row-block cap from the SHARED VMEM tile budget (the accessor
    rnn_scan/attention/norm also size against): up to ~8 concurrent
    (rows, 128) f32 tiles live at once (w, g, m, v, the outputs, the
    per-element hparam vectors). At the default 4 MiB budget the
    256-row Mosaic cap stays the binding limit."""
    rows = vmem_tile_budget() // max(1, 8 * _LANES * 4)
    return min(_BLOCK_ROWS, max(8, (rows // 8) * 8))


def _pad2d(flat, rows, dtype=None, fill=0):
    """(P,) → (rows, 128) zero-padded lane layout."""
    p = int(flat.shape[0])
    total = rows * _LANES
    if p != total:
        flat = jnp.pad(flat, (0, total - p), constant_values=fill)
    out = flat.reshape(rows, _LANES)
    return out.astype(dtype) if dtype is not None else out


def _state_body(kind, cfg, w, g, lr, wd, t, rescale, clip):
    """New optimizer state from loaded blocks (the rule's state half;
    ``lr`` folds into SGD's momentum buffer exactly as in _rule)."""
    g = g * rescale
    if cfg["has_clip"]:
        g = jnp.clip(g, -clip, clip)
    g = g + wd * w
    if kind == "sgd":
        (m,) = cfg["states"]
        return (cfg["momentum"] * m - lr * g,)
    b1, b2 = cfg["beta1"], cfg["beta2"]
    m, v = cfg["states"]
    return (b1 * m + (1 - b1) * g, b2 * v + (1 - b2) * g * g)


def _weight_body(kind, cfg, w, new_states, g, lr, wd, t, rescale,
                 clip):
    """New weight from the NEW state values (plus the prepared grad
    for stateless SGD)."""
    if kind == "sgd":
        if cfg["momentum"] == 0.0:
            g = g * rescale
            if cfg["has_clip"]:
                g = jnp.clip(g, -clip, clip)
            g = g + wd * w
            return w - lr * g
        (m,) = new_states
        return w + m
    b1, b2, eps = cfg["beta1"], cfg["beta2"], cfg["epsilon"]
    m, v = new_states
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    return w - lr * mhat / (jnp.sqrt(vhat) + eps)


def _rule_body(kind, cfg, w, g, lr, wd, t, rescale, clip):
    """The fused rule (state + weight halves composed in-register) —
    the single-kernel TPU path."""
    if kind == "sgd" and cfg["momentum"] == 0.0:
        return _weight_body(kind, cfg, w, (), g, lr, wd, t, rescale,
                            clip), ()
    new_states = _state_body(kind, cfg, w, g, lr, wd, t, rescale, clip)
    return _weight_body(kind, cfg, w, new_states, g, lr, wd, t,
                        rescale, clip), new_states


def _opt_kernel(kind, cfg, vec, n_states, part, *refs):
    """``part`` is 'fused' today (one kernel, both outputs); the
    'state'/'weight' halves exist for callers that want the two-pass
    form. Note on the last ulp: XLA may DUPLICATE the state
    expression into the weight-output fusion and fp-contract the copy
    differently (it eliminates optimization barriers on the CPU
    backend, so the duplication is not preventable in-program) — the
    stored states are always bit-exact vs the XLA reference chain;
    the weight can sit 1 ulp from `w ± <stored state math>` under
    GSPMD partitioning. tests/test_kernels.py pins exactly this
    contract."""
    refs = list(refs)
    w_ref, g_ref = refs[0], refs[1]
    state_refs = refs[2:2 + n_states]
    lr_ref, wd_ref, t_ref, rs_ref, clip_ref = refs[2 + n_states:
                                                   7 + n_states]
    out_refs = refs[7 + n_states:]
    if vec:
        lr, wd, t = lr_ref[...], wd_ref[...], t_ref[...]
    else:
        # t as one lane row: Adam's beta ** t is a vector pow (the
        # scalar core has no transcendental unit)
        lr, wd = lr_ref[0, 0], wd_ref[0, 0]
        t = jnp.full((1, _LANES), t_ref[0, 0])
    # float t: Mosaic's pow takes floating operands only; XLA converts
    # the int32 exponent the same way, so the paths stay bit-identical
    t = t.astype(jnp.float32)
    states = tuple(s[...] for s in state_refs)
    body_cfg = dict(cfg, states=states)
    args = (w_ref[...], g_ref[...], lr, wd, t, rs_ref[0, 0],
            clip_ref[0, 0])
    if part == "fused":
        new_w, new_states = _rule_body(kind, body_cfg, *args)
        out_refs[0][...] = new_w.astype(out_refs[0].dtype)
        for o, s in zip(out_refs[1:], new_states):
            o[...] = s.astype(o.dtype)
    elif part == "state":
        for o, s in zip(out_refs, _state_body(kind, body_cfg, *args)):
            o[...] = s.astype(o.dtype)
    else:
        # 'weight': the state slots hold the NEW states
        new_w = _weight_body(kind, body_cfg, args[0], states, args[1],
                             lr, wd, t, args[5], args[6])
        out_refs[0][...] = new_w.astype(out_refs[0].dtype)


def unit_update(kind: str, cfg: dict, w, g, lr, wd, t, rescale, clip,
                states, interpret: bool):
    """One flat unit (a whole parameter's shard or a fused bucket
    shard) through the Pallas update kernel. ``lr``/``wd``/``t`` are
    scalars or per-element (P,) vectors (``pack_shard_hparams``).
    Returns ``(new_w, new_states)`` shaped like the inputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p = int(w.shape[0])
    rows = -(-p // _LANES)
    block_r = min(_block_rows_cap(), -(-rows // 8) * 8)
    rows = -(-rows // block_r) * block_r
    grid = rows // block_r
    vec = getattr(lr, "ndim", 0) >= 1

    wdt = w.dtype
    w2 = _pad2d(w, rows)
    g2 = _pad2d(jnp.asarray(g, wdt), rows)
    st2 = tuple(_pad2d(s, rows) for s in states)

    blk = pl.BlockSpec((block_r, _LANES), lambda i: (i, 0))
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0),
                        memory_space=pltpu.SMEM)
    as11 = lambda v, dt: jnp.asarray(v, dt).reshape(1, 1)

    in_specs = [blk, blk] + [blk] * len(st2)
    if vec:
        in_specs += [blk, blk, blk]
        # pad tail gets lr=wd=0 / t=1: the pack_shard_hparams pad
        # convention — keeps Adam's 1/(1-beta**t) finite on padding
        hparams = [_pad2d(jnp.asarray(lr, jnp.float32), rows),
                   _pad2d(jnp.asarray(wd, jnp.float32), rows),
                   _pad2d(jnp.asarray(t, jnp.int32), rows, fill=1)]
    else:
        in_specs += [smem, smem, smem]
        hparams = [as11(lr, jnp.float32), as11(wd, jnp.float32),
                   as11(t, jnp.int32)]
    in_specs += [smem, smem]
    hparams += [as11(rescale, jnp.float32), as11(clip, jnp.float32)]

    n_out = 1 + len(st2)
    outs = pl.pallas_call(
        functools.partial(_opt_kernel, kind, cfg, vec, len(st2),
                          "fused"),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[blk] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), wdt)] * n_out,
        compiler_params=_parallel_params(),
        interpret=interpret,
    )(w2, g2, *st2, *hparams)
    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
    new_w = outs[0].reshape(-1)[:p]
    new_states = tuple(o.reshape(-1)[:p] for o in outs[1:])
    return new_w, new_states


def _parallel_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=("parallel",))


def opt_kernel_kind(opt) -> Optional[tuple]:
    """(kind, cfg) when ``opt`` is an EXACT SGD/Adam instance (a
    subclass may override the rule), else None."""
    from ...optimizer.optimizer import SGD, Adam
    if type(opt) is SGD:
        return "sgd", {"momentum": float(opt.momentum),
                       "has_clip": opt.clip_gradient is not None}
    if type(opt) is Adam:
        return "adam", {"beta1": float(opt.beta1),
                        "beta2": float(opt.beta2),
                        "epsilon": float(opt.epsilon),
                        "has_clip": opt.clip_gradient is not None}
    return None


def kernel_step_fn(opt):
    """A drop-in for ``Optimizer.fused_step_fn`` routing every flat
    unit through the Pallas update kernel — or None when the gate
    picks XLA / the optimizer is not kernelized. Only valid for FLAT
    (1-d) units, i.e. the ZeRO shard layout."""
    kk = opt_kernel_kind(opt)
    path, _ = dispatch(
        "opt_update", supported=kk is not None,
        reason=None if kk else
        f"{type(opt).__name__} update rule is not kernelized "
        "(exact SGD/Adam only)")
    if path == "xla":
        return None
    kind, cfg = kk
    interpret = path == "interpret"

    def stepfn(ws, gs, lrs, wds, ts, rescale, clip, states):
        new_ws, new_ss = [], []
        for i, (w, g, st) in enumerate(zip(ws, gs, states)):
            nw, ns = unit_update(kind, cfg, w, g, lrs[i], wds[i],
                                 ts[i], rescale, clip, st, interpret)
            new_ws.append(nw)
            new_ss.append(ns)
        return tuple(new_ws), tuple(new_ss)

    return stepfn
