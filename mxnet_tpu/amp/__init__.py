"""Automatic mixed precision (reference: python/mxnet/contrib/amp/).

Reference mechanics: fp16 allow/deny op lists (contrib/amp/lists/
symbol_fp16.py), runtime patching of op invocation (amp.py:282), dynamic
``LossScaler`` (loss_scaler.py), and a ``ReducePrecision`` graph pass.

TPU-native redesign: the mixed dtype is **bfloat16** — same exponent range
as f32, so no loss scaling is *required* (the LossScaler is kept for API
parity and for true fp16). ``amp.init()`` installs an invoke wrapper with
the reference's list semantics (amp.py:282 runtime patching):

- TARGET_DTYPE_OPS (MXU-bound: matmul/conv/attention/rnn) cast f32 inputs
  down and their outputs FLOW in the low dtype — exactly like the
  reference's FP16_FUNCS, whose fp16 outputs propagate. This is the
  performance-critical half: activations between ops live in bf16, halving
  HBM traffic (the TPU bottleneck), while master weights stay f32.
- FP32_OPS (softmax/loss/exp-log reductions) cast low-precision inputs UP
  to f32 (reference FP32_FUNCS).
- Everything else follows its input dtypes (reference WIDEST_TYPE_CASTS
  falls out of jnp promotion).

Normalization layers are in FP32_OPS only for true fp16; under bf16 they
flow bf16 — safe because every norm kernel computes its statistics in f32
internally (ops/nn.py _stat_dtype), which is the half the reference's
FP32 pinning actually protects.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax.numpy as jnp

from ..base import MXNetError
from ..ops import registry as _registry
from .loss_scaler import LossScaler

__all__ = ["init", "uninit", "is_enabled", "init_trainer", "scale_loss",
           "convert_hybrid_block", "LossScaler", "TARGET_DTYPE_OPS",
           "FP32_OPS"]

# MXU-bound ops by their INVOKE-FUNNEL names (ops/registry.py invoke_raw
# call sites — the names the wrapper actually sees): cast inputs to the
# target dtype (reference lists/symbol_fp16.py FP16_FUNCS analog). The
# fused RNN layers invoke as "rnn_<mode>", matched by prefix below.
TARGET_DTYPE_OPS = {
    "fully_connected", "convolution", "deconvolution", "dot", "batch_dot",
    "linalg_gemm2", "flash_attention", "flash_attention_vl",
    "masked_attention", "bert_decoder_proj", "moe_ffn", "moe_experts",
    "shared_expert", "Correlation", "DeformableConvolution",
}

# Norm ops: f32-pinned only for true fp16 (their kernels already compute
# statistics in f32 internally — ops/nn.py _stat_dtype — so bf16 may flow).
NORM_OPS = {
    "batch_norm", "layer_norm", "rms_norm", "group_norm", "instance_norm",
    "SyncBatchNorm",
}

# Numerically-sensitive ops pinned to f32 (reference FP32_FUNCS analog):
# low-precision inputs are cast UP. Everything else runs in whatever dtype
# flows in (WIDEST_TYPE_CASTS behavior falls out of jnp promotion).
FP32_OPS = NORM_OPS | {
    "softmax", "log_softmax", "softmax_cross_entropy", "norm", "moments",
    "exp", "log", "l2_normalization", "lrn",
    # the router of the dropless expert layer: logits, top-k and the
    # softmax of the chosen logits (a bf16 logit flips near-tied choices)
    "moe_route",
    # the two norms of latent attention's compressed q and kv, and the
    # per-head norms of q and k (MultiHeadAttention ``qk_norm``)
    "latent_norm", "qk_norm",
    # a state-space mixer's step size (softplus of dt + its bias; the
    # scan's decays exp(dt A) are built from it in float32) and its gated
    # group norm; the scan itself (``ssd_scan``) is in neither list: its
    # x, B, C flow in bf16 and its dt, A_log, D arrive in float32
    "mamba_dt", "mamba_norm",
}

_state = {"enabled": False, "dtype": None, "wrapper": None}


def _cast_down(x, dtype):
    if hasattr(x, "dtype") and hasattr(x, "astype") and \
            x.dtype == jnp.float32:
        return x.astype(dtype)
    return x


def _cast_up(x, dtype):
    if hasattr(x, "dtype") and hasattr(x, "astype") and x.dtype == dtype:
        return x.astype(jnp.float32)
    return x


def _make_wrapper(target_dtype):
    fp32_ops = FP32_OPS if target_dtype == jnp.float16 \
        else FP32_OPS - NORM_OPS

    def wrapper(name, fn):
        if name in TARGET_DTYPE_OPS or name.startswith("rnn_"):
            def amp_fn(*args, **kwargs):
                cast_args = [_cast_down(a, target_dtype) for a in args]
                # output flows in target_dtype (reference FP16_FUNCS
                # semantics): activations stay low-precision between ops
                return fn(*cast_args, **kwargs)
            return amp_fn
        if name in fp32_ops:
            def fp32_fn(*args, **kwargs):
                cast_args = [_cast_up(a, target_dtype) for a in args]
                return fn(*cast_args, **kwargs)
            return fp32_fn
        return fn
    return wrapper


def init(target_dtype: str = "bfloat16"):
    """Enable AMP process-wide (reference amp.init, amp.py:282)."""
    if _state["enabled"]:
        return
    if target_dtype in ("bfloat16", "bf16"):
        dt = jnp.bfloat16
    elif target_dtype in ("float16", "fp16"):
        dt = jnp.float16
    else:
        raise MXNetError(f"unsupported AMP target dtype {target_dtype!r}")
    w = _make_wrapper(dt)
    _registry.add_invoke_wrapper(w)
    _state.update(enabled=True, dtype=dt, wrapper=w)


def uninit():
    """Disable AMP (test/debug helper; the reference has no un-init)."""
    if _state["enabled"]:
        _registry.remove_invoke_wrapper(_state["wrapper"])
        _state.update(enabled=False, dtype=None, wrapper=None)


def is_enabled() -> bool:
    return _state["enabled"]


def init_trainer(trainer):
    """Attach a dynamic LossScaler to a Gluon Trainer (reference
    amp.init_trainer). A no-op numerically for bf16 (scale stays 1) but
    the scaler object is attached for API parity and fp16 use."""
    scaler = LossScaler(
        init_scale=1.0 if _state["dtype"] == jnp.bfloat16 else 2. ** 16)
    trainer._amp_loss_scaler = scaler
    return scaler


@contextlib.contextmanager
def scale_loss(loss, trainer):
    """Yield the scaled loss; trainer.step unscales via trainer._scale
    (reference amp.scale_loss contextmanager)."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None:
        scaler = init_trainer(trainer)
    # trainer._scale must keep dividing out the loss scale through the
    # trainer.step() that follows this context — set it persistently,
    # against the original scale (idempotent across steps as the dynamic
    # scale changes).
    if not hasattr(trainer, "_amp_original_scale"):
        trainer._amp_original_scale = trainer._scale
    trainer._scale = trainer._amp_original_scale / scaler.loss_scale
    if scaler.loss_scale == 1.0:  # bf16 default: no-op passthrough
        yield loss
    elif isinstance(loss, (list, tuple)):
        yield type(loss)(l * scaler.loss_scale for l in loss)
    else:
        yield loss * scaler.loss_scale


def convert_hybrid_block(block, target_dtype: str = "bfloat16"):
    """Cast a Gluon block's parameters for low-precision *inference*
    (reference amp.convert_hybrid_block): all params to target dtype
    except normalization-layer params, which stay f32."""
    from ..gluon import nn as _nn
    norm_types = (_nn.BatchNorm, _nn.LayerNorm, _nn.GroupNorm,
                  _nn.InstanceNorm)
    # cast every parameter not owned by a norm layer
    norm_params = set()
    stack = [block]
    while stack:
        b = stack.pop()
        if isinstance(b, norm_types):
            for p in b.collect_params().values():
                norm_params.add(id(p))
        stack.extend(getattr(b, "_children", {}).values())
    for p in block.collect_params().values():
        if id(p) not in norm_params and p._data is not None and \
                p.dtype == "float32":
            p.cast(target_dtype)
    return block
