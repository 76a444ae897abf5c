"""Transformer layers: MultiHeadAttention, FFN, encoder stack.

Reference parity note: MXNet 2.0-dev keeps attention out-of-tree (gluon-nlp
composed it from batch_dot + softmax — no fused kernel, SURVEY.md §2.3/§5).
Here attention is a first-class fused op (ops/attention.py: Pallas flash
kernel on TPU, ring attention for context parallelism), and these layers are
the Gluon-API building blocks over it, used by model_zoo.bert.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax.numpy as jnp

from ...base import MXNetError
from ...ndarray import ops as F
from ...ndarray.ndarray import NDArray
from ...ops import attention as ATT
from ...ops import nn as _nn
from ...ops.kernels import count_traced
from ...ops.registry import invoke_raw, scope
from ..block import HybridBlock
from ..parameter import Parameter
from .basic_layers import Dense, Dropout, LayerNorm

__all__ = ["MultiHeadAttention", "LatentAttention", "PositionwiseFFN",
           "TransformerEncoderCell", "TransformerEncoder"]


def _masked_attention(q, k, v, mask, sm_scale, causal=False,
                      valid_length=None):
    """Arbitrary-additive-mask attention — delegates to the shared oracle
    impl in ops/attention.py (unfused; XLA fuses the softmax). Only used
    for masks that aren't expressible as valid_length — padding alone
    should pass ``valid_length`` and stay on the flash path. When both are
    given, padding is folded into the additive mask here."""
    if valid_length is not None:
        sk = k.shape[2]
        keep = jnp.arange(sk)[None, :] < valid_length[:, None]
        mask = mask + jnp.where(keep, 0.0, ATT._NEG_INF)[:, None, None, :]
    return ATT.attention_reference(q, k, v, causal=causal,
                                   sm_scale=sm_scale, mask=mask)


class MultiHeadAttention(HybridBlock):
    """Multi-head attention over (batch, seq, units) inputs.

    ``forward(q, k=None, v=None, mask=None, valid_length=None)``:
    self-attention when k/v are omitted. ``valid_length`` (B,) masks padded
    keys and stays on the fused flash path (blockwise, O(S·block) memory).
    ``mask`` is an arbitrary additive float mask broadcastable to
    (batch, heads, seq_q, seq_k) (0 keep / -inf drop) — that path is
    unfused; prefer valid_length for plain padding.

    Decoder options, all on the fused flash path and on no other:
    ``head_dim`` (default units // num_heads; the projections are then
    units -> heads * head_dim and back), ``num_kv_heads`` fewer key/value
    heads than query heads (query head n reads head n // group; k and v
    are projected to that many heads and never repeated), ``window`` (with
    ``causal``: key j is seen by query i iff 0 <= i - j < window),
    ``rope_theta`` (rotary position embedding of q and k in front of the
    call; None: no position signal at all), ``qk_norm`` (the epsilon of
    an RMSNorm of each head of q and of k, with a gain of head width
    each, ``q_norm_gamma`` and ``k_norm_gamma``, after the projections
    and before RoPE, in float32 under the ``qk_norm`` scope; None: no
    such norm).
    """

    def __init__(self, units: int, num_heads: int, dropout: float = 0.0,
                 use_bias: bool = True, causal: bool = False,
                 head_dim: Optional[int] = None,
                 num_kv_heads: Optional[int] = None,
                 window: Optional[int] = None,
                 rope_theta: Optional[float] = None,
                 qk_norm: Optional[float] = None, **kwargs):
        super().__init__(**kwargs)
        if head_dim is None:
            if units % num_heads:
                raise MXNetError(
                    f"units {units} not divisible by heads {num_heads}")
            head_dim = units // num_heads
        kv_heads = num_heads if num_kv_heads is None else num_kv_heads
        if kv_heads < 1 or num_heads % kv_heads:
            raise MXNetError(f"heads {num_heads} not a multiple of K/V "
                             f"heads {kv_heads}")
        if window is not None and not causal:
            raise MXNetError("a window needs causal=True")
        self._units = units
        self._num_heads = num_heads
        self._kv_heads = kv_heads
        self._head_dim = head_dim
        self._causal = causal
        self._window = window
        self._rope_theta = rope_theta
        self._qk_norm = qk_norm
        if qk_norm is not None:
            self.q_norm_gamma = Parameter("q_norm_gamma", shape=(head_dim,),
                                          init="ones")
            self.k_norm_gamma = Parameter("k_norm_gamma", shape=(head_dim,),
                                          init="ones")
        width = num_heads * head_dim
        self.query_proj = Dense(width, use_bias=use_bias, flatten=False,
                                in_units=units)
        self.key_proj = Dense(kv_heads * head_dim, use_bias=use_bias,
                              flatten=False, in_units=units)
        self.value_proj = Dense(kv_heads * head_dim, use_bias=use_bias,
                                flatten=False, in_units=units)
        self.out_proj = Dense(units, use_bias=use_bias, flatten=False,
                              in_units=width)
        self.dropout = Dropout(dropout)

    def _split(self, x):
        b, s, _ = x.shape
        return F.transpose(
            F.reshape(x, (b, s, self._num_heads, self._head_dim)),
            axes=(0, 2, 1, 3))

    def _rope(self, x, heads):
        fn = functools.partial(ATT.rope, num_heads=heads,
                               theta=self._rope_theta)
        return invoke_raw("rope", fn, [x])

    def _norm_heads(self, x, gamma, heads):
        """RMSNorm of each of ``heads`` heads of (B, S, H*D) ``x``."""
        def fn(x_, g):
            b, s, hd = x_.shape
            return _nn.rms_norm(x_.reshape(b, s, heads, hd // heads), g,
                                eps=self._qk_norm).reshape(b, s, hd)
        return invoke_raw("qk_norm", fn, [x, gamma.data()])

    def forward(self, q, k=None, v=None, mask=None, valid_length=None):
        k = q if k is None else k
        v = k if v is None else v
        qp, kp, vp = self.query_proj(q), self.key_proj(k), self.value_proj(v)
        if self._qk_norm is not None:
            qp = self._norm_heads(qp, self.q_norm_gamma, self._num_heads)
            kp = self._norm_heads(kp, self.k_norm_gamma, self._kv_heads)
        d = self._head_dim
        scale = 1.0 / math.sqrt(d)
        if mask is None and valid_length is None:
            if self._rope_theta is not None:
                qp = self._rope(qp, self._num_heads)
                kp = self._rope(kp, self._kv_heads)
            # the flash kernels read the projections where they lie and
            # write what out_proj reads: no head transpose on either side
            fn = functools.partial(ATT.flash_attention_bsh,
                                   num_heads=self._num_heads,
                                   causal=self._causal, sm_scale=scale)
            if self._kv_heads != self._num_heads or self._window is not None:
                fn = functools.partial(fn, num_kv_heads=self._kv_heads,
                                       window=self._window)
            out = invoke_raw("flash_attention", fn, [qp, kp, vp])
            return self.dropout(self.out_proj(out))
        if self._kv_heads != self._num_heads or self._window is not None \
                or self._rope_theta is not None:
            raise MXNetError("MultiHeadAttention: grouped K/V heads, a "
                             "window and rope go with neither mask nor "
                             "valid_length")
        qh, kh, vh = self._split(qp), self._split(kp), self._split(vp)
        if mask is not None:
            inputs = [qh, kh, vh, mask if isinstance(mask, NDArray)
                      else NDArray(jnp.asarray(mask))]
            if valid_length is not None:
                vl_data = valid_length._data \
                    if isinstance(valid_length, NDArray) \
                    else jnp.asarray(valid_length)
                inputs.append(NDArray(jnp.asarray(vl_data, jnp.float32)))

                def fn(q_, k_, v_, m_, vl_):
                    return _masked_attention(q_, k_, v_, m_, scale,
                                             causal=self._causal,
                                             valid_length=vl_)
            else:
                fn = functools.partial(_masked_attention, sm_scale=scale,
                                       causal=self._causal)
            out = invoke_raw("masked_attention", fn, inputs)
        else:
            def fn(q_, k_, v_, vl_):
                return ATT.flash_attention(q_, k_, v_, causal=self._causal,
                                           sm_scale=scale, valid_length=vl_)
            vl_data = valid_length._data if isinstance(valid_length, NDArray) \
                else jnp.asarray(valid_length)
            # float32: integer tape inputs would get float0 cotangents
            vl = NDArray(jnp.asarray(vl_data, jnp.float32))
            out = invoke_raw("flash_attention_vl", fn, [qh, kh, vh, vl])
        b, _, s, _ = out.shape
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        (b, s, self._num_heads * d))
        return self.dropout(self.out_proj(out))


class LatentAttention(HybridBlock):
    """Multi-head latent attention (MLA, the DeepSeek-V2/V3 family) over
    (batch, seq, units), causal, in its TRAINING form ("expanded": keys
    and values are written out a head; the decode form that attends in
    the latent space over a cached ``c_kv`` is not built here)::

        c_q = RMSNorm(u W_qa)                          units -> q_rank
        q   = c_q W_qb -> heads x [q_n (nope) | q_r (rope)];  q_r <- RoPE
        [c_kv | k_r] = u W_kva;  c_kv <- RMSNorm(c_kv);  k_r <- RoPE
                                                       k_r: ONE head
        c_kv W_kvb -> heads x [k_n (nope) | v (v_dim)]
        k = [k_n | k_r], k_r the same for every head
        out = softmax(q k^T / sqrt(nope + rope) + causal) v  W_o

    No bias. The two latent norms run in float32 (``latent_norm`` is on
    AMP's float32 list). RoPE turns the ``rope`` lanes behind each
    head's ``nope`` lanes, neighbouring pairs (2i, 2i+1) with
    ``rope_interleave``. ``k_r`` is broadcast into every head's key in
    front of the attention call, whose kernels take keys ``nope + rope``
    wide beside values ``v_dim`` wide (``ops.attention._Tiles``).
    Everything in front of the call sits under the ``latent_proj``
    scope."""

    def __init__(self, units: int, num_heads: int, q_rank: int,
                 kv_rank: int, nope: int, rope: int, v_dim: int,
                 rope_theta: float, rope_interleave: bool = True,
                 epsilon: float = 1e-6, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._nope, self._rope_dim = num_heads, nope, rope
        self._v_dim, self._kv_rank, self._eps = v_dim, kv_rank, epsilon
        self._theta, self._interleave = float(rope_theta), rope_interleave

        def dense(out, inp):
            return Dense(out, use_bias=False, flatten=False, in_units=inp)
        self.q_a_proj = dense(q_rank, units)
        self.q_a_norm_gamma = Parameter("q_a_norm_gamma", shape=(q_rank,),
                                        init="ones")
        self.q_b_proj = dense(num_heads * (nope + rope), q_rank)
        self.kv_a_proj = dense(kv_rank + rope, units)
        self.kv_a_norm_gamma = Parameter("kv_a_norm_gamma",
                                         shape=(kv_rank,), init="ones")
        self.kv_b_proj = dense(num_heads * (nope + v_dim), kv_rank)
        self.out_proj = dense(units, num_heads * v_dim)

    def _norm(self, x, gamma):
        """RMSNorm of the latent in front of ``x``'s lanes (all of c_q;
        c_kv ahead of k_r), in float32 under AMP."""
        def fn(x_, g):
            return _nn.rms_norm(x_[..., :g.shape[0]], g, eps=self._eps)
        return invoke_raw("latent_norm", fn, [x, gamma.data()])

    def _keys_values(self, kv_a, kv):
        """From ``[c_kv | k_r]`` (kept for its k_r) and the up-projected
        heads x [k_n | v]: k (B, S, H * (nope + rope)), every head's
        rotary lanes the one turned k_r, and v (B, S, H * v_dim)."""
        heads, nope, v_dim = self._heads, self._nope, self._v_dim

        def fn(kv_a_, kv_):
            b, s, _ = kv_.shape
            k_r = ATT.rope(kv_a_[..., self._kv_rank:], 1, self._theta,
                           interleave=self._interleave)
            kv_ = kv_.reshape(b, s, heads, nope + v_dim)
            k_r = jnp.broadcast_to(k_r[:, :, None, :].astype(kv_.dtype),
                                   (b, s, heads, k_r.shape[-1]))
            k = jnp.concatenate([kv_[..., :nope], k_r], -1)
            return (k.reshape(b, s, -1),
                    kv_[..., nope:].reshape(b, s, heads * v_dim))
        return invoke_raw("latent_keys_values", fn, [kv_a, kv], n_outputs=2)

    def forward(self, u):
        heads, d = self._heads, self._nope + self._rope_dim
        count_traced("LATENT_ATTENTION", "form", "expanded")
        with scope("latent_proj"):
            c_q = self._norm(self.q_a_proj(u), self.q_a_norm_gamma)
            q = invoke_raw("rope", functools.partial(
                ATT.rope, num_heads=heads, theta=self._theta,
                lanes=(self._nope, self._rope_dim),
                interleave=self._interleave), [self.q_b_proj(c_q)])
            kv_a = self.kv_a_proj(u)
            c_kv = self._norm(kv_a, self.kv_a_norm_gamma)
            k, v = self._keys_values(kv_a, self.kv_b_proj(c_kv))
        fn = functools.partial(ATT.flash_attention_bsh, num_heads=heads,
                               causal=True, sm_scale=1.0 / math.sqrt(d))
        return self.out_proj(invoke_raw("flash_attention", fn, [q, k, v]))


class PositionwiseFFN(HybridBlock):
    """Transformer FFN: dense → activation → dense (+ dropout).

    With ``activation='gelu'`` the first dense's bias add and the GELU
    fuse into one Pallas kernel when the MXNET_PALLAS gate selects it
    (ops/kernels/norm.py ``bias_gelu``; the matmul stays on the MXU) —
    XLA otherwise materializes the (tokens, hidden) pre-activation to
    HBM between the two. Identical math: gelu((x W^T) + b), exact erf
    form, same parameters."""

    def __init__(self, units: int, hidden_size: int, dropout: float = 0.0,
                 activation: str = "gelu", **kwargs):
        super().__init__(**kwargs)
        self.ffn_1 = Dense(hidden_size, flatten=False, in_units=units)
        self.ffn_2 = Dense(units, flatten=False, in_units=hidden_size)
        self._activation = activation
        self.dropout = Dropout(dropout)

    def _bias_gelu_path(self, x):
        """'interpret'/'pallas' when the fused bias-GELU kernel should
        take this call, else None (reference Dense→Activation)."""
        if self._activation != "gelu" or self.ffn_1.bias is None:
            return None
        from ...ops.kernels import dispatch as _kdispatch
        from ...ops.kernels import norm as _knorm
        why = _knorm.norm_supported(x, self.ffn_1.weight.shape[0])
        path, _ = _kdispatch("bias_gelu", supported=why is None,
                             reason=why)
        return None if path == "xla" else path

    def forward(self, x):
        path = self._bias_gelu_path(x)
        if path is not None:
            from ...ops.kernels.norm import bias_gelu
            interpret = path == "interpret"

            def fn(x_, w_, b_):
                return bias_gelu(x_ @ w_.T, b_, interpret=interpret)

            h = invoke_raw("bias_gelu_dense", fn,
                           [x, self.ffn_1.weight.data(),
                            self.ffn_1.bias.data()])
        else:
            h = F.Activation(self.ffn_1(x), act_type=self._activation)
        return self.dropout(self.ffn_2(h))


class TransformerEncoderCell(HybridBlock):
    """Post-LN (BERT-style) or pre-LN transformer encoder layer."""

    def __init__(self, units: int, hidden_size: int, num_heads: int,
                 dropout: float = 0.0, pre_norm: bool = False,
                 activation: str = "gelu", causal: bool = False, **kwargs):
        super().__init__(**kwargs)
        self._pre_norm = pre_norm
        self.attention = MultiHeadAttention(units, num_heads, dropout=dropout,
                                            causal=causal)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout,
                                   activation=activation)
        self.ln_1 = LayerNorm(in_channels=units)
        self.ln_2 = LayerNorm(in_channels=units)

    def forward(self, x, mask=None, valid_length=None):
        # MultiHeadAttention/PositionwiseFFN already apply output dropout —
        # no extra dropout here (rate would compound past the configured p).
        if self._pre_norm:
            x = x + self.attention(self.ln_1(x), mask=mask,
                                   valid_length=valid_length)
            return x + self.ffn(self.ln_2(x))
        x = self.ln_1(x + self.attention(x, mask=mask,
                                         valid_length=valid_length))
        return self.ln_2(x + self.ffn(x))


class TransformerEncoder(HybridBlock):
    """Stack of encoder cells."""

    def __init__(self, num_layers: int, units: int, hidden_size: int,
                 num_heads: int, dropout: float = 0.0, pre_norm: bool = False,
                 activation: str = "gelu", causal: bool = False, **kwargs):
        super().__init__(**kwargs)
        self.layers = []
        for i in range(num_layers):
            cell = TransformerEncoderCell(units, hidden_size, num_heads,
                                          dropout=dropout, pre_norm=pre_norm,
                                          activation=activation, causal=causal)
            setattr(self, f"layer{i}", cell)
            self.layers.append(cell)

    def forward(self, x, mask=None, valid_length=None):
        for cell in self.layers:
            x = cell(x, mask=mask, valid_length=valid_length)
        return x
