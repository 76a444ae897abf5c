"""State-space mixer layers.

No reference analog (the reference's recurrent layers are gluon/rnn);
``Mamba2Mixer`` is the mixer of the Mamba-2 / Nemotron-H family and
``ShortConvMixer`` LFM2's gated short convolution, both over
``ops/ssm.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import _tape
from ...base import MXNetError
from ...ndarray.ndarray import NDArray
from ...ops import ssm as ssm_ops
from ...ops.kernels import count_traced
from ...ops.registry import invoke_raw, scope
from ...telemetry.names import SCOPE_CONV_MIXER
from ..block import HybridBlock
from ..parameter import Parameter
from .basic_layers import Dense

__all__ = ["Mamba2Mixer", "ShortConvMixer"]


class ShortConvMixer(HybridBlock):
    """LFM2's gated short-convolution mixer: ``out = mixer(u)``, u (B, S,
    units), no bias anywhere::

        [B | C | x] = u W_in               units -> 3 units
        v   = conv(B * x)                  causal, depthwise, ``taps`` taps
        out = (C * v) W_out                units -> units

    No state and no activation. The gates and the conv are
    ``ops.ssm.gated_short_conv`` (float32 inside, the projection's dtype
    out: bf16 under AMP); everything, projections included, sits under
    the ``conv_mixer`` scope, the gates and the conv alone under
    ``short_conv``. ``mx_short_conv_total`` counts the traced calls."""

    def __init__(self, units: int, taps: int = 3, **kwargs):
        super().__init__(**kwargs)
        self.conv_weight = Parameter("conv_weight", shape=(units, taps))
        self.in_proj = Dense(3 * units, use_bias=False, flatten=False,
                             in_units=units)
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=units)

    def forward(self, u):
        count_traced("SHORT_CONV")
        with scope(SCOPE_CONV_MIXER):
            y = invoke_raw("gated_short_conv", ssm_ops.gated_short_conv,
                           [self.in_proj(u), self.conv_weight.data()])
            return self.out_proj(y)


class Mamba2Mixer(HybridBlock):
    """Mamba-2 mixer: ``out = mixer(u)``, u (B, S, units).

    ``H`` heads of width ``P`` (``d_inner = H P``), a state of ``N`` lanes
    a head lane, ``B`` and ``C`` shared by the heads of each of ``G``
    groups; no bias but the conv's::

        [z | xBC | dt] = u W_in            units -> d_inner + (d_inner +
                                           2 G N) + H
        xBC = silu(conv(xBC))              causal, depthwise, ``conv_kernel``
                                           taps over the d_inner + 2 G N
                                           channels
        dt  = softplus(dt + dt_bias);  A = -exp(A_log)         float32
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T;  y_t = H_t C_t + D x_t
        y   = RMSNorm_groups(y * silu(z)) * gain      the gate BEFORE the
                                           norm, over each group's lanes
        out = y W_out                      d_inner -> units

    The scan runs chunked (``ops.ssm.ssd_scan``, chunks of
    ``chunk_size``). Under AMP the two projections and the products inside
    the chunks take bf16 operands; ``dt``, ``A``, the cumulative decays,
    the states and the norm stay float32 (``amp.FP32_OPS``: ``mamba_dt``,
    ``mamba_norm``).

    What the backward makes again instead of keeping: conv, scan and
    gated norm are one ``jax.checkpoint`` (the ``segment``) that keeps
    ``u W_in`` and the chunk-boundary states; the conv, the step sizes and
    the norm's elementwise work are made twice, the projections once. On
    the scan's kernel tier (one TPU chip, ops/kernels/ssd_scan.py) the
    segment keeps the scan's y as well, so the forward kernel is launched
    once and the backward kernel makes a chunk's decays and scores again
    in VMEM; on the XLA tier the in-chunk products are made forward, again
    under the segment and backward. No checkpoint can span ops the
    imperative tape records one by one: under ``autograd.record()`` only
    the scan is one (``none``: what it never keeps is its (Q x Q) decay
    matrices, by its own checkpoint or its custom VJP).
    ``mx_mamba_recompute_total{span}`` counts which a traced layer took.
    Scopes: ``mamba_mixer`` around all of it, inside it ``mamba_proj``
    (both projections), ``mamba_conv``, ``ssd_scan``, ``mamba_norm``.
    """

    def __init__(self, units: int, num_heads: int, head_dim: int,
                 state_size: int, n_groups: int = 1, conv_kernel: int = 4,
                 chunk_size: int = 128, epsilon: float = 1e-5, **kwargs):
        super().__init__(**kwargs)
        if num_heads % n_groups:
            raise MXNetError(f"{num_heads} heads do not divide into "
                             f"{n_groups} groups")
        self._heads, self._width = num_heads, head_dim
        self._groups, self._state = n_groups, state_size
        self._chunk, self._eps = chunk_size, epsilon
        inner = num_heads * head_dim
        conv = inner + 2 * n_groups * state_size
        self.in_proj = Dense(inner + conv + num_heads, use_bias=False,
                             flatten=False, in_units=units)
        self.conv_weight = Parameter("conv_weight",
                                     shape=(conv, conv_kernel))
        self.conv_bias = Parameter("conv_bias", shape=(conv,),
                                   init="zeros")
        self.dt_bias = Parameter("dt_bias", shape=(num_heads,),
                                 init="zeros")
        self.A_log = Parameter("A_log", shape=(num_heads,), init="zeros")
        self.D = Parameter("D", shape=(num_heads,), init="ones")
        self.norm_gamma = Parameter("norm_gamma", shape=(inner,),
                                    init="ones")
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=inner)

    def _core(self, zxbcdt, own_checkpoint: bool):
        """conv, scan and gated norm: ``u W_in`` -> what ``W_out`` reads."""
        heads, width = self._heads, self._width
        groups, state = self._groups, self._state
        inner, gn = heads * width, groups * state
        z = zxbcdt[:, :, :inner]
        xbc = zxbcdt[:, :, inner:2 * inner + 2 * gn]
        dt = zxbcdt[:, :, 2 * inner + 2 * gn:]

        def conv(xbc_, w, b):
            return jax.nn.silu(ssm_ops.causal_conv1d(xbc_, w, b))
        xbc = invoke_raw("mamba_conv", conv, [xbc, self.conv_weight.data(),
                                              self.conv_bias.data()])

        def step_size(dt_, bias):
            return jax.nn.softplus(dt_ + bias)
        dt = invoke_raw("mamba_dt", step_size, [dt, self.dt_bias.data()])

        def scan(xbc_, dt_, a_log, skip):
            b, s, _ = xbc_.shape
            y = ssm_ops.ssd_scan(
                xbc_[..., :inner].reshape(b, s, heads, width), dt_,
                -jnp.exp(a_log.astype(jnp.float32)),
                xbc_[..., inner:inner + gn].reshape(b, s, groups, state),
                xbc_[..., inner + gn:].reshape(b, s, groups, state),
                skip, chunk=self._chunk, recompute=own_checkpoint)
            return y.reshape(b, s, inner)
        y = invoke_raw("ssd_scan", scan, [xbc, dt, self.A_log.data(),
                                          self.D.data()])

        def norm(y_, z_, gain):
            return ssm_ops.gated_group_rms_norm(y_, z_, gain, groups,
                                                self._eps)
        return invoke_raw("mamba_norm", norm, [y, z, self.norm_gamma.data()])

    def forward(self, u):
        u = u if isinstance(u, NDArray) else NDArray(u)
        taped = _tape.is_recording()
        count_traced("MAMBA_RECOMPUTE", "span", "none" if taped else "segment")
        with scope("mamba_mixer"):
            with scope("mamba_proj"):
                zxbcdt = self.in_proj(u)
            if taped:
                y = self._core(zxbcdt, True)
            else:
                # ONE jax.checkpoint: it keeps u W_in, the parameters the
                # segment reads, the chunk-boundary states and, on the
                # scan's kernel tier, its y (what the norm's backward
                # reads: kept, the forward kernel is launched once)
                segment = jax.checkpoint(
                    lambda data: self._core(NDArray(data), False)._data,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        ssm_ops.SSD_STATES, ssm_ops.SSD_OUTPUT))
                y = NDArray(segment(zxbcdt._data))
            with scope("mamba_proj"):
                return self.out_proj(y)
