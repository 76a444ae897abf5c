"""Basic Gluon layers (reference: python/mxnet/gluon/nn/basic_layers.py).

Layers are HybridBlocks whose forward is plain imperative NDArray code; under
``hybridize()`` the same code traces into one XLA computation. Shape
inference is inline: a layer with unknown input dims completes its parameter
shapes on first forward (replacing the reference's deferred-init machinery).
"""
from __future__ import annotations

from typing import Optional

import numpy as onp

from ... import autograd
from ...base import MXNetError
from ...ndarray import ops as F
from ...ndarray import nn_ops as FNN
from ...ndarray.ndarray import NDArray
from ...ndarray.random import next_key
from ...ops import nn as K
from ...ops.registry import invoke_raw
from ..block import Block, HybridBlock
from ..parameter import Parameter

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "Embedding",
           "BatchNorm", "BatchNormReLU", "SyncBatchNorm", "LayerNorm", "RMSNorm",
           "GroupNorm",
           "InstanceNorm", "Flatten", "Activation", "LeakyReLU", "PReLU",
           "ELU", "SELU", "GELU", "Swish", "SiLU", "Lambda", "HybridLambda",
           "Identity", "Concatenate", "HybridConcatenate"]


class Sequential(Block):
    """Sequentially-stacked blocks (reference basic_layers.py Sequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, i):
        return list(self._children.values())[i]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, i):
        return list(self._children.values())[i]

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer: out = act(x W^T + b) (reference Dense;
    the op is reference FullyConnected, src/operator/nn/fully_connected.cc).
    Weight layout (units, in_units) matches the reference for checkpoint
    compat; XLA folds the transpose into the MXU matmul."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._flatten = flatten
        self._activation = activation
        self.weight = Parameter("weight", shape=(units, in_units),
                                dtype=dtype, init=weight_initializer)
        self.bias = Parameter("bias", shape=(units,), dtype=dtype,
                              init=bias_initializer) if use_bias else None

    def _infer(self, x):
        if self.weight._data is None:
            in_units = int(onp.prod(x.shape[1:])) if self._flatten \
                else x.shape[-1]
            self.weight.shape = (self._units, in_units)
            if self.weight._deferred_init_args is not None:
                self.weight._finish_deferred_init()
            if self.bias is not None and self.bias._deferred_init_args is not None:
                self.bias._finish_deferred_init()

    def forward(self, x):
        self._infer(x)
        out = F.FullyConnected(x, self.weight.data(),
                               None if self.bias is None else self.bias.data(),
                               num_hidden=self._units,
                               no_bias=self.bias is None,
                               flatten=self._flatten)
        if self._activation:
            out = F.Activation(out, act_type=self._activation)
        return out


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        if self._rate == 0:
            return x
        return F.Dropout(x, p=self._rate, axes=self._axes)


class Embedding(HybridBlock):
    """Embedding lookup (reference gluon Embedding). ``sparse_grad=True``
    gives the weight a row_sparse gradient: backward produces only the
    touched rows and lazy optimizers (SGD/Adam/AdaGrad) update only those
    rows — the O(rows) path for large vocabularies. Requires the eager
    (non-hybridized) path; inside a jit trace gradients are dense."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = sparse_grad
        self.weight = Parameter(
            "weight", shape=(input_dim, output_dim), dtype=dtype,
            init=weight_initializer,
            grad_stype="row_sparse" if sparse_grad else "default")

    def forward(self, x):
        return F.Embedding(x, self.weight.data(), input_dim=self._input_dim,
                           output_dim=self._output_dim,
                           sparse_grad=self._sparse_grad)


class BatchNorm(HybridBlock):
    """Batch normalization (reference BatchNorm layer + batch_norm op).

    Running stats update functionally: the parameter handle is rebound, which
    the hybridize trace captures as an extra output and writes back after the
    compiled step (see block.py _build_cache)."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._momentum = momentum
        self._eps = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        ch = in_channels
        self.gamma = Parameter("gamma", shape=(ch,),
                               init=gamma_initializer,
                               grad_req="write" if scale else "null")
        self.beta = Parameter("beta", shape=(ch,), init=beta_initializer,
                              grad_req="write" if center else "null")
        self.running_mean = Parameter("running_mean", shape=(ch,),
                                      init=running_mean_initializer,
                                      grad_req="null")
        self.running_var = Parameter("running_var", shape=(ch,),
                                     init=running_variance_initializer,
                                     grad_req="null")

    def _infer(self, x):
        if self.gamma._data is None:
            ch = x.shape[self._axis]
            for p in (self.gamma, self.beta, self.running_mean,
                      self.running_var):
                p.shape = (ch,)
                if p._deferred_init_args is not None:
                    p._finish_deferred_init()

    def forward(self, x):
        self._infer(x)
        if self._axis != 1:
            x = x.swapaxes(1, self._axis)
        g, b = self.gamma.data(), self.beta.data()
        mm, mv = self.running_mean.data(), self.running_var.data()
        training = autograd.is_training() and not self._use_global_stats
        if not training:
            out = invoke_raw(
                "batch_norm",
                lambda xx, gg, bb, m, v: K.batch_norm_infer(
                    xx, gg, bb, m, v, self._eps),
                [x, g, b, mm, mv])
        else:
            res = invoke_raw(
                "batch_norm",
                lambda xx, gg, bb: K.batch_norm_train(xx, gg, bb, self._eps),
                [x, g, b], n_outputs=3)
            out, bmean, bvar = res
            mom = self._momentum
            with autograd.pause():
                self.running_mean._data = mom * mm + (1 - mom) * bmean
                self.running_var._data = mom * mv + (1 - mom) * bvar
        if self._axis != 1:
            out = out.swapaxes(1, self._axis)
        return out


class SyncBatchNorm(BatchNorm):
    """Cross-device BatchNorm (reference contrib SyncBatchNorm): under a
    sharded data-parallel step the batch axis is a mesh axis and XLA computes
    global batch stats via psum when the input is sharded; single-device
    behavior equals BatchNorm."""

    def __init__(self, in_channels=0, num_devices=None, **kwargs):
        super().__init__(in_channels=in_channels, **kwargs)
        self._num_devices = num_devices


class BatchNormReLU(BatchNorm):
    """BatchNorm with a fused trailing ReLU (reference gluon/nn
    basic_layers.py BatchNormReLU, backed by the _npx_batch_norm+relu
    kernel there). Here the relu composes onto the BN output and XLA
    fuses the pair into one kernel."""

    def forward(self, x):
        from ... import ndarray as F
        return F.relu(super().forward(x))


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=gamma_initializer,
                               grad_req="write" if scale else "null")
        self.beta = Parameter("beta", shape=(in_channels,),
                              init=beta_initializer,
                              grad_req="write" if center else "null")

    def _infer(self, x):
        if self.gamma._data is None:
            ch = x.shape[self._axis]
            for p in (self.gamma, self.beta):
                p.shape = (ch,)
                if p._deferred_init_args is not None:
                    p._finish_deferred_init()

    def forward(self, x):
        self._infer(x)
        return FNN.LayerNorm(x, self.gamma.data(), self.beta.data(),
                             axis=self._axis, eps=self._eps)


class RMSNorm(HybridBlock):
    """Root-mean-square norm over the last axis with a learned gain
    (``gamma``) and no bias: ``x / sqrt(mean(x^2) + epsilon) * gamma``."""

    def __init__(self, epsilon=1e-6, gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=gamma_initializer)

    def forward(self, x):
        if self.gamma._data is None:
            self.gamma.shape = (x.shape[-1],)
            if self.gamma._deferred_init_args is not None:
                self.gamma._finish_deferred_init()
        return FNN.RMSNorm(x, self.gamma.data(), eps=self._eps)


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._ngroups = num_groups
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=gamma_initializer,
                               grad_req="write" if scale else "null")
        self.beta = Parameter("beta", shape=(in_channels,),
                              init=beta_initializer,
                              grad_req="write" if center else "null")

    def _infer(self, x):
        if self.gamma._data is None:
            ch = x.shape[1]
            for p in (self.gamma, self.beta):
                p.shape = (ch,)
                if p._deferred_init_args is not None:
                    p._finish_deferred_init()

    def forward(self, x):
        self._infer(x)
        return FNN.GroupNorm(x, self.gamma.data(), self.beta.data(),
                             num_groups=self._ngroups, eps=self._eps)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=gamma_initializer,
                               grad_req="write" if scale else "null")
        self.beta = Parameter("beta", shape=(in_channels,),
                              init=beta_initializer,
                              grad_req="write" if center else "null")

    def _infer(self, x):
        if self.gamma._data is None:
            ch = x.shape[self._axis]
            for p in (self.gamma, self.beta):
                p.shape = (ch,)
                if p._deferred_init_args is not None:
                    p._finish_deferred_init()

    def forward(self, x):
        self._infer(x)
        if self._axis != 1:
            x = x.swapaxes(1, self._axis)
        out = FNN.InstanceNorm(x, self.gamma.data(), self.beta.data(),
                               eps=self._eps)
        if self._axis != 1:
            out = out.swapaxes(1, self._axis)
        return out


class Flatten(HybridBlock):
    def forward(self, x):
        return x.flatten()

    def __repr__(self):
        return "Flatten"


class Activation(HybridBlock):
    def __init__(self, activation, **kwargs):
        super().__init__(**kwargs)
        self._act_type = activation

    def forward(self, x):
        return F.Activation(x, act_type=self._act_type)

    def __repr__(self):
        return f"Activation({self._act_type})"


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def forward(self, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer="constant", in_channels=1, **kwargs):
        super().__init__(**kwargs)
        from ... import initializer as I
        init = I.Constant(0.25) if alpha_initializer == "constant" \
            else alpha_initializer
        self.alpha = Parameter("alpha", shape=(in_channels,), init=init)

    def forward(self, x):
        return F.LeakyReLU(x, act_type="prelu", gamma=self.alpha.data())


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def forward(self, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def forward(self, x):
        return F.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    def __init__(self, approximation="erf", **kwargs):
        super().__init__(**kwargs)
        self._approx = approximation

    def forward(self, x):
        return F.LeakyReLU(x, act_type="gelu")


class Swish(HybridBlock):
    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def forward(self, x):
        return x * F.sigmoid(self._beta * x)


SiLU = Swish


class Lambda(Block):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        if isinstance(function, str):
            function = getattr(F, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        if isinstance(function, str):
            function = getattr(F, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)


class Identity(HybridBlock):
    def forward(self, x):
        return x


class Concatenate(Sequential):
    """Run children on the same input, concat outputs (reference
    contrib Concurrent)."""

    def __init__(self, axis=-1, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis

    def forward(self, x):
        outs = [block(x) for block in self._children.values()]
        return F.concat(*outs, dim=self._axis)


class HybridConcatenate(HybridSequential):
    def __init__(self, axis=-1, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis

    def forward(self, x):
        outs = [block(x) for block in self._children.values()]
        return F.concat(*outs, dim=self._axis)
