"""Mixture-of-Experts Gluon layers.

No reference analog (the reference has no MoE — SURVEY §2.3 lists expert
parallelism as absent); TPU-native extensions backed by ``ops/moe.py``:

- ``MoE``: the GShard/Switch layer, a capacity-bounded router (tokens past
  an expert's capacity are DROPPED) over batched two-matrix expert
  einsums;
- ``SparseMoE``: the dropless layer of today's sparse-expert decoders:
  top-k of the router's scores by the model's rule (a softmax over the
  chosen logits; or sigmoid scores, a selection bias, weights normalised
  over the chosen and scaled), gated experts (ReGLU or SwiGLU) or
  experts without a gate (``relu2``), optionally a shared expert every
  token passes, no capacity and no dropped token, and a layer that is
  told which experts it holds (``held``).
"""
from __future__ import annotations

from ...base import MXNetError
from ...ndarray.ndarray import NDArray
from ...ops.registry import invoke_raw
from ...ops import moe as moe_ops
from ...ops.kernels import count_traced
from ...telemetry import device_counters, names as _names
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["MoE", "SparseMoE"]


class MoE(HybridBlock):
    """Sparse expert FFN: ``out, aux = moe(x)``.

    x (..., units) is flattened to tokens; each token routes to ``top_k`` of
    ``num_experts`` expert FFNs (units -> hidden -> units). ``aux`` is the
    load-balance loss (≈1 when balanced) to add to the training objective.
    ``ops.moe.moe_ffn`` with ``axis_name`` (inside shard_map, the expert
    dimension of ``w1/w2`` sharded over an 'ep' mesh axis) is the same
    capacity-bound layer with an all-to-all on either side; it is not how
    ``SparseMoE`` is shared out (see ``held`` there)."""

    def __init__(self, units, hidden, num_experts, top_k=2,
                 capacity_factor=1.25, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._e, self._k = num_experts, top_k
        self._cf = capacity_factor
        self.gate = Parameter("gate", shape=(units, num_experts),
                              dtype=dtype)
        self.w1 = Parameter("w1", shape=(num_experts, units, hidden),
                            dtype=dtype)
        self.w2 = Parameter("w2", shape=(num_experts, hidden, units),
                            dtype=dtype)

    def forward(self, x):
        units = self.w1.shape[1]
        shape = x.shape

        def fn(xd, gw, w1, w2):
            tokens = xd.reshape(-1, units)
            out, aux = moe_ops.moe_ffn(tokens, gw, w1, w2, top_k=self._k,
                                       capacity_factor=self._cf)
            return out.reshape(shape), aux

        count_traced("MOE_DISPATCH", "path", "capacity")
        out, aux = invoke_raw(
            "moe_ffn", fn,
            [x if isinstance(x, NDArray) else NDArray(x),
             self.gate.data(), self.w1.data(), self.w2.data()],
            n_outputs=2)
        return out, aux


class SparseMoE(HybridBlock):
    """Dropless sparse expert layer: ``out = moe(x)``, x (..., units).

    The router (``router_weight``, (num_experts, units)) scores every
    token against ALL ``num_experts`` experts, in float32; each token
    takes its ``top_k`` best and weighs them, by ``score``:

    - ``"softmax"`` (SmallThinker): the k largest logits, weighed by the
      softmax of those k logits;
    - ``"sigmoid"`` (the DeepSeek-V3 family, LFM2): scores
      sigmoid(logits); the k largest of score + ``router_bias``
      ((num_experts,), a parameter that picks and never weighs: its
      gradient is exactly zero, so an optimizer leaves it where the job's
      balance rule put it), weighed by ``routed_scale`` * score / (the
      chosen scores' sum + ``norm_eps``).

    Expert e is ``W_down (act(W_gate x) * (W_up x))``, width ``hidden``,
    no bias; ``activation`` names act: ``"relu"`` (sparse ReGLU) or
    ``"silu"`` (SwiGLU). With ``gated=False`` an expert is ``W_down
    act(W_up x)`` and has no ``gate_weight`` (Nemotron-H's experts, act
    ``"relu2"``, relu squared). With ``shared_hidden`` one more expert of
    the same form and that width, which every token passes with weight
    1, is added to the sum: it is whole on every chip, so the shares of a
    layer count it once (``shared_expert(x)`` is its term alone).

    ``held = (first, count)`` says which experts this layer holds:
    ``(0, num_experts)`` is the whole layer, ``(8 * j, 8)`` chip j's part
    when eight chips share a 64-expert layer. The layer computes ITS
    experts' part of each token's sum and nothing else: pairs whose expert
    is held elsewhere are not multiplied, and on a TPU chip not moved
    back either (the products are the kernels of
    ``ops/kernels/grouped_dot.py``, whose grid is as long as the groups,
    and behind them the row movers of ``ops/kernels/moe_rows.py`` walk
    the held pairs; the XLA tier multiplies by ``lax.ragged_dot`` and its
    gathers run over the whole static list, masked), and nothing stands
    in for the other chips. The parts of all the shares
    add up to the whole layer's output (tests/test_smallthinker.py,
    tests/test_joyai.py).

    ``route(u)`` is the router alone, for a model whose router reads
    another tensor than the experts do (before attention); ``forward(x,
    routing)`` then takes what it returned.
    """

    def __init__(self, units, hidden, num_experts, top_k, held=None,
                 dtype="float32", score="softmax", routed_scale=1.0,
                 activation="relu", shared_hidden=0, gated=True,
                 norm_eps=0.0, **kwargs):
        super().__init__(**kwargs)
        if score not in moe_ops.SCORES:
            raise MXNetError(f"score {score!r} is none of "
                             f"{moe_ops.SCORES}")
        if activation not in moe_ops.ACTIVATIONS:
            raise MXNetError(f"activation {activation!r} is none of "
                             f"{sorted(moe_ops.ACTIVATIONS)}")
        first, count = (0, num_experts) if held is None else held
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise MXNetError(f"held={held!r} is no range of "
                             f"{num_experts} experts")
        if top_k > num_experts:
            raise MXNetError(f"top_k {top_k} > {num_experts} experts")
        self._units, self._k = units, top_k
        self._held = (int(first), int(count))
        self._score, self._scale = score, float(routed_scale)
        self._norm_eps = float(norm_eps)
        self._activation, self._gated = activation, bool(gated)
        self.router_weight = Parameter(
            "router_weight", shape=(num_experts, units), dtype=dtype)
        self.router_bias = None
        if score == "sigmoid":
            self.router_bias = Parameter(
                "router_bias", shape=(num_experts,), dtype=dtype,
                init="zeros")
        self.gate_weight = Parameter(
            "gate_weight", shape=(count, hidden, units), dtype=dtype) \
            if gated else None
        self.up_weight = Parameter(
            "up_weight", shape=(count, hidden, units), dtype=dtype)
        self.down_weight = Parameter(
            "down_weight", shape=(count, units, hidden), dtype=dtype)
        self.shared_gate_weight = self.shared_up_weight = \
            self.shared_down_weight = None
        if shared_hidden:
            if gated:
                self.shared_gate_weight = Parameter(
                    "shared_gate_weight", shape=(shared_hidden, units),
                    dtype=dtype)
            self.shared_up_weight = Parameter(
                "shared_up_weight", shape=(shared_hidden, units),
                dtype=dtype)
            self.shared_down_weight = Parameter(
                "shared_down_weight", shape=(units, shared_hidden),
                dtype=dtype)

    def _tokens(self, x):
        x = x if isinstance(x, NDArray) else NDArray(x)
        return x.reshape((-1, self._units))

    def route(self, u):
        """``(weights, order, place, sizes)`` of ``ops.moe.moe_route``
        for the tokens of ``u``."""
        count_traced("MOE_ROUTER", "score", self._score)
        inputs = [self._tokens(u), self.router_weight.data()]
        if self.router_bias is not None:
            inputs.append(self.router_bias.data())

        def fn(x, w, bias=None):
            return moe_ops.moe_route(x, w, self._k, self._held, self._score,
                                     bias, self._scale, self._norm_eps)
        return invoke_raw("moe_route", fn, inputs, n_outputs=4)

    def shared_expert(self, x):
        """The shared expert's term alone, ``x``'s shape."""
        act = moe_ops.ACTIVATIONS[self._activation]

        def gated(x_, gate, up, down):
            return (act(x_ @ gate.T) * (x_ @ up.T)) @ down.T

        def ungated(x_, up, down):
            return act(x_ @ up.T) @ down.T
        fn = gated if self._gated else ungated
        return invoke_raw("shared_expert", fn,
                          [x if isinstance(x, NDArray) else NDArray(x)]
                          + self._matrices("shared_"))

    def _matrices(self, prefix=""):
        """``[gate,] up, down`` of the routed (or the shared) experts."""
        return [p.data() for p in (
            getattr(self, f"{prefix}{part}_weight")
            for part in ("gate", "up", "down")) if p is not None]

    def forward(self, x, routing=None):
        shape = x.shape
        tokens = self._tokens(x)
        weights, order, place, sizes = routing or self.route(x)
        count_traced("MOE_DISPATCH", "path", "grouped")
        # the pairs each held expert was given, out of the compiled step
        device_counters.emit(_names.COUNTER_MOE_HELD_PAIRS, sizes)
        no_gate = () if self._gated else (None,)

        def experts(x_, order_, place_, sizes_, *weights):
            return moe_ops.moe_experts(x_, order_, place_, sizes_, *no_gate,
                                       *weights, activation=self._activation)
        y = invoke_raw("moe_experts", experts,
                       [tokens, order, place, sizes] + self._matrices())
        out = invoke_raw("moe_combine", moe_ops.moe_combine,
                         [y, weights, order, place, sizes]).reshape(shape)
        if self.shared_up_weight is not None:
            out = out + self.shared_expert(x)
        return out

    def routing_stats(self, x):
        """Eager, outside any step: ``{"pairs": pairs each held expert is
        given for the tokens of x, "held_share": their share of all
        tokens x top_k pairs}`` as numpy / float. Inside a compiled
        train step the same pairs are the device counter
        ``moe_held_pairs``, a row a layer on the step's ``window`` span
        (docs/OBSERVABILITY.md "Device counters")."""
        import numpy as onp
        bias = None if self.router_bias is None else \
            self.router_bias.data()._data
        sizes, share = moe_ops.routing_counts(
            self._tokens(x)._data, self.router_weight.data()._data,
            self._k, self._held, score=self._score, bias=bias,
            scale=self._scale, norm_eps=self._norm_eps)
        return {"pairs": onp.asarray(sizes), "held_share": float(share)}

