"""LFM2-MoE: a decoder-only hybrid language model (LiquidAI;
``LFM2-24B-A2B`` ``config.json``, ``model_type`` ``lfm2_moe``).

Every layer, pre-norm, RMSNorm, no bias::

    u  = RMSNorm_op(h)
    h' = h + (ShortConv(u) if layer_types[i] == "conv" else Attn(u))
    h''= h' + FF_i(RMSNorm_ffn(h'))

``ShortConv`` is the gated short convolution (``gluon.nn.ShortConvMixer``:
``[B | C | x] = u W_in``, ``C * conv(B * x)`` over ``conv_L_cache`` taps,
``W_out``); ``Attn`` causal attention with grouped key/value heads, an
RMSNorm on each head of q and k (``qk_norm``) and then RoPE. The first
``num_dense_layers`` layers' FF is a SwiGLU of ``intermediate_size``; every
other layer's sparse SwiGLU experts, chosen top-k by sigmoid scores plus a
selection bias, weighed by the chosen scores over their sum plus 1e-6
(:data:`ROUTER_NORM_EPS`), times ``routed_scaling_factor``; no shared
expert. After the last layer a norm and the head, which is the embedding
table itself (tied).

One chip's share of a deployment is the same model: the expert layers are
told which experts they hold (``gluon.nn.SparseMoE`` ``held``), the
vocabulary may be a slice (``vocab_rows``), and the layers built are the
first ``num_hidden_layers`` entries of ``layer_types``.
"""
from __future__ import annotations

from ...ndarray import ops as F
from ..block import HybridBlock
from ..nn.basic_layers import Embedding, RMSNorm
from ..nn.moe import SparseMoE
from ..nn.ssm import ShortConvMixer
from ..nn.transformer import MultiHeadAttention
from .joyai import GatedFFN

__all__ = ["LFM2Layer", "LFM2MoeLM", "MIXERS", "ROUTER_NORM_EPS"]

#: the mixer kinds of ``layer_types``
MIXERS = ("conv", "full_attention")
#: what the family's router adds to the chosen scores' sum before it
#: divides by it (``Lfm2MoeSparseMoeBlock``; not a key of the config)
ROUTER_NORM_EPS = 1e-6


def _mixer(cfg: dict, kind: str):
    units = cfg["hidden_size"]
    if kind == "conv":
        if cfg["conv_bias"]:
            raise ValueError("LFM2MoeLM builds its convs without bias")
        return ShortConvMixer(units, cfg["conv_L_cache"])
    if kind == "full_attention":
        rope = cfg["rope_parameters"]
        if rope["rope_type"] != "default":
            raise ValueError(f"no rope_type {rope['rope_type']!r} here")
        heads = cfg["num_attention_heads"]
        return MultiHeadAttention(
            units, heads, use_bias=False, causal=True,
            head_dim=units // heads, num_kv_heads=cfg["num_key_value_heads"],
            rope_theta=float(rope["rope_theta"]), qk_norm=cfg["norm_eps"])
    raise ValueError(f"no mixer {kind!r} in layer_types; it knows "
                     f"{list(MIXERS)}")


class LFM2Layer(HybridBlock):
    """One decoder layer: the mixer its kind names, then the dense
    feed-forward (``dense=True``) or the sparse experts."""

    def __init__(self, cfg: dict, kind: str, dense: bool, **kwargs):
        super().__init__(**kwargs)
        units, eps = cfg["hidden_size"], cfg["norm_eps"]
        self.operator_norm = RMSNorm(epsilon=eps, in_channels=units)
        self.mixer = _mixer(cfg, kind)
        self.ffn_norm = RMSNorm(epsilon=eps, in_channels=units)
        if dense:
            self.ffn = GatedFFN(units, cfg["intermediate_size"])
        else:
            held = cfg["num_experts"]
            self.experts = SparseMoE(
                units, cfg["moe_intermediate_size"],
                cfg.get("moe_router_width", held),
                cfg["num_experts_per_tok"],
                held=(cfg.get("moe_first_expert", 0), held), score="sigmoid",
                routed_scale=cfg["routed_scaling_factor"], activation="silu",
                norm_eps=ROUTER_NORM_EPS)
        self._dense = dense

    def forward(self, h):
        h = h + self.mixer(self.operator_norm(h))
        x = self.ffn_norm(h)
        return h + (self.ffn(x) if self._dense else self.experts(x))


class LFM2MoeLM(HybridBlock):
    """Token ids (B, S) -> logits (B, S, rows). ``cfg`` holds the
    published ``config.json`` keys; besides them ``vocab_rows`` (the rows
    of the vocabulary held here, default ``vocab_size``),
    ``moe_router_width`` (all the experts the router scores, default
    ``num_experts``, which counts the experts HELD) and
    ``moe_first_expert`` (default 0). The first ``num_hidden_layers``
    entries of ``layer_types`` are built, the first ``num_dense_layers``
    of them dense."""

    def __init__(self, cfg: dict, **kwargs):
        super().__init__(**kwargs)
        units = cfg["hidden_size"]
        rows = cfg.get("vocab_rows", cfg["vocab_size"])
        kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
        if len(kinds) != cfg["num_hidden_layers"]:
            raise ValueError(f"layer_types names {len(kinds)} layers, "
                             f"num_hidden_layers {cfg['num_hidden_layers']}")
        if not cfg["norm_topk_prob"] or not cfg["use_expert_bias"]:
            raise ValueError("LFM2MoeLM weighs the chosen scores normalised "
                             "and picks them with a selection bias")
        self.layer_types = kinds
        self.embed = Embedding(rows, units)
        self.layers = []
        for i, kind in enumerate(kinds):
            layer = LFM2Layer(cfg, kind, dense=i < cfg["num_dense_layers"])
            setattr(self, f"layer{i}", layer)
            self.layers.append(layer)
        self.final_norm = RMSNorm(epsilon=cfg["norm_eps"], in_channels=units)
        self._rows = rows

    def forward(self, tokens):
        h = self.embed(tokens)
        for layer in self.layers:
            h = layer(h)
        return F.FullyConnected(self.final_norm(h), self.embed.weight.data(),
                                num_hidden=self._rows, no_bias=True,
                                flatten=False)
