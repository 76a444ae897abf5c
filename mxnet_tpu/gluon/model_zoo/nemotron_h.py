"""Nemotron-H: a decoder-only hybrid language model (NVIDIA;
``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` ``config.json``, ``model_type``
``nemotron_h``, 31.6B-A3.2B).

A layer is ONE mixer behind a pre-norm and a residual, ``h <- h +
mixer(RMSNorm(h))``, and ``hybrid_override_pattern`` says layer by layer
which: ``M`` a Mamba-2 mixer (``gluon.nn.Mamba2Mixer``), ``*`` causal
attention with grouped key/value heads and NO positional embedding (the
family's attention layers carry none), ``E`` sparse experts without a
gate, ``W_down relu(W_up x)^2``, chosen top-k by sigmoid scores plus a
selection bias, weights normalised and scaled, beside one shared expert
of the same form (``gluon.nn.SparseMoE(gated=False)``). After the last
layer a norm and the head, untied from the embedding. No bias anywhere
but the mixers' conv.

One chip's share of a deployment is the same model: the expert layers are
told which experts they hold (``SparseMoE`` ``held``), the vocabulary may
be a slice (``vocab_rows``), and ``num_hidden_layers`` says how many of
the pattern's layers, from its first, are built here.
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn.basic_layers import Dense, Embedding, RMSNorm
from ..nn.moe import SparseMoE
from ..nn.ssm import Mamba2Mixer
from ..nn.transformer import MultiHeadAttention

__all__ = ["NemotronHLayer", "NemotronHLM", "MIXERS"]

#: the mixer kinds of ``hybrid_override_pattern``
MIXERS = {"M": "mamba", "*": "attention", "E": "experts"}


def _mixer(cfg: dict, kind: str):
    units = cfg["hidden_size"]
    if kind == "M":
        return Mamba2Mixer(
            units, cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["chunk_size"], epsilon=cfg["layer_norm_epsilon"])
    if kind == "*":
        return MultiHeadAttention(
            units, cfg["num_attention_heads"], use_bias=False, causal=True,
            head_dim=cfg["head_dim"],
            num_kv_heads=cfg["num_key_value_heads"])
    if kind == "E":
        held = cfg["n_routed_experts"]
        return SparseMoE(
            units, cfg["moe_intermediate_size"],
            cfg.get("moe_router_width", held), cfg["num_experts_per_tok"],
            held=(cfg.get("moe_first_expert", 0), held), score="sigmoid",
            routed_scale=cfg["routed_scaling_factor"],
            activation=cfg["mlp_hidden_act"], gated=False,
            shared_hidden=cfg["n_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"])
    raise ValueError(f"no mixer {kind!r} in hybrid_override_pattern; it "
                     f"knows {sorted(MIXERS)}")


class NemotronHLayer(HybridBlock):
    """``h + mixer(RMSNorm(h))`` for one character of the pattern."""

    def __init__(self, cfg: dict, kind: str, **kwargs):
        super().__init__(**kwargs)
        self.norm = RMSNorm(epsilon=cfg["layer_norm_epsilon"],
                            in_channels=cfg["hidden_size"])
        self.mixer = _mixer(cfg, kind)

    def forward(self, h):
        return h + self.mixer(self.norm(h))


class NemotronHLM(HybridBlock):
    """Token ids (B, S) -> logits (B, S, rows). ``cfg`` holds the
    published ``config.json`` keys; besides them ``vocab_rows`` (the rows
    of the vocabulary held here, default ``vocab_size``),
    ``moe_router_width`` (all the experts the router scores, default
    ``n_routed_experts``, which counts the experts HELD) and
    ``moe_first_expert`` (default 0). The first ``num_hidden_layers``
    characters of ``hybrid_override_pattern`` are built."""

    def __init__(self, cfg: dict, **kwargs):
        super().__init__(**kwargs)
        units = cfg["hidden_size"]
        rows = cfg.get("vocab_rows", cfg["vocab_size"])
        pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
        if len(pattern) != cfg["num_hidden_layers"]:
            raise ValueError(
                f"hybrid_override_pattern names {len(pattern)} layers, "
                f"num_hidden_layers {cfg['num_hidden_layers']}")
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1 \
                or not cfg.get("norm_topk_prob", True):
            raise ValueError("NemotronHLM routes over one group with "
                             "weights normalised over the chosen")
        self.pattern = pattern
        self.embed = Embedding(rows, units)
        self.layers = []
        for i, kind in enumerate(pattern):
            layer = NemotronHLayer(cfg, kind)
            setattr(self, f"layer{i}", layer)
            self.layers.append(layer)
        self.final_norm = RMSNorm(epsilon=cfg["layer_norm_epsilon"],
                                  in_channels=units)
        self.head = Dense(rows, use_bias=False, flatten=False,
                          in_units=units)

    def forward(self, tokens):
        h = self.embed(tokens)
        for layer in self.layers:
            h = layer(h)
        return self.head(self.final_norm(h))
