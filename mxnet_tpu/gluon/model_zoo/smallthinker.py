"""SmallThinker: a decoder-only sparse-expert language model (PowerInfer,
2025-07; ``SmallThinker-21BA3B-Instruct`` ``config.json``).

Every layer, pre-norm::

    u  = RMSNorm(h)
    r  = router(u)                 the router reads what attention reads
    h' = h + attention(u)          28 q / 4 kv heads of 128; layout 1: a
                                   causal window of 4096 with RoPE, layout
                                   0: full causal, no position signal
    h''= h' + experts(RMSNorm(h'), r)   top-6 of 64 sparse ReGLU experts,
                                   softmax over the chosen logits, dropless

No bias, no qk-norm, no shared expert, no auxiliary loss; embedding and
head untied. The config's ``rope_layout`` and ``sliding_window_layout``
say layer by layer which attention a layer has.

One chip's share of a deployment is the same model: the expert layers are
told which experts they hold (``gluon.nn.SparseMoE`` ``held``) and the
vocabulary may be a slice (``vocab_rows``).
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn.basic_layers import Dense, Embedding, RMSNorm
from ..nn.moe import SparseMoE
from ..nn.transformer import MultiHeadAttention

__all__ = ["SmallThinkerLayer", "SmallThinkerLM"]


class SmallThinkerLayer(HybridBlock):
    def __init__(self, cfg: dict, index: int, **kwargs):
        super().__init__(**kwargs)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        windowed = bool(cfg["sliding_window_layout"][index])
        rotary = bool(cfg["rope_layout"][index])
        self.attn_norm = RMSNorm(epsilon=eps, in_channels=units)
        self.attention = MultiHeadAttention(
            units, cfg["num_attention_heads"], use_bias=False, causal=True,
            head_dim=cfg["head_dim"],
            num_kv_heads=cfg["num_key_value_heads"],
            window=cfg["sliding_window_size"] if windowed else None,
            rope_theta=float(cfg["rope_theta"]) if rotary else None)
        self.ffn_norm = RMSNorm(epsilon=eps, in_channels=units)
        held = cfg["moe_num_primary_experts"]
        self.experts = SparseMoE(
            units, cfg["moe_ffn_hidden_size"],
            cfg.get("moe_router_width", held),
            cfg["moe_num_active_primary_experts"],
            held=(cfg.get("moe_first_expert", 0), held))

    def forward(self, h):
        u = self.attn_norm(h)
        routing = self.experts.route(u)
        h = h + self.attention(u)
        return h + self.experts(self.ffn_norm(h), routing)


class SmallThinkerLM(HybridBlock):
    """Token ids (B, S) -> logits (B, S, rows). ``cfg`` holds the
    published ``config.json`` keys; besides them ``vocab_rows`` (the rows
    of the vocabulary held here, default ``vocab_size``),
    ``moe_router_width`` (all the experts the router scores, default
    ``moe_num_primary_experts``, which counts the experts HELD) and
    ``moe_first_expert`` (default 0)."""

    def __init__(self, cfg: dict, **kwargs):
        super().__init__(**kwargs)
        units = cfg["hidden_size"]
        rows = cfg.get("vocab_rows", cfg["vocab_size"])
        self.embed = Embedding(rows, units)
        self.layers = []
        for i in range(cfg["num_hidden_layers"]):
            layer = SmallThinkerLayer(cfg, i)
            setattr(self, f"layer{i}", layer)
            self.layers.append(layer)
        self.final_norm = RMSNorm(epsilon=cfg["rms_norm_eps"],
                                  in_channels=units)
        self.head = Dense(rows, use_bias=False, flatten=False,
                          in_units=units)

    def forward(self, tokens):
        h = self.embed(tokens)
        for layer in self.layers:
            h = layer(h)
        return self.head(self.final_norm(h))
