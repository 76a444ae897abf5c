"""JoyAI-LLM-Flash: a decoder-only language model of the DeepSeek-V3
family (jdopensource, 2026-04; ``JoyAI-LLM-Flash`` ``config.json``,
``model_type`` ``joyai_llm_flash``, 48B-A2.7B).

Every layer, pre-norm, RMSNorm, no bias::

    h' = h + latent_attention(RMSNorm(h))     MLA: 32 heads, keys 128 +
                                              64 rotary lanes wide (the
                                              rotary lanes ONE head shared
                                              by all), values 128 wide
    x  = RMSNorm(h')
    h''= h' + W_down (silu(W_gate x) * W_up x)          the first
                                              ``first_k_dense_replace``
                                              layers, width
                                              ``intermediate_size``
    h''= h' + E_shared(x) + sum_{e in T} w_e E_e(x)     every other layer:
         s = sigmoid(x W_r);  T = top-8 of (s + b);  w_e = 2.5 s_e /
         sum_{T} s;  experts SwiGLU of ``moe_intermediate_size``

and ``num_nextn_predict_layers`` (0 or 1) multi-token-prediction module
behind the trunk, a training objective: position i joins the trunk's
last hidden state (before the final norm) with the embedding of token
i + 1 and predicts token i + 2 through one more expert layer and the
trunk's own embedding and head tables::

    g = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh;  g <- layer(g)
    logits'_i = RMSNorm_s(g_i) W_head^T

Embedding and head are untied from each other; the module ties to both.

One chip's share of a deployment is the same model: the expert layers are
told which experts they hold (``gluon.nn.SparseMoE`` ``held``) and the
vocabulary may be a slice (``vocab_rows``).
"""
from __future__ import annotations

from ...ndarray import ops as F
from ...ops.kernels import count_traced
from ...ops.registry import scope
from ..block import HybridBlock
from ..nn.basic_layers import Dense, Embedding, RMSNorm
from ..nn.moe import SparseMoE
from ..nn.transformer import LatentAttention

__all__ = ["GatedFFN", "JoyAILayer", "JoyAIMTP", "JoyAILM"]


class GatedFFN(HybridBlock):
    """``W_down (act(W_gate x) * (W_up x))``, no bias: the dense layers'
    feed-forward (SwiGLU with ``silu``)."""

    def __init__(self, units: int, hidden: int, activation: str = "silu",
                 **kwargs):
        super().__init__(**kwargs)
        self._activation = activation

        def dense(out, inp):
            return Dense(out, use_bias=False, flatten=False, in_units=inp)
        self.gate_proj = dense(hidden, units)
        self.up_proj = dense(hidden, units)
        self.down_proj = dense(units, hidden)

    def forward(self, x):
        gate = F.Activation(self.gate_proj(x), act_type=self._activation)
        return self.down_proj(gate * self.up_proj(x))


class JoyAILayer(HybridBlock):
    """One decoder layer: latent attention, then the dense feed-forward
    (``dense=True``) or the sparse experts with their shared expert."""

    def __init__(self, cfg: dict, dense: bool, **kwargs):
        super().__init__(**kwargs)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.attn_norm = RMSNorm(epsilon=eps, in_channels=units)
        self.attention = LatentAttention(
            units, cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["rope_theta"],
            rope_interleave=cfg["rope_interleave"], epsilon=eps)
        self.ffn_norm = RMSNorm(epsilon=eps, in_channels=units)
        if dense:
            self.ffn = GatedFFN(units, cfg["intermediate_size"],
                                cfg["hidden_act"])
        else:
            held = cfg["n_routed_experts"]
            self.experts = SparseMoE(
                units, cfg["moe_intermediate_size"],
                cfg.get("moe_router_width", held),
                cfg["num_experts_per_tok"],
                held=(cfg.get("moe_first_expert", 0), held),
                score=cfg["scoring_func"],
                routed_scale=cfg["routed_scaling_factor"],
                activation=cfg["hidden_act"],
                shared_hidden=cfg["n_shared_experts"]
                * cfg["moe_intermediate_size"])
        self._dense = dense

    def forward(self, h):
        h = h + self.attention(self.attn_norm(h))
        x = self.ffn_norm(h)
        return h + (self.ffn(x) if self._dense else self.experts(x))


class JoyAIMTP(HybridBlock):
    """What one multi-token-prediction module holds of its own: the two
    norms and the projection that join a token's embedding to the
    trunk's hidden state, one expert layer, and the norm in front of the
    (trunk's) head. ``forward(e, h)``: embeddings of the next tokens and
    the trunk's last hidden states -> what the head reads."""

    def __init__(self, cfg: dict, **kwargs):
        super().__init__(**kwargs)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.embed_norm = RMSNorm(epsilon=eps, in_channels=units)
        self.hidden_norm = RMSNorm(epsilon=eps, in_channels=units)
        self.proj = Dense(units, use_bias=False, flatten=False,
                          in_units=2 * units)
        self.block = JoyAILayer(cfg, dense=False)
        self.head_norm = RMSNorm(epsilon=eps, in_channels=units)

    def forward(self, e, h):
        g = self.proj(F.concat(self.embed_norm(e), self.hidden_norm(h),
                               dim=2))
        return self.head_norm(self.block(g))


class JoyAILM(HybridBlock):
    """Token ids -> logits. ``cfg`` holds the published ``config.json``
    keys; besides them ``vocab_rows`` (the rows of the vocabulary held
    here, default ``vocab_size``), ``moe_router_width`` (all the experts
    the router scores, default ``n_routed_experts``, which counts the
    experts HELD) and ``moe_first_expert`` (default 0).

    Without an MTP module: ids (B, S) -> (B, S, rows). With one: ids
    (B, S + 1) -> (B, 2 S, rows); the trunk reads ids[:, :S] and gives
    rows [0, S), which predict ids[:, 1:]; the module reads the trunk's
    hidden states with ids[:, 1:] and gives rows [S, 2 S), of which row
    S + i predicts token i + 2. One softmax cross-entropy over the 2 S
    rows against [t_1 .. t_S, t_2 .. t_{S+1}] is then the mean of the
    two losses (an MTP weight of 1)."""

    def __init__(self, cfg: dict, **kwargs):
        super().__init__(**kwargs)
        units = cfg["hidden_size"]
        rows = cfg.get("vocab_rows", cfg["vocab_size"])
        if cfg["num_nextn_predict_layers"] not in (0, 1) \
                or cfg.get("moe_layer_freq", 1) != 1:
            raise ValueError("JoyAILM builds 0 or 1 MTP modules and an "
                             "expert layer at every layer past the dense")
        self.embed = Embedding(rows, units)
        self.layers = []
        for i in range(cfg["num_hidden_layers"]):
            layer = JoyAILayer(cfg, dense=i < cfg["first_k_dense_replace"])
            setattr(self, f"layer{i}", layer)
            self.layers.append(layer)
        self.final_norm = RMSNorm(epsilon=cfg["rms_norm_eps"],
                                  in_channels=units)
        self.head = Dense(rows, use_bias=False, flatten=False,
                          in_units=units)
        self.mtp = JoyAIMTP(cfg) if cfg["num_nextn_predict_layers"] else None

    def forward(self, tokens):
        if self.mtp is None:
            h = self.embed(tokens)
        else:
            s = tokens.shape[1] - 1
            h = self.embed(tokens[:, :s])
        for layer in self.layers:
            h = layer(h)
        logits = self.head(self.final_norm(h))
        if self.mtp is None:
            return logits
        count_traced("MTP_MODULES")
        with scope("mtp"):
            # the module's tables are the trunk's: both collect a second
            # gradient here
            more = self.head(self.mtp(self.embed(tokens[:, 1:]), h))
        return F.concat(logits, more, dim=1)
