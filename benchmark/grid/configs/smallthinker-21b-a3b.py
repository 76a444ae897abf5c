"""smallthinker-21b-a3b: the program's net, its traffic, its operation
counts and its plain reference. Sizes come from
``smallthinker-21b-a3b.json``: one chip's share of an 8-chip deployment
(8 of the 64 experts of each layer, an eighth of the vocabulary, attention
and router whole), four layers = one period of the layer pattern.

The layer, as published (``u`` normed, pre-norm residual)::

    u   = RMSNorm(h; g1)                r = u W_r   (all 64 logits)
    q, k, v = u W_q (28 x 128), u W_k (4 x 128), u W_v (4 x 128)
    layout 1: q, k <- RoPE(theta 1.5e6, rotate-half over 128); layout 0: none
    a   = softmax(q k^T / sqrt(128) + mask) v,  q head n reads kv head n // 7
          mask: causal; layout 1 also hides key j unless 0 <= i - j < 4096
    h'  = h + a W_o                      x = RMSNorm(h'; g2)
    T(t) = top-6 of r_t;  w_t = softmax(r_t[T(t)])   in float32
    y_e(x) = W_down,e (relu(W_gate,e x) * (W_up,e x))
    h'' = h' + sum over e in T(t), e held here, of w_t,e y_e(x_t)
    logits = RMSNorm(h_L; g_f) W_head^T
"""
import math

import numpy as onp

NAME = "smallthinker-21b-a3b"


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_net(cfg: dict, traffic: dict):
    """``SmallThinkerLM`` of the model zoo at the configuration's sizes."""
    from mxnet_tpu.gluon.model_zoo import smallthinker
    return smallthinker.SmallThinkerLM(cfg)


# ---------------------------------------------------------------------------
# weights and traffic, from the seed
# ---------------------------------------------------------------------------

def _dims(cfg: dict) -> dict:
    d = cfg["head_dim"]
    return {"h": cfg["hidden_size"], "d": d,
            "q": cfg["num_attention_heads"] * d,
            "kv": cfg["num_key_value_heads"] * d,
            "f": cfg["moe_ffn_hidden_size"],
            "held": cfg["moe_num_primary_experts"],
            "router": cfg["moe_router_width"],
            "k": cfg["moe_num_active_primary_experts"],
            "rows": cfg["vocab_rows"], "layers": cfg["num_hidden_layers"]}


def param_spec(cfg: dict) -> list:
    """``[(name, shape, kind, scale)]`` under the names the program's
    ``collect_params()`` gives, in its order. Every matrix is
    ``normal(0, initializer_range)`` but the embedding table, whose rows
    are ``normal(0, embed_initializer_range)``: the stream the routers read
    is then each token's own, every seed routes about the expected share
    of pairs to the held experts and the work of a step stays what it was
    at the first (the configuration's ``assumed`` says why)."""
    n, s = _dims(cfg), cfg["initializer_range"]
    spec = [("embed.weight", (n["rows"], n["h"]), "normal",
             cfg["embed_initializer_range"])]
    for layer in range(n["layers"]):
        pre = f"layer{layer}"
        spec += [
            (f"{pre}.attn_norm.gamma", (n["h"],), "gamma", s),
            (f"{pre}.attention.query_proj.weight", (n["q"], n["h"]),
             "normal", s),
            (f"{pre}.attention.key_proj.weight", (n["kv"], n["h"]),
             "normal", s),
            (f"{pre}.attention.value_proj.weight", (n["kv"], n["h"]),
             "normal", s),
            (f"{pre}.attention.out_proj.weight", (n["h"], n["q"]),
             "normal", s),
            (f"{pre}.ffn_norm.gamma", (n["h"],), "gamma", s),
            (f"{pre}.experts.router_weight", (n["router"], n["h"]),
             "normal", s),
            (f"{pre}.experts.gate_weight", (n["held"], n["f"], n["h"]),
             "normal", s),
            (f"{pre}.experts.up_weight", (n["held"], n["f"], n["h"]),
             "normal", s),
            (f"{pre}.experts.down_weight", (n["held"], n["h"], n["f"]),
             "normal", s)]
    spec += [("final_norm.gamma", (n["h"],), "gamma", s),
             ("head.weight", (n["rows"], n["h"]), "normal", s)]
    return spec


def batches(cfg: dict, traffic: dict, seed: int) -> list:
    """The pool of distinct host batches: inputs and next-token targets
    drawn uniformly from the slice of the vocabulary held here."""
    rng = onp.random.default_rng(seed)
    shape = (traffic["batch"], traffic["seq"])
    return [(rng.integers(0, cfg["vocab_rows"], shape, dtype="int32"),
             rng.integers(0, cfg["vocab_rows"], shape, dtype="int32"))
            for _ in range(traffic["pool"])]


def tokens_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq"]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------

def attended_pairs(seq: int, window=None) -> int:
    """Query-key pairs one head of one sequence attends to: causal, and
    with a window the last ``window`` keys only."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _layer_pairs(cfg: dict, seq: int) -> list:
    """``attended_pairs`` of each layer here, by its layout."""
    return [attended_pairs(seq, cfg["sliding_window_size"] if windowed
                           else None)
            for windowed in
            cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]]


def _expert_flops(cfg: dict) -> int:
    """Forward FLOPs of one expert on one token: gate, up, down."""
    return 3 * 2 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def held_pairs_per_token(cfg: dict) -> float:
    """EXPECTED token-expert pairs a token gives the experts held here,
    under a uniform router: k * held / router width (6 * 8 / 64)."""
    return cfg["moe_num_active_primary_experts"] \
        * cfg["moe_num_primary_experts"] / cfg["moe_router_width"]


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """Forward and backward matrix-product FLOPs a token requires (the
    backward pass twice the forward; recomputation not counted): the
    projections, the router, scores and PV over the causal in-window
    pairs only, the EXPECTED share of held experts (the true share is
    the routing's: see ``kernel_costs``), the head."""
    n, seq = _dims(cfg), traffic["seq"]
    proj = 2 * (2 * n["h"] * n["q"] + 2 * n["h"] * n["kv"])
    router = 2 * n["h"] * n["router"]
    attention = sum(_layer_pairs(cfg, seq)) * 4 * n["q"] / seq
    experts = n["layers"] * held_pairs_per_token(cfg) * _expert_flops(cfg)
    head = 2 * n["h"] * n["rows"]
    return 3.0 * (n["layers"] * (proj + router) + attention + experts + head)


def kernel_costs(cfg: dict, traffic: dict) -> dict:
    """Per step, for each kernel scope: the FLOPs and the HBM bytes the
    algorithm needs, whatever implements it.

    ``flash_attention``: QK^T and PV over the causal in-window pairs only
    (a kernel that does not skip the rest reads low), backward twice the
    forward; q, o, do, dq at the query heads' width and k, v, dk, dv at
    the key/value heads' (forward reads q, k, v, writes o; backward reads
    q, k, v, o, do, writes dq, dk, dv), bf16.

    ``moe_experts``: the three products of the EXPECTED held pairs, N * k
    * held / router width (6,144 a layer in the cell), backward twice the
    forward; the held weights read twice (bf16) and their float32
    gradient written once. The true count is the routing's. With the
    embedding rows at ``embed_initializer_range`` two seeds on the chip
    (PR 28) gave their layers 5,939 to 6,263 held pairs at the first step
    (97 % to 102 % of the expectation) and 6 % more after 93 steps: only
    the held experts reach this chip's loss, so Adam still pulls tokens
    towards them, slowly. (At 0.02 a layer read 216 to 9,013, and a step's
    pairs 2.6 times the first step's after 93.) A share against these
    FLOPs moves with that."""
    n, b, seq, act = _dims(cfg), traffic["batch"], traffic["seq"], 2
    attn_flops = 3 * 4 * n["q"] * b * sum(_layer_pairs(cfg, seq))
    attn_bytes = n["layers"] * 6 * b * seq * (n["q"] + n["kv"]) * act
    pairs = b * seq * held_pairs_per_token(cfg)
    weights = n["held"] * 3 * n["h"] * n["f"]
    return {
        "flash_attention": {"flops": float(attn_flops),
                            "bytes": float(attn_bytes)},
        "moe_experts": {
            "flops": float(n["layers"] * 3 * pairs * _expert_flops(cfg)),
            "bytes": float(n["layers"] * weights * (2 * act + 4))}}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def loss_sum(cfg: dict, dot):
    """``f(params, tokens, targets)``: the SUM over the rows of each row's
    mean softmax cross-entropy over its positions, in float32, every
    matrix product through ``dot``.

    Departures from the published description, the program's too: the
    experts are the ``moe_num_primary_experts`` held here (first
    ``moe_first_expert``), each run on EVERY token and kept by a 0/1 mask
    of the router's choice (no sort, no gather, no kernel), what the other
    experts would add left out; the vocabulary is the slice of
    ``vocab_rows`` rows. To fit beside ``reference.follow``'s state each
    layer is a ``jax.checkpoint`` and attention takes one query head at a
    time (a head's S x S float32 scores at S = 8192 are 268 MB)."""
    import jax
    import jax.numpy as jnp
    n, eps = _dims(cfg), cfg["rms_norm_eps"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group, d = heads // kv_heads, n["d"]
    first = cfg.get("moe_first_expert", 0)
    layouts = list(zip(cfg["rope_layout"], cfg["sliding_window_layout"]))

    def rms(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def rope(x):                                    # (B, S, H, D)
        s = x.shape[1]
        inv = cfg["rope_theta"] ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1)

    def attention(q, k, v, windowed):               # (B, S, H or Hkv, D)
        s = q.shape[1]
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        seen = j <= i
        if windowed:
            seen = seen & (i - j < cfg["sliding_window_size"])
        kt, vt = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)

        @jax.checkpoint
        def one_head(args):
            qh, head = args                         # (B, S, D)
            kh, vh = kt[head // group], vt[head // group]
            scores = dot("bqd,bkd->bqk", qh, kh) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
            return dot("bqk,bkd->bqd", probs, vh)

        out = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0),
                                     jnp.arange(heads)))
        return jnp.moveaxis(out, 0, 2)              # (B, S, H, D)

    def experts(x, logits, p, pre):                 # (N, h), (N, router)
        top_vals, top_idx = jax.lax.top_k(logits, n["k"])
        weights = jax.nn.softmax(top_vals, -1)
        out = jnp.zeros_like(x)
        for e in range(n["held"]):
            chosen = jnp.sum(jnp.where(top_idx == first + e, weights, 0.0),
                             -1)
            gate = dot("nh,fh->nf", x, p[f"{pre}.experts.gate_weight"][e])
            up = dot("nh,fh->nf", x, p[f"{pre}.experts.up_weight"][e])
            y = dot("nf,hf->nh", jax.nn.relu(gate) * up,
                    p[f"{pre}.experts.down_weight"][e])
            out = out + chosen[:, None] * y
        return out

    def layer(h, p, pre, rotary, windowed):
        b, s, _ = h.shape
        u = rms(h, p[f"{pre}.attn_norm.gamma"])
        logits = dot("bsh,eh->bse", u, p[f"{pre}.experts.router_weight"])
        q = dot("bsh,oh->bso", u, p[f"{pre}.attention.query_proj.weight"]) \
            .reshape(b, s, heads, d)
        k = dot("bsh,oh->bso", u, p[f"{pre}.attention.key_proj.weight"]) \
            .reshape(b, s, kv_heads, d)
        v = dot("bsh,oh->bso", u, p[f"{pre}.attention.value_proj.weight"]) \
            .reshape(b, s, kv_heads, d)
        if rotary:
            q, k = rope(q), rope(k)
        a = attention(q, k, v, windowed).reshape(b, s, heads * d)
        h = h + dot("bso,ho->bsh", a,
                    p[f"{pre}.attention.out_proj.weight"])
        x = rms(h, p[f"{pre}.ffn_norm.gamma"])
        moe = experts(x.reshape(b * s, -1), logits.reshape(b * s, -1), p,
                      pre)
        return h + moe.reshape(b, s, -1)

    def f(p, tokens, targets):
        h = p["embed.weight"][tokens]
        for index in range(n["layers"]):
            pre = f"layer{index}"
            rotary, windowed = layouts[index]
            mine = {k: v for k, v in p.items() if k.startswith(pre + ".")}
            h = jax.checkpoint(
                lambda h_, p_, pre=pre, rotary=rotary, windowed=windowed:
                layer(h_, p_, pre, bool(rotary), bool(windowed)))(h, mine)
        logits = dot("bsh,vh->bsv", rms(h, p["final_norm.gamma"]),
                     p["head.weight"])
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(logp, targets[..., None], -1)
        return -jnp.sum(picked) / tokens.shape[1]

    return f
