"""lfm2-24b-a2b: the program's net, its traffic, its operation counts and
its plain reference. Sizes come from ``lfm2-24b-a2b.json``: one chip's
share of an 8-chip deployment (8 of the 64 routed experts of each expert
layer, an eighth of the vocabulary; the conv mixers, attention, router and
dense layer whole), the model's layer 0 and layers 2-5
(``built_layer_types``: conv, full_attention, conv, conv, conv).

The model, as published (``model_type`` ``lfm2_moe``; pre-norm, RMSNorm
``x rsqrt(mean x^2 + 1e-5) g`` everywhere, no bias)::

    layer l:   u  = RMSNorm_op(h)
               h  = h + (ShortConv(u) if the layer is "conv" else Attn(u))
               h  = h + FF_l(RMSNorm_ffn(h))
    ShortConv: [B | C | x] = u W_in                  2048 -> 3 x 2048
               v[t, c] = sum_{j=0..2} w[c, j] (B * x)[t - 2 + j, c]
               out = (C * v) W_out                   2048 -> 2048
    Attn:      q = RMSNorm_head(u W_q) per head of 64; k = RMSNorm_head(u W_k)
               q, k <- RoPE(theta 1e6, pairs (i, i + 32)); v = u W_v
               out = softmax(q k^T / 8 + causal) v  W_o     32 q / 8 kv heads
    FF_l:      l < num_dense_layers: W2 (silu(W1 y) * W3 y)          11776
               else: s = sigmoid(y W_r), all 64;  E = top-4 of (s + b)
                     w_e = s_e / (sum_{E} s + 1e-6) * routed_scaling_factor
                     sum_{e in E, e held here} w_e SwiGLU_e(y)       1536
    logits = RMSNorm_final(h) E^T          the embedding's rows, tied
"""
import math

import numpy as onp

NAME = "lfm2-24b-a2b"

#: what the family's router adds to the chosen scores' sum
ROUTER_NORM_EPS = 1e-6


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def built(cfg: dict) -> dict:
    """The configuration with ``layer_types`` the layers built here (the
    published list stays under its own key in the file)."""
    return dict(cfg, layer_types=cfg["built_layer_types"])


def build_net(cfg: dict, traffic: dict):
    """``LFM2MoeLM`` of the model zoo at the configuration's sizes."""
    from mxnet_tpu.gluon.model_zoo import lfm2
    return lfm2.LFM2MoeLM(built(cfg))


# ---------------------------------------------------------------------------
# weights and traffic, from the seed
# ---------------------------------------------------------------------------

def layer_types(cfg: dict) -> list:
    """The mixers built here: the first ``num_hidden_layers`` entries of
    ``built_layer_types``."""
    return cfg["built_layer_types"][:cfg["num_hidden_layers"]]


def _dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {"h": cfg["hidden_size"], "heads": heads,
            "kv_heads": cfg["num_key_value_heads"],
            "d": cfg["hidden_size"] // heads, "taps": cfg["conv_L_cache"],
            "dense": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "held": cfg["num_experts"],
            "router": cfg["moe_router_width"],
            "k": cfg["num_experts_per_tok"], "rows": cfg["vocab_rows"],
            "first_dense": cfg["num_dense_layers"]}


def _layer_spec(pre: str, kind: str, dense: bool, n: dict,
                cfg: dict) -> list:
    """One layer's leaves under ``pre``, in the program's order."""
    s = cfg["initializer_range"]
    spec = [(f"{pre}.operator_norm.gamma", (n["h"],), "gamma", s)]
    mix = f"{pre}.mixer"
    if kind == "conv":
        spec += [
            (f"{mix}.conv_weight", (n["h"], n["taps"]), "uniform",
             cfg["conv_initializer_range"]),
            (f"{mix}.in_proj.weight", (3 * n["h"], n["h"]), "normal", s),
            (f"{mix}.out_proj.weight", (n["h"], n["h"]), "normal", s)]
    else:
        wide, narrow = n["heads"] * n["d"], n["kv_heads"] * n["d"]
        spec += [
            (f"{mix}.q_norm_gamma", (n["d"],), "gamma", s),
            (f"{mix}.k_norm_gamma", (n["d"],), "gamma", s),
            (f"{mix}.query_proj.weight", (wide, n["h"]), "normal", s),
            (f"{mix}.key_proj.weight", (narrow, n["h"]), "normal", s),
            (f"{mix}.value_proj.weight", (narrow, n["h"]), "normal", s),
            (f"{mix}.out_proj.weight", (n["h"], wide), "normal", s)]
    spec.append((f"{pre}.ffn_norm.gamma", (n["h"],), "gamma", s))
    if dense:
        return spec + [
            (f"{pre}.ffn.gate_proj.weight", (n["dense"], n["h"]), "normal", s),
            (f"{pre}.ffn.up_proj.weight", (n["dense"], n["h"]), "normal", s),
            (f"{pre}.ffn.down_proj.weight", (n["h"], n["dense"]), "normal",
             s)]
    exp = f"{pre}.experts"
    return spec + [
        (f"{exp}.router_weight", (n["router"], n["h"]), "normal", s),
        (f"{exp}.router_bias", (n["router"],), "normal",
         cfg["router_bias_range"]),
        (f"{exp}.gate_weight", (n["held"], n["f"], n["h"]), "normal", s),
        (f"{exp}.up_weight", (n["held"], n["f"], n["h"]), "normal", s),
        (f"{exp}.down_weight", (n["held"], n["h"], n["f"]), "normal", s)]


def param_spec(cfg: dict) -> list:
    """``[(name, shape, kind, scale)]`` under the names the program's
    ``collect_params()`` gives, in its order. Every matrix is
    ``normal(0, initializer_range)`` and every gain 1 + that; the
    embedding rows (the head's too: tied) ``normal(0,
    embed_initializer_range)``, the routers' selection bias ``normal(0,
    router_bias_range)``, the conv taps ``uniform`` in
    +-``conv_initializer_range`` (the configuration's ``assumed`` says
    why each)."""
    n = _dims(cfg)
    spec = [("embed.weight", (n["rows"], n["h"]), "normal",
             cfg["embed_initializer_range"])]
    for index, kind in enumerate(layer_types(cfg)):
        spec += _layer_spec(f"layer{index}", kind, index < n["first_dense"],
                            n, cfg)
    return spec + [("final_norm.gamma", (n["h"],), "gamma",
                    cfg["initializer_range"])]


def batches(cfg: dict, traffic: dict, seed: int) -> list:
    """The pool of distinct host batches: a row is one stream of seq + 1
    ids drawn uniformly from the slice of the vocabulary held here, the
    input its first seq ids and the targets its last seq."""
    rng = onp.random.default_rng(seed)
    b, s = traffic["batch"], traffic["seq"]
    pool = []
    for _ in range(traffic["pool"]):
        t = rng.integers(0, cfg["vocab_rows"], (b, s + 1), dtype="int32")
        pool.append((t[:, :s], t[:, 1:]))
    return pool


def tokens_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq"]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------

def attended_pairs(seq: int) -> int:
    """Query-key pairs one head of one sequence attends to, causal."""
    return seq * (seq + 1) // 2


def held_pairs_per_token(cfg: dict) -> float:
    """EXPECTED token-expert pairs a token gives the experts held here,
    under a uniform router: k * held / router width (4 * 8 / 64)."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["moe_router_width"]


def forward_flops(cfg: dict, traffic: dict) -> dict:
    """Forward matrix-product FLOPs of ONE sequence, part by part (one
    layer of its kind). The short conv's gates and taps are elementwise,
    a few FLOPs a lane: not counted."""
    n, seq = _dims(cfg), traffic["seq"]
    swiglu = lambda width: 3 * 2 * n["h"] * width
    return {
        "conv_proj": seq * 2 * n["h"] * (3 * n["h"] + n["h"]),
        "attn_proj": seq * 2 * n["h"] * 2 * n["d"]
        * (n["heads"] + n["kv_heads"]),
        "attention": attended_pairs(seq) * n["heads"] * 2 * 2 * n["d"],
        "dense_ffn": seq * swiglu(n["dense"]),
        "router": seq * 2 * n["h"] * n["router"],
        "held_experts": seq * held_pairs_per_token(cfg) * swiglu(n["f"]),
        "head": seq * 2 * n["h"] * n["rows"]}


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """Forward and backward FLOPs a token requires (the backward pass
    twice the forward; recomputation not counted): every projection,
    attention's scores and values over the causal pairs only, the dense
    layer's SwiGLU, router and the EXPECTED share of held experts in every
    other layer, and the head."""
    n, f = _dims(cfg), forward_flops(cfg, traffic)
    mixer = {"conv": f["conv_proj"],
             "full_attention": f["attn_proj"] + f["attention"]}
    forward = f["head"]
    for index, kind in enumerate(layer_types(cfg)):
        forward += mixer[kind] + (
            f["dense_ffn"] if index < n["first_dense"]
            else f["router"] + f["held_experts"])
    return 3.0 * forward / traffic["seq"]


def kernel_costs(cfg: dict, traffic: dict) -> dict:
    """Per step, for each kernel scope: the FLOPs and the HBM bytes the
    algorithm needs, whatever implements it.

    ``short_conv``: the gates and the conv between a conv mixer's two
    projections (``ops.ssm.gated_short_conv``), in every conv layer. No
    matrix product; elementwise work, bound by bytes: forward reads ``[B |
    C | x]`` (3 x 2048 lanes) and writes y (2048), backward reads dy
    (2048) and ``[B | C | x]`` again and writes its cotangent (3 x 2048),
    bf16. A form that writes ``B * x`` or the conv's output to HBM
    between passes spends time, not work: it reads low, and none reads
    over 100."""
    n, b, seq, act = _dims(cfg), traffic["batch"], traffic["seq"], 2
    convs = layer_types(cfg).count("conv")
    lanes = 3 * n["h"] + n["h"] + n["h"] + 3 * n["h"] + 3 * n["h"]
    return {"short_conv": {"flops": 0.0,
                           "bytes": float(convs * b * seq * lanes * act)}}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def loss_sum(cfg: dict, dot):
    """``f(params, tokens, targets)``: the SUM over the rows of each row's
    mean softmax cross-entropy over its seq predictions, in float32, every
    matrix product through ``dot`` (the logits :func:`forward`'s)."""
    import jax
    import jax.numpy as jnp
    logits_of = forward(cfg, dot)

    def f(p, tokens, targets):
        logp = jax.nn.log_softmax(logits_of(p, tokens), -1)
        return -jnp.sum(jnp.take_along_axis(
            logp, targets[..., None], -1)) / targets.shape[1]

    return f


def forward(cfg: dict, dot):
    """``f(params, tokens) -> logits`` (B, S, rows), in float32, every
    matrix product through ``dot``.

    Independent of the program's algorithm: the short conv is three
    shifted multiply-adds between its gates; attention is a masked softmax
    a head, keys and values repeated for the query heads that share them.
    Departures from the published description, the program's too: the
    experts are the ``num_experts`` held here (first
    ``moe_first_expert``), each run on EVERY token and kept by the
    router's weight or 0 (no sort, no gather, no kernel), what the other
    experts would add left out; the vocabulary is the slice of
    ``vocab_rows`` rows. To fit beside ``reference.follow``'s state each
    layer is a ``jax.checkpoint`` and attention takes one head at a
    time."""
    import jax
    import jax.numpy as jnp
    n, eps = _dims(cfg), cfg["norm_eps"]
    heads, kv, d = n["heads"], n["kv_heads"], n["d"]
    first = cfg.get("moe_first_expert", 0)
    rope = cfg["rope_parameters"]
    if cfg["conv_bias"] or not cfg["norm_topk_prob"] \
            or not cfg["use_expert_bias"] or rope["rope_type"] != "default":
        raise ValueError("the reference is written for convs without bias, "
                         "sigmoid scores picked with a bias and normalised "
                         "over the chosen, and the default RoPE")

    def rms(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def turn(x):                  # (B, S, H, d), pairs (i, i + d / 2)
        s = x.shape[1]
        inv = float(rope["rope_theta"]) ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def short_conv(u, p, pre):                       # u (B, S, h)
        s, h = u.shape[1], n["h"]
        bcx = dot("bsh,oh->bso", u, p[f"{pre}.in_proj.weight"])
        gated = bcx[..., :h] * bcx[..., 2 * h:]      # B * x
        back = jnp.pad(gated, ((0, 0), (n["taps"] - 1, 0), (0, 0)))
        w = p[f"{pre}.conv_weight"]
        conv = sum(w[:, j] * back[:, j:j + s] for j in range(n["taps"]))
        return dot("bsc,hc->bsh", bcx[..., h:2 * h] * conv,
                   p[f"{pre}.out_proj.weight"])

    def attention(u, p, pre):
        b, s, _ = u.shape
        q = rms(dot("bsh,oh->bso", u, p[f"{pre}.query_proj.weight"])
                .reshape(b, s, heads, d), p[f"{pre}.q_norm_gamma"])
        k = rms(dot("bsh,oh->bso", u, p[f"{pre}.key_proj.weight"])
                .reshape(b, s, kv, d), p[f"{pre}.k_norm_gamma"])
        v = dot("bsh,oh->bso", u, p[f"{pre}.value_proj.weight"]) \
            .reshape(b, s, kv, d)
        q = turn(q)
        k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (turn(k), v))
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

        @jax.checkpoint
        def one_head(args):
            q_h, k_h, v_h = args                             # (B, S, d)
            scores = dot("bqd,bkd->bqk", q_h, k_h) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
            return dot("bqk,bkd->bqd", probs, v_h)

        out = jax.lax.map(one_head, tuple(jnp.moveaxis(a, 2, 0)
                                          for a in (q, k, v)))
        return dot("bso,ho->bsh", jnp.moveaxis(out, 0, 2)
                   .reshape(b, s, heads * d), p[f"{pre}.out_proj.weight"])

    def swiglu(x, gate, up, down):
        return dot("nf,hf->nh", jax.nn.silu(dot("nh,fh->nf", x, gate))
                   * dot("nh,fh->nf", x, up), down)

    def experts(x, p, pre):                          # x (N, h)
        scores = jax.nn.sigmoid(dot("nh,eh->ne", x,
                                    p[f"{pre}.router_weight"]))
        _, top_idx = jax.lax.top_k(
            jax.lax.stop_gradient(scores + p[f"{pre}.router_bias"][None]),
            n["k"])
        chosen = jnp.take_along_axis(scores, top_idx, 1)
        weights = chosen / (jnp.sum(chosen, -1, keepdims=True)
                            + ROUTER_NORM_EPS) \
            * cfg["routed_scaling_factor"]
        out = 0.0
        for e in range(n["held"]):
            w_e = jnp.sum(jnp.where(top_idx == first + e, weights, 0.0), -1)
            out = out + w_e[:, None] * swiglu(
                x, p[f"{pre}.gate_weight"][e], p[f"{pre}.up_weight"][e],
                p[f"{pre}.down_weight"][e])
        return out

    mixers = {"conv": short_conv, "full_attention": attention}

    def layer(h, p, pre, kind, dense):
        b, s, _ = h.shape
        h = h + mixers[kind](rms(h, p[f"{pre}.operator_norm.gamma"]), p,
                             f"{pre}.mixer")
        y = rms(h, p[f"{pre}.ffn_norm.gamma"]).reshape(b * s, -1)
        if dense:
            y = swiglu(y, p[f"{pre}.ffn.gate_proj.weight"],
                       p[f"{pre}.ffn.up_proj.weight"],
                       p[f"{pre}.ffn.down_proj.weight"])
        else:
            y = experts(y, p, f"{pre}.experts")
        return h + y.reshape(b, s, -1)

    def run_layer(h, p, pre, kind, dense):
        mine = {k: v for k, v in p.items() if k.startswith(pre + ".")}
        return jax.checkpoint(
            lambda h_, p_: layer(h_, p_, pre, kind, dense))(h, mine)

    def f(p, tokens):
        h = p["embed.weight"][tokens]
        for index, kind in enumerate(layer_types(cfg)):
            h = run_layer(h, p, f"layer{index}", kind,
                          index < n["first_dense"])
        return dot("bsh,vh->bsv", rms(h, p["final_norm.gamma"]),
                   p["embed.weight"])

    return f
