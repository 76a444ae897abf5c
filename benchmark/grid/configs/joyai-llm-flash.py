"""joyai-llm-flash: the program's net, its traffic, its operation counts
and its plain reference. Sizes come from ``joyai-llm-flash.json``: one
chip's share of a 32-chip deployment (8 of the 256 routed experts of each
layer, an eighth of the vocabulary; attention, router, shared expert and
the dense layer whole), the first five layers (one dense, four with
experts) and the multi-token-prediction module.

The model, as published (pre-norm, RMSNorm, no bias)::

    u    = RMSNorm(h)
    c_q  = RMSNorm(u W_qa)                        2048 -> 1536
    q    = c_q W_qb -> 32 heads x [q_n (128) | q_r (64)];  q_r <- RoPE(q_r)
    [c_kv (512) | k_r (64)] = u W_kva;  c_kv <- RMSNorm(c_kv);  k_r <- RoPE(k_r)
    c_kv W_kvb -> 32 heads x [k_n (128) | v (128)]
    a    = softmax((q_n k_n^T + q_r k_r^T) / sqrt(192) + causal) v
           k_r is ONE head of 64 lanes, the same for all 32 query heads
    h'   = h + a W_o;   x = RMSNorm(h')
    RoPE: theta 32e6 over the 64 rotary lanes, pairs (2i, 2i + 1)
    layer 0:      h'' = h' + W_down (silu(W_gate x) * W_up x)      width 7168
    layers 1..:   s = sigmoid(x W_r), all 256;  T = top-8 of (s + b)
                  w_e = 2.5 s_e / sum_{e' in T} s_e'
                  h'' = h' + E_shared(x) + sum_{e in T, e held here} w_e E_e(x)
                  E(x) = W_down (silu(W_gate x) * W_up x)          width 768
    logits  = RMSNorm(h_L) W_head^T                    predicts t_{i+1}
    MTP:  g = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_L,i)] W_eh
          g <- one expert layer as above (attention and all)
          logits'_i = RMSNorm_s(g_i) W_head^T          predicts t_{i+2}
          Emb and W_head are the trunk's own tables
"""
import math

import numpy as onp

NAME = "joyai-llm-flash"


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_net(cfg: dict, traffic: dict):
    """``JoyAILM`` of the model zoo at the configuration's sizes."""
    from mxnet_tpu.gluon.model_zoo import joyai
    return joyai.JoyAILM(cfg)


# ---------------------------------------------------------------------------
# weights and traffic, from the seed
# ---------------------------------------------------------------------------

def _dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return {"h": cfg["hidden_size"], "heads": heads, "nope": nope,
            "rope": rope, "v": v, "qk": nope + rope,
            "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
            "dense": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"],
            "router": cfg["moe_router_width"],
            "k": cfg["num_experts_per_tok"],
            "rows": cfg["vocab_rows"], "layers": cfg["num_hidden_layers"],
            "first_dense": cfg["first_k_dense_replace"],
            "mtp": cfg["num_nextn_predict_layers"]}


def _layer_spec(pre: str, n: dict, dense: bool, s: float,
                bias_scale: float) -> list:
    """One decoder layer's leaves under ``pre``, in the program's order."""
    attn = f"{pre}.attention"
    spec = [
        (f"{pre}.attn_norm.gamma", (n["h"],), "gamma", s),
        (f"{attn}.q_a_norm_gamma", (n["q_rank"],), "gamma", s),
        (f"{attn}.kv_a_norm_gamma", (n["kv_rank"],), "gamma", s),
        (f"{attn}.q_a_proj.weight", (n["q_rank"], n["h"]), "normal", s),
        (f"{attn}.q_b_proj.weight", (n["heads"] * n["qk"], n["q_rank"]),
         "normal", s),
        (f"{attn}.kv_a_proj.weight", (n["kv_rank"] + n["rope"], n["h"]),
         "normal", s),
        (f"{attn}.kv_b_proj.weight",
         (n["heads"] * (n["nope"] + n["v"]), n["kv_rank"]), "normal", s),
        (f"{attn}.out_proj.weight", (n["h"], n["heads"] * n["v"]),
         "normal", s),
        (f"{pre}.ffn_norm.gamma", (n["h"],), "gamma", s)]
    if dense:
        return spec + [
            (f"{pre}.ffn.gate_proj.weight", (n["dense"], n["h"]),
             "normal", s),
            (f"{pre}.ffn.up_proj.weight", (n["dense"], n["h"]), "normal", s),
            (f"{pre}.ffn.down_proj.weight", (n["h"], n["dense"]),
             "normal", s)]
    exp = f"{pre}.experts"
    return spec + [
        (f"{exp}.router_weight", (n["router"], n["h"]), "normal", s),
        (f"{exp}.router_bias", (n["router"],), "normal", bias_scale),
        (f"{exp}.gate_weight", (n["held"], n["f"], n["h"]), "normal", s),
        (f"{exp}.up_weight", (n["held"], n["f"], n["h"]), "normal", s),
        (f"{exp}.down_weight", (n["held"], n["h"], n["f"]), "normal", s),
        (f"{exp}.shared_gate_weight", (n["shared"], n["h"]), "normal", s),
        (f"{exp}.shared_up_weight", (n["shared"], n["h"]), "normal", s),
        (f"{exp}.shared_down_weight", (n["h"], n["shared"]), "normal", s)]


def param_spec(cfg: dict) -> list:
    """``[(name, shape, kind, scale)]`` under the names the program's
    ``collect_params()`` gives, in its order. Every matrix is
    ``normal(0, initializer_range)`` and every gain 1 + that, but the
    embedding table, whose rows are ``normal(0,
    embed_initializer_range)`` (each token's stream is then its own and
    every seed routes about the expected share of pairs to the held
    experts), and the routers' selection bias, ``normal(0,
    router_bias_range)`` (the configuration's ``assumed`` says why
    neither is left at the family's 0.02 or at zero)."""
    n, s = _dims(cfg), cfg["initializer_range"]
    b = cfg["router_bias_range"]
    spec = [("embed.weight", (n["rows"], n["h"]), "normal",
             cfg["embed_initializer_range"])]
    for layer in range(n["layers"]):
        spec += _layer_spec(f"layer{layer}", n, layer < n["first_dense"], s,
                            b)
    spec += [("final_norm.gamma", (n["h"],), "gamma", s),
             ("head.weight", (n["rows"], n["h"]), "normal", s)]
    if n["mtp"]:
        spec += [("mtp.embed_norm.gamma", (n["h"],), "gamma", s),
                 ("mtp.hidden_norm.gamma", (n["h"],), "gamma", s),
                 ("mtp.proj.weight", (n["h"], 2 * n["h"]), "normal", s)]
        spec += _layer_spec("mtp.block", n, False, s, b)
        spec += [("mtp.head_norm.gamma", (n["h"],), "gamma", s)]
    return spec


def batches(cfg: dict, traffic: dict, seed: int) -> list:
    """The pool of distinct host batches. A row is cut from ONE stream t
    of seq + 2 ids drawn uniformly from the slice of the vocabulary held
    here: the input is t[0 .. seq] (seq + 1 ids: the trunk reads the
    first seq, the MTP module the last seq), the targets t[1 .. seq]
    then t[2 .. seq + 1], the trunk's rows of the output then the
    module's."""
    rng = onp.random.default_rng(seed)
    b, s = traffic["batch"], traffic["seq"]
    pool = []
    for _ in range(traffic["pool"]):
        t = rng.integers(0, cfg["vocab_rows"], (b, s + 2), dtype="int32")
        pool.append((t[:, :s + 1],
                     onp.concatenate([t[:, 1:s + 1], t[:, 2:]], axis=1)))
    return pool


def tokens_per_step(cfg: dict, traffic: dict) -> int:
    """Positions a step trains on; each is predicted twice (by the trunk
    and by the MTP module), which doubles the output's rows, not the
    tokens."""
    return traffic["batch"] * traffic["seq"]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------

def attended_pairs(seq: int) -> int:
    """Query-key pairs one head of one sequence attends to, causal."""
    return seq * (seq + 1) // 2


def held_pairs_per_token(cfg: dict) -> float:
    """EXPECTED token-expert pairs a token gives the experts held here,
    under a uniform router: k * held / router width (8 * 8 / 256)."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["moe_router_width"]


def _attention_blocks(cfg: dict) -> int:
    """Decoder layers a step runs: the trunk's and the MTP module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def forward_flops(cfg: dict, traffic: dict) -> dict:
    """Forward matrix-product FLOPs of ONE sequence, part by part."""
    n, seq = _dims(cfg), traffic["seq"]
    proj = seq * 2 * (n["h"] * n["q_rank"]
                      + n["q_rank"] * n["heads"] * n["qk"]
                      + n["h"] * (n["kv_rank"] + n["rope"])
                      + n["kv_rank"] * n["heads"] * (n["nope"] + n["v"])
                      + n["heads"] * n["v"] * n["h"])
    attention = attended_pairs(seq) * n["heads"] * 2 * (n["qk"] + n["v"])
    gated = lambda width: 3 * 2 * n["h"] * width
    return {"proj": proj, "attention": attention,
            "dense_ffn": seq * gated(n["dense"]),
            "shared": seq * gated(n["shared"]),
            "router": seq * 2 * n["h"] * n["router"],
            "held_experts": seq * held_pairs_per_token(cfg) * gated(n["f"]),
            "mtp_proj": seq * 2 * 2 * n["h"] * n["h"],
            "head": seq * 2 * n["h"] * n["rows"]}


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """Forward and backward matrix-product FLOPs a token requires (the
    backward pass twice the forward; recomputation not counted): the
    low-rank projections, scores over 192 lanes and PV over 128 of the
    causal pairs only, the dense layer's feed-forward, router, shared
    expert and the EXPECTED share of held experts in every other layer,
    the MTP module's projection and layer, and the head twice."""
    n, f = _dims(cfg), forward_flops(cfg, traffic)
    mixer = f["proj"] + f["attention"]
    expert_layer = mixer + f["shared"] + f["router"] + f["held_experts"]
    forward = n["first_dense"] * (mixer + f["dense_ffn"]) \
        + (n["layers"] - n["first_dense"]) * expert_layer + f["head"] \
        + n["mtp"] * (f["mtp_proj"] + expert_layer + f["head"])
    return 3.0 * forward / traffic["seq"]


def kernel_costs(cfg: dict, traffic: dict) -> dict:
    """Per step, for each kernel scope: the FLOPs and the HBM bytes the
    algorithm needs, whatever implements it.

    ``flash_attention``: QK^T over the keys' 192 lanes and PV over the
    values' 128, of the causal pairs only, backward twice the forward,
    in every decoder layer and in the MTP module's; q, k, dq, dk at 32 x
    192 and v, o, do, dv at 32 x 128 (forward reads q, k, v, writes o;
    backward reads q, k, v, o, do, writes dq, dk, dv), bf16. A kernel
    that pads a width or computes what the mask hides spends time, not
    work: it reads low, and none reads over 100. (The shared rotary key
    is counted as the 32 heads' keys it is broadcast into: what a kernel
    that reads it once would save is bytes, and it shows as a share over
    this count's.)"""
    n, b, seq, act = _dims(cfg), traffic["batch"], traffic["seq"], 2
    blocks = _attention_blocks(cfg)
    flops = 3 * blocks * b * forward_flops(cfg, traffic)["attention"]
    wide = n["heads"] * (n["qk"] + n["v"])
    return {"flash_attention": {
        "flops": float(flops),
        "bytes": float(blocks * 6 * b * seq * wide * act)}}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def loss_sum(cfg: dict, dot):
    """``f(params, tokens, targets)``: the SUM over the rows of each row's
    mean softmax cross-entropy over its 2 x seq predictions (the trunk's
    and the MTP module's, weight 1 each), in float32, every matrix
    product through ``dot``.

    Departures from the published description, the program's too: the
    experts are the ``n_routed_experts`` held here (first
    ``moe_first_expert``), each run on EVERY token and kept by the
    router's weight or 0 (no sort, no gather, no kernel), what the other
    experts would add left out; the vocabulary is the slice of
    ``vocab_rows`` rows. Unlike the program the reference never writes
    the shared rotary key into the heads' keys: a head's scores are q_n
    k_n^T + q_r k_r^T. To fit beside ``reference.follow``'s state each
    layer is a ``jax.checkpoint`` and attention takes one head at a
    time."""
    import jax
    import jax.numpy as jnp
    n, eps = _dims(cfg), cfg["rms_norm_eps"]
    heads, nope, rope_w, v_w = n["heads"], n["nope"], n["rope"], n["v"]
    first = cfg.get("moe_first_expert", 0)
    act = {"silu": jax.nn.silu}[cfg["hidden_act"]]
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or not cfg["rope_interleave"] or cfg["n_group"] != 1:
        raise ValueError("the reference is written for sigmoid scores "
                         "normalised over the chosen, one group, and "
                         "interleaved RoPE")

    def rms(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def rope(x):                          # (..., S, rope_w), pairs (2i, 2i+1)
        s = x.shape[-2]
        inv = cfg["rope_theta"] ** (
            -jnp.arange(0, rope_w, 2, dtype=jnp.float32) / rope_w)
        angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        pairs = x.reshape(x.shape[:-1] + (rope_w // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)

    def attention(u, p, pre):                        # u (B, S, h)
        b, s, _ = u.shape
        c_q = rms(dot("bsh,rh->bsr", u, p[f"{pre}.q_a_proj.weight"]),
                  p[f"{pre}.q_a_norm_gamma"])
        q = dot("bsr,or->bso", c_q, p[f"{pre}.q_b_proj.weight"]) \
            .reshape(b, s, heads, nope + rope_w)
        kv_a = dot("bsh,rh->bsr", u, p[f"{pre}.kv_a_proj.weight"])
        c_kv = rms(kv_a[..., :n["kv_rank"]], p[f"{pre}.kv_a_norm_gamma"])
        k_r = rope(kv_a[..., n["kv_rank"]:])         # (B, S, 64): one head
        kv = dot("bsr,or->bso", c_kv, p[f"{pre}.kv_b_proj.weight"]) \
            .reshape(b, s, heads, nope + v_w)
        q_n = jnp.moveaxis(q[..., :nope], 2, 0)      # (H, B, S, 128)
        q_r = rope(jnp.moveaxis(q[..., nope:], 2, 0))
        k_n = jnp.moveaxis(kv[..., :nope], 2, 0)
        v = jnp.moveaxis(kv[..., nope:], 2, 0)
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

        @jax.checkpoint
        def one_head(args):
            qn, qr, kn, vh = args                    # (B, S, D)
            scores = (dot("bqd,bkd->bqk", qn, kn)
                      + dot("bqd,bkd->bqk", qr, k_r)) \
                / math.sqrt(nope + rope_w)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
            return dot("bqk,bkd->bqd", probs, vh)

        out = jax.lax.map(one_head, (q_n, q_r, k_n, v))
        a = jnp.moveaxis(out, 0, 2).reshape(b, s, heads * v_w)
        return dot("bso,ho->bsh", a, p[f"{pre}.out_proj.weight"])

    def gated(x, gate, up, down):
        return dot("nf,hf->nh", act(dot("nh,fh->nf", x, gate))
                   * dot("nh,fh->nf", x, up), down)

    def experts(x, p, pre):                          # x (N, h)
        scores = jax.nn.sigmoid(dot("nh,eh->ne", x,
                                    p[f"{pre}.router_weight"]))
        _, top_idx = jax.lax.top_k(
            jax.lax.stop_gradient(scores + p[f"{pre}.router_bias"][None]),
            n["k"])
        chosen = jnp.take_along_axis(scores, top_idx, 1)
        weights = cfg["routed_scaling_factor"] * chosen \
            / jnp.sum(chosen, -1, keepdims=True)
        out = gated(x, p[f"{pre}.shared_gate_weight"],
                    p[f"{pre}.shared_up_weight"],
                    p[f"{pre}.shared_down_weight"])
        for e in range(n["held"]):
            w_e = jnp.sum(jnp.where(top_idx == first + e, weights, 0.0), -1)
            out = out + w_e[:, None] * gated(
                x, p[f"{pre}.gate_weight"][e], p[f"{pre}.up_weight"][e],
                p[f"{pre}.down_weight"][e])
        return out

    def layer(h, p, pre, dense):
        b, s, _ = h.shape
        h = h + attention(rms(h, p[f"{pre}.attn_norm.gamma"]), p,
                          f"{pre}.attention")
        x = rms(h, p[f"{pre}.ffn_norm.gamma"]).reshape(b * s, -1)
        if dense:
            y = gated(x, p[f"{pre}.ffn.gate_proj.weight"],
                      p[f"{pre}.ffn.up_proj.weight"],
                      p[f"{pre}.ffn.down_proj.weight"])
        else:
            y = experts(x, p, f"{pre}.experts")
        return h + y.reshape(b, s, -1)

    def run_layer(h, p, pre, dense):
        mine = {k: v for k, v in p.items() if k.startswith(pre + ".")}
        return jax.checkpoint(
            lambda h_, p_: layer(h_, p_, pre, dense))(h, mine)

    def nll(h, gain, head, targets):
        logits = dot("bsh,vh->bsv", rms(h, gain), head)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))

    def f(p, tokens, targets):
        s = tokens.shape[1] - n["mtp"]
        h = p["embed.weight"][tokens[:, :s]]
        for index in range(n["layers"]):
            h = run_layer(h, p, f"layer{index}", index < n["first_dense"])
        total = nll(h, p["final_norm.gamma"], p["head.weight"],
                    targets[:, :s])
        if n["mtp"]:
            e = rms(p["embed.weight"][tokens[:, 1:]],
                    p["mtp.embed_norm.gamma"])
            g = dot("bsc,hc->bsh", jnp.concatenate(
                [e, rms(h, p["mtp.hidden_norm.gamma"])], -1),
                p["mtp.proj.weight"])
            g = run_layer(g, p, "mtp.block", False)
            total = total + nll(g, p["mtp.head_norm.gamma"],
                                p["head.weight"], targets[:, s:])
        return total / targets.shape[1]

    return f
