"""nemotron-3-nano-30b-a3b: the program's net, its traffic, its operation
counts and its plain reference. Sizes come from
``nemotron-3-nano-30b-a3b.json``: one chip's share of a 16-chip deployment
(8 of the 128 routed experts of each expert layer, an eighth of the
vocabulary; the Mamba-2 mixers, attention, router and shared expert
whole), the model's first nine layers ``MEMEM*EME``.

The model, as published (``model_type`` ``nemotron_h``): every layer ONE
mixer, ``h <- h + mixer(RMSNorm(h))``, eps 1e-5, no bias but the conv's;
``hybrid_override_pattern`` names the mixer of each layer::

    M   [z | xBC | dt] = u W_in       2688 -> 4096 + (4096 + 2*8*128) + 64
        xBC = silu(conv(xBC))         causal depthwise, 4 taps, with bias
        dt  = softplus(dt + dt_bias);  A = -exp(A_log)     per head, 64
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T         (64 x 128) a head
        y_t = H_t C_t + D x_t         head h reads group h // 8 of B and C
        y   = RMSNorm_groups(y * silu(z)) * gain           groups of 512
        out = y W_out                 4096 -> 2688
    *   q 2688 -> 32 x 128, k and v 2688 -> 2 x 128 (query head i reads
        head i // 16), softmax((q k^T) / sqrt(128) + causal) v, o 4096 ->
        2688; NO positional embedding
    E   s = sigmoid(u W_r), all 128;  T = top-6 of (s + b)
        w_e = 2.5 s_e / sum_{e' in T} s_e'
        out = E_shared(u) + sum_{e in T, e held here} w_e E_e(u)
        E(u) = W_down relu(W_up u)^2  width 1856; the shared one 3712

    logits = RMSNorm(h_L) W_head^T    embedding and head untied

Where the reference runs. ``reference.follow`` keeps the float32 weights,
Adam's two moments and the gradient, and its update is a jitted call
without donation: the old and the new state live side by side, 7 copies of
the weights at the first step and 8 after. At this configuration's
666,963,456 parameters that is 18.7 and 21.3 GB where a v5e chip offers
16.9, so on a TPU :func:`loss_sum` makes the HOST's CPU backend JAX's
default device, and the reference's weights, steps and update run there in
float32 (the host of one chip has 40 GiB); :func:`build_net`, which every
``run.Program`` calls first, puts back the default that :func:`loss_sum`
found (the chip's, unless the caller had set one of its own). The
program's side is untouched: it is built, stepped and timed on the chip
before the reference is. PERF.md section 7 asks the next ``benchmark``
issue to donate in ``reference.follow`` and take this out.
"""
import math

import numpy as onp

NAME = "nemotron-3-nano-30b-a3b"


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_net(cfg: dict, traffic: dict):
    """``NemotronHLM`` of the model zoo at the configuration's sizes."""
    import jax
    if _DEFAULT_DEVICE_BEFORE:
        jax.config.update("jax_default_device", _DEFAULT_DEVICE_BEFORE.pop())
    from mxnet_tpu.gluon.model_zoo import nemotron_h
    return nemotron_h.NemotronHLM(cfg)


# ---------------------------------------------------------------------------
# weights and traffic, from the seed
# ---------------------------------------------------------------------------

def pattern(cfg: dict) -> str:
    """The mixers built here: the first ``num_hidden_layers`` characters of
    the published pattern."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def _dims(cfg: dict) -> dict:
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * width
    return {"h": cfg["hidden_size"], "m_heads": heads, "m_width": width,
            "groups": groups, "state": state, "inner": inner,
            "conv": inner + 2 * groups * state, "taps": cfg["conv_kernel"],
            "in": 2 * inner + 2 * groups * state + heads,
            "chunk": cfg["chunk_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "f": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"],
            "held": cfg["n_routed_experts"],
            "router": cfg["moe_router_width"],
            "k": cfg["num_experts_per_tok"], "rows": cfg["vocab_rows"]}


def _mixer_spec(pre: str, kind: str, n: dict, cfg: dict) -> list:
    """One layer's leaves under ``pre``, in the program's order."""
    s = cfg["initializer_range"]
    spec = [(f"{pre}.norm.gamma", (n["h"],), "gamma", s)]
    mix = f"{pre}.mixer"
    if kind == "M":
        conv = cfg["conv_initializer_range"]
        return spec + [
            (f"{mix}.conv_weight", (n["conv"], n["taps"]), "uniform", conv),
            (f"{mix}.conv_bias", (n["conv"],), "uniform", conv),
            (f"{mix}.dt_bias", (n["m_heads"],), "uniform",
             cfg["dt_bias_range"]),
            (f"{mix}.A_log", (n["m_heads"],), "uniform", cfg["A_log_range"]),
            (f"{mix}.D", (n["m_heads"],), "gamma", s),
            (f"{mix}.norm_gamma", (n["inner"],), "gamma", s),
            (f"{mix}.in_proj.weight", (n["in"], n["h"]), "normal", s),
            (f"{mix}.out_proj.weight", (n["h"], n["inner"]), "normal", s)]
    if kind == "*":
        wide, narrow = n["heads"] * n["d"], n["kv_heads"] * n["d"]
        return spec + [
            (f"{mix}.query_proj.weight", (wide, n["h"]), "normal", s),
            (f"{mix}.key_proj.weight", (narrow, n["h"]), "normal", s),
            (f"{mix}.value_proj.weight", (narrow, n["h"]), "normal", s),
            (f"{mix}.out_proj.weight", (n["h"], wide), "normal", s)]
    return spec + [
        (f"{mix}.router_weight", (n["router"], n["h"]), "normal", s),
        (f"{mix}.router_bias", (n["router"],), "normal",
         cfg["router_bias_range"]),
        (f"{mix}.up_weight", (n["held"], n["f"], n["h"]), "normal", s),
        (f"{mix}.down_weight", (n["held"], n["h"], n["f"]), "normal", s),
        (f"{mix}.shared_up_weight", (n["shared"], n["h"]), "normal", s),
        (f"{mix}.shared_down_weight", (n["h"], n["shared"]), "normal", s)]


def param_spec(cfg: dict) -> list:
    """``[(name, shape, kind, scale)]`` under the names the program's
    ``collect_params()`` gives, in its order. Every projection matrix is
    ``normal(0, initializer_range)`` and every gain (and ``D``) 1 + that;
    the embedding rows ``normal(0, embed_initializer_range)``, the routers'
    selection bias ``normal(0, router_bias_range)``, the conv ``uniform``
    in +-``conv_initializer_range``, ``A_log`` and ``dt_bias`` ``uniform``
    in their ranges (the configuration's ``assumed`` says why each)."""
    n = _dims(cfg)
    spec = [("embed.weight", (n["rows"], n["h"]), "normal",
             cfg["embed_initializer_range"])]
    for index, kind in enumerate(pattern(cfg)):
        spec += _mixer_spec(f"layer{index}", kind, n, cfg)
    return spec + [
        ("final_norm.gamma", (n["h"],), "gamma", cfg["initializer_range"]),
        ("head.weight", (n["rows"], n["h"]), "normal",
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


def in_chunk_pairs(seq: int, chunk: int) -> int:
    """Read-write pairs (t, s), s <= t, inside the chunks of one sequence
    (the last chunk may be short)."""
    whole, rest = divmod(seq, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def held_pairs_per_token(cfg: dict) -> float:
    """EXPECTED token-expert pairs a token gives the experts held here,
    under a uniform router: k * held / router width (6 * 8 / 128)."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["moe_router_width"]


def scan_flops(cfg: dict, seq: int) -> int:
    """Forward FLOPs of ONE Mamba-2 layer's selective scan over one
    sequence, in the chunked form at the published chunk, whatever
    implements it: the scores C.B a group and (L * scores) applied to x a
    head over the causal in-chunk pairs, the chunk states built and read
    (2 N P a head and token each)."""
    n = _dims(cfg)
    pairs = in_chunk_pairs(seq, n["chunk"])
    return pairs * (n["groups"] * 2 * n["state"]
                    + n["m_heads"] * 2 * n["m_width"]) \
        + 2 * seq * n["m_heads"] * 2 * n["state"] * n["m_width"]


def forward_flops(cfg: dict, traffic: dict) -> dict:
    """Forward FLOPs of ONE sequence, part by part (one layer of its
    kind)."""
    n, seq = _dims(cfg), traffic["seq"]
    return {
        "mamba_proj": seq * 2 * n["h"] * (n["in"] + n["inner"]),
        "scan": scan_flops(cfg, seq),
        "attn_proj": seq * 2 * n["h"] * 2 * n["d"]
        * (n["heads"] + n["kv_heads"]),
        "attention": attended_pairs(seq) * n["heads"] * 2 * 2 * n["d"],
        "router": seq * 2 * n["h"] * n["router"],
        "shared": seq * 2 * 2 * n["h"] * n["shared"],
        "held_experts": seq * held_pairs_per_token(cfg)
        * 2 * 2 * n["h"] * n["f"],
        "head": seq * 2 * n["h"] * n["rows"]}


def layer_flops(cfg: dict, traffic: dict) -> dict:
    """Forward FLOPs of one sequence through one layer of each kind."""
    f = forward_flops(cfg, traffic)
    return {"M": f["mamba_proj"] + f["scan"],
            "*": f["attn_proj"] + f["attention"],
            "E": f["router"] + f["shared"] + f["held_experts"]}


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """Forward and backward FLOPs a token requires (the backward pass
    twice the forward; recomputation not counted): every projection, the
    scan as :func:`scan_flops` counts it, attention's scores and values
    over the causal pairs only, router, shared expert and the EXPECTED
    share of held experts, and the head."""
    per_layer = layer_flops(cfg, traffic)
    forward = sum(per_layer[kind] for kind in pattern(cfg)) \
        + forward_flops(cfg, traffic)["head"]
    return 3.0 * forward / traffic["seq"]


def kernel_costs(cfg: dict, traffic: dict) -> dict:
    """Per step, for each kernel scope: the FLOPs and the HBM bytes the
    algorithm needs, whatever implements it.

    ``ssd_scan``: :func:`scan_flops` in every Mamba-2 layer, backward twice
    the forward, recomputation NOT counted; x, B, C and dt read and y
    written once in bf16, and as much again for their cotangents. An
    implementation that writes the (Q x Q) decay matrices to HBM, makes
    the forward twice or walks the chunks one by one spends time, not
    work: it reads low, and none reads over 100.

    ``flash_attention``: QK^T and PV over the causal pairs only in every
    attention layer, backward twice the forward; q, o, do, dq at the 32
    query heads' width and k, v, dk, dv at the 2 key/value heads' (forward
    reads q, k, v, writes o; backward reads q, k, v, o, do, writes dq, dk,
    dv), bf16.

    ``moe_experts``: the two products of the EXPECTED held pairs, N * k *
    held / router width (1,536 a layer in the cell), in every expert
    layer, backward twice the forward; the held weights read twice (bf16)
    and their float32 gradient written once. The true count is the
    routing's, which training raises (about 45 % in 100 steps: only a held
    expert can lower this chip's loss), so a share against these FLOPs
    reads high late in a window. The shared expert is not under that
    scope. No accepted metric lists this cell for these two scopes yet
    (``flash_attention_roofline``, ``moe_experts_roofline``: a
    ``benchmark`` issue's edit)."""
    n, b, seq, act = _dims(cfg), traffic["batch"], traffic["seq"], 2
    layers = {kind: pattern(cfg).count(kind) for kind in "M*E"}
    forward = forward_flops(cfg, traffic)
    lanes = 2 * n["inner"] + 2 * n["groups"] * n["state"] + n["m_heads"]
    return {
        "ssd_scan": {
            "flops": float(3 * layers["M"] * b * forward["scan"]),
            "bytes": float(layers["M"] * 2 * b * seq * lanes * act)},
        "flash_attention": {
            "flops": float(3 * layers["*"] * b * forward["attention"]),
            "bytes": float(layers["*"] * 6 * b * seq * n["d"]
                           * (n["heads"] + n["kv_heads"]) * act)},
        "moe_experts": {
            "flops": float(3 * layers["E"] * b * forward["held_experts"]),
            "bytes": float(layers["E"] * n["held"] * 2 * n["h"] * n["f"]
                           * (2 * act + 4))}}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

#: JAX's default device as :func:`loss_sum` found it before it moved the
#: reference to the host (one entry, or none while nothing is moved)
_DEFAULT_DEVICE_BEFORE = []


def _reference_on_the_host():
    """On a TPU, make the host's CPU backend the default device (the
    module's docstring says why) and keep what it was;
    :func:`build_net` puts that back."""
    import jax
    if jax.default_backend() == "tpu":
        if not _DEFAULT_DEVICE_BEFORE:
            _DEFAULT_DEVICE_BEFORE.append(jax.config.jax_default_device)
        jax.config.update("jax_default_device", jax.devices("cpu")[0])


def loss_sum(cfg: dict, dot):
    """``f(params, tokens, targets)``: the SUM over the rows of each row's
    mean softmax cross-entropy over its seq predictions, in float32, every
    matrix product through ``dot``.

    Independent of the program's algorithm: the scan is the QUADRATIC
    form, ``y = ((C B^T) * L) (dt x) + D x`` with the full S x S ``L[t, s]
    = exp(sum_{s < k <= t} dt_k A)`` of a head, head by head (no chunks,
    no recurrence, no carried state); the conv is four shifted
    multiply-adds; attention is a masked softmax a head. Departures from
    the published description, the program's too: the experts are the
    ``n_routed_experts`` held here (first ``moe_first_expert``), each run
    on EVERY token and kept by the router's weight or 0 (no sort, no
    gather, no kernel), what the other experts would add left out; the
    vocabulary is the slice of ``vocab_rows`` rows. To fit beside
    ``reference.follow``'s state each layer is a ``jax.checkpoint`` and a
    head's S x S arrays are made again in the backward."""
    import jax
    import jax.numpy as jnp
    _reference_on_the_host()
    n, eps = _dims(cfg), cfg["layer_norm_epsilon"]
    first = cfg.get("moe_first_expert", 0)
    if cfg["mlp_hidden_act"] != "relu2" or cfg["mamba_hidden_act"] != "silu" \
            or not cfg["norm_topk_prob"] or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or not cfg["use_conv_bias"]:
        raise ValueError("the reference is written for relu2 experts, a "
                         "silu mixer with a conv bias, and sigmoid scores "
                         "normalised over the chosen in one group")

    def rms(x, gain):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain

    def seen(s):
        return jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def mamba(u, p, pre):                            # u (B, S, h)
        b, s, _ = u.shape
        heads, width, state = n["m_heads"], n["m_width"], n["state"]
        inner, gn = n["inner"], n["groups"] * n["state"]
        zxbcdt = dot("bsh,oh->bso", u, p[f"{pre}.in_proj.weight"])
        z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:inner + n["conv"]]
        dt = jax.nn.softplus(zxbcdt[..., inner + n["conv"]:]
                             + p[f"{pre}.dt_bias"])              # (B, S, H)
        back = jnp.pad(xbc, ((0, 0), (n["taps"] - 1, 0), (0, 0)))
        w = p[f"{pre}.conv_weight"]
        xbc = jax.nn.silu(p[f"{pre}.conv_bias"] + sum(
            w[:, j] * back[:, j:j + s] for j in range(n["taps"])))
        x = xbc[..., :inner].reshape(b, s, heads, width)
        per_group = heads // n["groups"]
        bm, cm = (jnp.repeat(part.reshape(b, s, n["groups"], state),
                             per_group, axis=2)
                  for part in (xbc[..., inner:inner + gn],
                               xbc[..., inner + gn:]))
        total = jnp.cumsum(dt * -jnp.exp(p[f"{pre}.A_log"]), axis=1)
        mask = seen(s)

        @jax.checkpoint
        def one_head(args):
            x_h, dt_h, total_h, b_h, c_h, skip = args        # (B, S, ..)
            decay = jnp.exp(jnp.where(
                mask[None], total_h[:, :, None] - total_h[:, None, :],
                -jnp.inf))
            scores = dot("btn,bsn->bts", c_h, b_h) * decay
            return dot("bts,bsp->btp", scores, dt_h[..., None] * x_h) \
                + skip * x_h

        y = jax.lax.map(one_head, (
            jnp.moveaxis(x, 2, 0), jnp.moveaxis(dt, 2, 0),
            jnp.moveaxis(total, 2, 0), jnp.moveaxis(bm, 2, 0),
            jnp.moveaxis(cm, 2, 0), p[f"{pre}.D"]))
        y = jnp.moveaxis(y, 0, 2).reshape(b, s, inner) * jax.nn.silu(z)
        grouped = y.reshape(b, s, n["groups"], inner // n["groups"])
        y = (grouped * jax.lax.rsqrt(jnp.mean(
            jnp.square(grouped), -1, keepdims=True) + eps)) \
            .reshape(b, s, inner) * p[f"{pre}.norm_gamma"]
        return dot("bsi,hi->bsh", y, p[f"{pre}.out_proj.weight"])

    def attention(u, p, pre):
        b, s, _ = u.shape
        heads, kv, d = n["heads"], n["kv_heads"], n["d"]
        q = dot("bsh,oh->bso", u, p[f"{pre}.query_proj.weight"]) \
            .reshape(b, s, heads, d)
        k, v = (jnp.repeat(dot("bsh,oh->bso", u, p[f"{pre}.{part}.weight"])
                           .reshape(b, s, kv, d), heads // kv, axis=2)
                for part in ("key_proj", "value_proj"))
        mask = seen(s)

        @jax.checkpoint
        def one_head(args):
            q_h, k_h, v_h = args                             # (B, S, d)
            scores = dot("bqd,bkd->bqk", q_h, k_h) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
            return dot("bqk,bkd->bqd", probs, v_h)

        out = jax.lax.map(one_head, tuple(jnp.moveaxis(a, 2, 0)
                                          for a in (q, k, v)))
        return dot("bso,ho->bsh", jnp.moveaxis(out, 0, 2)
                   .reshape(b, s, heads * d), p[f"{pre}.out_proj.weight"])

    def expert(x, up, down):
        return dot("nf,hf->nh", jnp.square(jax.nn.relu(
            dot("nh,fh->nf", x, up))), down)

    def experts(u, p, pre):
        b, s, _ = u.shape
        x = u.reshape(b * s, -1)
        scores = jax.nn.sigmoid(dot("nh,eh->ne", x,
                                    p[f"{pre}.router_weight"]))
        _, top_idx = jax.lax.top_k(
            jax.lax.stop_gradient(scores + p[f"{pre}.router_bias"][None]),
            n["k"])
        chosen = jnp.take_along_axis(scores, top_idx, 1)
        weights = cfg["routed_scaling_factor"] * chosen \
            / jnp.sum(chosen, -1, keepdims=True)
        out = expert(x, p[f"{pre}.shared_up_weight"],
                     p[f"{pre}.shared_down_weight"])
        for e in range(n["held"]):
            w_e = jnp.sum(jnp.where(top_idx == first + e, weights, 0.0), -1)
            out = out + w_e[:, None] * expert(
                x, p[f"{pre}.up_weight"][e], p[f"{pre}.down_weight"][e])
        return out.reshape(b, s, -1)

    mixers = {"M": mamba, "*": attention, "E": experts}

    def run_layer(h, p, pre, kind):
        mine = {k: v for k, v in p.items() if k.startswith(pre + ".")}
        return jax.checkpoint(lambda h_, p_: h_ + mixers[kind](
            rms(h_, p_[f"{pre}.norm.gamma"]), p_, f"{pre}.mixer"))(h, mine)

    def f(p, tokens, targets):
        h = p["embed.weight"][tokens]
        for index, kind in enumerate(pattern(cfg)):
            h = run_layer(h, p, f"layer{index}", kind)
        logits = dot("bsh,vh->bsv", rms(h, p["final_norm.gamma"]),
                     p["head.weight"])
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.sum(jnp.take_along_axis(
            logp, targets[..., None], -1)) / targets.shape[1]

    return f
