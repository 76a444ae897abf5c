"""bert-base: the program's net, its traffic, its operation counts and its
plain reference. Sizes come from ``bert-base.json``; nothing here is
specific to one traffic mix."""
import math

import numpy as onp

NAME = "bert-base"


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_net(cfg: dict, traffic: dict):
    """``BERTClassifier`` over ``BERTModel`` from the model zoo, the
    construction of bench.py's BERT leg, at the configuration's sizes."""
    from mxnet_tpu.gluon.model_zoo import bert
    model = bert.BERTModel(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        max_length=cfg["max_position_embeddings"],
        token_type_vocab_size=cfg["type_vocab_size"],
        dropout=cfg["hidden_dropout_prob"])
    return bert.BERTClassifier(model, num_classes=cfg["num_labels"],
                               dropout=cfg["hidden_dropout_prob"])


# ---------------------------------------------------------------------------
# weights and traffic, from the seed
# ---------------------------------------------------------------------------

def param_spec(cfg: dict) -> list:
    """``[(name, shape, kind, scale)]`` under the names the program's
    ``collect_params()`` gives, in its order."""
    h, i, s = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["initializer_range"]
    spec = []

    def dense(name, out, inp):
        spec.append((f"{name}.weight", (out, inp), "normal", s))
        spec.append((f"{name}.bias", (out,), "normal", s))

    def ln(name):
        spec.append((f"{name}.gamma", (h,), "gamma", s))
        spec.append((f"{name}.beta", (h,), "normal", s))

    spec.append(("bert.word_embed.weight", (cfg["vocab_size"], h),
                 "normal", s))
    spec.append(("bert.token_type_embed.weight",
                 (cfg["type_vocab_size"], h), "normal", s))
    spec.append(("bert.position_embed.weight",
                 (cfg["max_position_embeddings"], h), "normal", s))
    ln("bert.embed_ln")
    for layer in range(cfg["num_hidden_layers"]):
        pre = f"bert.encoder.layer{layer}"
        for proj in ("query_proj", "key_proj", "value_proj", "out_proj"):
            dense(f"{pre}.attention.{proj}", h, h)
        dense(f"{pre}.ffn.ffn_1", i, h)
        dense(f"{pre}.ffn.ffn_2", h, i)
        ln(f"{pre}.ln_1")
        ln(f"{pre}.ln_2")
    dense("bert.pooler", h, h)
    dense("classifier", cfg["num_labels"], h)
    return spec


def batches(cfg: dict, traffic: dict, seed: int) -> list:
    """The pool of distinct host batches: token ids from the whole
    vocabulary, one label per sequence. Every batch has the same number of
    rows of class 1 (``positive_rows``), which rows drawn from the seed:
    with random tokens all rows look nearly alike to the net, so its
    gradient is close to ``sum(p - y)`` times one direction, and a batch
    whose labels happen to balance leaves a gradient that is rounding
    noise in any precision (PERF.md, Findings, PR 24)."""
    rng = onp.random.default_rng(seed)
    shape = (traffic["batch"], traffic["seq"])
    pool = []
    for _ in range(traffic["pool"]):
        tokens = rng.integers(0, cfg["vocab_size"], shape, dtype="int32")
        labels = onp.zeros(shape[:1], dtype="int32")
        labels[rng.permutation(shape[0])[:traffic["positive_rows"]]] = 1
        pool.append((tokens, labels))
    return pool


def tokens_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq"]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------

def flops_per_token(cfg: dict, traffic: dict) -> float:
    """Forward and backward matrix-product FLOPs a token requires (the
    backward pass twice the forward; recomputation not counted)."""
    h, i, s = cfg["hidden_size"], cfg["intermediate_size"], traffic["seq"]
    layer = 8 * h * h + 4 * h * i + 4 * s * h   # q,k,v,o; ffn; QK^T and PV
    head = (2 * h * h + 2 * h * cfg["num_labels"]) / s   # once a sequence
    return 3.0 * (cfg["num_hidden_layers"] * layer + head)


def kernel_costs(cfg: dict, traffic: dict) -> dict:
    """Per step, for each kernel scope: the FLOPs and the HBM bytes the
    algorithm needs, whatever implements it."""
    b, s, h = traffic["batch"], traffic["seq"], cfg["hidden_size"]
    layers, act = cfg["num_hidden_layers"], 2       # bf16 activations
    # forward QK^T and PV: 4*S*S*H a sequence; backward dQ, dK, dV, dP: 2x
    flops = layers * 3 * 4 * b * s * s * h
    # forward reads q, k, v and writes o; backward reads q, k, v, o, do
    # and writes dq, dk, dv
    nbytes = layers * 12 * b * s * h * act
    return {"flash_attention": {"flops": float(flops),
                                "bytes": float(nbytes)}}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def loss_sum(cfg: dict, dot):
    """``f(params, tokens, labels)``: the SUM over the rows of the
    classifier's softmax cross-entropy, in float32, every matrix product
    through ``dot``."""
    import jax
    import jax.numpy as jnp
    heads, eps = cfg["num_attention_heads"], cfg["layer_norm_eps"]
    layers = cfg["num_hidden_layers"]

    def ln(x, p, name):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * p[f"{name}.gamma"] \
            + p[f"{name}.beta"]

    def dense(x, p, name):
        return dot("...i,oi->...o", x, p[f"{name}.weight"]) \
            + p[f"{name}.bias"]

    def gelu_tanh(x):
        return 0.5 * x * (1 + jnp.tanh(
            math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))

    def f(p, tokens, labels):
        b, s = tokens.shape
        x = p["bert.word_embed.weight"][tokens] \
            + p["bert.position_embed.weight"][:s][None] \
            + p["bert.token_type_embed.weight"][0][None, None]
        x = ln(x, p, "bert.embed_ln")
        d = x.shape[-1] // heads
        for layer in range(layers):
            pre = f"bert.encoder.layer{layer}"
            q, k, v = (dense(x, p, f"{pre}.attention.{n}_proj")
                       .reshape(b, s, heads, d)
                       for n in ("query", "key", "value"))
            scores = dot("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = dot("bnqk,bknd->bqnd", probs, v).reshape(b, s, heads * d)
            x = ln(x + dense(ctx, p, f"{pre}.attention.out_proj"), p,
                   f"{pre}.ln_1")
            ff = dense(gelu_tanh(dense(x, p, f"{pre}.ffn.ffn_1")), p,
                       f"{pre}.ffn.ffn_2")
            x = ln(x + ff, p, f"{pre}.ln_2")
        pooled = jnp.tanh(dense(x[:, 0], p, "bert.pooler"))
        logp = jax.nn.log_softmax(dense(pooled, p, "classifier"), -1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))

    return f
