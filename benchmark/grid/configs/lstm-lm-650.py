"""lstm-lm-650: the program's net, its traffic, its operation counts and
its plain reference. Sizes come from ``lstm-lm-650.json``."""
import importlib.util
import os

import numpy as onp

NAME = "lstm-lm-650"


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def build_net(cfg: dict, traffic: dict):
    """``WordLM`` of ``examples/train_lstm_lm.py``, as bench.py's LSTM leg
    builds it: embedding, ``gluon.rnn.LSTM`` (NTC), dense head."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, os.pardir, os.pardir, "examples",
                        "train_lstm_lm.py")
    spec = importlib.util.spec_from_file_location("train_lstm_lm", path)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    return example.WordLM(cfg["vocab_size"], cfg["embedding_size"],
                          cfg["hidden_size"], cfg["num_layers"])


# ---------------------------------------------------------------------------
# weights and traffic, from the seed
# ---------------------------------------------------------------------------

def param_spec(cfg: dict) -> list:
    h, e, v, s = cfg["hidden_size"], cfg["embedding_size"], \
        cfg["vocab_size"], cfg["init_scale"]
    spec = [("emb.weight", (v, e), "uniform", s)]
    for layer in range(cfg["num_layers"]):
        inp = e if layer == 0 else h
        spec += [(f"lstm.l{layer}_i2h_weight", (4 * h, inp), "uniform", s),
                 (f"lstm.l{layer}_h2h_weight", (4 * h, h), "uniform", s),
                 (f"lstm.l{layer}_i2h_bias", (4 * h,), "uniform", s),
                 (f"lstm.l{layer}_h2h_bias", (4 * h,), "uniform", s)]
    spec += [("head.weight", (v, h), "uniform", s),
             ("head.bias", (v,), "uniform", s)]
    return spec


def batches(cfg: dict, traffic: dict, seed: int) -> list:
    """The pool of distinct host batches: inputs and next-token targets
    from the whole vocabulary."""
    rng = onp.random.default_rng(seed)
    shape = (traffic["batch"], traffic["seq"])
    return [(rng.integers(0, cfg["vocab_size"], shape, dtype="int32"),
             rng.integers(0, cfg["vocab_size"], shape, dtype="int32"))
            for _ in range(traffic["pool"])]


def tokens_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq"]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------

def _rnn_flops_per_token(cfg: dict) -> float:
    h, e = cfg["hidden_size"], cfg["embedding_size"]
    total = 0
    for layer in range(cfg["num_layers"]):
        inp = e if layer == 0 else h
        total += 2 * (inp + h) * 4 * h      # i2h and h2h of four gates
    return float(total)


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """Forward and backward matrix-product FLOPs a token requires (the
    backward pass twice the forward; recomputation not counted)."""
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return 3.0 * (_rnn_flops_per_token(cfg) + head)


def kernel_costs(cfg: dict, traffic: dict) -> dict:
    """Per step, what the ``rnn_lstm`` op needs forward and backward: the
    input projections and the recurrence of every layer."""
    tokens = tokens_per_step(cfg, traffic)
    h, e, act = cfg["hidden_size"], cfg["embedding_size"], 2
    nbytes = 0
    for layer in range(cfg["num_layers"]):
        inp = e if layer == 0 else h
        weights = 4 * h * (inp + h + 2)
        # forward reads x and writes y; backward reads x, y, dy, writes dx;
        # weights read twice (bf16) and their gradient written once (f32)
        nbytes += tokens * act * (3 * inp + 3 * h) + weights * (2 * act + 4)
    return {"rnn_lstm": {"flops": 3.0 * _rnn_flops_per_token(cfg) * tokens,
                         "bytes": float(nbytes)}}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def loss_sum(cfg: dict, dot):
    """``f(params, tokens, targets)``: the SUM over the rows of each row's
    mean softmax cross-entropy over its positions, in float32, every
    matrix product through ``dot``. Gate order i, f, g, o."""
    import jax
    import jax.numpy as jnp
    h, layers = cfg["hidden_size"], cfg["num_layers"]

    def f(p, tokens, targets):
        b, t = tokens.shape
        x = jnp.transpose(p["emb.weight"][tokens], (1, 0, 2))   # (T, B, E)
        for layer in range(layers):
            pre = f"lstm.l{layer}"
            w_hh = p[f"{pre}_h2h_weight"]
            xw = dot("tbe,ge->tbg", x, p[f"{pre}_i2h_weight"]) \
                + p[f"{pre}_i2h_bias"] + p[f"{pre}_h2h_bias"]

            def cell(carry, xw_t, w_hh=w_hh):
                hid, c = carry
                gates = xw_t + dot("bh,gh->bg", hid, w_hh)
                i, fg, g, o = jnp.split(gates, 4, axis=-1)
                c = jax.nn.sigmoid(fg) * c \
                    + jax.nn.sigmoid(i) * jnp.tanh(g)
                hid = jax.nn.sigmoid(o) * jnp.tanh(c)
                return (hid, c), hid

            zero = jnp.zeros((b, h), jnp.float32)
            _, x = jax.lax.scan(cell, (zero, zero), xw)
        logits = dot("tbh,vh->tbv", x, p["head.weight"]) + p["head.bias"]
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(logp, targets.T[..., None], -1)
        return -jnp.sum(picked) / t

    return f
