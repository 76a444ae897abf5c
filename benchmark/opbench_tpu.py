#!/usr/bin/env python
"""Chip-level per-op microbenchmark.

`opbench.py`'s eager per-call loop is the CPU regression tool; on an
accelerator it measures per-call dispatch, not the chip. This harness
runs each op chained inside ONE compiled `lax.fori_loop` — dispatch and
the final fetch are paid once per measurement, and their constant cost
is eliminated by timing the loop at two iteration counts and taking the
slope.

Chaining strategies (XLA must not be able to hoist or CSE the body):
- matmul/FC: the output feeds back as the next input (roofline style),
  with an rsqrt(mean-square) renormalization so values never overflow.
- conv: a scalar derived from the output perturbs the *weights* (cheap:
  weights are KBs, activations are MBs) — data-dependent, so XLA cannot
  constant-fold it even though the perturbation is numerically ~0.
- elementwise/BN: output shape == input shape, direct feedback.

Ops are invoked through the framework's own nd API (they trace under
jit exactly as Gluon's CachedOp traces them), so a regression in the
invoke funnel or kernel emitters shows up here.

Case set = the shapes that carry ResNet-50 bs=128 and BERT-base bs=32
(the two bench.py models), per docs/PERF_NOTES.md MFU attribution.
Reference analog: benchmark/opperf per-op sweeps (reference
benchmark/opperf/opperf.py), re-targeted at what a TPU cares about.

Run: python benchmark/opbench_tpu.py [--n1 20] [--reps 3]
(the second iteration count is chosen adaptively per case). Writes one
JSON line per case; commit output as benchmark/opbench.tpu.json.
"""
import argparse
import functools
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax


def _flush(c):
    leaf = jax.tree_util.tree_leaves(c)[0]
    return float(jnp.reshape(leaf, (-1,))[0].astype(jnp.float32))


def _time_loop(body, init, n1, reps, target_delta=2.0, n_cap=20000):
    """Seconds per iteration of `body`, free of dispatch overhead: time
    the compiled fori_loop at two iteration counts, slope =
    (t2-t1)/(n2-n1).

    n2 is adaptive: the iteration delta must represent >=
    `target_delta` seconds of on-chip work or host-side jitter makes the
    slope noise (first cut with a fixed n2=120 measured a 4096 matmul at
    205 TFLOP/s — above the chip's 197 peak)."""
    f1 = jax.jit(lambda c: lax.fori_loop(0, n1, body, c))
    _flush(f1(init))  # compile + warm
    t0 = time.perf_counter()
    _flush(f1(init))
    t_n1 = time.perf_counter() - t0
    # estimate overhead with an n=1 loop (same compile shape, 1 iter)
    g1 = jax.jit(lambda c: lax.fori_loop(0, 1, body, c))
    _flush(g1(init))
    t0 = time.perf_counter()
    _flush(g1(init))
    t_ovh = time.perf_counter() - t0
    est_iter = max((t_n1 - t_ovh) / max(n1 - 1, 1), 1e-7)
    n2 = n1 + min(int(target_delta / est_iter) + 1, n_cap)
    f2 = jax.jit(lambda c: lax.fori_loop(0, n2, body, c))
    _flush(f2(init))  # compile + warm
    slopes = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _flush(f1(init))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        _flush(f2(init))
        t2 = time.perf_counter() - t0
        slopes.append((t2 - t1) / (n2 - n1))
    return float(onp.median(slopes))


def _nd(x):
    from mxnet_tpu.ndarray.ndarray import NDArray
    return NDArray(x)


def _renorm(y):
    return y * lax.rsqrt(jnp.mean(jnp.square(y.astype(jnp.float32))) +
                         1e-6).astype(y.dtype)


def _cases(rng):
    """[(name, build)] where build() -> (init_carry, body(i, c) -> c,
    flops_per_iter, bytes_per_iter). Lazy: device arrays materialize only
    for selected cases."""
    from mxnet_tpu import nd

    cases = []

    def arr(shape, dtype):
        return jnp.asarray(rng.randn(*shape).astype("float32")).astype(dtype)

    # ---- MXU: square matmuls (the roofline the model competes against)
    def make_matmul(n, dt):
        def build():
            a = arr((n, n), dt)

            def body(i, c):
                return _renorm(nd.dot(_nd(a), _nd(c))._data)

            return a, body, 2 * n ** 3, None
        return build

    for n, dt in ((4096, "bfloat16"), (8192, "bfloat16"), (4096, "float32")):
        cases.append((f"matmul_{n}_{dt}", make_matmul(n, dt)))

    # ---- ResNet-50 bs=128 conv shapes (NCHW API; bf16 as AMP runs them)
    B = 128

    def make_conv(ci, co, hw, k, s, p):
        def build():
            x = arr((B, ci, hw, hw), "bfloat16")
            w = arr((co, ci, k, k), "bfloat16")
            ho = hw // s

            def body(i, c):
                weff = w + c.astype(w.dtype)
                y = nd.Convolution(_nd(x), _nd(weff), kernel=(k, k),
                                   stride=(s, s), pad=(p, p), num_filter=co,
                                   no_bias=True)._data
                # carry depends on EVERY output element (a single-element
                # carry lets XLA slice the conv down to one output pixel —
                # first cut "measured" 17,000 TFLOP/s that way)
                return jnp.sum(y.astype(jnp.float32)) * 1e-30

            return (jnp.float32(0.0), body,
                    2 * B * ho * ho * co * ci * k * k, None)
        return build

    for name, ci, co, hw, k, s, p in [
        ("conv7x7s2_3to64_224", 3, 64, 224, 7, 2, 3),
        ("conv3x3_64c_56", 64, 64, 56, 3, 1, 1),
        ("conv3x3_128c_28", 128, 128, 28, 3, 1, 1),
        ("conv3x3_256c_14", 256, 256, 14, 3, 1, 1),
        ("conv3x3_512c_7", 512, 512, 7, 3, 1, 1),
        ("conv1x1_64to256_56", 64, 256, 56, 1, 1, 0),
        ("conv1x1_256to64_56", 256, 64, 56, 1, 1, 0),
    ]:
        cases.append((f"rn50_{name}_bf16", make_conv(ci, co, hw, k, s, p)))

    # ---- bandwidth-bound tails of the ResNet step
    def build_bnrelu():
        x0 = arr((B, 256, 56, 56), "bfloat16")
        g, b, mm = arr((256,), "float32"), arr((256,), "float32"), \
            arr((256,), "float32")
        mv = jnp.abs(arr((256,), "float32")) + 1.0

        def body(i, c):
            y = nd.BatchNorm(_nd(c), _nd(g), _nd(b), _nd(mm), _nd(mv))._data
            return nd.relu(_nd(y))._data

        return x0, body, None, x0.size * 2 * 2  # read + write, bf16

    cases.append(("bn_relu_128x256x56x56_bf16", build_bnrelu))

    def build_add():
        x0 = arr((B, 256, 56, 56), "bfloat16")

        def body(i, c):
            return (c + x0) * jnp.bfloat16(0.5)

        return x0, body, None, x0.size * 3 * 2  # 2 reads + 1 write

    cases.append(("residual_add_128x256x56x56_bf16", build_add))

    def build_stream():
        big = arr((1 << 26,), "float32")  # 256 MB

        def body(i, c):
            return c + jnp.float32(1.0)

        return big, body, None, big.size * 4 * 2

    cases.append(("stream_add_256MB_f32", build_stream))

    # ---- FC / BERT shapes
    def build_fc():
        wfc = arr((1000, 2048), "bfloat16")

        def body(i, c):
            y = nd.FullyConnected(_nd(c), _nd(wfc), num_hidden=1000,
                                  no_bias=True)._data
            # 128x1000 -> feed back as 128x2048 via renormalized tile
            y = _renorm(y)
            return jnp.concatenate([y, y, y], axis=1)[:, :2048] \
                .astype(c.dtype)

        return (arr((128, 2048), "bfloat16"), body,
                2 * 128 * 2048 * 1000, None)

    cases.append(("fc_128x2048to1000_bf16", build_fc))

    def build_ffn():
        wf1 = arr((768, 3072), "bfloat16")
        wf2 = arr((3072, 768), "bfloat16")
        xb = arr((16384, 768), "bfloat16")

        def body(i, c):
            h = nd.dot(_nd(c), _nd(wf1))._data
            h = jnp.maximum(h, 0)
            return _renorm(nd.dot(_nd(h), _nd(wf2))._data).astype(c.dtype)

        return xb, body, 2 * 16384 * 768 * 3072 * 2, None

    cases.append(("bert_ffn_16384_768_3072_bf16", build_ffn))

    # ---- BERT-base bs=32 seq=512 attention internals (VERDICT r4 #2:
    # measure the asserted "attention tail" instead of guessing).
    # Shapes: (B, H, S, D) = (32, 12, 512, 64); tokens = B*S = 16384.
    BH, S, D = 32 * 12, 512, 64
    attn_flops = 2 * 2 * BH * S * S * D  # QK^T + PV, 2-FLOP convention

    def build_flash_fwd(use_pallas):
        def build():
            from mxnet_tpu.ops import attention as ATT
            q = arr((32, 12, S, D), "bfloat16")
            k = arr((32, 12, S, D), "bfloat16")
            v = arr((32, 12, S, D), "bfloat16")

            def body(i, c):
                o = ATT.flash_attention(c, k, v, use_pallas=use_pallas)
                return _renorm(o).astype(c.dtype)

            return q, body, attn_flops, None
        return build

    cases.append(("bert_flash_attn_fwd_pallas_bf16", build_flash_fwd(True)))
    cases.append(("bert_flash_attn_fwd_xlascan_bf16",
                  build_flash_fwd(False)))

    def build_flash_fwdbwd(use_pallas):
        def build():
            from mxnet_tpu.ops import attention as ATT
            q = arr((32, 12, S, D), "bfloat16")
            k = arr((32, 12, S, D), "bfloat16")
            v = arr((32, 12, S, D), "bfloat16")

            def loss(q_, k_, v_):
                o = ATT.flash_attention(q_, k_, v_, use_pallas=use_pallas)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            gfn = jax.grad(loss, argnums=(0, 1, 2))

            def body(i, c):
                dq, dk, dv = gfn(c, k, v)
                return _renorm(dq).astype(c.dtype)

            # fwd (2 matmuls) + bwd (5 matmuls: dq, dk, dv, 2 recompute)
            return q, body, attn_flops * 7 // 2, None
        return build

    cases.append(("bert_flash_attn_fwdbwd_pallas_bf16",
                  build_flash_fwdbwd(True)))
    cases.append(("bert_flash_attn_fwdbwd_xlascan_bf16",
                  build_flash_fwdbwd(False)))

    def build_softmax():
        x0 = arr((BH, S, S), "bfloat16")

        def body(i, c):
            y = nd.softmax(_nd(c), axis=-1)._data
            return (y * jnp.bfloat16(2.0) - jnp.bfloat16(0.5)).astype(
                c.dtype)

        # unfused S^2 softmax: what the flash kernel avoids materializing
        return x0, body, None, x0.size * 2 * 2

    cases.append(("bert_softmax_384x512x512_bf16", build_softmax))

    def build_layernorm():
        x0 = arr((16384, 768), "bfloat16")
        g = arr((768,), "float32")
        b2 = arr((768,), "float32")

        def body(i, c):
            y = nd.LayerNorm(_nd(c), _nd(g), _nd(b2))._data
            return (y + jnp.bfloat16(0.01)).astype(c.dtype)

        return x0, body, None, x0.size * 2 * 2

    cases.append(("bert_layernorm_16384x768_bf16", build_layernorm))

    def build_bias_gelu():
        x0 = arr((16384, 3072), "bfloat16")
        b3 = arr((3072,), "float32")

        def body(i, c):
            y = nd.Activation(_nd(c + b3.astype(c.dtype)),
                              act_type="gelu")._data
            return _renorm(y).astype(c.dtype)

        return x0, body, None, x0.size * 2 * 2

    cases.append(("bert_bias_gelu_16384x3072_bf16", build_bias_gelu))

    def build_dropout():
        x0 = arr((16384, 768), "bfloat16")
        key = jax.random.PRNGKey(7)

        def body(i, c):
            k = jax.random.fold_in(key, i)
            keep = jax.random.bernoulli(k, 0.9, c.shape)
            return jnp.where(keep, c / jnp.bfloat16(0.9),
                             jnp.bfloat16(0.0))

        return x0, body, None, x0.size * 2 * 2

    cases.append(("bert_dropout_16384x768_bf16", build_dropout))

    def build_qkv_proj():
        w = arr((768, 768), "bfloat16")

        def body(i, c):
            return _renorm(nd.dot(_nd(c), _nd(w))._data).astype(c.dtype)

        return (arr((16384, 768), "bfloat16"), body,
                2 * 16384 * 768 * 768, None)

    cases.append(("bert_proj_16384x768x768_bf16", build_qkv_proj))

    return cases


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n1", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--target-delta", type=float, default=2.0,
                    help="seconds of on-chip work between the two "
                    "timed iteration counts")
    ap.add_argument("--ops", type=str, default="",
                    help="comma-separated substring filter")
    args = ap.parse_args()

    backend = jax.default_backend()
    wanted = [s for s in args.ops.split(",") if s]
    rng = onp.random.RandomState(0)

    results = []
    for name, build in _cases(rng):
        if wanted and not any(w in name for w in wanted):
            continue
        # per-case isolation: one failing case must not kill the
        # remaining sweep; it is reported in the case's row
        try:
            init, body, flops, nbytes = build()
            sec = _time_loop(body, init, args.n1, args.reps,
                             target_delta=args.target_delta)
        except Exception as e:  # pragma: no cover - platform-dependent
            print(json.dumps({"op": name, "error":
                              f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
            continue
        rec = {"op": name, "usec": round(sec * 1e6, 2)}
        if flops:
            rec["tflops"] = round(flops / sec / 1e12, 2)
        if nbytes:
            rec["gbps"] = round(nbytes / sec / 1e9, 1)
        results.append(rec)
        print(json.dumps(rec), flush=True)
    print(json.dumps({"summary": True, "backend": backend,
                      "method": "chained-fori_loop slope",
                      "ops_measured": len(results)}))


if __name__ == "__main__":
    main()
