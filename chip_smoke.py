#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process (a chip belongs to one process; no children), every phase in
it, through the entry points users call:

- ``device``  — ``jax.devices()``: a TPU of a kind the kernels are sized
  for, with the jax / jaxlib / libtpu versions;
- ``train``   — BERT-base at full width and depth (bs 32 x seq 512, bf16
  AMP, Adam, dropout off) for ten steps on one seeded batch through
  ``gluon.TrainLoop`` over ``Trainer.compile_step``: one fused program,
  one trace, buffers on the chip, loss finite and falling, no compile
  after warm-up;
- ``kernels`` — every name in ``ops.kernels.KERNELS`` compiled by Mosaic,
  forward and backward, at the shapes of the source-paper cells, against
  the XLA reference the dispatch gate already falls back to;
- ``dp``      — with more than one device: the same loop ZeRO-sharded
  under ``make_mesh({"dp": N})``; shards on N distinct devices,
  collectives in the compiled program, loss parity with ``train`` (the
  kernels take the XLA tier there: Mosaic does not lower into a
  GSPMD-partitioned program);
- ``hybrid``  — a small ``NemotronHLM`` (Mamba-2, expert and attention
  layers, one mixer a layer) through the same ``TrainLoop``: fused, one
  trace, loss falling; the chunked selective scan (``ops.ssm.ssd_scan``)
  and its gradients against the recurrence, float32 at ``highest`` and
  bf16; the counters ``mx_ssd_scan_chunks_total``,
  ``mx_mamba_recompute_total`` and ``mx_ssd_scan_total``, which must read
  the compiled tier alone, once a mixer (ops/kernels/ssd_scan.py);
- ``serve``   — ``TinyDecoder`` and ``GQADecoder`` through
  ``serving.run_decode`` (``DecodeEngine.warmup()`` AOT-compiles every
  ladder bucket): all requests finish and the speculative + shared-prefix
  stream equals plain greedy token for token.

Without a TPU it exits non-zero before doing any work and prints no
result. Any failed phase makes the exit code non-zero. The LAST line of
stdout is the verdict, one JSON object with exactly two keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
the device as JAX reports it. The line BEFORE it is the report, one JSON
object too: the same ``ok`` and ``device``, then ``phases`` (each with its
status, seconds and what it found: versions, losses, kernel paths,
collectives), the compile-cache directory with hits and misses, whether
the native library was built in this run, and ``"claim": null``.

    python3 chip_smoke.py

The compile cache (``runtime.setup_compile_cache``) lives at
``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``: a second run
over the same directory shows hits and no misses for the first run's
programs. tests/test_chip_smoke.py cross-lowers ``kernel_cases()`` for the
TPU on the CPU and rehearses the phases at tiny sizes; a rehearsal is
never a pass.
"""
import contextlib
import json
import os
import sys
import time
import traceback
from typing import Callable, NamedTuple

SEED = 21

# Dropout is off and the step small, so that "the loss fell" is read off
# the optimizer and not off noise (PR 21 chip runs): with the model's
# default dropout 0.1 the per-step loss moves +-0.09 with the mask — the
# same trajectory to two digits under six optimizer settings — while ten
# stable steps move it 0.02; and Adam from a random init with no warm-up
# overshoots from lr 1e-6 up (0.70 -> 0.76 at 1e-6, -> 1.34 at 3e-6).
# At 3e-7 the loss falls 0.705 -> 0.684 in ten steps with +-0.003 wiggle.
TRAIN = dict(batch=32, seq=512, steps=10, vocab=1000, bert="bert_base",
             optimizer=("adam", {"learning_rate": 3e-7}))

# a small SmallThinkerLM for the train phase: head width and layer pattern
# of the benchmark's configuration (benchmark/grid/configs/
# smallthinker-21b-a3b.json), everything else cut down; the fixed batch is
# memorised, so Adam at 1e-3 makes the loss fall
SPARSE = dict(batch=2, seq=512, steps=8,
              optimizer=("adam", {"learning_rate": 1e-3}),
              model=dict(hidden_size=256, head_dim=128,
                         num_attention_heads=4, num_key_value_heads=2,
                         num_hidden_layers=4, rope_layout=[0, 1, 1, 1],
                         sliding_window_layout=[0, 1, 1, 1],
                         sliding_window_size=128, rope_theta=1.5e6,
                         rms_norm_eps=1e-6, moe_ffn_hidden_size=256,
                         moe_router_width=8, moe_num_primary_experts=4,
                         moe_num_active_primary_experts=2, vocab_size=512))

# a small JoyAILM for the train phase: the head widths of the benchmark's
# configuration (benchmark/grid/configs/joyai-llm-flash.json: keys of 128 +
# 64 rotary lanes beside values of 128), one dense and one expert layer and
# the MTP module, everything else cut down
LATENT = dict(batch=2, seq=512, steps=8,
              optimizer=("adam", {"learning_rate": 1e-3}),
              model=dict(hidden_size=256, intermediate_size=512,
                         q_lora_rank=192, kv_lora_rank=128,
                         qk_nope_head_dim=128, qk_rope_head_dim=64,
                         v_head_dim=128, num_attention_heads=4,
                         rope_theta=32e6, rope_interleave=True,
                         rms_norm_eps=1e-6, hidden_act="silu",
                         first_k_dense_replace=1, num_hidden_layers=2,
                         num_nextn_predict_layers=1,
                         moe_intermediate_size=256, n_routed_experts=4,
                         moe_router_width=8, num_experts_per_tok=2,
                         n_shared_experts=1, scoring_func="sigmoid",
                         routed_scaling_factor=2.5, vocab_size=512))

# a small NemotronHLM for the hybrid phase: the Mamba-2 head width, state,
# chunk and conv of the benchmark's configuration (benchmark/grid/configs/
# nemotron-3-nano-30b-a3b.json), its attention heads of 128 with no
# position signal and its ungated relu2 experts behind the sigmoid router,
# everything else cut down; the experts' width is a multiple of 128 lanes
# here, so their two forward and three backward products are the grouped-
# product kernels' with nothing padded (the cell's 1856 lanes moe_experts
# zero-pads to 1920 for them)
HYBRID = dict(batch=2, seq=512, steps=8,
              optimizer=("adam", {"learning_rate": 1e-3}),
              scan=dict(seq=512, heads=8, groups=2),
              model=dict(hidden_size=256, hybrid_override_pattern="MEM*E",
                         num_hidden_layers=5, mamba_num_heads=8,
                         mamba_head_dim=64, ssm_state_size=128, n_groups=2,
                         conv_kernel=4, chunk_size=128,
                         layer_norm_epsilon=1e-5, num_attention_heads=4,
                         num_key_value_heads=2, head_dim=128,
                         moe_intermediate_size=256,
                         moe_shared_expert_intermediate_size=512,
                         n_shared_experts=1, n_routed_experts=4,
                         moe_router_width=8, num_experts_per_tok=2,
                         routed_scaling_factor=2.5, mlp_hidden_act="relu2",
                         vocab_size=512))

# the accelerator sizes of bench.py's decode leg
SERVE = dict(vocab=256, d_model=128, heads=4, requests=16,
             ladder=(1, 2, 4, 8), page_size=16)

#: max |kernel - oracle| over max |oracle|, per tensor. The oracle is the
#: XLA reference on the same inputs upcast to f32, its dots at HIGHEST
#: precision. A wrong mask, index or carry shows up at O(1), not here.
#: TOL_BF16 — bf16 outputs are rounded to 8 mantissa bits (2^-9 relative,
#: each) and the recurrence/attention kernels round p, h and c to bf16
#: once per step or block on the way: 4 ulp of bf16 covers both (0.002 to
#: 0.006 on the chip, PR 21). It is also the bound for f32 THROUGH THE
#: MXU: at default precision an f32 dot is one bf16 pass, in Mosaic as in
#: XLA, so flash attention and the RNN kernels carry bf16-sized error in
#: f32 too (0.0013 to 0.0076, PR 21).
#: TOL_F32 — f32 elementwise kernels (bias-GELU, LayerNorm, optimizer
#: update): only the exp/rsqrt/pow expansions differ (at most 9e-7, PR 21).
#: TOL_F32_PRODUCTS — f32 products at HIGHEST on both sides (the grouped
#: products of the expert layer): every product is exact to f32, but the
#: tiers add in another order (a contraction tile, a group's rows a visit)
#: and the router's gradient is a difference of near-equal sums: 4.2e-07
#: at the SmallThinker cell's shape, 1.6e-05 at the JoyAI cell's (top-8
#: of 256, an eighth as many pairs held) where one bf16 pass anywhere
#: reads 4.4e-03 (PR 33).
TOL_BF16 = 4 * 2.0 ** -8
TOL_F32 = 1e-5
TOL_F32_PRODUCTS = 1e-4

#: dp-vs-one-chip loss trajectory, absolute, on a loss that starts near
#: ln 2: the same program partitioned four ways sums gradients in
#: another order and tiles its bf16 matmuls differently, and Adam divides
#: by sqrt(v) — differences of a few bf16 ulps in the loss per step.
DP_LOSS_ATOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# kernel cases — shared with the tier-1 cross-lowering test
# ---------------------------------------------------------------------------

class KernelCase(NamedTuple):
    kernel: str              # name in ops.kernels.KERNELS
    label: str
    dtype: str               # of the float arguments
    tol: float               # TOL_BF16 | TOL_F32
    make: Callable           # RandomState -> tuple of f32/int numpy args
    fn: Callable             # *args -> array or tuple of arrays
    grad_argnums: tuple      # () = forward only


def kernel_cases(tiny: bool = False):
    """Every Pallas kernel through its public, dispatching entry point at
    the shape of the cell it was written for (``tiny``: the same cases
    cut down for an interpret-mode rehearsal on the CPU)."""
    import numpy as onp
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attention, kernels
    from mxnet_tpu.ops import moe as ops_moe
    from mxnet_tpu.ops import nn as ops_nn
    from mxnet_tpu.ops import ssm as ops_ssm
    from mxnet_tpu.ops.kernels import norm, rnn_scan

    cases = []

    def add(kernel, label, dtype, make, fn, grad_argnums=()):
        dots = kernel in ("flash_attention", "rnn_scan", "rnn_decode_step",
                          "ssd_scan")
        tol = TOL_BF16 if dtype != "float32" or dots else \
            TOL_F32_PRODUCTS if kernel == "grouped_dot" else TOL_F32
        cases.append(KernelCase(kernel, f"{label} {dtype}", dtype, tol,
                                make, fn, grad_argnums))

    def f32(rng, *shape, scale=1.0):
        return (rng.standard_normal(shape) * scale).astype("float32")

    def bias_gelu(x, b):
        path, _ = kernels.dispatch("bias_gelu")
        if path == "xla":
            return jax.nn.gelu(x + b, approximate=False)
        return norm.bias_gelu(x, b, interpret=path == "interpret")

    def cell_args(rng, lead, n, h, g=4):
        return (f32(rng, *lead, n, g * h, scale=0.5),
                f32(rng, n, h, scale=0.5), f32(rng, n, h, scale=0.5),
                f32(rng, g * h, h, scale=h ** -0.5),
                f32(rng, g * h, scale=0.1))

    def sparse_experts(top_k, held, activation="relu", gated=True):
        # float32 products at HIGHEST in the kernel tier too, so that both
        # sides multiply alike and the comparison reads the rows moved:
        # at the default precision a float32 product is one bf16 pass,
        # gate values within its rounding of zero change sign, and ReLU's
        # mask then moves whole rows of the gate weights' gradient (0.69
        # of its scale at these widths, whatever multiplies: PR 31).
        # bf16 products stay as they are: they are exact in float32, and
        # XLA's own bf16 ragged-dot kernel refuses HIGHEST ("Bad lhs type")
        def layer(x, router_w, *matrices):
            # without a gate: (w_up, w_down)
            with jax.default_matmul_precision(
                    "highest" if x.dtype == jnp.float32 else "default"):
                weights, order, place, sizes = ops_moe.moe_route(
                    x, router_w, top_k, held)
                y = ops_moe.moe_experts(x, order, place, sizes,
                                        *(() if gated else (None,)),
                                        *matrices, activation=activation)
                return ops_moe.moe_combine(y, weights, order, place, sizes)
        return layer

    def expert_weights(rng, n, d, f, e, c):
        return (f32(rng, n, d), f32(rng, e, d),
                f32(rng, c, f, d, scale=d ** -0.5),
                f32(rng, c, f, d, scale=d ** -0.5),
                f32(rng, c, d, f, scale=f ** -0.5))

    for dtype in ("bfloat16", "float32"):
        # BERT-base FFN: 32 x 512 tokens, hidden 3072
        r, c = (64, 256) if tiny else (16384, 3072)
        add("bias_gelu", f"bias_gelu {r}x{c}", dtype,
            lambda rng, r=r, c=c: (f32(rng, r, c), f32(rng, c)),
            bias_gelu, (0, 1))

        # BERT-base LayerNorm over 768 units
        shp = (4, 8, 128) if tiny else (32, 512, 768)
        add("layernorm", f"layernorm {'x'.join(map(str, shp))}", dtype,
            lambda rng, shp=shp: (f32(rng, *shp),
                                  1 + f32(rng, shp[-1], scale=0.1),
                                  f32(rng, shp[-1], scale=0.1)),
            lambda x, g, b: ops_nn.layer_norm(x, g.astype(jnp.float32),
                                              b.astype(jnp.float32)),
            (0, 1, 2))

        # BERT-base attention (one 512 block: the fused backward) and a
        # 2k causal sequence (the two-kernel backward)
        flash = (((1, 2, 64, 64), False), ((1, 2, 1024, 64), True)) \
            if tiny else (((32, 12, 512, 64), False),
                          ((32, 12, 512, 64), True),
                          ((8, 12, 2048, 64), True))
        for shp, causal in flash:
            add("flash_attention",
                f"flash_attention {'x'.join(map(str, shp))}"
                f"{' causal' if causal else ''}", dtype,
                lambda rng, shp=shp: tuple(f32(rng, *shp)
                                           for _ in range(3)),
                lambda q, k, v, causal=causal: attention.flash_attention(
                    q, k, v, causal=causal), (0, 1, 2))
        # the same two at the model's own layout, (B, S, H*D): the
        # benchmark cell's attention layer, and the 2k causal sequence
        flash = (((1, 64, 128), 2, False), ((1, 1024, 128), 2, True)) \
            if tiny else (((32, 512, 768), 12, False),
                          ((8, 2048, 768), 12, True))
        for shp, heads, causal in flash:
            add("flash_attention",
                f"flash_attention bsh {'x'.join(map(str, shp))} h{heads}"
                f"{' causal' if causal else ''}", dtype,
                lambda rng, shp=shp: tuple(f32(rng, *shp)
                                           for _ in range(3)),
                lambda q, k, v, heads=heads, causal=causal:
                attention.flash_attention_bsh(q, k, v, heads, causal=causal),
                (0, 1, 2))

        # the SmallThinker cell's attention: 28 query heads over 4
        # key/value heads of 128, causal, with and without the window;
        # one block at 512, eight at 8192 (there one group, 7 over 1: the
        # oracle's float32 scores of all 28 heads do not fit the chip)
        flash = ((64, 2, 1, 24), (1024, 2, 1, None), (1024, 2, 1, 600)) \
            if tiny else ((512, 28, 4, 256), (8192, 7, 1, None),
                          (8192, 7, 1, 4096))
        for seq, heads, kv_heads, window in flash:
            add("flash_attention",
                f"flash_attention bsh 1x{seq} h{heads}/{kv_heads}x128 "
                f"causal window {window}", dtype,
                lambda rng, seq=seq, heads=heads, kv_heads=kv_heads: (
                    f32(rng, 1, seq, heads * 128),
                    f32(rng, 1, seq, kv_heads * 128),
                    f32(rng, 1, seq, kv_heads * 128)),
                lambda q, k, v, heads=heads, kv_heads=kv_heads,
                window=window: attention.flash_attention_bsh(
                    q, k, v, heads, causal=True, num_kv_heads=kv_heads,
                    window=window), (0, 1, 2))

        # the JoyAI cell's latent attention: keys 192 wide (128 + 64
        # rotary lanes) beside values 128 wide, causal, 4096 long; 8 of
        # its 32 heads (four blocks of two heads on 384 / 256 lanes)
        seq, heads = (64, 4) if tiny else (4096, 8)
        add("flash_attention",
            f"flash_attention bsh 1x{seq} h{heads} keys 192 values 128 "
            "causal", dtype,
            lambda rng, seq=seq, heads=heads: (
                f32(rng, 1, seq, heads * 192), f32(rng, 1, seq, heads * 192),
                f32(rng, 1, seq, heads * 128)),
            lambda q, k, v, heads=heads: attention.flash_attention_bsh(
                q, k, v, heads, causal=True), (0, 1, 2))

        # the SmallThinker cell's expert layer, one chip's share: 8192
        # tokens of 2560, top-6 of 64 experts of width 768, 8 held, so an
        # eighth of the 49,152 list rows are live. The row movers behind
        # lax.ragged_dot, forward and every gradient (the router's too:
        # it is the weights' gradient that reaches it)
        n, d, f, e, k, held = (128, 128, 64, 8, 2, (2, 4)) if tiny \
            else (8192, 2560, 768, 64, 6, (0, 8))
        add("moe_rows", f"moe_rows {n}x{d} f{f} top{k}/{e} held {held[1]}",
            dtype,
            lambda rng, n=n, d=d, f=f, e=e, c=held[1]: expert_weights(
                rng, n, d, f, e, c),
            sparse_experts(k, held), (0, 1, 2, 3, 4))

        # the grouped products between them, ops/kernels/grouped_dot.py
        # against lax.ragged_dot, at both MoE cells' shapes: the same
        # layer (49,152 list rows x 2560 x 768, about 6,144 live, ReGLU),
        # and the JoyAI cell's (4096 tokens of 2048, top-8 of 256, 8
        # held: 32,768 rows x 2048 x 768, about 1,024 live in groups of
        # 128, SwiGLU); judged like the row movers, through the layer
        for (n, d, f, e, k, held), act in (
                (((128, 128, 128, 8, 2, (2, 4)), "relu"),
                 ((128, 256, 128, 16, 4, (4, 4)), "silu")) if tiny else
                (((8192, 2560, 768, 64, 6, (0, 8)), "relu"),
                 ((4096, 2048, 768, 256, 8, (0, 8)), "silu"))):
            add("grouped_dot", f"grouped_dot {n * min(k, held[1])}x{d}x{f} "
                f"top{k}/{e} held {held[1]} {act}", dtype,
                lambda rng, n=n, d=d, f=f, e=e, c=held[1]: expert_weights(
                    rng, n, d, f, e, c),
                sparse_experts(k, held, act), (0, 1, 2, 3, 4))

        # experts WITHOUT a gate, relu^2 (two products forward, three
        # backward), at the Nemotron cell's shape but for the width: 4096
        # tokens of 2688, top-6 of 128, 8 held (24,576 rows, about 1,536
        # live). The cell's own 1856 = 14.5 lane tiles the kernels decline;
        # 1920 is the next they take, and what moe_experts pads the cell's to
        n, d, f, e, k, held = (128, 128, 256, 8, 2, (2, 4)) if tiny \
            else (4096, 2688, 1920, 128, 6, (0, 8))
        add("grouped_dot", f"grouped_dot {n * min(k, held[1])}x{d}x{f} "
            f"top{k}/{e} held {held[1]} relu2 ungated", dtype,
            lambda rng, n=n, d=d, f=f, e=e, c=held[1]: expert_weights(
                rng, n, d, f, e, c)[:2] + expert_weights(
                rng, n, d, f, e, c)[3:],
            sparse_experts(k, held, "relu2", gated=False), (0, 1, 2, 3))

        # the Nemotron cell's selective scan: 1 x 4096, 64 heads of 64 in
        # 8 groups over a state of 128, chunks of 128; step sizes and
        # decay rates spread as the cell's weights spread them, forward
        # and all six gradients against _ssd_chunked
        seq, heads, groups = (256, 16, 2) if tiny else (4096, 64, 8)
        add("ssd_scan", f"ssd_scan 1x{seq} h{heads}x64 g{groups} n128",
            dtype,
            lambda rng, seq=seq, heads=heads, groups=groups: (
                f32(rng, 1, seq, heads, 64), f32(rng, 1, seq, heads),
                (rng.rand(heads) * 2.78 - 1.39).astype("float32"),
                f32(rng, 1, seq, groups, 128), f32(rng, 1, seq, groups, 128),
                f32(rng, heads)),
            lambda x, dt, a_log, b, c, skip: ops_ssm.ssd_scan(
                x, jax.nn.softplus(dt.astype(jnp.float32)),
                -jnp.exp(a_log.astype(jnp.float32)), b, c,
                skip.astype(jnp.float32)), (0, 1, 2, 3, 4, 5))

        # the SmallThinker cell's embedding: 8,192 ids into its share of
        # the vocabulary, 18,992 rows of 2560, and the table's gradient
        # (tiny: 200 ids, no multiple of 128, some past either end)
        n, rows, d = (200, 512, 128) if tiny else (8192, 18992, 2560)
        add("embedding_grad", f"embedding {n} ids x {rows}x{d}", dtype,
            lambda rng, n=n, rows=rows, d=d: (
                rng.randint(-8, rows + 8, size=n).astype("int32"),
                f32(rng, rows, d)),
            ops_nn.embedding, (1,))

        # LSTM LM: bptt 35, bs 64, hidden 650 (pads to 768); both layers
        # run this recurrence shape (embed = hidden = 650)
        t, n, h = (5, 4, 50) if tiny else (35, 64, 650)
        add("rnn_scan", f"rnn_scan lstm T{t}xN{n}xH{h}", dtype,
            lambda rng, t=t, n=n, h=h: cell_args(rng, (t,), n, h),
            lambda xw, h0, c0, w, b: rnn_scan.rnn_scan(
                xw, h0, c0, w, b, "lstm"), (0, 1, 2, 3, 4))
        add("rnn_scan", f"rnn_scan gru T{t}xN{n}xH{h}", dtype,
            lambda rng, t=t, n=n, h=h: tuple(
                a for i, a in enumerate(cell_args(rng, (t,), n, h, g=3))
                if i != 2),
            lambda xw, h0, w, b: rnn_scan.rnn_scan(
                xw, h0, None, w, b, "gru")[:2], (0, 1, 2, 3))

        # TinyDecoder's cell: 8 slots, d_model 128; verify = spec_k + 1
        # positions
        n, h, k = (2, 32, 3) if tiny else (8, SERVE["d_model"], 5)
        add("rnn_decode_step", f"rnn_decode_step lstm N{n}xH{h}", dtype,
            lambda rng, n=n, h=h: cell_args(rng, (), n, h),
            lambda xw, h_, c_, w, b: rnn_scan.rnn_decode_step(
                xw, h_, c_, w, b, "lstm"))
        add("rnn_decode_step", f"rnn_verify_scan lstm K{k}xN{n}xH{h}",
            dtype,
            lambda rng, n=n, h=h, k=k: cell_args(rng, (k,), n, h)
            + (rng.rand(k, n) < 0.7,),
            lambda xw, h_, c_, w, b, valid: rnn_scan.rnn_verify_scan(
                xw, h_, c_, w, b, "lstm", valid))

    # one ZeRO unit: a BERT-base FFN weight's 1/4 shard, flat f32
    p = 1000 if tiny else 768 * 3072 // 4
    for name, kw, n_states in (("sgd", {"momentum": 0.9}, 1),
                               ("adam", {}, 2)):
        opt = mx.optimizer.create(name, learning_rate=0.01, wd=1e-4, **kw)
        for vec in (False, True):
            def opt_args(rng, n_states=n_states, vec=vec):
                hp = (onp.full(p, 0.01, "float32"),
                      onp.full(p, 1e-4, "float32"),
                      rng.randint(1, 20, size=p).astype("int32")) \
                    if vec else (onp.float32(0.01), onp.float32(1e-4),
                                 onp.int32(7))
                # second Adam moment: non-negative
                states = tuple(onp.abs(f32(rng, p, scale=0.1))
                               for _ in range(n_states))
                return (f32(rng, p), f32(rng, p)) + hp + states

            def update(w, g, lr, wd, t, *states, opt=opt):
                step = opt.kernel_step_fn() or opt.fused_step_fn()
                new_w, new_s = step((w,), (g,), (lr,), (wd,), (t,),
                                    jnp.float32(1.0 / 32), jnp.float32(0),
                                    (tuple(states),))
                return (new_w[0],) + tuple(new_s[0])

            add("opt_update", f"opt_update {name} "
                f"{'vector' if vec else 'scalar'} hparams P{p}", "float32",
                opt_args, update)
    return cases


def case_args(case: KernelCase, rng) -> tuple:
    """The case's arguments on the device, floats in the case's dtype."""
    import jax.numpy as jnp
    return tuple(jnp.asarray(a, case.dtype if a.dtype.kind == "f" else None)
                 for a in case.make(rng))


@contextlib.contextmanager
def _pallas_off():
    """Trace under MXNET_PALLAS=off: the dispatch gate's XLA reference."""
    prev = os.environ.get("MXNET_PALLAS")
    os.environ["MXNET_PALLAS"] = "off"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["MXNET_PALLAS"]
        else:
            os.environ["MXNET_PALLAS"] = prev


def kernel_outputs(case: KernelCase):
    """*args -> the case's outputs as a flat tuple of arrays."""
    def outputs(*args):
        out = case.fn(*args)
        return tuple(o for o in (out if isinstance(out, tuple) else (out,))
                     if o is not None)
    return outputs


def kernel_program(case: KernelCase):
    """The jittable program a case checks, ``(cots, *args) -> arrays``:
    its outputs and, where the kernel has a VJP, the gradients of
    sum(output * cotangent). ``cots`` are traced arguments (one f32 array
    per output), not 50M-element constants in the program."""
    import jax
    import jax.numpy as jnp
    outputs = kernel_outputs(case)

    def program(cots, *args):
        if not case.grad_argnums:
            return outputs(*args)

        def loss(*a):
            outs = outputs(*a)
            return sum(jnp.sum(o.astype(jnp.float32) * c)
                       for o, c in zip(outs, cots)), outs
        (_, outs), grads = jax.value_and_grad(
            loss, argnums=case.grad_argnums, has_aux=True)(*args)
        return outs + tuple(grads)

    return program


def _compiled_tier() -> str:
    """What the dispatch gate calls a kernel that really ran: compiled on
    a TPU; on the CPU rehearsal (MXNET_PALLAS=on) the interpreted body."""
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def check_kernel(case: KernelCase) -> dict:
    """Compile and run one case both ways; → its row of the path table."""
    import numpy as onp
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import kernels

    rng = onp.random.RandomState(SEED)
    args = case_args(case, rng)
    cots = [jnp.asarray(rng.standard_normal(s.shape), jnp.float32)
            for s in jax.eval_shape(kernel_outputs(case), *args)]
    program = kernel_program(case)
    t0 = time.perf_counter()
    got = jax.block_until_ready(jax.jit(program)(cots, *args))
    path, reason = kernels.decisions()[case.kernel]
    row = {"kernel": case.kernel, "case": case.label, "path": path,
           "seconds": round(time.perf_counter() - t0, 2)}
    if path != _compiled_tier():
        # off the tier by a static `supported` reason is a recorded
        # retirement; switched off from outside is not a check at all
        if path != "xla" or reason.startswith("MXNET_PALLAS"):
            raise RuntimeError(f"{case.label}: dispatched to {path} "
                               f"({reason})")
        row["reason"] = reason
        return row
    oracle_args = tuple(a.astype(jnp.float32) if a.dtype == jnp.bfloat16
                        else a for a in args)
    with _pallas_off(), jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(program)(cots, *oracle_args))
    worst = 0.0
    for g, w in zip(got, want):
        g, w = onp.asarray(g, "float32"), onp.asarray(w, "float32")
        if not onp.isfinite(g).all():
            raise RuntimeError(f"{case.label}: non-finite kernel output")
        worst = max(worst, float(onp.abs(g - w).max()
                                 / (onp.abs(w).max() + 1e-30)))
    row["error"] = float(f"{worst:.3g}")
    if worst > case.tol:
        raise RuntimeError(
            f"{case.label}: kernel is {worst:.3g} of the tensor scale "
            f"away from the XLA reference (tolerance {case.tol:.3g})")
    return row


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    from importlib import metadata
    import jax
    import jaxlib
    from mxnet_tpu.ops.kernels import SUPPORTED_DEVICE_KINDS
    d = jax.devices()[0]
    if d.device_kind not in SUPPORTED_DEVICE_KINDS:
        raise RuntimeError(
            f"device_kind {d.device_kind!r} is not one the program knows "
            f"({', '.join(SUPPORTED_DEVICE_KINDS)}): its VMEM sizing and "
            "peak table would be guesses")
    return {"versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__,
                         "libtpu": metadata.version("libtpu")}}


def _bert_loop(cfg: dict):
    """(net, loop, x, y): the seeded BERT classifier, its TrainLoop and
    one seeded batch — built identically for ``train`` and ``dp`` (the
    construction of bench.py's BERT leg, on Adam and kvstore='tpu')."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.gluon.model_zoo import bert
    mx.random.seed(SEED)
    rng = onp.random.RandomState(SEED)
    net = bert.BERTClassifier(
        getattr(bert, cfg["bert"])(max_length=cfg["seq"], dropout=0.0),
        num_classes=2, dropout=0.0)
    net.initialize()          # every shape is declared: nothing deferred
    trainer = mx.gluon.Trainer(net.collect_params(), *cfg["optimizer"],
                               kvstore="tpu")
    loop = mx.gluon.TrainLoop(net, trainer, SoftmaxCrossEntropyLoss())
    x = mx.nd.array(rng.randint(0, cfg["vocab"],
                                size=(cfg["batch"], cfg["seq"]))
                    .astype("int32"))
    y = mx.nd.array(rng.randint(0, 2, size=(cfg["batch"],))
                    .astype("int32"))
    return net, loop, x, y


def _run_steps(loop, x, y, steps: int):
    """Warm-up step, then ``steps - 1`` more with the compile cache's
    request count held still. → per-step mean loss."""
    import numpy as onp
    from mxnet_tpu import runtime

    def compiles():
        s = runtime.compile_cache_stats()
        return s["hits"] + s["misses"]

    losses = [loop.step(x, y)]
    loop.synchronize()
    losses[0].asnumpy()
    warm = compiles()
    losses += [loop.step(x, y) for _ in range(steps - 1)]
    loop.synchronize()
    losses = [float(onp.mean(l.asnumpy(), dtype="float64")) for l in losses]
    if compiles() != warm:
        raise RuntimeError(f"{compiles() - warm} compile(s) after warm-up")
    step = loop.compiled_step
    if step.mode != "fused" or step.n_traces != 1:
        raise RuntimeError(f"step mode={step.mode} n_traces="
                           f"{step.n_traces}, expected fused / 1")
    if not all(onp.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")
    return losses


def _log_decisions() -> dict:
    """Print the dispatch table — with the reason for every kernel that
    did not take the compiled tier — and return {kernel: path}."""
    from mxnet_tpu.ops import kernels
    table = kernels.decisions()
    for name in kernels.KERNELS:
        path, reason = table.get(name, ("-", "not on this model's path"))
        log(f"  decision {name}: {path} ({reason})")
    return {k: v[0] for k, v in table.items()}


def _flash_layouts() -> dict:
    """``mx_flash_attention_layout_total`` as {layout: calls so far}."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.attention import FLASH_LAYOUTS
    from mxnet_tpu.telemetry import names
    return {lay: int(telemetry.value(names.FLASH_ATTENTION_LAYOUT, lay) or 0)
            for lay in FLASH_LAYOUTS}


def _grid_steps(before: dict = None) -> dict:
    """``mx_flash_attention_grid_steps_total`` as {kind: steps since
    ``before``}; a dead step (in a flash kernel's grid, its body
    skipped) fails the phase."""
    from mxnet_tpu.telemetry import names
    now = {"live": 0, "dead": 0, **_counter(names.FLASH_ATTENTION_GRID_STEPS)}
    if before is None:
        return now
    steps = {k: n - before[k] for k, n in now.items()}
    if steps["dead"] or not steps["live"]:
        raise RuntimeError(f"the flash kernels' grids ran {steps}, "
                           "expected live steps and none dead")
    return steps


def _bwd_forms(before: dict = None) -> dict:
    """``mx_flash_attention_bwd_total`` as {form: calls since
    ``before``}: the flash backward's one-block, fused multi-block or
    split (dq and dk/dv kernels) form."""
    from mxnet_tpu.telemetry import names
    now = {"one_block": 0, "fused": 0, "split": 0,
           **_counter(names.FLASH_ATTENTION_BWD)}
    return now if before is None else \
        {k: n - before[k] for k, n in now.items()}


def _counter(name: str) -> dict:
    """A labelled counter of the program as {label: count so far}."""
    from mxnet_tpu import telemetry
    return {k: int(v) for k, v in
            telemetry.registry().counter(name).values().items()}


def _train_small_lm(net, cfg: dict, x, y, tracked: tuple):
    """A model-zoo LM on seeded ``normal(0, 0.02)`` weights (gains 1)
    through ``TrainLoop`` under bf16 AMP for ``cfg["steps"]`` steps on one
    batch; → ``(losses, {counter name: {label: counts while tracing}})``
    for the ``tracked`` counters, the flash kernels' layouts, their grid
    steps and the backward's forms among them."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.telemetry import names
    net.initialize(mx.init.Normal(0.02))
    for name, p in net.collect_params().items():
        if name.endswith("gamma"):
            p.set_data(mx.nd.ones(p.shape))
    trainer = mx.gluon.Trainer(net.collect_params(), *cfg["optimizer"],
                               kvstore="tpu")
    loop = mx.gluon.TrainLoop(net, trainer, SoftmaxCrossEntropyLoss())
    before = {n: _counter(n) for n in tracked}
    layouts_before, steps_before = _flash_layouts(), _grid_steps()
    forms_before = _bwd_forms()
    mx.amp.init()
    try:
        losses = _run_steps(loop, x, y, cfg["steps"])
    finally:
        mx.amp.uninit()
    counted = {n: {k: v - before[n].get(k, 0)
                   for k, v in _counter(n).items()
                   if v > before[n].get(k, 0)} for n in before}
    counted["flash_layouts"] = {k: n - layouts_before[k]
                                for k, n in _flash_layouts().items()}
    counted[names.FLASH_ATTENTION_GRID_STEPS] = _grid_steps(steps_before)
    counted[names.FLASH_ATTENTION_BWD] = _bwd_forms(forms_before)
    return losses, counted


def _kernel_products(counted: dict, layers: int, model: str) -> None:
    """Eight grouped products an expert layer (three forward, five
    backward), each traced at least once, all by the kernels of
    ops/kernels/grouped_dot.py; ``lax.ragged_dot`` took none."""
    from mxnet_tpu.telemetry import names
    products = counted[names.MOE_GROUPED_DOT]
    if set(products) != {_compiled_tier()} or \
            products[_compiled_tier()] < 8 * layers:
        raise RuntimeError(f"{model}'s grouped products took {products}, "
                           f"expected {_compiled_tier()} alone, "
                           f"{8 * layers} or more")


def _sparse_lm(cfg: dict) -> dict:
    """A small ``SmallThinkerLM`` through ``TrainLoop`` under bf16 AMP:
    fused, traced once, loss falling; → its losses and what the program
    counted while tracing it (``mx_moe_dispatch_total``,
    ``mx_attention_mask_total``, ``mx_moe_row_mover_total``,
    ``mx_embedding_grad_total``, ``mx_flash_attention_grid_steps_total``,
    and ``mx_flash_attention_bwd_total``, which must read ``fused``
    alone)."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.smallthinker import SmallThinkerLM
    from mxnet_tpu.telemetry import names
    mx.random.seed(SEED)
    rng = onp.random.RandomState(SEED)
    shape = (cfg["batch"], cfg["seq"])
    x, y = (mx.nd.array(rng.randint(0, cfg["model"]["vocab_size"],
                                    size=shape).astype("int32"))
            for _ in range(2))
    losses, counted = _train_small_lm(
        SmallThinkerLM(cfg["model"]), cfg, x, y,
        (names.MOE_DISPATCH, names.ATTENTION_MASK, names.MOE_ROW_MOVER,
         names.MOE_GROUPED_DOT, names.EMBEDDING_GRAD))
    del counted["flash_layouts"]
    layers = cfg["model"]["num_hidden_layers"]
    windowed = sum(cfg["model"]["sliding_window_layout"][:layers])
    if counted[names.MOE_DISPATCH] != {"grouped": layers} or \
            counted[names.ATTENTION_MASK].get("window") != windowed:
        raise RuntimeError(f"SmallThinkerLM traced {counted}, expected "
                           f"{layers} grouped expert layers, {windowed} "
                           "of them behind a window")
    # three row movements a layer (the weighted sum back, its gradient,
    # the gradient of the tokens' gather), each traced at least once, all
    # by the kernels
    movers = counted[names.MOE_ROW_MOVER]
    if set(movers) != {_compiled_tier()} or \
            movers[_compiled_tier()] < 3 * layers:
        raise RuntimeError(f"SmallThinkerLM's row movers took {movers}, "
                           f"expected {_compiled_tier()} alone, {3 * layers} "
                           "or more")
    _kernel_products(counted, layers, "SmallThinkerLM")
    # the table's gradient: the row-scatter kernel, not XLA's scatter-add
    if set(counted[names.EMBEDDING_GRAD]) != {_compiled_tier()}:
        raise RuntimeError("SmallThinkerLM's embedding gradient took "
                           f"{counted[names.EMBEDDING_GRAD]}, expected "
                           f"{_compiled_tier()} alone")
    # grouped heads: every layer's backward one kernel, dq, dk and dv
    # resident in VMEM
    forms = counted[names.FLASH_ATTENTION_BWD]
    if forms["split"] or forms["fused"] < layers:
        raise RuntimeError(f"SmallThinkerLM's flash backward took {forms}, "
                           f"expected {layers} or more fused and none split")
    log(f"  SmallThinkerLM: {counted}")
    return {"loss": [round(l, 4) for l in losses], **counted}


def _latent_lm(cfg: dict) -> dict:
    """A small ``JoyAILM`` through ``TrainLoop`` under bf16 AMP: fused,
    traced once, loss falling; → its losses and what the program counted
    while tracing it: latent attention in every layer and in the MTP
    module (``mx_latent_attention_total``), sigmoid routers
    (``mx_moe_router_total``), one MTP module, and no flash call that
    pads its 192-lane keys in HBM."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.joyai import JoyAILM
    from mxnet_tpu.telemetry import names
    mx.random.seed(SEED)
    rng = onp.random.RandomState(SEED)
    model, seq = cfg["model"], cfg["seq"]
    t = rng.randint(0, model["vocab_size"],
                    size=(cfg["batch"], seq + 2)).astype("int32")
    x = mx.nd.array(t[:, :seq + 1])
    y = mx.nd.array(onp.concatenate([t[:, 1:seq + 1], t[:, 2:]], 1))
    losses, counted = _train_small_lm(
        JoyAILM(model), cfg, x, y,
        (names.LATENT_ATTENTION, names.MOE_ROUTER, names.MTP_MODULES,
         names.MOE_DISPATCH, names.MOE_ROW_MOVER, names.MOE_GROUPED_DOT))
    blocks = model["num_hidden_layers"] + model["num_nextn_predict_layers"]
    routers = blocks - model["first_k_dense_replace"]
    if counted[names.LATENT_ATTENTION] != {"expanded": blocks} or \
            counted[names.MOE_ROUTER] != {"sigmoid": routers} or \
            sum(counted[names.MTP_MODULES].values()) != 1 or \
            counted[names.MOE_DISPATCH] != {"grouped": routers}:
        raise RuntimeError(f"JoyAILM traced {counted}, expected {blocks} "
                           f"latent attentions, {routers} sigmoid routers "
                           "and one MTP module")
    layouts = counted["flash_layouts"]
    if layouts != {"packed": blocks, "unpadded": 0, "padded": 0}:
        raise RuntimeError(f"JoyAILM's attention layers took {layouts}, "
                           f"expected {blocks} packed and none padded")
    if set(counted[names.MOE_ROW_MOVER]) != {_compiled_tier()}:
        raise RuntimeError("JoyAILM's row movers took "
                           f"{counted[names.MOE_ROW_MOVER]}")
    _kernel_products(counted, routers, "JoyAILM")
    log(f"  JoyAILM: {counted}")
    return {"loss": [round(l, 4) for l in losses], **counted}


def phase_train(cfg: dict = TRAIN, sparse: dict = SPARSE,
                latent: dict = LATENT) -> dict:
    import jax
    import mxnet_tpu as mx
    platform = jax.devices()[0].platform
    net, loop, x, y = _bert_loop(cfg)
    before, steps_before = _flash_layouts(), _grid_steps()
    forms_before = _bwd_forms()
    mx.amp.init()
    try:
        losses = _run_steps(loop, x, y, cfg["steps"])
    finally:
        mx.amp.uninit()
    layouts = {k: n - before[k] for k, n in _flash_layouts().items()}
    grid_steps = _grid_steps(steps_before)
    bwd_forms = _bwd_forms(forms_before)
    arrays = [p.data()._data for p in net.collect_params().values()]
    placed = {d.platform for a in arrays + [x._data, y._data]
              for d in a.devices()}
    if placed != {platform}:
        raise RuntimeError(f"parameters/batch live on {placed}, "
                           f"jax.devices()[0] is {platform}")
    paths = _log_decisions()
    for name in ("flash_attention", "layernorm"):
        if paths.get(name) != _compiled_tier():
            raise RuntimeError(f"BERT dispatched {name} to {paths.get(name)}")
    # one traced call a layer; BERT-base's 12 heads of 64 read the
    # projections where they lie ("packed"), and no BERT here pads a head
    if layouts["padded"] or not any(layouts.values()):
        raise RuntimeError(f"BERT's attention layers took {layouts}, "
                           "expected one call a layer and none padded")
    return {"loss": [round(l, 4) for l in losses], "kernel_paths": paths,
            "flash_layouts": layouts, "flash_grid_steps": grid_steps,
            "flash_bwd_forms": bwd_forms,
            "sparse_lm": _sparse_lm(sparse), "latent_lm": _latent_lm(latent)}


def _scan_against_the_recurrence(cfg: dict) -> dict:
    """``ops.ssm.ssd_scan`` (chunked) and every gradient of it against
    ``ssd_scan_reference`` (a ``lax.scan`` over time, float32) on the
    device: float32 at ``highest`` on both sides, and bf16 operands beside
    float32 step sizes as AMP hands them over (three roundings to bf16
    along a path: the decayed scores, the decay-weighted x, the entering
    states; 0.005 on the CPU). → the worst gap a dtype, max |got -
    oracle| over max |oracle| a tensor, y and its six gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from mxnet_tpu.ops import ssm
    rng = onp.random.RandomState(SEED)
    seq, heads, groups = cfg["scan"]["seq"], cfg["scan"]["heads"], \
        cfg["scan"]["groups"]
    model = cfg["model"]
    width, state = model["mamba_head_dim"], model["ssm_state_size"]

    def f32(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x, B, C = f32(1, seq, heads, width), f32(1, seq, groups, state), \
        f32(1, seq, groups, state)
    dt = jax.nn.softplus(f32(1, seq, heads))
    A, D = -jnp.exp(jnp.asarray(rng.uniform(-1.39, 1.39, heads),
                                jnp.float32)), f32(heads)
    weigh = f32(1, seq, heads, width)

    def both(fn, *args):
        """y and the gradient of sum(y * weigh) by every operand."""
        grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                            * weigh), argnums=tuple(range(6)))
        return jax.jit(lambda *a: (fn(*a),) + grads(*a))(*args)

    def chunked(*a):
        return ssm.ssd_scan(*a, chunk=model["chunk_size"])
    gaps = {}
    with jax.default_matmul_precision("highest"):
        for dtype, tol in (("float32", TOL_F32_PRODUCTS),
                           ("bfloat16", TOL_BF16)):
            low = [a.astype(dtype) for a in (x, B, C)]
            # the oracle on the same inputs upcast to float32
            want = both(ssm.ssd_scan_reference, low[0].astype(jnp.float32),
                        dt, A, *(a.astype(jnp.float32) for a in low[1:]), D)
            got = both(chunked, low[0], dt, A, low[1], low[2], D)
            gap = max(float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                            / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30))
                      for g, w in zip(got, want))
            gaps[dtype] = gap
            if not gap <= tol:
                raise RuntimeError(f"ssd_scan in {dtype} is {gap:.3g} off "
                                   f"the recurrence (limit {tol:.3g})")
    return gaps


def phase_hybrid(cfg: dict = HYBRID) -> dict:
    """The module docstring's ``hybrid`` phase."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.model_zoo.nemotron_h import NemotronHLM
    from mxnet_tpu.ops import kernels
    from mxnet_tpu.telemetry import names
    rng = onp.random.RandomState(SEED)
    model = cfg["model"]
    t = rng.randint(0, model["vocab_size"],
                    size=(cfg["batch"], cfg["seq"] + 1)).astype("int32")
    x, y = mx.nd.array(t[:, :-1]), mx.nd.array(t[:, 1:])
    layers = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    mixers, experts = layers.count("M"), layers.count("E")
    out = {"scan_gap": _scan_against_the_recurrence(cfg)}
    mx.random.seed(SEED)
    chunks = telemetry.value(names.SSD_SCAN_CHUNKS) or 0
    losses, counted = _train_small_lm(
        NemotronHLM(model), cfg, x, y,
        (names.MAMBA_RECOMPUTE, names.MOE_ROUTER, names.MOE_DISPATCH,
         names.MOE_GROUPED_DOT, names.SSD_SCAN))
    counted[names.SSD_SCAN_CHUNKS] = int(
        telemetry.value(names.SSD_SCAN_CHUNKS) - chunks)
    per_scan = -(-cfg["seq"] // model["chunk_size"])
    if counted[names.MAMBA_RECOMPUTE] != {"segment": mixers} or \
            counted[names.SSD_SCAN_CHUNKS] != mixers * per_scan or \
            counted[names.MOE_ROUTER] != {"sigmoid": experts} or \
            counted[names.MOE_DISPATCH] != {"grouped": experts}:
        raise RuntimeError(
            f"NemotronHLM traced {counted}, expected {mixers} mixers of "
            f"{per_scan} chunks, each one checkpointed segment, and "
            f"{experts} sigmoid-routed expert layers")
    # the scans are the kernels of ops/kernels/ssd_scan.py, one a mixer
    path, reason = kernels.decisions()["ssd_scan"]
    log(f"  decision ssd_scan: {path} ({reason})")
    if counted[names.SSD_SCAN] != {_compiled_tier(): mixers}:
        raise RuntimeError(f"the mixers' scans took {counted[names.SSD_SCAN]}"
                           f", expected {_compiled_tier()} alone, one a "
                           f"mixer ({mixers}): {reason}")
    # experts without a gate: two products forward, three backward
    products = counted[names.MOE_GROUPED_DOT]
    if set(products) != {_compiled_tier()} or \
            products[_compiled_tier()] < 5 * experts:
        raise RuntimeError(f"the ungated experts' products took "
                           f"{products}, expected {_compiled_tier()} "
                           f"alone, {5 * experts} or more")
    log(f"  NemotronHLM: {counted}")
    out["lm"] = {"loss": [round(l, 4) for l in losses], **counted}
    return out


def phase_kernels(tiny: bool = False) -> dict:
    """Every case runs, so one chip call names every kernel Mosaic
    refuses; any failed case fails the phase."""
    paths, failed = {}, []
    for case in kernel_cases(tiny):
        try:
            row = check_kernel(case)
        except Exception:
            traceback.print_exc(file=sys.stdout)
            row = {"kernel": case.kernel, "case": case.label,
                   "path": "failed"}
            failed.append(case.label)
        log("  " + json.dumps(row))
        paths.setdefault(case.kernel, set()).add(row["path"])
    return {"kernel_paths": {k: "+".join(sorted(v))
                             for k, v in paths.items()}, "failed": failed}


def phase_dp(train_loss, cfg: dict = TRAIN) -> dict:
    import jax
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh
    n = len(jax.devices())
    if n < 2:
        return {"skipped": "1 device"}
    with make_mesh({"dp": n}):
        _, loop, x, y = _bert_loop(cfg)
        step = loop.compiled_step
        mx.amp.init()
        try:
            losses = _run_steps(loop, x, y, cfg["steps"])
            hlo = step.lower_entry(x, y)["lowered"].compile().as_text()
        finally:
            mx.amp.uninit()
        paths = _log_decisions()
        if not step.zero_sharded:
            raise RuntimeError("the step is not ZeRO-sharded")
        shards = step.optimizer_state_buffers() \
            + [step.input_placement()(x._data)]
    for a in shards:
        on = {s.device for s in a.addressable_shards}
        if len(on) != n or a.addressable_shards[0].data.size * n > \
                a.size + n:
            raise RuntimeError(
                f"a {a.shape} optimizer-state/batch buffer is not split "
                f"over {n} distinct devices: {sorted(map(str, on))}")
    collectives = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                   for op in ("reduce-scatter", "all-gather", "all-reduce")}
    # XLA:TPU spells a reduce-scatter as a fused `all-reduce-scatter`
    collectives["reduce-scatter"] += hlo.count("calls=%all-reduce-scatter")
    # the zero-dp plan: gradients reduce-scattered (or all-reduced and
    # sliced) onto the shards, new weights all-gathered back
    if not ((collectives["reduce-scatter"] or collectives["all-reduce"])
            and collectives["all-gather"]):
        raise RuntimeError(f"compiled program lacks the ZeRO "
                           f"collectives: {collectives}")
    gap = float(onp.abs(onp.subtract(losses, train_loss)).max())
    if gap > DP_LOSS_ATOL:
        raise RuntimeError(
            f"dp loss {losses} is {gap:.3g} away from the one-device "
            f"trajectory {train_loss} (tolerance {DP_LOSS_ATOL})")
    return {"loss": [round(l, 4) for l in losses], "devices": n,
            "max_loss_gap": float(f"{gap:.3g}"), "collectives": collectives,
            "kernel_paths": paths}


def phase_serve(cfg: dict = SERVE) -> dict:
    import numpy as onp
    from mxnet_tpu import serving
    from mxnet_tpu.gluon import GQADecoder
    rng = onp.random.RandomState(SEED)
    ps, vocab = cfg["page_size"], cfg["vocab"]
    # mixed lengths; every other prompt extends one shared three-page
    # base (prefix sharing), every fourth decodes long (heavy tail)
    base = rng.randint(0, vocab, size=3 * ps)
    prompts, max_new = [], []
    for i in range(cfg["requests"]):
        tail = rng.randint(0, vocab, size=int(rng.randint(2, 12)))
        prompts.append(onp.concatenate([base, tail]) if i % 2 else tail)
        max_new.append(32 if i % 4 == 0 else int(rng.randint(2, 8)))
    models = {
        "TinyDecoder": serving.TinyDecoder(
            vocab=vocab, d_model=cfg["d_model"], num_heads=cfg["heads"],
            seed=0),
        "GQADecoder": GQADecoder(
            vocab=vocab, d_model=cfg["d_model"],
            num_heads=cfg["heads"] * 2, num_kv_heads=cfg["heads"],
            num_layers=2, seed=0),
    }
    out = {}
    for name, model in models.items():
        kw = dict(ladder=cfg["ladder"], page_size=ps)
        greedy = serving.run_decode(model, prompts, max_new, spec_k=0,
                                    prefix_share=False, **kw)
        spec = serving.run_decode(model, prompts, max_new, spec_k=4,
                                  prefix_share=True, **kw)
        for run in (greedy, spec):
            if [len(t) for t in run["token_ids"]] != max_new:
                raise RuntimeError(
                    f"{name}: requests emitted "
                    f"{[len(t) for t in run['token_ids']]} tokens, "
                    f"asked for {max_new}")
        bad = [i for i, (a, b) in enumerate(zip(
            greedy["token_ids"], spec["token_ids"])) if a != b]
        if bad:
            raise RuntimeError(
                f"{name}: speculative + shared-prefix streams differ from "
                f"plain greedy in requests {bad}")
        out[name] = {"tokens": spec["tokens"], "steps": greedy["steps"],
                     "spec_steps": spec["steps"],
                     "prefix_hits": spec["prefix_hits"]}
        log(f"  {name}: {out[name]}")
    return out


def run_phases(phases: dict) -> dict:
    """Run every phase, each handed the report so far; a phase that
    raises (or returns a non-empty ``failed`` list) is recorded as failed
    with its traceback printed, and the caller exits non-zero."""
    report = {}
    for name, fn in phases.items():
        log(f"[{name}]")
        t0 = time.perf_counter()
        try:
            detail = fn(report) or {}
            status = "failed" if detail.get("failed") else \
                "skipped" if "skipped" in detail else "ok"
        except Exception:
            traceback.print_exc(file=sys.stdout)
            detail, status = {}, "failed"
        report[name] = dict(detail, status=status,
                            seconds=round(time.perf_counter() - t0, 1))
        log(f"[{name}] {status} {report[name]['seconds']}s")
    return report


def phases() -> dict:
    """The run's phases in order, each ``fn(report so far)``."""
    return {
        "device": lambda _: phase_device(),
        "train": lambda _: phase_train(),
        # a failed train phase fails dp too: there is nothing to match.
        # Before `kernels`, so the dispatch table dp prints is BERT's
        "dp": lambda r: phase_dp(r["train"]["loss"]),
        "kernels": lambda _: phase_kernels(),
        "hybrid": lambda _: phase_hybrid(),
        "serve": lambda _: phase_serve(),
    }


def main() -> int:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform="
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    from mxnet_tpu import _native, runtime
    report = run_phases(phases())
    return finish(report, devices, runtime.compile_cache_stats(),
                  _native.built_this_run())


def finish(report: dict, devices, cache: dict, native_built: bool) -> int:
    """Print the report line, then the verdict line — the last line of
    stdout, exactly ``ok`` and ``device`` — and return the exit code."""
    verdict = {
        "ok": all(p["status"] != "failed" for p in report.values()),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
    }
    print(json.dumps(dict(
        verdict, phases=report,
        compile_cache={k: cache[k] for k in ("dir", "hits", "misses")},
        native_built_this_run=native_built, claim=None)), flush=True)
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
