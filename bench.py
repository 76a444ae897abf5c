#!/usr/bin/env python
"""Headline benchmark: Gluon ResNet-50 training throughput + efficiency.

Baseline: reference MXNet-CUDA ResNet-50 training, bs=128 on V100 =
363.69 img/s (docs/static_site/src/pages/api/faq/perf.md:254; BASELINE.md).
The driver runs this on one real TPU chip; vs_baseline is img/s-per-chip
against the V100 row, per BASELINE.json's north star.

Prints ONE JSON line with the primary metric plus efficiency fields:
  {"metric": "resnet50_v1_train_img_per_sec", "value": N, "unit": "img/s",
   "vs_baseline": N, "dtype": "bf16", "tflops": N, "mfu": N,
   "bert_tokens_per_sec": N, "bert_tflops": N, "bert_mfu": N,
   "matmul_roofline_tflops": N, "peak_tflops": N, "device": "..."}

- tflops    = FLOPs actually executed per second: XLA's cost_analysis of
              the one compiled train step (fwd + bwd + update — the whole
              program the chip runs) / 1e12. Note this is the compiled-
              program count, not the "3x forward" analytic convention;
              it is the honest numerator for what the silicon does.
- mfu       = tflops / peak_tflops for the detected TPU generation.
- matmul_roofline_tflops = achieved bf16 GEMM rate of a large square
              matmul on the same chip — the practical ceiling the model
              competes against (distinguishes "framework leaves perf on
              the table" from "platform caps throughput").

The whole training step (forward, loss, backward, SGD-momentum update) is one
donated-buffer XLA computation — the TPU-native answer to the reference's
CachedOp static_alloc + bulking + fused multi_sgd (SURVEY §3.2/§3.4). Since
PR 1 the resnet/bert/lstm legs build that program through the FRAMEWORK
(gluon.TrainLoop over Trainer.compile_step, gluon/fused_step.py) rather than
the bespoke make_train_step sidecar — the bench measures the product path.

AMP note: ``mx.amp.init()`` is enabled AFTER the eager shape-materializing
forward and applies inside the jitted step (one compile). bf16 then FLOWS
between ops (amp/__init__.py), halving HBM activation traffic — the lever
the reference's fp16 row pulls on V100 (perf.md:196,210).

MXNET_BENCH_MODEL=resnet50|bert runs one model only (bert skips the
resnet fields and vice versa); default "all" runs both and emits the
combined line. MXNET_BENCH_DTYPE=fp32 disables AMP.
"""
import json
import os
import sys
import time

import numpy as onp

import jax
import jax.numpy as jnp

BASELINE_IMG_S = 363.69  # V100 fp32 training, bs=128

# bf16 peak TFLOP/s per chip by device_kind substring (public specs).
_PEAK_BF16 = [
    ("v5 lite", 197.0), ("v5litepod", 197.0), ("v5e", 197.0),
    ("v6 lite", 918.0), ("v6e", 918.0),
    ("v5p", 459.0),
    ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def peak_tflops():
    d = jax.devices()[0]
    kind = getattr(d, "device_kind", "").lower()
    if jax.default_backend() == "cpu":
        return None, kind or "cpu"
    for key, peak in _PEAK_BF16:
        if key in kind:
            return peak, kind
    return None, kind


def compile_step(step_fn, *args):
    """AOT-compile the train step ONCE; return (callable, flops). The same
    executable drives the timed loop — no second jit compile just to read
    cost_analysis."""
    comp = jax.jit(step_fn, donate_argnums=(0, 1)).lower(*args).compile()
    flops = None
    try:
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        f = float(ca.get("flops", 0.0))
        flops = f if f > 0 else None
    except Exception as e:  # pragma: no cover - platform-dependent
        log(f"bench: cost_analysis unavailable ({type(e).__name__})")
    return comp, flops


def _kernel_path():
    """{kernel: pallas|interpret|xla} under the live env/backend
    (ops/kernels dispatch gate)."""
    try:
        from mxnet_tpu.ops import kernels as _k
        return _k.dispatch_table()
    except Exception:  # pragma: no cover - must not kill a bench
        return None


def framework_loop(net, lr, momentum=0.9):
    """The PRODUCT train-step path: gluon.TrainLoop over
    Trainer.compile_step — forward+backward+update as ONE donated-buffer
    XLA program built by the framework itself. The resnet/bert/lstm legs
    run through this (previously a bespoke make_train_step sidecar in
    __graft_entry__ — the bench now measures what users get)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    trainer = mx.gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": lr, "momentum": momentum}, kvstore=None)
    return mx.gluon.TrainLoop(net, trainer, SoftmaxCrossEntropyLoss())


def analyze_framework_step(tag, loop, x_nd, y_nd):
    """Structural fingerprint of the compiled step for the BENCH json:
    n_traces, collective census, donated bytes, copied-donation and
    host-transfer counts (mx.analysis program lint). A perf regression
    then ships WITH its structural diff — e.g. img/s dropped AND
    donated_bytes went to 0 says "donation broke", not just "slower"."""
    report = loop.compiled_step.analyze(x_nd, y_nd)
    d = report.to_dict()
    out = {"mode": d["mode"], "n_traces": d["n_traces"],
           "collectives": d["collectives"],
           "donated_bytes": d["donated_bytes"],
           "donation_copied": len(report.donation.copied),
           "host_transfers": d["host_transfers"],
           "dtype_drift": d["dtype_drift"],
           # fusion posture next to MFU (docs/ANALYSIS.md "Fusion
           # census"): the pending hardware re-capture records these
           # as the per-leg baselines the regression gate bands around
           "fusion": d["fusion"],
           # sharding posture (docs/ANALYSIS.md "Sharding analysis"):
           # {implicit_reshards, reshard_bytes, comm_cost_est_s,
           # sharding_table_digest} — a perf regression on a sharded
           # leg ships with its reshard diff, and the digest pins
           # whether two captures laid buffers out identically
           "sharding": d["sharding"],
           # exposed-comm posture next to comm_cost_est_s
           # (docs/PERF_NOTES.md "Communication overlap"):
           # {exposed_comm_s, overlap_fraction, zero_bucket_bytes, ...}
           # — a perf delta on a sharded leg says whether collectives
           # were hidden behind compute, not just how many bytes moved
           "overlap": d["overlap"],
           # which implementation produced this number: per-kernel
           # MXNET_PALLAS dispatch (pallas/interpret/xla) — a perf
           # delta between captures must name its kernel path
           "kernel_path": _kernel_path()}
    # autotune posture (docs/PERF_NOTES.md "Autotuner"): the legs run
    # under MXNET_AUTOTUNE=cached, so a capture records WHICH tuned
    # config (if any) produced its numbers, how many trials it cost
    # (0 on replay), and the tuner's estimated win over the defaults —
    # the next hardware re-capture ships its tuning provenance
    at = getattr(loop.compiled_step, "autotune_result", None)
    out.update(at.bench_dict() if at is not None else
               {"autotune_config": None, "autotune_trials": None,
                "autotune_delta_pct": None})
    log(f"bench[{tag}]: analysis {out}")
    return out


def numerics_probe(tag, loop, x_nd, y_nd, steps=6):
    """Numerics-domain fingerprint + overhead for one leg
    (docs/OBSERVABILITY.md "numerics"): re-time a short pipelined loop
    with numerics OFF, switch the step to MXNET_NUMERICS=global (one
    extra compile for the instrumented bucket — the mode is part of the
    cache signature), time again, and report {grad_norm_final,
    update_ratio, nonfinite_events, numerics_overhead_pct}. The main
    timed loop above keeps its numbers untouched."""
    from mxnet_tpu import telemetry
    step = loop.compiled_step
    if step.mode != "fused":
        return None
    prev_mode = step.numerics

    def timed():
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = loop.step(x_nd, y_nd)
        loop.synchronize()
        jax.block_until_ready(loss._data)
        return (time.perf_counter() - t0) / steps

    try:
        step.set_numerics("off")
        loop.step(x_nd, y_nd)        # (re)warm the uninstrumented bucket
        loop.synchronize()
        t_off = timed()
        step.set_numerics("global")
        loop.step(x_nd, y_nd)        # compile the instrumented bucket
        loop.synchronize()
        t_on = timed()
        last = telemetry.numerics.monitor().last() or {}
        nf = telemetry.value(telemetry.names.ANOMALIES,
                             "nonfinite_grad") or 0
        def sig(v):
            v = float(v)
            return float(f"{v:.6g}") if onp.isfinite(v) else repr(v)

        out = {
            "grad_norm_final": sig(last.get("grad_norm", 0.0)),
            "update_ratio": sig(last.get("update_ratio", 0.0)),
            "nonfinite_events": int(nf),
            "numerics_overhead_pct":
                round((t_on - t_off) / t_off * 100.0, 2)
                if t_off > 0 else None,
        }
        log(f"bench[{tag}]: numerics {out}")
        return out
    except Exception as e:  # pragma: no cover - must not kill the leg
        log(f"bench[{tag}]: numerics probe failed "
            f"({type(e).__name__}: {e})")
        return None
    finally:
        try:
            step.set_numerics(prev_mode)
        except Exception:  # pragma: no cover - defensive
            pass


def run_framework_bench(tag, loop, x, y, warmup, steps):
    """AOT-compile the framework step for this shape bucket, then run
    warmup + the timed loop. The timed loop runs PIPELINED: batches are
    staged onto the device by the background prefetcher
    (gluon/data/prefetcher.py), ``loop.step`` dispatches ahead of the
    device under the bounded in-flight window (MXNET_INFLIGHT_STEPS),
    and NO per-step host read happens — ``block_until_ready`` at the end
    is the completion barrier the throughput number needs. The loop runs
    with
    MXNET_TELEMETRY semantics ON, so the leg ships the full telemetry
    story: the engine dict ({input_wait_ms, inflight_window,
    host_sync_count, ...}, now read from the metrics registry instead of
    hand-rolled counters) plus a telemetry dict with the phase-duration
    summary, the MFU gauge (cost_analysis flops / step time / roofline),
    anomaly count, and the full registry snapshot. Returns (dt_seconds,
    flops, final_loss, analysis_dict, engine_dict, telemetry_dict)."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    names = telemetry.names
    x_nd, y_nd = mx.nd.from_jax(x), mx.nd.from_jax(y)
    flops = loop.compiled_step.aot_compile(x_nd, y_nd)
    telemetry.enable(True)
    t0 = time.perf_counter()
    for _ in range(warmup):
        loss = loop.step(x_nd, y_nd)
    loop.synchronize()
    jax.block_until_ready(loss._data)
    log(f"bench[{tag}]: warmup (incl. compile) "
        f"{time.perf_counter() - t0:.1f}s, "
        f"loss={float(loss._data.mean()):.3f}, mode="
        f"{loop.compiled_step.mode}, traces={loop.compiled_step.n_traces}")
    if loop.compiled_step.mode != "fused":
        raise RuntimeError(
            f"bench[{tag}]: the step runs {loop.compiled_step.mode}, not "
            "the fused program this leg is named for")
    # the context is not the device: mx.tpu(0) resolves to a CPU device
    # where no chip is found (context.py), so look at where the step ran
    platform = jax.devices()[0].platform
    placed = {d.platform for d in loss._data.devices()}
    if placed != {platform}:
        raise RuntimeError(f"bench[{tag}]: the step ran on {placed}, "
                           f"jax.devices()[0] is {platform}")
    # zero every series so the leg's registry reads ARE the timed loop
    telemetry.reset()
    peak, _ = peak_tflops()
    if flops:
        loop.arm_mfu(x_nd, y_nd,
                     peak_flops=peak * 1e12 if peak else None)
    t0 = time.perf_counter()
    for bx, by in loop.prefetch((x_nd, y_nd) for _ in range(steps)):
        loss = loop.step(bx, by)
    loop.synchronize()
    jax.block_until_ready(loss._data)   # completion barrier
    dt = time.perf_counter() - t0
    es = loop.engine_stats()

    def val(name, label=None, scale=1.0, digits=None):
        v = telemetry.value(name, label)
        if v is None:
            return None
        v = v * scale
        return round(v, digits) if digits is not None else int(v)

    engine = {
        # host syncs the pipeline did NOT design: NDArray-level
        # asnumpy/item/wait_to_read inside the timed loop (target: 0)
        "host_sync_count": val(names.HOST_SYNCS, "wait_to_read"),
        "inflight_window": es.get("inflight_window"),
        # consumer-side wait on input staging (prefetch hides h2d copy)
        "input_wait_ms": val(names.PREFETCH_INPUT_WAIT, scale=1e3,
                             digits=2),
        "window_retires": val(names.HOST_SYNCS, "window_retire"),
        "prefetch_starvation": val(names.PREFETCH_STARVATION),
    }
    phase_summary = {
        phase: {k: round(v, 3) for k, v in s.items()}
        for phase, s in telemetry.timeline().summary().items()}
    wd = telemetry.watchdog()
    # space-domain fingerprint (docs/OBSERVABILITY.md "memory"): the
    # compiled program's static peak, the census's live bytes by pool,
    # and the measured per-replica optimizer-state bytes — a ZeRO leg
    # must show the ~N× `optimizer` drop HERE, in measured bytes (the
    # dryrun zero-sharded leg asserts it; these fields put the same
    # numbers next to every BENCH throughput figure)
    try:
        mem_report = loop.compiled_step.memory_report(x_nd, y_nd)
    except Exception as e:  # pragma: no cover - platform-dependent
        log(f"bench[{tag}]: memory_report unavailable "
            f"({type(e).__name__}: {e})")
        mem_report = None
    memory = {
        "compiled_peak_bytes": mem_report.peak_bytes if mem_report
        else None,
        "compiled": mem_report.to_dict() if mem_report else None,
        "live_bytes_by_pool":
            telemetry.memory.census().live_bytes_by_pool(),
        "optimizer_state_bytes":
            loop.compiled_step.optimizer_state_bytes(),
    }
    telem = {
        "mfu_gauge": telemetry.value(names.MFU),
        "flops_per_step": telemetry.value(names.MODEL_FLOPS_PER_STEP),
        "step_time_ewma_ms": val(names.STEP_TIME_EWMA, scale=1e3,
                                 digits=3),
        "anomalies": len(wd.anomalies()),
        "phase_summary": phase_summary,
        "memory": memory,
        "snapshot": telemetry.snapshot(),
    }
    # numerics-domain fingerprint AFTER the snapshot: the probe runs
    # its own short loops and must not skew the timed-loop series
    telem["numerics"] = numerics_probe(tag, loop, x_nd, y_nd)
    # elastic fingerprint (only when MXNET_ELASTIC is explicitly armed):
    # recoveries the supervisor logged this process + their total
    # downtime — a bench leg that silently recovered mid-timing must
    # say so next to its throughput number
    try:
        from mxnet_tpu import elastic
        if elastic.armed():
            evs = elastic.recovery_log().events()
            telem["elastic"] = {
                "recoveries": len(evs),
                "recovery_downtime_s": round(
                    sum(e["downtime_s"] for e in evs), 3),
            }
    except Exception as e:  # pragma: no cover - defensive
        log(f"bench[{tag}]: elastic stats unavailable "
            f"({type(e).__name__}: {e})")
    log(f"bench[{tag}]: final loss={float(loss._data.mean()):.3f} "
        f"engine={engine} mfu_gauge={telem['mfu_gauge']} "
        f"anomalies={telem['anomalies']} "
        f"peak_bytes={memory['compiled_peak_bytes']} "
        f"pools={memory['live_bytes_by_pool']}")
    analysis = analyze_framework_step(tag, loop, x_nd, y_nd)
    return dt, flops, loss, analysis, engine, telem


def matmul_roofline():
    """Achieved bf16 GEMM TFLOP/s: best over several large matmul shapes.
    8192³ underreports the chip by ~40%; the max lives at big-K
    rectangular shapes where the output write is amortized (r5 measured:
    8192x65536x8192 at 163 TFLOP/s = 83% of v5e peak vs 113 for 8192³).
    Skipped on CPU (meaningless there)."""
    if jax.default_backend() == "cpu":
        return None
    best = None
    for m, k, n in ((8192, 8192, 8192), (12288, 12288, 12288),
                    (8192, 65536, 8192), (16384, 32768, 16384)):
        # ~35 TFLOP of work per shape so each probe times comparably
        iters = max(3, int(round(35e12 / (2 * m * k * n))))
        a = jnp.asarray(onp.random.randn(m, k), jnp.bfloat16)
        b = jnp.asarray(onp.random.randn(k, n), jnp.bfloat16)
        f = jax.jit(lambda a, b: a @ b)
        c = f(a, b)
        jax.block_until_ready(c)
        t0 = time.perf_counter()
        for _ in range(iters):
            c = f(a, b)
        jax.block_until_ready(c)
        dt = time.perf_counter() - t0
        tfs = 2 * m * k * n * iters / dt / 1e12
        log(f"bench: roofline probe {m}x{k}x{n} iters={iters}: "
            f"{tfs:.1f} TFLOP/s")
        best = tfs if best is None or tfs > best else best
        del a, b, c
    return best


def bench_resnet(dtype):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from __graft_entry__ import _init_net

    on_accel = jax.default_backend() != "cpu"
    try:
        bs = int(os.environ.get("MXNET_BENCH_BS") or 128) if on_accel \
            else 4
    except ValueError:
        raise SystemExit("MXNET_BENCH_BS must be an integer, got "
                         f"{os.environ['MXNET_BENCH_BS']!r}")
    if bs <= 0:
        raise SystemExit(f"MXNET_BENCH_BS must be positive, got {bs}")
    size = 224 if on_accel else 32
    warmup = 3 if on_accel else 1
    steps = 20 if on_accel else 2

    onp.random.seed(0)
    net = vision.resnet50_v1(classes=1000)
    # eager init runs BEFORE amp.init(): the fp32 eager path is
    # compile-cached across runs, while flowing-bf16 eager would trigger
    # ~100 fresh compiles
    _init_net(net, (1, 3, size, size))
    if dtype == "bf16":
        mx.amp.init()
    try:
        loop = framework_loop(net, lr=0.1)
        x = jnp.asarray(onp.random.uniform(size=(bs, 3, size, size))
                        .astype("float32"))
        y = jnp.asarray(onp.random.randint(0, 1000, size=(bs,))
                        .astype("int32"))
        dt, flops, _, ana, eng, tel = run_framework_bench(
            "resnet", loop, x, y, warmup, steps)
    finally:
        if dtype == "bf16":
            mx.amp.uninit()
    img_s = bs * steps / dt
    tfs = flops * steps / dt / 1e12 if flops and on_accel else None
    return {"img_s": img_s, "tflops": tfs, "bs": bs, "analysis": ana,
            "engine": eng, "telemetry": tel}


def bench_bert(dtype):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import bert

    on_accel = jax.default_backend() != "cpu"
    bs, seqlen = (32, 512) if on_accel else (2, 32)
    warmup, steps = (3, 10) if on_accel else (1, 2)
    log(f"bench[bert]: bs={bs} seq={seqlen}")

    onp.random.seed(0)
    net = bert.BERTClassifier(
        bert.bert_base(max_length=seqlen) if on_accel
        else bert.bert_small_test(), num_classes=2)
    vocab = 1000 if on_accel else 128  # stay inside the model's vocab
    tokens = onp.random.randint(0, vocab, size=(1, seqlen)).astype("int32")
    net.initialize()
    net(mx.nd.array(tokens))  # eager init pre-AMP (see bench_resnet note)
    if dtype == "bf16":
        mx.amp.init()
    try:
        # lr small enough that random-label steps stay finite on every
        # config (throughput is lr-independent)
        loop = framework_loop(net, lr=1e-3)
        x = jnp.asarray(onp.random.randint(0, vocab, size=(bs, seqlen))
                        .astype("int32"))
        y = jnp.asarray(onp.random.randint(0, 2, size=(bs,)).astype("int32"))
        dt, flops, _, ana, eng, tel = run_framework_bench(
            "bert", loop, x, y, warmup, steps)
    finally:
        if dtype == "bf16":
            mx.amp.uninit()
    tok_s = bs * seqlen * steps / dt
    tfs = flops * steps / dt / 1e12 if flops and on_accel else None
    return {"tok_s": tok_s, "tflops": tfs, "analysis": ana,
            "engine": eng, "telemetry": tel}


def bench_lstm(dtype):
    """LSTM LM training throughput (BASELINE.md row 4: reference
    example/rnn word_lm on the cuDNN RNN path; here gluon.rnn.LSTM
    lowers to one lax.scan). Medium config: vocab 33278 (wikitext-2),
    650-d embed/hidden, 2 layers, bs=64, bptt=35."""
    import importlib.util
    import mxnet_tpu as mx

    spec = importlib.util.spec_from_file_location(
        "train_lstm_lm",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "examples", "train_lstm_lm.py"))
    ex = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ex)

    on_accel = jax.default_backend() != "cpu"
    vocab, embed, hidden, layers = (33278, 650, 650, 2) if on_accel \
        else (128, 16, 32, 1)
    bs, seq = (64, 35) if on_accel else (4, 8)
    warmup, steps = (3, 20) if on_accel else (1, 2)
    log(f"bench[lstm]: vocab={vocab} hidden={hidden} bs={bs} bptt={seq}")

    onp.random.seed(0)
    net = ex.WordLM(vocab, embed, hidden, layers)
    net.initialize()
    tokens = onp.random.randint(0, vocab, size=(1, seq)).astype("int32")
    net(mx.nd.array(tokens))  # eager init pre-AMP (see bench_resnet note)
    if dtype == "bf16":
        mx.amp.init()
    try:
        loop = framework_loop(net, lr=0.5)
        x = jnp.asarray(onp.random.randint(
            0, vocab, size=(bs, seq)).astype("int32"))
        y = jnp.asarray(onp.random.randint(
            0, vocab, size=(bs, seq)).astype("int32"))
        dt, flops, _, ana, eng, tel = run_framework_bench(
            "lstm", loop, x, y, warmup, steps)
    finally:
        if dtype == "bf16":
            mx.amp.uninit()
    tok_s = bs * seq * steps / dt
    tfs = flops * steps / dt / 1e12 if flops and on_accel else None
    return {"tok_s": tok_s, "tflops": tfs, "analysis": ana,
            "engine": eng, "telemetry": tel}


class _SSDResNet50:
    """Builder for the SSD-ResNet50 bench model (BASELINE.md row 5):
    resnet50_v1 features (minus global pool) + two extra downsample
    scales, 3x3 cls/loc heads per scale, anchors via MultiBoxPrior —
    the reference example/ssd architecture re-expressed in this Gluon."""

    @staticmethod
    def build(num_classes=20):
        from mxnet_tpu import gluon, nd
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.model_zoo import vision

        SIZES = [(0.2, 0.272), (0.37, 0.447), (0.54, 0.619)]
        RATIOS = (1.0, 2.0, 0.5)
        A = len(SIZES[0]) + len(RATIOS) - 1

        class SSD(gluon.Block):
            def __init__(self):
                super().__init__()
                base = vision.resnet50_v1()
                self.backbone = nn.Sequential()
                feats = list(base.features._children.values())[:-1]
                for blk in feats:
                    self.backbone.add(blk)
                self.extra1 = nn.Sequential()
                self.extra1.add(nn.Conv2D(512, 3, strides=2, padding=1,
                                          activation="relu"))
                self.extra2 = nn.Sequential()
                self.extra2.add(nn.Conv2D(256, 3, strides=2, padding=1,
                                          activation="relu"))
                self.cls_heads = []
                self.loc_heads = []
                for i in range(3):
                    ch = nn.Conv2D(A * (num_classes + 1), 3, padding=1)
                    lh = nn.Conv2D(A * 4, 3, padding=1)
                    setattr(self, f"cls{i}", ch)
                    setattr(self, f"loc{i}", lh)
                    self.cls_heads.append(ch)
                    self.loc_heads.append(lh)
                self._nc = num_classes

            def forward(self, x):
                feats = [self.backbone(x)]
                feats.append(self.extra1(feats[-1]))
                feats.append(self.extra2(feats[-1]))
                anchors, clses, locs = [], [], []
                for i, f in enumerate(feats):
                    anchors.append(nd.contrib.MultiBoxPrior(
                        f, sizes=SIZES[i], ratios=RATIOS))
                    c = self.cls_heads[i](f)
                    b, _, h, w = c.shape
                    clses.append(c.transpose((0, 2, 3, 1)).reshape(
                        (b, h * w * A, self._nc + 1)))
                    locs.append(self.loc_heads[i](f).transpose(
                        (0, 2, 3, 1)).reshape((b, -1)))
                return (nd.concat(*anchors, dim=1),
                        nd.concat(*clses, dim=1),
                        nd.concat(*locs, dim=1))

        return SSD()


def bench_ssd(dtype):
    """SSD-ResNet50 training throughput, MultiBoxTarget matching inside
    the compiled step and one on-device-NMS eval (MultiBoxDetection)."""
    import mxnet_tpu as mx
    from mxnet_tpu import _tape, nd
    from mxnet_tpu.ndarray.ndarray import NDArray
    from __graft_entry__ import _functional_apply

    on_accel = jax.default_backend() != "cpu"
    bs, size = (32, 300) if on_accel else (2, 64)
    warmup, steps = (3, 10) if on_accel else (1, 2)
    log(f"bench[ssd]: bs={bs} size={size}")

    onp.random.seed(0)
    net = _SSDResNet50.build()
    net.initialize()
    net(mx.nd.array(onp.random.uniform(
        size=(1, 3, size, size)).astype("float32")))  # eager init pre-AMP
    if dtype == "bf16":
        mx.amp.init()
    try:
        params = [p for p in net.collect_params().values()
                  if p._data is not None]
        trainable = tuple(p.grad_req != "null" for p in params)
        apply_fn = _functional_apply(net, params, train=True,
                                     with_state=True)
        lr, momentum = 1e-3, 0.9

        def loss_fn(pd, x, labels):
            (anchors, cls, loc), state = apply_fn(pd, x,
                                                  jax.random.PRNGKey(0))
            prev = _tape.set_recording(False)
            try:
                loc_t, loc_mask, cls_t = nd.contrib.MultiBoxTarget(
                    NDArray(jax.lax.stop_gradient(anchors)),
                    NDArray(labels),
                    NDArray(jax.lax.stop_gradient(cls)
                            .transpose((0, 2, 1))))
                ce = nd.softmax_cross_entropy(
                    NDArray(cls.reshape((-1, cls.shape[-1]))),
                    NDArray(cls_t._data.reshape((-1,))))
                l1 = nd.abs(NDArray(loc) * loc_mask - loc_t * loc_mask)
            finally:
                _tape.set_recording(prev)
            l = ce._data / cls.shape[0] / cls.shape[1] \
                + jnp.mean(l1._data)
            return l, state

        def train_step(pd, mom, x, labels):
            (loss, state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(pd, x, labels)
            new_mom = tuple(momentum * m + g for m, g in zip(mom, grads))
            new_pd = tuple(d - lr * m if t else s
                           for d, m, s, t in zip(pd, new_mom, state,
                                                 trainable))
            return new_pd, new_mom, loss

        pd = tuple(jnp.array(p._data._data, copy=True) for p in params)
        mom = tuple(jnp.zeros_like(d) for d in pd)
        x = jnp.asarray(onp.random.uniform(
            size=(bs, 3, size, size)).astype("float32"))
        # one random ground-truth box per image: (B, 1, 5) [cls x0 y0 x1 y1]
        lab = onp.zeros((bs, 1, 5), "float32")
        lab[:, 0, 0] = onp.random.randint(0, 20, size=bs)
        x0 = onp.random.uniform(0, 0.6, size=(bs, 2)).astype("float32")
        lab[:, 0, 1:3] = x0
        lab[:, 0, 3:5] = x0 + 0.3
        labels = jnp.asarray(lab)

        step, flops = compile_step(train_step, pd, mom, x, labels)
        t0 = time.perf_counter()
        for _ in range(warmup):
            pd, mom, loss = step(pd, mom, x, labels)
        jax.block_until_ready(loss)
        log(f"bench[ssd]: warmup {time.perf_counter() - t0:.1f}s, "
            f"loss={float(loss):.3f}")
        t0 = time.perf_counter()
        for _ in range(steps):
            pd, mom, loss = step(pd, mom, x, labels)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0

        # on-device NMS eval pass (the reference's custom CUDA NMS; here
        # MultiBoxDetection's lax loop) — ONE jitted program, not
        # per-op eager dispatch
        eval_apply = _functional_apply(net, params, train=False)

        def eval_prog(pd, xe):
            anchors, cls, loc = eval_apply(pd, xe, jax.random.PRNGKey(0))
            prev = _tape.set_recording(False)
            try:
                probs = nd.softmax(NDArray(cls).transpose((0, 2, 1)),
                                   axis=1)
                det = nd.contrib.MultiBoxDetection(
                    probs, NDArray(loc), NDArray(anchors),
                    nms_threshold=0.45, threshold=0.01)
            finally:
                _tape.set_recording(prev)
            return det._data

        xe = jnp.asarray(onp.random.uniform(
            size=(4, 3, size, size)).astype("float32"))
        t0 = time.perf_counter()
        det = jax.jit(eval_prog)(pd, xe)
        onp.asarray(det)
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        onp.asarray(jax.jit(eval_prog)(pd, xe))
        nms_s = time.perf_counter() - t0
        log(f"bench[ssd]: on-device NMS eval (bs=4): {nms_s*1e3:.0f} ms "
            f"(+{t_compile:.1f}s compile)")
    finally:
        if dtype == "bf16":
            mx.amp.uninit()
    img_s = bs * steps / dt
    tfs = flops * steps / dt / 1e12 if flops and on_accel else None
    return {"img_s": img_s, "tflops": tfs}


def bench_serving(dtype):
    """Inference serving leg (mx.serving, docs/SERVING.md): a 3-layer
    MLP served through the AOT-compiled predictor, measured three ways —

    - closed-loop UNBATCHED baseline: 8 concurrent clients, requests
      served ONE AT A TIME (the device is an exclusive resource — one
      program executes at a time; a lock models that on the CPU
      backend, where concurrent XLA calls would otherwise borrow host
      parallelism no accelerator offers) — the pre-serving-engine
      posture;
    - closed-loop through the DynamicBatcher: same 8 clients, requests
      coalesced into shape buckets and pipelined through the dispatch
      window — the acceptance bar is batched QPS > unbatched QPS;
    - open-loop Poisson arrivals at ~30% of the batched closed-loop
      capacity: the honest latency distribution without coordinated
      omission (closed loops self-throttle and hide queueing).

    Reports p50/p99 latency, QPS, batch-fill ratio, and the persistent
    compile-cache hit rate next to the training legs, plus an INT8
    variant probe through the post-training-quantization path."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.runtime import compile_cache_stats
    from mxnet_tpu.serving import loadgen

    on_accel = jax.default_backend() != "cpu"
    in_dim, hidden, classes = (1024, 4096, 1000) if on_accel \
        else (256, 1024, 64)
    requests = 512 if on_accel else 256
    conc = 8
    buckets = (1, 2, 4, 8, 16, 32)
    log(f"bench[serving]: mlp {in_dim}->{hidden}x2->{classes} "
        f"concurrency={conc} requests={requests} buckets={buckets}")

    onp.random.seed(0)

    def build_net():
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu", in_units=in_dim),
                nn.Dense(hidden, activation="relu", in_units=hidden),
                nn.Dense(classes, in_units=hidden))
        net.initialize()
        net(mx.nd.array(onp.zeros((1, in_dim), "float32")))
        return net

    serve_dtype = "bfloat16" if dtype == "bf16" and on_accel \
        else "float32"
    pred = serving.predictor_for(build_net(), dtype=serve_dtype,
                                 bucket_sizes=buckets)
    telemetry.enable(True)
    x1 = mx.nd.array(onp.random.randn(1, in_dim).astype("float32"))
    t0 = time.perf_counter()
    pred.warmup(x1)
    t_warm = time.perf_counter() - t0
    log(f"bench[serving]: warmup (AOT all buckets) {t_warm:.1f}s, "
        f"programs={pred.n_traces}")
    telemetry.reset()

    X = onp.random.randn(requests, in_dim).astype("float32")

    # one-request-at-a-time: the device executes one program at a time
    # (a lock models the exclusive accelerator on the CPU backend)
    import threading
    device_lock = threading.Lock()

    def issue_unbatched(i):
        with device_lock:
            out = pred.predict(
                mx.nd.array(X[i % requests:i % requests + 1]))
            jax.block_until_ready(out._data)

    unbatched = loadgen.run_closed_loop(issue_unbatched, conc, requests)
    log(f"bench[serving]: unbatched {unbatched}")

    batcher = serving.DynamicBatcher(pred, max_batch=buckets[-1],
                                     timeout_ms=2.0)
    batched = loadgen.run_closed_loop(
        lambda i: batcher.submit(
            mx.nd.array(X[i % requests:i % requests + 1])).result(120),
        conc, requests)
    fill = batcher.batch_fill
    bstats = dict(batcher.stats)
    batcher.close()
    log(f"bench[serving]: batched {batched} fill={fill} {bstats}")

    open_rep = None
    if batched.get("qps"):
        batcher2 = serving.DynamicBatcher(pred, max_batch=buckets[-1],
                                          timeout_ms=2.0)
        open_rep = loadgen.run_open_loop(
            lambda i: batcher2.submit(
                mx.nd.array(X[i % requests:i % requests + 1])).result,
            rate_qps=0.3 * batched["qps"],
            requests=max(64, requests // 2))
        batcher2.close()
        log(f"bench[serving]: open-loop {open_rep}")

    # INT8 serving variant through the post-training-quantization path
    int8_probe = None
    try:
        calib = [mx.nd.array(X[i:i + 8]) for i in range(0, 32, 8)]
        pred8 = serving.predictor_for(build_net(), dtype="int8",
                                      calib_data=calib,
                                      bucket_sizes=buckets)
        pred8.warmup(x1, buckets=(1, buckets[-1]))
        b8 = serving.DynamicBatcher(pred8, max_batch=buckets[-1],
                                    timeout_ms=2.0)
        int8_probe = loadgen.run_closed_loop(
            lambda i: b8.submit(
                mx.nd.array(X[i % requests:i % requests + 1])).result(120),
            conc, max(64, requests // 4))
        b8.close()
        log(f"bench[serving]: int8 {int8_probe}")
    except Exception as e:  # pragma: no cover - variant must not kill leg
        log(f"bench[serving]: int8 probe failed ({type(e).__name__}: {e})")

    # resilience probes (docs/SERVING.md "Resilient serving"):
    # (a) overload A/B — open-loop Poisson at ~2x the measured batched
    # capacity with a per-request deadline. The unshedded baseline
    # accepts everything and its p99 blows past the deadline as the
    # queue grows; MXNET_SERVING_SHED=deadline rejects at admission
    # (typed Overloaded) so the ACCEPTED requests keep their p99.
    # Both runs land in the BENCH json.
    overload = None
    saved_shed = os.environ.get("MXNET_SERVING_SHED")
    try:
        if batched.get("qps"):
            rate = 2.0 * batched["qps"]
            deadline_ms = max(25.0, 4.0 * (batched.get("p50_ms") or 5.0))
            n_over = max(96, requests // 4)
            overload = {"rate_qps": round(rate, 1),
                        "deadline_ms": round(deadline_ms, 1)}
            # baseline: no shedding, no deadline — the honest p99 of
            # an overloaded FIFO queue
            os.environ["MXNET_SERVING_SHED"] = "off"
            b_off = serving.DynamicBatcher(pred, max_batch=buckets[-1],
                                           timeout_ms=2.0)
            rep_off = loadgen.run_open_loop(
                lambda i: b_off.submit(
                    mx.nd.array(X[i % requests:i % requests + 1]),
                    deadline_ms=0).result,
                rate_qps=rate, requests=n_over)
            b_off.close()
            overload["shed_off"] = {
                k: rep_off.get(k) for k in
                ("qps", "goodput_qps", "p50_ms", "p99_ms",
                 "reject_rate", "deadline_miss_rate", "outcomes")}
            miss_base = (rep_off.get("p99_ms") or 0) > deadline_ms
            # shed=deadline: same traffic, per-request deadline armed
            os.environ["MXNET_SERVING_SHED"] = "deadline"
            b_on = serving.DynamicBatcher(pred, max_batch=buckets[-1],
                                          timeout_ms=2.0)
            rep_on = loadgen.run_open_loop(
                lambda i: b_on.submit(
                    mx.nd.array(X[i % requests:i % requests + 1]),
                    deadline_ms=deadline_ms).result,
                rate_qps=rate, requests=n_over,
                deadline_s=deadline_ms / 1e3)
            b_on.close()
            overload["shed_deadline"] = {
                k: rep_on.get(k) for k in
                ("qps", "goodput_qps", "p50_ms", "p99_ms",
                 "reject_rate", "deadline_miss_rate", "outcomes")}
            overload["baseline_missed_deadline"] = bool(miss_base)
            overload["shed_kept_p99_in_deadline"] = bool(
                (rep_on.get("p99_ms") or 1e9) <= deadline_ms)
            log(f"bench[serving]: overload A/B @ {rate:.0f} req/s "
                f"deadline={deadline_ms:.0f}ms — off p99="
                f"{rep_off.get('p99_ms')}ms goodput="
                f"{rep_off.get('goodput_qps')} | deadline p99="
                f"{rep_on.get('p99_ms')}ms goodput="
                f"{rep_on.get('goodput_qps')} reject_rate="
                f"{rep_on.get('reject_rate')}")
    except Exception as e:  # pragma: no cover - probe must not kill leg
        log(f"bench[serving]: overload probe failed "
            f"({type(e).__name__}: {e})")
    finally:
        if saved_shed is None:
            os.environ.pop("MXNET_SERVING_SHED", None)
        else:
            os.environ["MXNET_SERVING_SHED"] = saved_shed

    # (b) device-loss recovery — a small supervised burst with one
    # injected revocation: {recoveries, recovery_downtime_s} prove the
    # ServingSupervisor's rebuild path end to end (a dedicated probe
    # net keeps the rebuild cheap; the machinery, not the model, is
    # under test)
    resilience = None
    try:
        from mxnet_tpu.testing import faults

        def build_probe():
            mx.random.seed(11)
            pnet = nn.HybridSequential()
            pnet.add(nn.Dense(64, activation="relu", in_units=32),
                     nn.Dense(8, in_units=64))
            pnet.initialize()
            pnet(mx.nd.array(onp.zeros((1, 32), "float32")))
            return serving.CompiledPredictor(pnet,
                                             bucket_sizes=(1, 2, 4))

        xp = mx.nd.array(onp.zeros((1, 32), "float32"))
        Xp = onp.random.randn(32, 32).astype("float32")
        sup = serving.ServingSupervisor(build_probe, example=(xp,),
                                        max_batch=4, timeout_ms=2.0)
        faults.configure("serving.dispatch:before=2:revoke:1")
        try:
            rep_r = loadgen.run_closed_loop(
                lambda i: sup.submit(
                    mx.nd.array(Xp[i % 32:i % 32 + 1])).result(60),
                concurrency=4, requests=48)
        finally:
            faults.reset()
            sup.close()
        resilience = {
            "recoveries": sup.stats["recoveries"],
            "recovery_downtime_s": round(
                sup.stats["recovery_downtime_s"], 3),
            "requeued": sup.stats["requeued"],
            "breaker": [s for s, _t, _c in sup.breaker.transitions],
            "outcomes": rep_r.get("outcomes"),
        }
        log(f"bench[serving]: recovery probe {resilience}")
    except Exception as e:  # pragma: no cover - probe must not kill leg
        log(f"bench[serving]: recovery probe failed "
            f"({type(e).__name__}: {e})")

    cc = compile_cache_stats()
    cache = {"enabled": cc["enabled"], "hits": cc["hits"],
             "misses": cc["misses"],
             "hit_rate": round(cc["hits"] / (cc["hits"] + cc["misses"]), 3)
             if (cc["hits"] + cc["misses"]) else None}
    speedup = round(batched["qps"] / unbatched["qps"], 2) \
        if batched.get("qps") and unbatched.get("qps") else None
    log(f"bench[serving]: batched-vs-unbatched QPS speedup {speedup}x "
        f"cache={cache}")
    return {
        "qps": batched.get("qps"),
        "p50_ms": batched.get("p50_ms"),
        "p99_ms": batched.get("p99_ms"),
        "concurrency": conc,
        "batch_fill": round(fill, 3) if fill is not None else None,
        "unbatched_qps": unbatched.get("qps"),
        "unbatched_p50_ms": unbatched.get("p50_ms"),
        "speedup_vs_unbatched": speedup,
        "open_loop": open_rep,
        "int8": int8_probe,
        # resilience posture (docs/SERVING.md "Resilient serving")
        "goodput_qps": batched.get("goodput_qps"),
        "reject_rate": batched.get("reject_rate"),
        "deadline_miss_rate": batched.get("deadline_miss_rate"),
        "overload": overload,
        "resilience": resilience,
        "recoveries": resilience["recoveries"]
        if resilience is not None else None,
        "recovery_downtime_s": resilience["recovery_downtime_s"]
        if resilience is not None else None,
        "compile_cache": cache,
        "warmup_s": round(t_warm, 2),
        "programs": pred.n_traces,
        "dtype": serve_dtype,
        "batcher": {k: bstats.get(k) for k in
                    ("requests", "batches", "rows", "padded_rows",
                     "flush_full", "flush_timeout", "flush_idle",
                     "errors")},
        # serving-scope autotune posture (tuned batcher knobs replayed
        # from MXNET_AUTOTUNE_CACHE, or the defaults on a miss)
        **(pred.autotune_result.bench_dict()
           if getattr(pred, "autotune_result", None) is not None else
           {"autotune_config": None, "autotune_trials": None,
            "autotune_delta_pct": None}),
    }


def bench_decode(dtype):
    """Continuous-batching decode leg (mx.serving.decode,
    docs/SERVING.md "Continuous batching"): the reference decoder
    served over a heavy-tailed request mix (mostly short decodes, a
    few long ones — the shape that makes whole-batch scheduling bleed)
    two ways with IDENTICAL compiled programs:

    - **static**: the classic whole-batch baseline — fill every slot,
      prefill all prompts, decode until the LAST member finishes;
    - **continuous**: iteration-level scheduling — finished slots
      refilled between steps, chunked prefill interleaved with decode.

    The acceptance bar is continuous token throughput >= 2x static at
    this mix, with lower short-request TTFT. Reports
    decode_tokens_per_sec, exact TTFT/TPOT percentiles, KV page
    utilization, and the kernel dispatch posture."""
    from mxnet_tpu import serving
    from mxnet_tpu.ops import kernels as _kern

    on_accel = jax.default_backend() != "cpu"
    vocab, d_model, heads = (256, 128, 4) if on_accel else (64, 32, 2)
    n_req = 32 if on_accel else 16
    ladder = (1, 2, 4, 8) if on_accel else (1, 2, 4)
    page_size = 16 if on_accel else 8
    rng = onp.random.RandomState(7)
    model = serving.TinyDecoder(vocab=vocab, d_model=d_model,
                                num_heads=heads, seed=0)
    prompts, mns = [], []
    for i in range(n_req):
        prompts.append(rng.randint(0, vocab,
                                   size=int(rng.randint(2, 12))))
        mns.append(48 if i % 8 == 0 else int(rng.randint(2, 6)))
    log(f"bench[decode]: {n_req} requests, ladder={ladder}, "
        f"page_size={page_size}, mix=heavy-tail "
        f"(len {min(mns)}..{max(mns)})")
    cont = serving.run_decode(model, prompts, mns, ladder=ladder,
                              page_size=page_size)
    stat = serving.run_decode(model, prompts, mns, ladder=ladder,
                              page_size=page_size, static=True)
    speedup = round(cont["decode_tokens_per_sec"]
                    / stat["decode_tokens_per_sec"], 2) \
        if cont.get("decode_tokens_per_sec") and \
        stat.get("decode_tokens_per_sec") else None
    log(f"bench[decode]: continuous {cont['decode_tokens_per_sec']} "
        f"tok/s (ttft p99 {cont['ttft_p99_ms']}ms) vs static "
        f"{stat['decode_tokens_per_sec']} tok/s (ttft p99 "
        f"{stat['ttft_p99_ms']}ms) — speedup {speedup}x")
    # --- speculative decode + prefix sharing A/B (docs/SERVING.md
    # "Speculative decode & prefix sharing"): a repeated-suffix mix
    # (prompt-lookup drafting territory) whose prompts extend one
    # shared base prefix, decoded plain-greedy vs draft->verify with
    # the prefix cache on. Emitted tokens are bit-identical by
    # contract; the delta is steps, not tokens.
    base = rng.randint(0, vocab, size=3 * page_size).astype(onp.int32)
    sp_prompts, sp_mns = [], []
    for i in range(max(8, n_req // 2)):
        tail = rng.randint(0, vocab, size=2 + (i % 3)).astype(onp.int32)
        sp_prompts.append(onp.concatenate([base, tail]))
        sp_mns.append(24)
    plain = serving.run_decode(model, sp_prompts, sp_mns,
                               ladder=ladder, page_size=page_size,
                               spec_k=0, prefix_share=False)
    spec = serving.run_decode(model, sp_prompts, sp_mns,
                              ladder=ladder, page_size=page_size,
                              spec_k=4, prefix_share=True)
    speedup_spec = round(spec["decode_tokens_per_sec"]
                         / plain["decode_tokens_per_sec"], 2) \
        if spec.get("decode_tokens_per_sec") and \
        plain.get("decode_tokens_per_sec") else None
    tps = (spec.get("tokens_per_step") or {}).get("mean")
    cap = max(1, spec.get("kv_num_pages", 2) - 1)
    shared_pct = round(100.0 * spec.get("kv_shared_peak", 0) / cap, 2)
    log(f"bench[decode]: speculative {spec['decode_tokens_per_sec']} "
        f"tok/s vs greedy {plain['decode_tokens_per_sec']} tok/s — "
        f"speedup {speedup_spec}x, acceptance "
        f"{spec.get('acceptance_rate')}, tokens/step {tps}, shared "
        f"pages peak {shared_pct}% of pool")

    # --- GQA transformer workload: the second decode model over the
    # same engine/cache (half the K/V heads -> half the cache bytes
    # per token at this query width)
    from mxnet_tpu.gluon import GQADecoder
    gqa = GQADecoder(vocab=vocab, d_model=d_model, num_heads=heads * 2,
                     num_kv_heads=heads, num_layers=2, seed=0)
    gqa_res = serving.run_decode(gqa, prompts[:8], mns[:8],
                                 ladder=ladder, page_size=page_size)
    log(f"bench[decode]: gqa transformer "
        f"{gqa_res['decode_tokens_per_sec']} tok/s "
        f"({gqa.num_heads} q heads / {gqa.num_kv_heads} kv heads)")
    return {
        "decode_tokens_per_sec": cont.get("decode_tokens_per_sec"),
        "ttft_p50_ms": cont.get("ttft_p50_ms"),
        "ttft_p99_ms": cont.get("ttft_p99_ms"),
        "tpot_p50_ms": cont.get("tpot_p50_ms"),
        "tpot_p99_ms": cont.get("tpot_p99_ms"),
        "kv_page_util": cont.get("kv_page_util"),
        "speedup_vs_static": speedup,
        "static_tokens_per_sec": stat.get("decode_tokens_per_sec"),
        "static_ttft_p99_ms": stat.get("ttft_p99_ms"),
        "tokens": cont.get("tokens"),
        "requests": n_req,
        "steps": cont.get("steps"),
        "static_steps": stat.get("steps"),
        "prefill_chunks": cont.get("prefill_chunks"),
        "slot_ladder": list(ladder),
        "page_size": page_size,
        "kernel_path": _kern.dispatch_table().get("rnn_decode_step"),
        "spec_acceptance_rate": spec.get("acceptance_rate"),
        "tokens_per_step": tps,
        "kv_shared_page_pct": shared_pct,
        "speedup_vs_nonspec": speedup_spec,
        "spec_detail": spec,
        "gqa_tokens_per_sec": gqa_res.get("decode_tokens_per_sec"),
        "gqa_detail": gqa_res,
        "continuous_detail": cont,
        "static_detail": stat,
    }


def bench_fleet(dtype):
    """Serving fleet leg (mx.serving.fleet, docs/SERVING.md "Serving
    fleet"): a small probe MLP served by a FleetController, measured
    four ways —

    - closed-loop goodput through ONE replica (the single-replica
      posture PR 15 ends at);
    - the same traffic through a 3-replica fleet behind the
      least-wait router (``fleet_speedup_vs_single``);
    - kill-one-mid-burst: a targeted device revocation at one
      replica's dispatch seam while the burst runs — goodput under
      failover, plus the replica's out-of-rotation window
      (``kill_recovery_downtime_s``: replica_lost -> restart, from
      the structured FleetEvent log);
    - a rolling weight swap under the same fleet
      (``swap_downtime_s``: the LONGEST single replica's
      drain->serving window; the fleet itself never goes dark).

    The probe model is deliberately tiny — the routing/failover/
    rollout machinery, not the matmuls, is under test."""
    import tempfile
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.checkpoint import atomic as ck_atomic
    from mxnet_tpu.checkpoint import state as ck_state
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import dist
    from mxnet_tpu.serving import loadgen
    from mxnet_tpu.testing import faults

    in_dim, hidden, classes = 32, 64, 8
    requests, conc, buckets = 96, 6, (1, 2, 4)
    n_dev = len(dist.available_devices())
    n_fleet = min(3, n_dev)

    def build_probe():
        mx.random.seed(17)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu", in_units=in_dim),
                nn.Dense(classes, in_units=hidden))
        net.initialize()
        net(mx.nd.array(onp.zeros((1, in_dim), "float32")))
        return net

    def build():
        return serving.CompiledPredictor(build_probe(),
                                         bucket_sizes=buckets)

    xp = mx.nd.array(onp.zeros((1, in_dim), "float32"))
    Xp = onp.random.RandomState(3).randn(64, in_dim).astype("float32")

    def make_args(i):
        return (mx.nd.array(Xp[i % 64:i % 64 + 1]),)

    log(f"bench[fleet]: probe mlp {in_dim}->{hidden}->{classes}, "
        f"{n_fleet} replicas over {n_dev} device(s), "
        f"requests={requests} concurrency={conc}")

    single = serving.FleetController(build, example=(xp,), replicas=1,
                                     max_batch=buckets[-1],
                                     timeout_ms=2.0)
    rep1 = loadgen.run_closed_loop(
        loadgen.fleet_issue(single.router, make_args, timeout=60),
        conc, requests)
    single.close()
    log(f"bench[fleet]: 1 replica {rep1}")

    fleet = serving.FleetController(build, example=(xp,),
                                    replicas=n_fleet,
                                    max_batch=buckets[-1],
                                    timeout_ms=2.0)
    repN = loadgen.run_closed_loop(
        loadgen.fleet_issue(fleet.router, make_args, timeout=60),
        conc, requests)
    log(f"bench[fleet]: {n_fleet} replicas {repN}")

    # kill-one-mid-burst A/B: revoke the last replica's device at its
    # dispatch seam while the burst runs; the router + failover keep
    # accepted traffic flowing on the survivors
    kill_rep, kill_downtime = None, None
    if n_fleet >= 2:
        victim = fleet.replicas[-1]
        faults.configure(f"serving.dispatch@{victim.name}:before=1:"
                         f"revoke:d{victim.device.id}")
        try:
            kill_rep = loadgen.run_closed_loop(
                loadgen.fleet_issue(fleet.router, make_args,
                                    timeout=60), conc, requests)
        finally:
            faults.reset()
        deadline = time.perf_counter() + 15.0
        while time.perf_counter() < deadline and not any(
                e.kind in ("restart", "restart_failed")
                for e in fleet.events):
            time.sleep(0.05)
        t_lost = next((e.t for e in fleet.events
                       if e.kind == "replica_lost"), None)
        t_back = next((e.t for e in fleet.events
                       if e.kind == "restart"), None)
        if t_lost is not None and t_back is not None:
            kill_downtime = round(max(0.0, t_back - t_lost), 3)
        log(f"bench[fleet]: kill-mid-burst {kill_rep} "
            f"recovery_downtime={kill_downtime}s "
            f"restarts={fleet.stats['restarts']}")

    # rolling weight swap: drain one replica at a time onto a fresh
    # CRC-verified checkpoint; the out-of-rotation window per replica
    # is the honest "downtime" (the fleet keeps serving throughout)
    swap_downtime, swap_total = None, None
    try:
        st = ck_state.capture_train_state(net=build_probe(), step=1)
        root = tempfile.mkdtemp(prefix="mx-fleet-swap-")
        ck_atomic.write_checkpoint(root, 1, st.arrays,
                                   array_meta=st.array_meta,
                                   meta=st.meta)
        t0 = time.perf_counter()
        fleet.swap_weights(root)
        swap_total = round(time.perf_counter() - t0, 3)
        drains = {e.replica: e.t for e in fleet.events
                  if e.kind == "swap_drain"}
        gaps = [e.t - drains[e.replica] for e in fleet.events
                if e.kind == "swap_done" and e.replica in drains]
        swap_downtime = round(max(gaps), 3) if gaps else None
        log(f"bench[fleet]: rolling swap total={swap_total}s "
            f"max_replica_window={swap_downtime}s")
    except Exception as e:  # pragma: no cover - probe must not kill leg
        log(f"bench[fleet]: swap probe failed "
            f"({type(e).__name__}: {e})")
    fstats = dict(fleet.stats)
    fleet.close()

    speedup = round(repN["goodput_qps"] / rep1["goodput_qps"], 2) \
        if repN.get("goodput_qps") and rep1.get("goodput_qps") else None
    log(f"bench[fleet]: fleet-vs-single goodput speedup {speedup}x")
    return {
        "fleet_goodput_qps": repN.get("goodput_qps"),
        "single_goodput_qps": rep1.get("goodput_qps"),
        "fleet_speedup_vs_single": speedup,
        "kill_recovery_downtime_s": kill_downtime,
        "swap_downtime_s": swap_downtime,
        "swap_total_s": swap_total,
        "replicas": n_fleet,
        "fleet_p50_ms": repN.get("p50_ms"),
        "fleet_p99_ms": repN.get("p99_ms"),
        "per_replica": repN.get("replicas"),
        "kill_outcomes": kill_rep.get("outcomes")
        if kill_rep is not None else None,
        "kill_goodput_qps": kill_rep.get("goodput_qps")
        if kill_rep is not None else None,
        "restarts": fstats.get("restarts"),
        "failovers": fstats.get("failovers"),
        "requeued": fstats.get("requeued"),
        "swaps": fstats.get("swaps"),
    }


def main():
    model = os.environ.get("MXNET_BENCH_MODEL", "all")
    dtype = os.environ.get("MXNET_BENCH_DTYPE", "bf16")
    if dtype not in ("bf16", "fp32"):
        raise SystemExit(f"MXNET_BENCH_DTYPE must be bf16|fp32, got {dtype}")
    # every leg runs under the autotune REPLAY gate: a tuned config
    # persisted by an offline MXNET_AUTOTUNE=on pass is applied with
    # zero trials, a miss runs the shipped defaults — the leg's
    # {autotune_config, autotune_trials, autotune_delta_pct} fields
    # record which happened (an explicit MXNET_AUTOTUNE wins)
    os.environ.setdefault("MXNET_AUTOTUNE", "cached")

    peak, kind = peak_tflops()
    log(f"bench: backend={jax.default_backend()} device={kind} "
        f"peak_bf16={peak} model={model} dtype={dtype}")

    out = {}
    if model in ("all", "resnet50"):
        r = bench_resnet(dtype)
        out.update({
            "metric": "resnet50_v1_train_img_per_sec",
            "value": round(r["img_s"], 2),
            "unit": "img/s",
            "vs_baseline": round(r["img_s"] / BASELINE_IMG_S, 3),
            "dtype": dtype,
            "tflops": round(r["tflops"], 2) if r["tflops"] else None,
            "mfu": round(r["tflops"] / peak, 4)
            if r["tflops"] and peak else None,
            # structural fingerprint (mx.analysis): a throughput drop
            # arrives WITH its program diff — traces, collectives,
            # donated bytes (docs/ANALYSIS.md)
            "resnet_analysis": r.get("analysis"),
            # async-engine observability: input-wait, in-flight window,
            # host syncs inside the timed loop (docs/PERF_NOTES.md)
            "resnet_engine": r.get("engine"),
            # full telemetry story: phase-duration summary, MFU gauge,
            # anomaly count, registry snapshot (docs/OBSERVABILITY.md)
            "resnet_telemetry": r.get("telemetry"),
        })
    failed = []

    def run_leg(name, fn):
        """A secondary leg that fails must not destroy the JSON line of
        the legs that ran — but the run then exits non-zero (below)."""
        try:
            return fn(dtype)
        except Exception as e:
            if model == name:
                raise
            log(f"bench[{name}]: FAILED ({type(e).__name__}: {e})")
            failed.append(name)
            return None

    if model in ("all", "bert"):
        b = run_leg("bert", bench_bert)
        if b is not None:
            if model == "bert":
                out.update({
                    "metric": "bert_base_train_tokens_per_sec",
                    "value": round(b["tok_s"], 1),
                    "unit": "tokens/s",
                    "vs_baseline": None,  # no in-tree reference number
                    "dtype": dtype,
                })
            out.update({
                "bert_tokens_per_sec": round(b["tok_s"], 1),
                "bert_tflops": round(b["tflops"], 2)
                if b["tflops"] else None,
                "bert_mfu": round(b["tflops"] / peak, 4)
                if b["tflops"] and peak else None,
                "bert_analysis": b.get("analysis"),
                "bert_engine": b.get("engine"),
                "bert_telemetry": b.get("telemetry"),
            })
    for name, fn, tok_field in (("lstm", bench_lstm, "lstm_tokens_per_sec"),
                                ("ssd", bench_ssd, "ssd_img_per_sec")):
        if model not in ("all", name):
            continue
        r = run_leg(name, fn)
        if r is None:
            continue
        val = r.get("tok_s") or r.get("img_s")
        if model == name:
            out.update({
                "metric": f"{name}_train_"
                          + ("tokens_per_sec" if "tok_s" in r
                             else "img_per_sec"),
                "value": round(val, 1),
                "unit": "tokens/s" if "tok_s" in r else "img/s",
                "vs_baseline": None,  # BASELINE rows 4-5: no in-tree number
                "dtype": dtype,
            })
        out.update({
            tok_field: round(val, 1),
            f"{name}_tflops": round(r["tflops"], 2) if r["tflops"] else None,
            f"{name}_mfu": round(r["tflops"] / peak, 4)
            if r["tflops"] and peak else None,
        })
        if r.get("analysis") is not None:
            out[f"{name}_analysis"] = r["analysis"]
        if r.get("engine") is not None:
            out[f"{name}_engine"] = r["engine"]
        if r.get("telemetry") is not None:
            out[f"{name}_telemetry"] = r["telemetry"]
    if model in ("all", "serving"):
        s = run_leg("serving", bench_serving)
        if s is not None:
            if model == "serving":
                out.update({
                    "metric": "serving_batched_qps",
                    "value": s["qps"],
                    "unit": "req/s",
                    "vs_baseline": s["speedup_vs_unbatched"],
                    "dtype": s["dtype"],
                })
            out.update({
                "serving_qps": s["qps"],
                "serving_p50_ms": s["p50_ms"],
                "serving_p99_ms": s["p99_ms"],
                "serving_batch_fill": s["batch_fill"],
                "serving_unbatched_qps": s["unbatched_qps"],
                "serving_speedup_vs_unbatched":
                    s["speedup_vs_unbatched"],
                "serving_cache_hit_rate":
                    s["compile_cache"]["hit_rate"],
                "serving_goodput_qps": s.get("goodput_qps"),
                "serving_reject_rate": s.get("reject_rate"),
                "serving_deadline_miss_rate":
                    s.get("deadline_miss_rate"),
                "serving_recoveries": s.get("recoveries"),
                "serving_recovery_downtime_s":
                    s.get("recovery_downtime_s"),
                "serving_detail": s,
            })
    if model in ("all", "decode"):
        d = run_leg("decode", bench_decode)
        if d is not None:
            if model == "decode":
                out.update({
                    "metric": "decode_tokens_per_sec",
                    "value": d["decode_tokens_per_sec"],
                    "unit": "tok/s",
                    "vs_baseline": d["speedup_vs_static"],
                    "dtype": dtype,
                })
            out.update({
                "decode_tokens_per_sec": d["decode_tokens_per_sec"],
                "decode_ttft_p50_ms": d["ttft_p50_ms"],
                "decode_ttft_p99_ms": d["ttft_p99_ms"],
                "decode_tpot_p50_ms": d["tpot_p50_ms"],
                "decode_kv_page_util": d["kv_page_util"],
                "decode_speedup_vs_static": d["speedup_vs_static"],
                "decode_detail": d,
            })
    if model in ("all", "fleet"):
        fl = run_leg("fleet", bench_fleet)
        if fl is not None:
            if model == "fleet":
                out.update({
                    "metric": "fleet_goodput_qps",
                    "value": fl["fleet_goodput_qps"],
                    "unit": "req/s",
                    "vs_baseline": fl["fleet_speedup_vs_single"],
                    "dtype": dtype,
                })
            out.update({
                "fleet_goodput_qps": fl["fleet_goodput_qps"],
                "fleet_speedup_vs_single":
                    fl["fleet_speedup_vs_single"],
                "kill_recovery_downtime_s":
                    fl["kill_recovery_downtime_s"],
                "swap_downtime_s": fl["swap_downtime_s"],
                "fleet_detail": fl,
            })
    roof = run_leg("roofline", lambda _dtype: matmul_roofline())
    out.update({
        "matmul_roofline_tflops": round(roof, 1) if roof else None,
        "peak_tflops": peak,
        "platform": jax.devices()[0].platform,
        "device": kind,
        "device_count": len(jax.devices()),
    })
    print(json.dumps(out))
    if failed:
        raise SystemExit(f"bench: legs failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
