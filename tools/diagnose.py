#!/usr/bin/env python
"""Diagnose the runtime environment for bug reports.

Reference analog: tools/diagnose.py — same sections (platform, python,
environment variables, build info) with the network-connectivity checks
made opt-in (``--network``): this framework targets egress-less
environments, and the useful diagnostics here are the accelerator ones
(jax backend, device kind, donation/compile sanity).
"""
import argparse
import os
import platform
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_pip():
    print("------------Pip Info-----------")
    try:
        import pip
        print("Version      :", pip.__version__)
    except ImportError:
        print("No corresponding pip install for current python.")


def check_mxnet():
    print("----------MXNet(TPU) Info-----------")
    try:
        import mxnet_tpu as mx
        print("Version      :", getattr(mx, "__version__", "dev"))
        print("Directory    :", os.path.dirname(mx.__file__))
        from mxnet_tpu.runtime import Features
        feats = Features()
        on = [f for f in feats.keys() if feats.is_enabled(f)]
        print("Enabled features:", ", ".join(sorted(on)))
    except Exception as e:  # pragma: no cover - env-dependent
        print("mxnet_tpu import failed:", repr(e))


def check_accelerator():
    print("----------Accelerator Info----------")
    try:
        import jax
        print("jax version  :", jax.__version__)
        print("backend      :", jax.default_backend())
        for d in jax.devices():
            print("device       :", d,
                  getattr(d, "device_kind", ""))
        import jax.numpy as jnp
        y = float((jnp.ones((8, 8)) @ jnp.ones((8, 8)))[0, 0])
        print("compile+run  : ok (8x8 matmul =", y, ")")
    except Exception as e:  # pragma: no cover - env-dependent
        print("accelerator check failed:", repr(e))


def check_analysis():
    """Compiled-program health: fuse a tiny MLP train step through
    Trainer.compile_step and print the mx.analysis ProgramReport
    (collective census, donation audit, host transfers, dtype drift) —
    so an environment report shows not just that the device compiles,
    but that the framework's ONE-program training contract holds on it
    (docs/ANALYSIS.md)."""
    print("----------Program Analysis----------")
    try:
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu.gluon import Trainer, nn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

        onp.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
        net.initialize()
        x = mx.nd.array(onp.random.randn(8, 16).astype("float32"))
        y = mx.nd.array(onp.random.randint(0, 8, size=(8,))
                        .astype("int32"))
        net(x)
        loss = SoftmaxCrossEntropyLoss()
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore=None)
        step = trainer.compile_step(lambda a, b: loss(net(a), b))
        step(x, y)
        report = step.analyze(x, y)
        print(report.summary())
        print("verdict      :", "OK" if report.ok else
              "VIOLATIONS (see findings above)")
    except Exception as e:  # pragma: no cover - env-dependent
        print("program analysis failed:", repr(e))


def check_engine():
    """Async-dispatch health: run a tiny MLP through the pipelined
    gluon.TrainLoop (device-prefetched inputs + bounded in-flight
    window) and print the dispatch stats — window size, host syncs per
    100 steps, prefetch depth/starvation — so a misconfigured pipeline
    (window 0, per-step syncs sneaking in, starved prefetcher) is
    visible without a profiler (docs/PERF_NOTES.md "async engine")."""
    print("----------Async Engine----------")
    try:
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu.analysis import guard as tguard
        from mxnet_tpu.gluon import Trainer, TrainLoop, nn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
        from mxnet_tpu.runtime import compile_cache_stats

        steps = 100
        onp.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
        net.initialize()
        x = mx.nd.array(onp.random.randn(16, 16).astype("float32"))
        y = mx.nd.array(onp.random.randint(0, 8, size=(16,))
                        .astype("int32"))
        net(x)
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore=None)
        loop = TrainLoop(net, trainer, SoftmaxCrossEntropyLoss())
        loop.step(x, y)          # compile outside the counted region
        loop.synchronize()
        tguard.reset_sync_counts()
        for bx, by in loop.prefetch((x, y) for _ in range(steps)):
            loop.step(bx, by)
        loop.synchronize()
        counts = tguard.sync_counts()
        s = loop.engine_stats()
        print("mode         :", loop.compiled_step.mode)
        print("window size  :", s["inflight_window"],
              "(MXNET_INFLIGHT_STEPS)")
        print("steps run    :", steps)
        print("max in-flight:", s["max_pending"])
        print("window waits :", counts.get("window_retire", 0),
              "(the designed retire syncs)")
        print("host syncs   :", counts.get("wait_to_read", 0),
              f"per {steps} steps (unplanned NDArray syncs; want 0)")
        print("prefetch     : depth", s.get("prefetch_depth"),
              "starvation", s.get("starvation_count"),
              f"input_wait {s.get('input_wait_ms', 0.0):.1f} ms")
        cc = compile_cache_stats()
        print("compile cache:", cc["dir"],
              f"hits={cc['hits']} misses={cc['misses']}")
    except Exception as e:  # pragma: no cover - env-dependent
        print("engine check failed:", repr(e))


def check_elastic():
    """Elastic-training health: run a tiny supervised TrainLoop, inject
    ONE fault mid-run (a device revocation when the world has >= 2
    devices, a transient IO error otherwise), and print the RecoveryLog
    table plus the restore provenance — the end-to-end proof that
    detection, mesh re-formation, and checkpoint recovery compose on
    this machine (docs/ROBUSTNESS.md "Elastic training")."""
    print("----------Elastic Supervisor----------")
    import tempfile
    try:
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu import elastic
        from mxnet_tpu.gluon import Trainer, nn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
        from mxnet_tpu.parallel import dist
        from mxnet_tpu.testing import faults

        ndev = len(dist.available_devices())
        print("world        :", ndev, "device(s) available")
        print("gates        : MXNET_ELASTIC",
              "on" if elastic.elastic_enabled() else "OFF",
              f"| max_retries {elastic.max_retries()}",
              f"| grace {elastic.preemption_grace_sec():.0f}s")

        def build():
            mx.random.seed(0)
            net = nn.HybridSequential()
            net.add(nn.Dense(16, in_units=8, activation="relu"),
                    nn.Dense(4, in_units=16))
            net.initialize()
            trainer = Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.1, "momentum": 0.9},
                              kvstore=None)
            return net, trainer, SoftmaxCrossEntropyLoss()

        def batch_fn(i):
            rng = onp.random.RandomState(100 + i)
            return (mx.nd.array(rng.randn(8, 8).astype("float32")),
                    mx.nd.array(rng.randint(0, 4, size=(8,))
                                .astype("int32")))

        if ndev >= 2:
            spec, mesh_axes = "step.dispatch:before=5:revoke:1", \
                {"dp": -1}
            print("injecting    : device revocation before step 5")
        else:
            spec, mesh_axes = "step.dispatch:before=5:error", None
            print("injecting    : transient IO error before step 5 "
                  "(single device: revocation cannot shrink)")
        log = elastic.RecoveryLog()
        with tempfile.TemporaryDirectory() as d:
            faults.configure(spec)
            try:
                sup = elastic.ElasticSupervisor(
                    build, d, mesh_axes=mesh_axes, checkpoint_every=2,
                    backoff_base=0.0, log=log)
                res = sup.run(batch_fn, 8)
            finally:
                faults.reset()
            print(f"run          : {res.final_step} steps, "
                  f"world {res.world_size}, "
                  f"{res.recoveries} recovery(ies), "
                  f"retries {res.retries}")
            mgr = sup.loop.checkpoint_manager if sup.loop else None
            prov = mgr.restore_provenance if mgr else None
            if prov:
                print(f"provenance   : restored step {prov['step']} "
                      f"from {os.path.basename(prov['resumed_from'])}"
                      + (f" ({prov['reshard']})" if prov.get("reshard")
                         else ""))
        print("-- recovery log --")
        print(log.table())
    except Exception as e:  # pragma: no cover - env-dependent
        print("elastic check failed:", repr(e))


def check_telemetry():
    """Runtime-telemetry health: run a tiny pipelined MLP TrainLoop with
    telemetry forced on and print (a) a metrics-registry snapshot of the
    headline series and (b) a 10-step timeline summary — p50/p99
    duration per step phase (docs/OBSERVABILITY.md)."""
    print("----------Runtime Telemetry----------")
    try:
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry
        from mxnet_tpu.gluon import Trainer, TrainLoop, nn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

        steps = 10
        onp.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
        net.initialize()
        x = mx.nd.array(onp.random.randn(16, 16).astype("float32"))
        y = mx.nd.array(onp.random.randint(0, 8, size=(16,))
                        .astype("int32"))
        net(x)
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore=None)
        loop = TrainLoop(net, trainer, SoftmaxCrossEntropyLoss())
        telemetry.enable(True)
        loop.step(x, y)          # compile outside the measured region
        loop.synchronize()
        telemetry.reset()
        for bx, by in loop.prefetch((x, y) for _ in range(steps)):
            loop.step(bx, by)
        loop.synchronize()

        names = telemetry.names
        print("-- registry snapshot (headline series) --")
        for name in (names.TRAIN_STEPS, names.WINDOW_RETIRES,
                     names.WINDOW_OCCUPANCY, names.PREFETCH_BATCHES,
                     names.PREFETCH_STARVATION, names.COMPILE_RETRACES,
                     names.CHECKPOINT_SAVES):
            print(f"{name:<36s}: {telemetry.value(name)}")
        hs = telemetry.registry().get(names.HOST_SYNCS).values()
        print(f"{names.HOST_SYNCS:<36s}: {hs or 0}")
        print(f"-- timeline summary (last {steps} steps) --")
        summary = telemetry.timeline().summary(last_steps=steps)
        print(f"{'phase':<12s}{'count':>6s}{'p50 ms':>10s}"
              f"{'p99 ms':>10s}{'max ms':>10s}")
        for phase, s in summary.items():
            print(f"{phase:<12s}{s['count']:>6d}{s['p50_ms']:>10.3f}"
                  f"{s['p99_ms']:>10.3f}{s['max_ms']:>10.3f}")
        wd = telemetry.watchdog()
        print("anomalies    :", len(wd.anomalies()) or "none")
        telemetry.enable(None)
    except Exception as e:  # pragma: no cover - env-dependent
        print("telemetry check failed:", repr(e))


def check_memory():
    """Device-memory health: compile a tiny MLP train step and print
    (a) the compiled program's memory report (argument/output/temp/
    generated-code/donated bytes + peak estimate), (b) the live-buffer
    census by pool with the jax.live_arrays() reconciliation (untracked
    bytes = suspected leaks), (c) per-device allocator stats with their
    source (allocator vs the documented live-array fallback on CPU),
    and (d) the MXNET_MEMORY_BUDGET headroom status
    (docs/OBSERVABILITY.md "memory")."""
    print("----------Device Memory----------")
    try:
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry
        from mxnet_tpu.gluon import Trainer, nn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

        onp.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
        net.initialize()
        x = mx.nd.array(onp.random.randn(16, 16).astype("float32"))
        y = mx.nd.array(onp.random.randint(0, 8, size=(16,))
                        .astype("int32"))
        net(x)
        trainer = Trainer(net.collect_params(), "adam",
                          {"learning_rate": 1e-3}, kvstore=None)
        step = trainer.compile_step(
            lambda a, b: SoftmaxCrossEntropyLoss()(net(a), b))
        step(x, y)
        report = step.memory_report(x, y)
        print("-- compiled step (per shape bucket) --")
        if report is None:
            print("no compiled program (eager mode)")
        else:
            for k, v in report.to_dict().items():
                print(f"{k:<22s}: {v}")
        census = telemetry.memory.census()
        rec = census.reconcile()
        print("-- live-buffer census --")
        print(f"{'pool':<12s}{'buffers':>8s}{'bytes':>14s}")
        for pool in telemetry.memory.POOLS:
            print(f"{pool:<12s}{rec['counts'][pool]:>8d}"
                  f"{rec['by_pool'][pool]:>14d}")
        u = rec["untracked"]
        print(f"{'untracked':<12s}{u['count']:>8d}{u['bytes']:>14d}"
              "   (suspected leaks / user temporaries)")
        print("-- per-device stats --")
        for dev, s in telemetry.memory.device_memory_stats().items():
            print(f"{dev}: in_use={s['bytes_in_use']} "
                  f"peak={s['peak_bytes_in_use']} "
                  f"limit={s['bytes_limit']} (source={s['source']})")
        print("-- budget --")
        status = telemetry.memory.maybe_check_budget()
        if status is None:
            print("MXNET_MEMORY_BUDGET unset (no headroom check)")
        else:
            print(f"budget={status['budget']} in_use={status['in_use']} "
                  f"over={status['over']} (source={status['source']})")
        dd = telemetry.memory.dump_dir()
        print("OOM dumps    :", dd or
              "disabled (set MXNET_MEMORY_DUMP_DIR)")
    except Exception as e:  # pragma: no cover - env-dependent
        print("memory check failed:", repr(e))


def check_numerics():
    """Training-numerics health: compile a tiny MLP train step with
    per-layer numerics instrumentation and print a 10-step norm table
    (global grad/param norm, update/weight ratio, non-finite counts),
    then a simulated-divergence demo — one overflow batch producing
    exactly one nonfinite_grad anomaly with NaN-origin forensics naming
    the offending op and an atomic post-mortem dump
    (docs/OBSERVABILITY.md "numerics")."""
    print("----------Training Numerics----------")
    try:
        import tempfile
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu import nd, telemetry
        from mxnet_tpu.gluon import Trainer, nn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

        steps = 10
        onp.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
        net.initialize()
        x = mx.nd.array(onp.random.randn(16, 16).astype("float32"))
        y = mx.nd.array(onp.random.randint(0, 8, size=(16,))
                        .astype("int32"))
        net(x)
        loss = SoftmaxCrossEntropyLoss()
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore=None)
        step = trainer.compile_step(
            lambda a, b: loss(net(nd.exp(a * 0.1)), b),
            numerics="per_layer")
        print(f"-- {steps}-step norm table (MXNET_NUMERICS=per_layer) --")
        print(f"{'step':>4s}{'grad_norm':>12s}{'param_norm':>12s}"
              f"{'upd/w ratio':>13s}{'nonfinite':>10s}")
        for i in range(1, steps + 1):
            step(x, y)
            v = step.numerics_values()
            print(f"{i:>4d}{v['grad_norm']:>12.5f}"
                  f"{v['param_norm']:>12.5f}"
                  f"{v['update_ratio']:>13.6f}"
                  f"{v['nonfinite_total']:>10d}")
        top = sorted(v["layer_grad_norm"].items(),
                     key=lambda kv: -kv[1])[:3]
        print("largest layer grad norms:",
              ", ".join(f"{k}={n:.5f}" for k, n in top))

        print("-- simulated divergence (overflow batch) --")
        dump_dir = os.environ.get("MXNET_NUMERICS_DUMP_DIR") \
            or tempfile.mkdtemp(prefix="mx_numerics_")
        os.environ.setdefault("MXNET_NUMERICS_DUMP_DIR", dump_dir)
        xbad = mx.nd.array(onp.full((16, 16), 1200.0, "float32"))
        step(xbad, y)                  # exp overflows -> inf gradients
        v = step.numerics_values()
        print("nonfinite elements:", v["nonfinite_total"])
        events = telemetry.watchdog().anomalies("nonfinite_grad")
        print("anomalies    :", len(events), "(want exactly 1)")
        if events:
            print("message      :", events[0]["message"][:200])
        n_dumps = telemetry.value(telemetry.names.NUMERICS_DUMPS)
        print("dump files   :", int(n_dumps or 0), "in", dump_dir)
    except Exception as e:  # pragma: no cover - env-dependent
        print("numerics check failed:", repr(e))


def _fusion_leg(title, step, x, y):
    """Compile one train-step leg and print its fusion census: the
    kernel table (kind, ops, FLOPs, boundary bytes, bound class), the
    headline posture, and the top stranded ops."""
    step(x, y)
    report = step.analyze(x, y)
    fr = report.fusion
    print(f"-- {title} (mode={report.mode}) --")
    if fr is None:
        print("no compiled program (eager mode) — nothing to audit")
        return
    print(fr.summary_line())
    print(fr.table(top=12))
    if fr.stranded:
        print("top stranded ops (unfused between two fusions):")
        for s in fr.stranded[:5]:
            print(f"  {s.name:<36s} {s.opcode:<12s} {s.bytes:>10d} B "
                  f"between {s.producer} -> {','.join(s.consumers[:2])}")
    else:
        print("stranded ops : none above the "
              f"{fr.stranded_floor} B floor")
    if fr.boundaries:
        print("largest boundary materializations:")
        for b in fr.boundaries[:5]:
            print(f"  {b.name:<36s} {b.opcode:<12s} {b.bytes:>10d} B -> "
                  f"{len(b.consumers)} consumer(s)")


def check_fusion():
    """Fusion-census health (docs/ANALYSIS.md "Fusion census"): audit
    XLA's fusion decisions for two canonical legs — a tiny MLP and the
    LSTM-LM architecture of examples/train_lstm_lm.py — printing each
    kernel's kind/ops/FLOPs/boundary bytes and bound class, plus any
    stranded ops the ideal-fusion diff of arXiv:2301.13062 flags."""
    print("----------Fusion Census----------")
    try:
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu.gluon import Trainer, nn, rnn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

        onp.random.seed(0)
        loss = SoftmaxCrossEntropyLoss()

        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
        net.initialize()
        x = mx.nd.array(onp.random.randn(16, 16).astype("float32"))
        y = mx.nd.array(onp.random.randint(0, 8, size=(16,))
                        .astype("int32"))
        net(x)
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore=None)
        step = trainer.compile_step(lambda a, b: loss(net(a), b))
        _fusion_leg("tiny MLP", step, x, y)

        class _LM(mx.gluon.HybridBlock):   # examples/train_lstm_lm.py
            def __init__(self, vocab, embed, hidden):
                super().__init__()
                self.emb = nn.Embedding(vocab, embed)
                self.lstm = rnn.LSTM(hidden, num_layers=1, layout="NTC")
                self.head = nn.Dense(vocab, flatten=False)

            def forward(self, tokens):
                return self.head(self.lstm(self.emb(tokens)))

        vocab = 16
        lm = _LM(vocab, 8, 16)
        lm.initialize()
        xt = mx.nd.array(onp.random.randint(0, vocab, size=(4, 8))
                         .astype("int32"))
        yt = mx.nd.array(onp.random.randint(0, vocab, size=(4, 8))
                         .astype("int32"))
        lm(xt)
        lm_tr = Trainer(lm.collect_params(), "adam",
                        {"learning_rate": 5e-3}, kvstore=None)
        lm_step = lm_tr.compile_step(lambda a, b: loss(lm(a), b))
        _fusion_leg("LSTM LM (worst-MFU leg)", lm_step, xt, yt)
    except Exception as e:  # pragma: no cover - env-dependent
        print("fusion check failed:", repr(e))


def check_sharding():
    """SPMD sharding-analysis health (docs/ANALYSIS.md "Sharding
    analysis"): compile the zero-sharded MLP on the virtual dp mesh
    and print its sharding-flow table (what layout every entry buffer
    actually got), the top implicit reshards, and the per-mesh-axis
    communication cost estimate."""
    print("----------Sharding Analysis----------")
    try:
        import numpy as onp
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu.gluon import Trainer, nn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
        from mxnet_tpu.parallel import make_mesh, shard_batch
        from mxnet_tpu.analysis import sharding as asharding

        ndev = min(4, len(jax.devices()))
        if ndev < 2:
            print(f"only {ndev} device(s) — sharding analysis needs a "
                  ">=2-device mesh (virtual CPU mesh: "
                  "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
            return
        onp.random.seed(0)
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, in_units=16, activation="relu"),
                nn.Dense(8, in_units=32))
        net.initialize()
        loss = SoftmaxCrossEntropyLoss()
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore=None)
        step = trainer.compile_step(lambda a, b: loss(net(a), b))
        x = mx.nd.array(onp.random.randn(8, 16).astype("float32"))
        y = mx.nd.array(onp.random.randint(0, 8, size=(8,))
                        .astype("int32"))
        with make_mesh({"dp": ndev}, jax.devices()[:ndev]) as mesh:
            xs, ys = shard_batch(x, mesh), shard_batch(y, mesh)
            step(xs, ys)
            report = step.analyze(xs, ys)
        audit = report.sharding
        if audit is None or audit.table is None:
            print("no sharding audit available (eager path?)")
            return
        prof = asharding.bandwidth_profile()
        print(f"mode={report.mode} dp={ndev} pack={audit.pack} "
              f"profile={prof.name} ({prof.default_gbps} GB/s)")
        print()
        print("sharding-flow table (entry buffers):")
        print(audit.table.table_str(top=16))
        print()
        if audit.reshards:
            print("top implicit reshards (not implied by the spec):")
            for r in audit.reshards[:5]:
                print(f"  {r.name:<28s} {r.kind:<18s} "
                      f"{r.payload_bytes:>9d} B payload "
                      f"{r.wire_bytes:>9d} B wire  ~{r.seconds:.2e} s  "
                      f"(from `{r.producer or '?'}`)")
        else:
            print("implicit reshards: none above the "
                  f"{audit.reshard_floor} B floor — every collective "
                  "is implied by the declared spec")
        print()
        print("per-axis communication cost (ring model):")
        if audit.cost is not None:
            print(audit.cost.table_str(top=8))
        print()
        print(f"table digest: {audit.table.digest()}  "
              f"(pins layout identity across captures)")
    except Exception as e:  # pragma: no cover - env-dependent
        print("sharding check failed:", repr(e))


def check_overlap():
    """Exposed-communication posture (docs/PERF_NOTES.md "Communication
    overlap"): compile the zero-sharded adam MLP on the virtual dp mesh
    twice — monolithic serial baseline (MXNET_ZERO_BUCKET_BYTES=0) vs
    bucketed (16 KiB) — and print each schedule's per-collective
    overlap windows. The bucketed program should show a positive
    overlap fraction (bucket k's all-gather hides behind bucket k+1's
    update) where the serial baseline measures ~0."""
    print("----------Communication Overlap----------")
    try:
        from unittest import mock
        import numpy as onp
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu.gluon import Trainer, nn
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
        from mxnet_tpu.parallel import make_mesh, shard_batch
        from mxnet_tpu.analysis.overlap import overlap_census

        ndev = min(8, len(jax.devices()))
        if ndev < 2:
            print(f"only {ndev} device(s) — overlap analysis needs a "
                  ">=2-device mesh (virtual CPU mesh: "
                  "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
            return

        def census_for(bucket_bytes):
            onp.random.seed(3)
            mx.random.seed(3)
            net = nn.HybridSequential()
            net.add(nn.Dense(64, in_units=32, activation="relu"),
                    nn.Dense(48, activation="relu"), nn.Dense(10))
            net.initialize()
            loss = SoftmaxCrossEntropyLoss()
            x = mx.nd.array(onp.random.randn(64, 32).astype("float32"))
            y = mx.nd.array(onp.random.randint(0, 10, size=(64,))
                            .astype("float32"))
            net(x)   # materialize deferred-init params off-mesh
            trainer = Trainer(net.collect_params(), "adam",
                              {"learning_rate": 0.01}, kvstore=None)
            step = trainer.compile_step(lambda a, b: loss(net(a), b))
            with mock.patch.dict(os.environ, {
                    "MXNET_ZERO_SHARD_MIN_SIZE": "1",
                    "MXNET_ZERO_BUCKET_BYTES": str(bucket_bytes)}):
                with make_mesh({"dp": ndev}, jax.devices()[:ndev]) as m:
                    xs, ys = shard_batch(x, m), shard_batch(y, m)
                    step(xs, ys)
                    info = step.lower_entry(xs, ys)
                    hlo = info["lowered"].compile().as_text()
                    return overlap_census(hlo, mesh=m)

        for label, bb in (("serial (bucket_bytes=0)", 0),
                          ("bucketed (bucket_bytes=16384)", 16384)):
            rep = census_for(bb)
            print(f"{label}: {rep.summary_line()}")
            print(rep.table_str(top=8))
            print()
    except Exception as e:  # pragma: no cover - env-dependent
        print("overlap check failed:", repr(e))


def check_kernels():
    """Pallas kernel-layer health (docs/PERF_NOTES.md "Pallas kernel
    layer"): the MXNET_PALLAS dispatch decision (path + reason) for
    every kernel the gate knows, then an interpret-vs-XLA parity probe
    on a tiny LSTM scan and LayerNorm — the kernel BODY runs (as plain
    XLA ops) and its outputs diff against the reference path."""
    print("----------Pallas Kernel Layer----------")
    try:
        import numpy as onp
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import kernels as K
        from mxnet_tpu.ops.kernels import norm as knorm
        from mxnet_tpu.ops.kernels import rnn_scan as krnn
        from mxnet_tpu.ops.rnn import scan_reference

        print(f"MXNET_PALLAS={K.pallas_mode()}  "
              f"backend={jax.default_backend()}")
        print(f"{'kernel':<18s}{'path':<11s}reason")
        for name in K.KERNELS:
            path, reason = K.dispatch(name)
            print(f"{name:<18s}{path:<11s}{reason}")

        onp.random.seed(0)
        T, N, H = 6, 8, 128
        xw = jnp.asarray(onp.random.randn(T, N, 4 * H)
                         .astype("float32") * 0.4)
        h0 = jnp.asarray(onp.random.randn(N, H).astype("float32"))
        c0 = jnp.asarray(onp.random.randn(N, H).astype("float32"))
        w = jnp.asarray((onp.random.randn(4 * H, H) * 0.3)
                        .astype("float32"))
        b = jnp.asarray((onp.random.randn(4 * H) * 0.1)
                        .astype("float32"))
        ys_r, _, _ = scan_reference(xw, h0, c0, w, b, "lstm")
        ys_k = krnn._scan_lstm("lstm", True, xw, h0, c0, w, b)[0]
        d = float(jnp.abs(ys_r - ys_k).max())
        print(f"lstm scan  interpret-vs-xla max|delta| = {d:.3e}"
              f"  ({'bit-exact' if d == 0.0 else 'nonzero'})")

        x = jnp.asarray(onp.random.randn(16, 256).astype("float32"))
        g = jnp.asarray(onp.random.randn(256).astype("float32"))
        be = jnp.asarray(onp.random.randn(256).astype("float32"))

        def ln_ref(x, g, be):       # the ops/nn.py reference recipe
            from jax import lax
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            return (x - mean) * lax.rsqrt(var + 1e-5) * g + be

        ref = jax.jit(ln_ref)(x, g, be)
        ker = jax.jit(lambda x, g, be: knorm.layer_norm(
            x, g, be, interpret=True))(x, g, be)
        d = float(jnp.abs(ref - ker).max())
        print(f"layernorm  interpret-vs-xla max|delta| = {d:.3e}"
              f"  ({'bit-exact' if d == 0.0 else 'nonzero'})")
    except Exception as e:  # pragma: no cover - env-dependent
        print("kernel check failed:", repr(e))


def check_serving():
    """Serving-engine health (docs/SERVING.md): AOT-compile a tiny
    predictor across its shape buckets, push a concurrent closed-loop
    burst through the dynamic batcher, and print the batcher stats
    table plus a p50/p99 latency probe — queue/coalescing/pipelining
    misconfiguration (zero batching, saturated queue, padding waste)
    is visible without a load rig."""
    print("----------Inference Serving----------")
    try:
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu import serving, telemetry
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.runtime import compile_cache_stats
        from mxnet_tpu.serving import loadgen

        import time
        onp.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(64, activation="relu", in_units=32),
                nn.Dense(8, in_units=64))
        net.initialize()
        x1 = mx.nd.array(onp.zeros((1, 32), "float32"))
        net(x1)
        buckets = (1, 2, 4, 8)
        pred = serving.CompiledPredictor(net, bucket_sizes=buckets)
        t0 = time.time()
        pred.warmup(x1)
        print("buckets      :", buckets,
              f"(AOT-compiled in {time.time() - t0:.2f}s, "
              f"{pred.n_traces} programs)")
        X = onp.random.randn(64, 32).astype("float32")
        requests, conc = 64, 4
        batcher = serving.DynamicBatcher(pred, max_batch=buckets[-1],
                                         timeout_ms=2.0)
        rep = loadgen.run_closed_loop(
            lambda i: batcher.submit(
                mx.nd.array(X[i % 64:i % 64 + 1])).result(60),
            conc, requests)
        fill = batcher.batch_fill
        stats = dict(batcher.stats)
        batcher.close()
        print(f"closed loop  : concurrency={conc} requests={requests}")
        print(f"throughput   : {rep['qps']} req/s")
        print(f"latency      : p50 {rep['p50_ms']} ms, "
              f"p99 {rep['p99_ms']} ms")
        print("-- batcher stats --")
        print(f"{'batches':<14s}{stats['batches']}")
        print(f"{'rows':<14s}{stats['rows']}")
        print(f"{'padded rows':<14s}{stats['padded_rows']}")
        print(f"{'batch fill':<14s}"
              f"{round(fill, 3) if fill is not None else None}")
        print(f"{'flush full':<14s}{stats['flush_full']}")
        print(f"{'flush timeout':<14s}{stats['flush_timeout']}")
        print(f"{'flush idle':<14s}{stats['flush_idle']}")
        print(f"{'errors':<14s}{stats['errors']}")
        lat = telemetry.registry().get(
            telemetry.names.SERVING_LATENCY)
        if lat is not None and lat.count():
            print(f"retire hist  : n={lat.count()} "
                  f"p50={lat.percentile(50) * 1e3:.2f} ms "
                  f"p99={lat.percentile(99) * 1e3:.2f} ms "
                  "(mx_serving_request_seconds)")
        cc = compile_cache_stats()
        print("compile cache:", cc["dir"],
              f"hits={cc['hits']} misses={cc['misses']}")

        # resilience panel: one injected device revocation under a
        # small burst, served through the ServingSupervisor — breaker
        # transitions, recovery downtime, and the outcome census show
        # whether device-loss recovery is wired (docs/SERVING.md
        # "Resilient serving")
        print("-- resilience (1 injected revocation under burst) --")
        from mxnet_tpu.testing import faults

        def build():
            mx.random.seed(11)
            net2 = nn.HybridSequential()
            net2.add(nn.Dense(64, activation="relu", in_units=32),
                     nn.Dense(8, in_units=64))
            net2.initialize()
            net2(x1)
            return serving.CompiledPredictor(net2,
                                             bucket_sizes=(1, 2, 4))

        sup = serving.ServingSupervisor(build, example=(x1,),
                                        max_batch=4, timeout_ms=2.0)
        outcomes = {"ok": 0, "rejected": 0, "deadline_missed": 0,
                    "error": 0}
        try:
            faults.configure("serving.dispatch:before=2:revoke:1")
            futs = []
            for i in range(24):
                try:
                    futs.append(sup.submit(
                        mx.nd.array(X[i % 64:i % 64 + 1])))
                except Exception as e:
                    futs.append(None)
                    outcomes[loadgen.classify_outcome(e)] += 1
            for f in futs:
                if f is None:
                    continue
                try:
                    f.result(60)
                    outcomes["ok"] += 1
                except Exception as e:
                    outcomes[loadgen.classify_outcome(e)] += 1
        finally:
            faults.reset()
            sup.close()
        print("breaker      :",
              " -> ".join(s for s, _t, _c in sup.breaker.transitions))
        print(f"recoveries   : {sup.stats['recoveries']} "
              f"(downtime {sup.stats['recovery_downtime_s']:.2f} s, "
              f"requeued {sup.stats['requeued']})")
        print("outcomes     :", outcomes)
        dl = serving.default_deadline_ms()
        print("shed policy  : MXNET_SERVING_SHED="
              f"{serving.shed_mode()} deadline="
              + (f"{dl:.0f} ms" if dl is not None else "unset"))
    except Exception as e:  # pragma: no cover - env-dependent
        print("serving check failed:", repr(e))


def check_decode():
    """Continuous-batching decode health (docs/SERVING.md "Continuous
    batching"): build the reference decoder + engine, stream a small
    mixed-length burst, and print the slot table, the page-allocator
    census, and the streamed-burst latency panel — a wedged scheduler
    (starved decode batch, leaked pages, dead slots) is visible
    without a load rig."""
    print("----------Continuous-Batching Decode----------")
    try:
        import numpy as onp
        from mxnet_tpu import serving
        from mxnet_tpu.ops import kernels as _kern
        import time

        model = serving.TinyDecoder(vocab=48, d_model=32, num_heads=2,
                                    seed=0)
        eng = serving.DecodeEngine(model, ladder=(1, 2, 4),
                                   max_context=48, page_size=8,
                                   start=False)
        t0 = time.time()
        eng.warmup()
        print(f"slot ladder  : {tuple(eng._ladder)} "
              f"(decode+prefill AOT-compiled in {time.time() - t0:.2f}s)")
        print(f"prefill chunk: {eng._chunk} tokens   "
              f"page size: {eng.kv.page_size} tokens")
        rng = onp.random.RandomState(3)
        prompts = [rng.randint(0, 48, size=int(n))
                   for n in (3, 11, 5, 2, 7, 4)]
        mns = [6, 3, 12, 4, 3, 5]
        t0 = time.time()
        streams = [eng.submit(p, max_new=m)
                   for p, m in zip(prompts, mns)]
        # mid-flight slot table: run a few iterations, then look
        for _ in range(4):
            eng.step_once()
        eng.sync()
        print("-- slot table (mid-burst) --")
        print(f"{'slot':<6}{'phase':<10}{'pos':<6}{'kv_len':<8}"
              f"{'tokens':<8}pages")
        for s in range(eng.slots):
            req = eng._occupant[s]
            if req is None:
                print(f"{s:<6}{'free':<10}")
                continue
            pages = [int(p) for p in eng._table[s] if p]
            print(f"{s:<6}{req.phase:<10}{req.pos:<6}"
                  f"{int(eng._device_len[s]):<8}{req.generated:<8}"
                  f"{pages}")
        print("-- page allocator --")
        for k, v in eng.kv.stats().items():
            print(f"{k:<15}{v}")
        eng.drain()
        recs = [s.record() for s in streams]
        wall = time.time() - t0
        from mxnet_tpu.serving import loadgen
        summ = loadgen.streaming_summary(recs, wall)
        print("-- streamed burst --")
        print(f"requests     : {len(prompts)} "
              f"({sum(r['tokens'] for r in recs)} tokens, "
              f"{eng.stats['steps']} decode steps, "
              f"{eng.stats['prefill_chunks']} prefill chunks)")
        print(f"ttft         : p50 {summ['ttft_p50_ms']} ms, "
              f"p99 {summ['ttft_p99_ms']} ms")
        print(f"tpot         : p50 {summ['tpot_p50_ms']} ms, "
              f"p99 {summ['tpot_p99_ms']} ms")
        print(f"goodput      : {summ['tokens_per_sec']} tok/s")
        print(f"kv util peak : {eng.stats['kv_util_peak']:.3f}")
        path, reason = _kern.decisions().get(
            "rnn_decode_step", ("?", "never dispatched"))
        print(f"decode kernel: {path} ({reason})")
        eng.close()

        # -- speculative decode + prefix sharing panel --
        print("-- speculative decode --")
        from mxnet_tpu.serving.decode import spec_k as _sk, \
            prefix_share as _psh
        print(f"spec_k       : {_sk()} (MXNET_DECODE_SPEC_K)   "
              f"prefix_share: {int(_psh())} "
              f"(MXNET_DECODE_PREFIX_SHARE)")
        sp = serving.DecodeEngine(model, ladder=(1, 4),
                                  max_context=64, page_size=8,
                                  start=False, spec_k=4,
                                  prefix_share=True)
        sp.warmup()
        base = rng.randint(0, 48, size=20).astype(onp.int32)
        s1 = sp.submit(base, max_new=12)
        for _ in range(5):
            sp.step_once()
            sp.sync()
        more = [sp.submit(onp.concatenate(
                    [base, onp.asarray([t, 5], onp.int32)]),
                    max_new=10)
                for t in (3, 4)]
        sp.drain()
        drafter = sp._drafter
        print(f"drafter      : {type(drafter).__name__}"
              f"{getattr(drafter, 'n', '')}")
        st = sp.stats
        rate = (st['spec_accepted'] / st['spec_drafted']
                if st['spec_drafted'] else None)
        print(f"verify steps : {st['spec_steps']} "
              f"({st['spec_drafted']} drafted, "
              f"{st['spec_accepted']} accepted, rate "
              f"{rate if rate is None else round(rate, 3)})")
        hist = st["accept_hist"]
        width = max(hist.values()) if hist else 1
        for n in sorted(hist):
            bar = "#" * max(1, int(24 * hist[n] / width))
            print(f"  accept {n:>2} | {bar} {hist[n]}")
        kvs = sp.kv.stats()
        print(f"prefix cache : {st['prefix_hits']} hits "
              f"({st['prefix_tokens']} tokens skipped), "
              f"{kvs['cow_copies']} COW copies, shared-page peak "
              f"{st['kv_shared_peak']}")
        for s in (s1, *more):
            s.result()
        sp.close()
    except Exception as e:  # pragma: no cover - env-dependent
        print("decode check failed:", repr(e))


def check_fleet():
    """Serving-fleet health (docs/SERVING.md "Serving fleet"): spin a
    small multi-replica fleet on the visible devices, push a routed
    burst through the FleetRouter, revoke one replica's device
    mid-traffic, and print the per-replica census, the failover /
    restart ledger, and the mx_fleet_* metric snapshot — a fleet that
    loses accepted requests or never restarts a dead replica is
    visible without a load rig."""
    print("----------Serving Fleet----------")
    try:
        import numpy as onp
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import serving, telemetry
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.serving import loadgen
        from mxnet_tpu.testing import faults

        import time
        n_dev = len(jax.devices())
        n = min(3, n_dev)
        print(f"devices      : {n_dev} visible, fleet size {n}"
              + ("" if n > 1 else "  (single device: failover leg "
                 "needs >=2 — set XLA_FLAGS="
                 "--xla_force_host_platform_device_count=4)"))
        print("env knobs    : "
              f"MXNET_FLEET_REPLICAS={serving.fleet_replicas()} "
              f"min={serving.fleet_min_replicas()} "
              f"max={serving.fleet_max_replicas()} "
              f"scale_up_wait={serving.fleet_scale_up_wait_s() * 1e3:.0f}ms "
              f"restart_retries={serving.fleet_restart_retries()}")

        def build():
            mx.random.seed(11)
            net = nn.HybridSequential()
            net.add(nn.Dense(64, activation="relu", in_units=32),
                    nn.Dense(8, in_units=64))
            net.initialize()
            net(mx.nd.array(onp.zeros((1, 32), "float32")))
            return serving.CompiledPredictor(net, bucket_sizes=(1, 2, 4))

        x1 = mx.nd.array(onp.zeros((1, 32), "float32"))
        t0 = time.time()
        fleet = serving.FleetController(build, example=(x1,),
                                        replicas=n, max_batch=4,
                                        timeout_ms=2.0)
        print(f"spawn        : {n} replica(s) warm in "
              f"{time.time() - t0:.2f}s "
              f"({[r.device.id for r in fleet.replicas]})")
        onp.random.seed(0)
        X = onp.random.randn(64, 32).astype("float32")
        victim = fleet.replicas[-1]
        try:
            if n > 1:
                # one targeted device revocation two dispatches into
                # the burst: the fleet must failover the victim's
                # backlog and restart it on a spare (or same) device
                faults.configure(
                    f"serving.dispatch@{victim.name}:before=2"
                    f":revoke:d{victim.device.id}")
            rep = loadgen.run_closed_loop(
                loadgen.fleet_issue(
                    fleet.router,
                    lambda i: (mx.nd.array(X[i % 64:i % 64 + 1]),),
                    timeout=60),
                concurrency=4, requests=32)
        finally:
            faults.reset()
        if n > 1:
            deadline = time.time() + 15
            while time.time() < deadline and not any(
                    e.kind in ("restart", "restart_failed")
                    for e in fleet.events):
                time.sleep(0.05)
        print(f"routed burst : 32 requests, concurrency 4 -> "
              f"{rep['qps']} req/s "
              f"(p50 {rep['p50_ms']} ms, p99 {rep['p99_ms']} ms)")
        print("outcomes     :", rep["outcomes"])
        for name, r in sorted(rep.get("replicas", {}).items()):
            print(f"  {name:<12}: {r['qps']} req/s  {r['outcomes']}")
        st = fleet.stats
        print(f"failover     : failovers={st['failovers']} "
              f"requeued={st['requeued']} restarts={st['restarts']} "
              f"failed_requeues={st['failed_requeues']}")
        kinds = [f"{e.kind}({e.replica})" for e in fleet.events
                 if e.kind not in ("spawn",)]
        if kinds:
            print("events       :", " -> ".join(kinds))
        print("-- replica table --")
        print(f"{'replica':<12}{'state':<12}{'device':<14}"
              f"{'version':<9}queued")
        for r in fleet.describe()["replicas"]:
            print(f"{r['name']:<12}{r['state']:<12}"
                  f"{str(r['device']):<14}{r['version']:<9}"
                  f"{r['queued']}")
        routed = telemetry.registry().get(telemetry.names.FLEET_ROUTED)
        if routed is not None:
            print(f"{telemetry.names.FLEET_ROUTED}:",
                  dict(sorted(routed.values().items())))
        wait = telemetry.registry().get(
            telemetry.names.FLEET_QUEUE_WAIT)
        if wait is not None and wait.count():
            print(f"{telemetry.names.FLEET_QUEUE_WAIT}   : "
                  f"n={wait.count()} "
                  f"p50={wait.percentile(50) * 1e3:.2f} ms "
                  f"p99={wait.percentile(99) * 1e3:.2f} ms")
        fleet.close()
    except Exception as e:  # pragma: no cover - env-dependent
        print("fleet check failed:", repr(e))


def check_threads():
    """Concurrency-audit panel (docs/ANALYSIS.md "Concurrency
    analysis"): the live audited-lock table, the observed lock-order
    graph with its cycle status, a planted two-lock inversion demo on
    a PRIVATE graph (so the demo never pollutes the process-global
    hierarchy), and a brief contention snapshot under a deliberately
    held lock — lock-order bugs and stalls are visible without
    attaching a debugger."""
    print("----------Concurrency Audit----------")
    try:
        import threading
        import time

        from mxnet_tpu import serving, telemetry  # noqa: F401 - wires locks
        from mxnet_tpu.analysis import threads

        print(f"env knobs    : MXNET_LOCK_STALL_SEC="
              f"{threads.stall_seconds():g} "
              f"MXNET_THREADS_DUMP_DIR={threads.dump_dir() or '<unset>'}")
        locks = threads.describe_locks()
        print(f"-- audited locks ({len(locks)} name(s)) --")
        print(f"{'name':<28}{'kind':<7}{'inst':<6}{'held':<6}"
              f"{'waiters':<9}owner")
        for l in locks:
            print(f"{l['name']:<28}{l['kind']:<7}{l['instances']:<6}"
                  f"{l['held']:<6}{l['waiters']:<9}{l['owner'] or '-'}")
        edges = threads.graph().edges()
        cycles = threads.find_cycles()
        print(f"order graph  : {len(edges)} edge(s), "
              f"{len(cycles)} cycle(s)"
              + ("  <- POTENTIAL DEADLOCK" if cycles else ""))
        for e in sorted(edges, key=lambda e: (e['from'], e['to']))[:12]:
            print(f"  {e['from']} -> {e['to']}  (x{e['count']}, "
                  f"thread {e['thread']})")
        if len(edges) > 12:
            print(f"  ... and {len(edges) - 12} more")

        # planted inversion demo on a PRIVATE graph: what a real
        # lock-cycle finding looks like, without touching the global
        # hierarchy the tier-1 baseline sweep audits
        demo = threads.LockOrderGraph()
        a = threads.mx_lock("demo.inversion.a", graph=demo)
        b = threads.mx_lock("demo.inversion.b", graph=demo)
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        findings = threads.cycle_findings(demo)
        print(f"-- planted inversion demo ({len(findings)} finding) --")
        for f in findings:
            print(" ", str(f)[:240])

        # contention snapshot: hold a probe lock, let one waiter block,
        # and show the waiter/longest-wait census the dump would rank
        probe = threads.mx_lock("demo.contention")
        seen = threading.Event()

        def waiter():
            seen.set()
            with probe:
                pass

        with probe:
            t = threading.Thread(target=waiter, name="demo-waiter",
                                 daemon=True)
            t.start()
            seen.wait(1.0)
            time.sleep(0.15)     # let the waiter enter its timed poll
            row = [l for l in threads.describe_locks()
                   if l["name"] == "demo.contention"]
            if row:
                print(f"-- contention snapshot --")
                print(f"demo.contention: held by {row[0]['owner']!r}, "
                      f"{row[0]['waiters']} waiter(s), longest wait "
                      f"{row[0]['longest_wait_s'] * 1e3:.0f} ms")
        t.join(2.0)
        wait_h = telemetry.registry().get(
            telemetry.names.THREADS_LOCK_WAIT)
        if wait_h is not None and wait_h.count():
            print(f"{telemetry.names.THREADS_LOCK_WAIT}: "
                  f"n={wait_h.count()} "
                  f"p99={wait_h.percentile(99) * 1e3:.2f} ms")
    except Exception as e:  # pragma: no cover - env-dependent
        print("threads check failed:", repr(e))


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())
    print("----------Hardware Info----------")
    print("machine      :", platform.machine())
    print("processor    :", platform.processor())
    if sys.platform.startswith("linux"):
        try:
            out = subprocess.run(["lscpu"], capture_output=True,
                                 text=True, timeout=10).stdout
            for line in out.splitlines():
                if any(k in line for k in ("Model name", "CPU(s)",
                                           "Thread", "Socket")):
                    print(line)
        except Exception:
            pass


def check_environment():
    print("----------Environment----------")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXNET_", "OMP_", "KMP_", "XLA_", "JAX_",
                         "LIBJPEG_", "TPU_")):
            print(f"{k}=\"{v}\"")


def check_network(timeout):
    # kept for reference parity; default-off because target
    # environments have no egress
    import socket
    print("----------Network Test----------")
    urls = {"MXNet github": "github.com",
            "PYPI": "pypi.python.org"}
    for name, host in urls.items():
        try:
            socket.setdefaulttimeout(timeout)
            socket.gethostbyname(host)
            print(f"DNS {name} ({host}): ok")
        except Exception as e:
            print(f"DNS {name} ({host}): FAILED ({e})")


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Diagnose the runtime environment")
    parser.add_argument("--network", action="store_true",
                        help="also run DNS connectivity checks "
                        "(off by default: egress-less environments)")
    parser.add_argument("--analysis", action="store_true",
                        help="also compile a tiny MLP train step and "
                        "print its mx.analysis ProgramReport "
                        "(collectives, donation, host transfers)")
    parser.add_argument("--engine", action="store_true",
                        help="also run a tiny pipelined TrainLoop and "
                        "print async-dispatch stats (in-flight window, "
                        "syncs per 100 steps, prefetch depth/starvation)")
    parser.add_argument("--telemetry", action="store_true",
                        help="also run a tiny pipelined TrainLoop with "
                        "telemetry on and print the metrics-registry "
                        "snapshot, a 10-step phase-timeline summary "
                        "(p50/p99)")
    parser.add_argument("--memory", action="store_true",
                        help="also compile a tiny train step and print "
                        "its memory report, the live-buffer census by "
                        "pool (+ untracked reconciliation), per-device "
                        "allocator stats, and the memory-budget status")
    parser.add_argument("--numerics", action="store_true",
                        help="also run a tiny numerics-instrumented "
                        "train step: 10-step grad/param-norm table plus "
                        "a simulated-divergence demo (one anomaly, "
                        "NaN-origin forensics, post-mortem dump)")
    parser.add_argument("--fusion", action="store_true",
                        help="also audit XLA's fusion decisions for a "
                        "tiny MLP and the LSTM-LM example: kernel "
                        "table (kind/ops/FLOPs/boundary bytes/bound "
                        "class) plus top stranded ops")
    parser.add_argument("--sharding", action="store_true",
                        help="also compile the zero-sharded MLP on the "
                        "virtual dp mesh and print its sharding-flow "
                        "table, top implicit reshards, and per-axis "
                        "communication cost estimate")
    parser.add_argument("--overlap", action="store_true",
                        help="also compile the zero-sharded adam MLP "
                        "serial vs bucketed on the virtual dp mesh and "
                        "print each schedule's per-collective overlap "
                        "windows and exposed-comm fractions")
    parser.add_argument("--kernels", action="store_true",
                        help="also print the Pallas kernel layer's "
                        "per-kernel dispatch decisions (pallas/"
                        "interpret/xla + reason) and an interpret-vs-"
                        "xla parity probe for a tiny LSTM scan and "
                        "LayerNorm")
    parser.add_argument("--serving", action="store_true",
                        help="also AOT-compile a tiny bucketed "
                        "predictor, run a concurrent burst through the "
                        "dynamic batcher, and print the batcher stats "
                        "table plus a p50/p99 latency probe")
    parser.add_argument("--decode", action="store_true",
                        help="also build the continuous-batching "
                        "decode engine, stream a mixed-length burst, "
                        "and print the slot table, page-allocator "
                        "census, and TTFT/TPOT panel")
    parser.add_argument("--fleet", action="store_true",
                        help="also spin a small multi-replica serving "
                        "fleet, route a burst (with one injected "
                        "replica-device revocation when >=2 devices "
                        "are visible), and print the per-replica "
                        "census, failover/restart ledger, and "
                        "mx_fleet_* metric snapshot")
    parser.add_argument("--threads", action="store_true",
                        help="also print the concurrency-audit panel: "
                        "live audited-lock table, observed lock-order "
                        "graph + cycle status, a planted two-lock "
                        "inversion demo (private graph), and a "
                        "contention snapshot")
    parser.add_argument("--elastic", action="store_true",
                        help="also run a tiny supervised TrainLoop, "
                        "inject one mid-run fault (device revocation / "
                        "transient error), and print the RecoveryLog "
                        "table and restore provenance")
    parser.add_argument("--timeout", type=int, default=10)
    args = parser.parse_args(argv)
    check_python()
    check_pip()
    check_mxnet()
    check_accelerator()
    if args.analysis:
        check_analysis()
    if args.engine:
        check_engine()
    if args.telemetry:
        check_telemetry()
    if args.memory:
        check_memory()
    if args.numerics:
        check_numerics()
    if args.fusion:
        check_fusion()
    if args.sharding:
        check_sharding()
    if args.overlap:
        check_overlap()
    if args.kernels:
        check_kernels()
    if args.serving:
        check_serving()
    if args.decode:
        check_decode()
    if args.fleet:
        check_fleet()
    if args.threads:
        check_threads()
    if args.elastic:
        check_elastic()
    check_os()
    check_environment()
    if args.network:
        check_network(args.timeout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
