#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/grid/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Whatever belongs
to one configuration, one traffic mix, one cell's limits or one per-layer
metric sits in a file found by the name in that entry:

    configs/<config>.json  .py    sizes; net, traffic, FLOPs, reference
    traffic/<traffic>.json        batch, sequence, pool, optimizer
    limits/<cell>.json            the limit of each number compared
    layer_metrics/<metric>.py     read(ctx) -> number or None

so a later PR adds a cell, a configuration, a mix or a metric by adding
files and appending entries; this file holds no ``if`` on any such name.

A run: weights on the device from ``--seed`` in one jitted call; the net,
``Trainer`` and ``gluon.TrainLoop`` under bf16 AMP; one feed
(``loop.prefetch`` over the cycled pool of seeded batches) that serves the
first three steps (whose losses, first gradient and parameter change are
kept as scalars), the warm-up and the measured window alike; the window of
``--seconds`` closed by a completion barrier; the peak of device memory;
then, with the program's state freed, the plain float32 reference follows
the same three steps and ``reference.compare`` decides ``correct``.
``setup_s`` runs from the moment JAX has found the chip to the window's
first step: the program's import, weights, build, compile or cache read,
followed steps and warm-up. The runtime's own start before that (9 to 13 s
on the v5e, by +-2 s from run to run, nothing a PR here can move) is
logged, not counted.

With ``--trace 1`` telemetry's spans are on for the window and the first
``trace_steps`` steps of it run under the profiler, inside a
``grid_window`` annotation closed by a barrier; ``trace_reduce`` turns that
into ``busy_s``, per-operation time and the top operations.

Standard output carries exactly one line (``lastline``). Without a TPU the
run exits non-zero and prints none; ``--rehearse`` (tests only) runs the
configuration's and the mix's ``tiny`` presets on the CPU and says
``platform: cpu``.
"""
import lastline  # noqa: E402  first: descriptor 1 now points at stderr
lastline.capture()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(f"[grid {time.perf_counter() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    name = "grid_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(manifest: dict, workload: str, rehearse: bool) -> dict:
    """Everything the cell's entry names, read from its files."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    cfg = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    limits = load_json("limits", workload + ".json")
    if rehearse:
        for preset in (cfg, traffic, limits):
            preset.update(preset["tiny"])
    readers = {m["name"]: load_module("layer_metrics", m["name"] + ".py")
               for m in manifest["per_layer"]
               if "workloads" not in m or workload in m["workloads"]}
    return {"cell": cell, "cfg": cfg, "traffic": traffic,
            "model": load_module("configs", cell["config"] + ".py"),
            "limits": limits["limits"],
            "readers": readers}


def find_devices(chips: int, rehearse: bool):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        return devices[:chips]
    if platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{platform} device(s)")
        sys.exit(EXIT_NO_CHIP)
    return devices


def open_cell(workload: str, rehearse: bool) -> tuple:
    """→ ``(manifest, the cell's files, its devices)``, the program's
    package on the path and JAX looked at for the first time."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    spec = load_cell(manifest, workload, rehearse)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    return manifest, spec, find_devices(spec["cell"]["chips"], rehearse)


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip: the allocator's peak of live arrays
    plus its peak reservation for the running program's temporaries (on
    the TPU ``peak_bytes_in_use`` leaves those out: with a 7 GB step it read
    0.8 GB, PR 24). The process's own peak where the backend keeps no count
    (the CPU of a rehearsal)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    if not peaks:
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return max(peaks)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

class Program:
    """The net, its ``TrainLoop`` and the one feed every step of the run
    goes through."""

    def __init__(self, spec: dict, seed: int):
        import mxnet_tpu as mx
        import reference
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
        from mxnet_tpu.ndarray.ndarray import NDArray
        cfg, traffic, model = spec["cfg"], spec["traffic"], spec["model"]
        self.mx, self.seed = mx, seed
        self.param_spec = model.param_spec(cfg)
        self.net = model.build_net(cfg, traffic)
        params = self.net.collect_params()
        shapes = {name: tuple(shape) for name, shape, _, _ in self.param_spec}
        if list(params) != list(shapes):
            raise RuntimeError(
                "the net's parameters are not the configuration's: "
                f"{sorted(set(params) ^ set(shapes))[:6]}")
        weights = reference.make_weights(self.param_spec, seed)
        for name, p in params.items():
            p.set_data(NDArray(weights[name]))
            if tuple(p.shape) != shapes[name]:
                raise RuntimeError(f"{name}: {p.shape} != {shapes[name]}")
        del weights
        self.params = list(params.values())
        self.names = list(params)
        opt = dict(traffic["optimizer"])
        self.optimizer = (opt.pop("name"), opt)
        trainer = mx.gluon.Trainer(params, self.optimizer[0], dict(opt),
                                   kvstore=traffic["kvstore"])
        self.loop = mx.gluon.TrainLoop(self.net, trainer,
                                       SoftmaxCrossEntropyLoss())
        if traffic["amp"] != "bfloat16":
            raise RuntimeError(f"no AMP mode {traffic['amp']!r}")
        mx.amp.init()
        self.pool = model.batches(cfg, traffic, seed)
        self.feed = iter(self.loop.prefetch(
            (mx.nd.array(x), mx.nd.array(y))
            for x, y in itertools.cycle(self.pool)))
        self.last = None

    def step(self):
        self.last = self.loop.step(*next(self.feed))
        return self.last

    def barrier(self):
        import jax
        self.loop.synchronize()
        jax.block_until_ready(self.last._data)

    def follow(self, steps: int) -> dict:
        """The first ``steps`` steps through the window's own call and
        feed. What the comparison needs is reduced to norms on the device
        before the next step donates the buffers it is read from."""
        import jax
        import jax.numpy as jnp
        import reference
        norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for x in xs])
        diff_norms = jax.jit(lambda xs, ys: [jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y))) for x, y in zip(xs, ys)])
        first = reference.first_gradient_from_state(*self.optimizer)
        losses, grad1 = [], None
        for t in range(1, steps + 1):
            losses.append(self.step())
            if t == 1:
                state = self.loop.compiled_step.optimizer_state_buffers()
                per = len(state) // len(self.params)
                if per * len(self.params) != len(state) or not per:
                    raise RuntimeError(
                        f"{len(state)} optimizer buffers for "
                        f"{len(self.params)} parameters")
                # a Trainer given a dict keeps its state in sorted-name order
                grad1 = dict(zip(sorted(self.names), norms(
                    [first(state[i * per:(i + 1) * per])
                     for i in range(len(self.params))])))
        start = reference.make_weights(self.param_spec, self.seed)
        change = diff_norms([p.data()._data for p in self.params],
                            [start[n] for n in self.names])
        del start
        return {"loss": [float(jnp.mean(l._data.astype(jnp.float32)))
                         for l in losses],
                "grad1": {k: float(v) for k, v in grad1.items()},
                "change": dict(zip(self.names, map(float, change)))}

    def state(self) -> dict:
        step = self.loop.compiled_step
        return {"mode": step.mode, "n_traces": step.n_traces}

    def hlo_text(self) -> str:
        """The optimized HLO of the step's program, for the scope of each
        instruction the trace names (a traced run only, after the window:
        one more lowering and a read of the compile cache)."""
        info = self.loop.compiled_step.lower_entry(*next(self.feed))
        return info["lowered"].compile().as_text()

    def free(self):
        """Stop the feed and drop every device buffer of the program."""
        self.feed.close()
        self.mx.amp.uninit()
        self.net = self.loop = self.params = self.feed = self.last = None
        gc.collect()


def compiles() -> int:
    from mxnet_tpu import runtime
    s = runtime.compile_cache_stats()
    return s["hits"] + s["misses"]


def p95(values: list) -> float:
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def run_window(prog: Program, seconds: float, traffic: dict, tracer=None):
    """Steps until ``seconds`` have passed, then the completion barrier.
    The time of a step is read over groups of ``group_steps`` steps, long
    enough for the host's clock. ``tracer`` (a traced run) wraps the first
    ``trace_steps`` steps."""
    group = traffic["group_steps"]
    prog.barrier()
    n, per_step = 0, []
    t0 = mark = time.perf_counter()
    if tracer is not None:
        n = tracer(prog, traffic["trace_steps"])
        mark = time.perf_counter()
    while True:
        prog.step()
        n += 1
        now = time.perf_counter()
        if n % group == 0:
            per_step.append((now - mark) / group)
            mark = now
        if now - t0 >= seconds and per_step:
            break
    prog.barrier()
    t1 = time.perf_counter()
    return {"steps": n, "seconds": t1 - t0, "t0": t0,
            "per_step_s": per_step}


class Tracer:
    """The profiler around ``trace_steps`` whole steps, the span closed by
    a completion barrier and marked on the trace's own clock."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.seconds = None
        self.steps = 0

    def __call__(self, prog: Program, steps: int) -> int:
        import jax
        from trace_reduce import WINDOW_ANNOTATION
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                for _ in range(steps):
                    prog.step()
                prog.barrier()
                self.seconds = time.perf_counter() - t0
        finally:
            jax.profiler.stop_trace()
        self.steps = steps
        return steps


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: the tiny presets on the CPU")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files and write a dump of "
                         "what the trace holds under chiprun_out/")
    args = ap.parse_args(argv)

    manifest, spec, devices = open_cell(args.workload, args.rehearse)
    t_found = time.perf_counter()
    cfg, traffic, model = spec["cfg"], spec["traffic"], spec["model"]
    kind = "rehearsal" if args.rehearse else devices[0].device_kind
    peaks = load_json("peaks.json").get(kind)
    if peaks is None:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    import mxnet_tpu  # noqa: F401  arms the compile cache at its fixed path
    from mxnet_tpu import runtime, telemetry
    import reference
    log(f"{args.workload} seed={args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}, found after {t_found - T_START:.2f}s "
        f"(the runtime's own start: not in setup_s); compile cache "
        f"{runtime.compile_cache_stats()['dir']}")

    # -- set-up: weights, program, first steps, warm-up ---------------------
    telemetry.enable(False)
    prog = Program(spec, args.seed)
    log("weights, net, trainer and feed built")
    got = prog.follow(traffic["followed_steps"])
    log(f"first {traffic['followed_steps']} steps followed")
    for _ in range(traffic["warmup_steps"]):
        prog.step()
    prog.barrier()
    from mxnet_tpu.ops import kernels
    log(f"warm; step {prog.state()}; kernels {kernels.decisions()}; "
        f"cache {runtime.compile_cache_stats()}")

    tracer = None
    if args.trace:
        tracer = Tracer(os.path.join(ROOT, ".grid_trace", args.workload))
        telemetry.enable(True)
        telemetry.timeline().clear()
    compiled_before = compiles()
    setup_s = time.perf_counter() - t_found

    # -- the measured window -------------------------------------------------
    window = run_window(prog, args.seconds, traffic, tracer)
    compiled_in_window = compiles() - compiled_before
    spans = [e for e in telemetry.timeline().events()
             if e["t0"] >= window["t0"]] if args.trace else []
    telemetry.enable(False)
    state = prog.state()
    peak_bytes = memory_peak_bytes(devices)
    hlo_text = None
    if args.trace:
        t_hlo = time.perf_counter()
        hlo_text = prog.hlo_text()
        log(f"HLO text of the step in {time.perf_counter() - t_hlo:.1f}s, "
            f"{compiles() - compiled_before - compiled_in_window} request(s) "
            f"to the compile cache: {runtime.compile_cache_stats()}")
    tokens = model.tokens_per_step(cfg, traffic)
    log(f"memory_stats {devices[0].memory_stats()}")
    log(f"window: {window['steps']} steps in {window['seconds']:.3f}s, "
        f"{compiled_in_window} compile(s), peak {peak_bytes / 2**30:.2f} GiB")
    groups = window["per_step_s"]
    slowest = sorted(range(len(groups)), key=groups.__getitem__)[-3:]
    log(f"step time over {len(groups)} groups of {traffic['group_steps']}: "
        f"median {1e3 * statistics.median(groups):.3f} ms; slowest "
        + ", ".join(f"#{i} {1e3 * groups[i]:.3f} ms" for i in slowest[::-1]))

    # -- the reference, with the program's state freed -----------------------
    prog.free()
    del prog
    t_ref = time.perf_counter()
    ref = reference.follow(
        model.loss_sum(cfg, reference.make_dot("f32")),
        reference.make_weights(model.param_spec(cfg), args.seed),
        model.batches(cfg, traffic, args.seed),
        (traffic["optimizer"]["name"], traffic["optimizer"]),
        steps=traffic["followed_steps"],
        block_rows=traffic["reference_block_rows"])
    correct, compared = reference.compare(got, ref, spec["limits"])
    compared["unfused"] = {"value": int(state["mode"] != "fused"),
                           "limit": 0}
    compared["retraces"] = {"value": state["n_traces"] - 1, "limit": 0}
    correct = correct and all(c["value"] <= c["limit"]
                              for c in compared.values())
    log(f"reference followed {traffic['followed_steps']} steps in "
        f"{time.perf_counter() - t_ref:.1f}s")

    # -- metrics -------------------------------------------------------------
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    breakdown = None
    if args.trace:
        import trace_reduce
        reduced = trace_reduce.reduce_trace(tracer.dir, tracer.seconds,
                                            hlo_text, rehearse=args.rehearse)
        log(f"trace: module {reduced['hlo_module']!r}, "
            f"{100 * reduced['scoped_share']:.1f}% of device time under a "
            f"scope, window from {reduced['window_from']}")
        if args.keep_trace:
            dump_trace(reduced, args.workload, tracer.dir)
        else:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        ctx = {"cell": spec["cell"], "cfg": cfg, "traffic": traffic,
               "model": model, "peaks": peaks, "chips": len(devices),
               "trace": reduced, "spans": spans,
               "counters": {"compile_requests": compiled_in_window},
               "window": {"steps": window["steps"],
                          "seconds": window["seconds"],
                          "tokens": window["steps"] * tokens},
               "traced": {"steps": tracer.steps, "seconds": tracer.seconds,
                          "tokens": tracer.steps * tokens}}
        values = {}
        for name, reader in spec["readers"].items():
            value = reader.read(ctx)
            if value is not None:
                values[name] = value
    else:
        values = {
            "train_tokens_per_s": window["steps"] * tokens
            / window["seconds"],
            "step_ms_p95": 1e3 * p95(window["per_step_s"]),
            "setup_s": setup_s,
        }
    result = lastline.build(
        manifest, args.workload, bool(args.trace), correct=correct,
        attempted=window["steps"], failed=0, values=values, device=device,
        compared=compared, breakdown=breakdown)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:g})"
              + (f" at {c['leaf']}" if "leaf" in c else ""),
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    lastline.emit(result)
    return 0


def dump_trace(reduced: dict, workload: str, trace_dir: str) -> None:
    """What the trace holds, for a reader's eyes: planes, lines, the
    longest events, the scope paths seen and every stat of a few events of
    each device line."""
    import trace_reduce
    from jax.profiler import ProfileData
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    samples = {}
    data = ProfileData.from_file(trace_reduce.newest_xplane(trace_dir))
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            evs = sorted(line.events, key=lambda e: -e.duration_ns)[:6]
            samples[f"{plane.name} / {line.name}"] = [
                {"name": e.name[:200], "name_len": len(e.name),
                 "name_tail": e.name[-600:], "ms": e.duration_ns * 1e-6,
                 "stats": {k: str(v)[:400] for k, v in e.stats}}
                for e in evs]
    leaf = sorted(reduced["leaf"], key=lambda p: -p[1])
    scopes = {}
    for ev, t in reduced["leaf"]:
        scopes[ev.scope] = scopes.get(ev.scope, 0.0) + t
    with open(os.path.join(out, f"trace_{workload}.json"), "w") as f:
        json.dump({
            "planes": reduced["planes"], "samples": samples,
            "window_from": reduced["window_from"],
            "hlo_module": reduced["hlo_module"],
            "scoped_share": reduced["scoped_share"],
            "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
            "sum_of_durations_s": sum(e.end - e.start for e, _ in leaf),
            "longest_events": [[e.name, e.end - e.start, t, e.scope]
                               for e, t in leaf[:40]],
            "by_name": sorted(reduced["by_name"].items(),
                              key=lambda kv: -kv[1])[:60],
            "by_scope": sorted(scopes.items(), key=lambda kv: -kv[1])[:80],
            "idle_gaps": reduced["idle_gaps"],
        }, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
