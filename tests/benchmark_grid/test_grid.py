"""Tests of the benchmark under ``benchmark/grid``. Written over whatever
cells ``BENCHMARK.json`` holds, so that they hold for a later cell too.

Every run of the harness here is a rehearsal: a subprocess with
``JAX_PLATFORMS=cpu``, ``--rehearse`` (the ``tiny`` presets) and
``--seconds 1``, its standard output parsed the way the driver parses it.
No test describes a TPU topology or loads libtpu.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GRID = os.path.join(ROOT, "benchmark", "grid")
RUN = os.path.join(GRID, "run.py")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def grid_module(name: str):
    """A module of benchmark/grid, loaded by path (``run.py`` is never
    imported here: importing it redirects descriptor 1)."""
    path = os.path.join(GRID, name)
    spec = importlib.util.spec_from_file_location(
        "grid_test_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def child_env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    env.pop("XLA_FLAGS", None)      # one CPU device, as one chip
    return env


def rehearse(cell: str, trace: int, prelude: str = "", seed: int = 2147483659,
             script: str = RUN, extra=()):
    """Run a script of the harness under ``--rehearse`` in a child that
    is noisy on purpose: once the script has parsed its arguments the
    child prints to stdout and stderr from Python and writes to
    descriptor 1, and an ``atexit`` hook writes there again."""
    argv = [script, "--workload", cell, "--rehearse", *extra]
    if script == RUN:
        argv += ["--seed", str(seed), "--seconds", "1", "--trace",
                 str(trace)]
    code = f"""
import argparse, atexit, os, runpy, sys
sys.path.insert(0, {GRID!r})
_parse = argparse.ArgumentParser.parse_args
def noisy(self, *a, **k):
    print("noise: print to stdout")
    print("noise: print to stderr", file=sys.stderr)
    sys.__stdout__.write("noise: sys.__stdout__\\n"); sys.__stdout__.flush()
    os.write(1, b"noise: os.write(1)\\n")
    return _parse(self, *a, **k)
argparse.ArgumentParser.parse_args = noisy
atexit.register(lambda: os.write(1, b"noise: atexit\\n"))
{prelude}
sys.argv = {argv!r}
runpy.run_path({script!r}, run_name="__main__")
"""
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=child_env(), capture_output=True, text=True,
                          timeout=600)


def one_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.endswith("\n") and proc.stdout.count("\n") == 1, \
        f"stdout is not exactly one line: {proc.stdout[:400]!r}"
    assert "noise: os.write(1)" in proc.stderr
    assert "noise: atexit" in proc.stderr
    return json.loads(proc.stdout)


# ---------------------------------------------------------------------------
# (a) the trace reduction
# ---------------------------------------------------------------------------

def test_busy_is_the_union_clipped_to_the_window():
    tr = grid_module("trace_reduce.py")
    ev = tr.Event
    line = [
        ev(-1.0, 0.5, "before", ""),          # half outside: 0.5 inside
        ev(1.0, 4.0, "while", ""),            # encloses its body
        ev(1.0, 2.0, "body.a", ""),
        ev(2.5, 3.5, "body.b", ""),
        ev(3.8, 4.5, "overlap", ""),          # overlaps the loop's tail
        ev(6.0, 7.0, "leaf", ""),
        ev(9.5, 12.0, "after", ""),           # half outside: 0.5 inside
        ev(20.0, 21.0, "outside", ""),
    ]
    out = tr.reduce_lines([line], (0.0, 10.0))
    # [0,.5] + [1,4.5] + [6,7] + [9.5,10] by hand
    assert out["busy_s"] == pytest.approx(0.5 + 3.5 + 1.0 + 0.5)
    assert out["busy_s"] <= out["window_s"] == 10.0
    durations = sum(min(e.end, 10.0) - max(e.start, 0.0) for e in line
                    if e.end > 0.0 and e.start < 10.0)
    assert durations > out["busy_s"]          # what a sum would have read
    # a loop of a whole window: the sum is twice the window, the union not
    loop = [ev(0.0, 10.0, "while", "")] + [ev(i, i + 1.0, "body", "")
                                            for i in range(10)]
    assert tr.reduce_lines([loop], (0.0, 10.0))["busy_s"] == 10.0


def test_per_name_time_is_self_time():
    tr = grid_module("trace_reduce.py")
    ev = tr.Event
    line = [ev(1.0, 4.0, "while", "jit(step)/rnn_lstm/while"),
            ev(1.0, 2.0, "body", "jit(step)/rnn_lstm/while/body/dot"),
            ev(2.5, 3.5, "body", "jit(step)/transpose(jvp(rnn_lstm))/dot"),
            ev(5.0, 6.0, "other", "jit(step)/flash_attention_vl/x")]
    out = tr.reduce_lines([line], (0.0, 10.0))
    assert out["by_name"]["while"] == pytest.approx(1.0)   # 3 less 2
    assert out["by_name"]["body"] == pytest.approx(2.0)
    assert sum(out["by_name"].values()) == pytest.approx(out["busy_s"])
    assert tr.scope_seconds(out, "rnn_lstm") == pytest.approx(3.0)
    assert tr.scope_seconds(out, "flash_attention") is None
    assert tr.scope_seconds(out, "flash_attention_vl") == pytest.approx(1.0)
    two = tr.reduce_lines([line, line], (0.0, 10.0))       # two chips
    assert two["busy_s"] == pytest.approx(out["busy_s"])   # averaged
    assert two["by_name"]["body"] == pytest.approx(4.0)    # summed


HLO_TEXT = """HloModule jit_fused, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %t = f32[8,8]{1,0} tanh(%p0), metadata={op_name="jit(fused)/jvp(rnn_lstm)/while/body/tanh"}
  ROOT %m = f32[8,8]{1,0} multiply(%t, %t), metadata={op_name="jit(fused)/jvp(rnn_lstm)/while/body/mul" stack_frame_id=3}
}

%body (c: f32[8,8]) -> f32[8,8] {
  %c = f32[8,8]{1,0} parameter(0)
  ROOT %fusion.1 = f32[8,8]{1,0} fusion(%c), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %copy.2 = f32[8,8]{0,1} copy(%x)
  %attn.3 = f32[8,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused)/transpose(jvp(flash_attention))/pallas_call"}
  ROOT %while.7 = f32[8,8]{1,0} while(%attn.3), condition=%cond, body=%body, metadata={op_name="jit(fused)/jvp(rnn_lstm)/while"}
}
"""


def test_scopes_come_from_the_programs_hlo_text():
    tr = grid_module("trace_reduce.py")
    hlo = tr.parse_hlo(HLO_TEXT)
    assert hlo.module == "jit_fused"
    assert hlo.scopes["while.7"] == "jit(fused)/jvp(rnn_lstm)/while"
    # a fusion with no metadata of its own takes its root's
    assert hlo.scopes["fusion.1"].endswith("/while/body/mul")
    assert "copy.2" in hlo.names and "copy.2" not in hlo.scopes
    ev = tr.Event
    line = [ev(0.0, 1.0, "attn.3 custom-call f32[8,8]", "", "jit_fused"),
            ev(1.0, 4.0, "while.7 while f32[8,8]", "", "jit_fused"),
            ev(1.5, 2.5, "fusion.1 fusion f32[8,8]", "", "jit_fused"),
            ev(3.0, 3.5, "copy.2 copy f32[8,8]", "", "jit_fused"),
            # another program's instruction of the same name: no scope
            ev(5.0, 6.0, "fusion.1 fusion u32[2]", "", "jit_split")]
    out = tr.reduce_lines([[tr._scoped(e, hlo) for e in line]], (0.0, 10.0))
    assert tr.scope_seconds(out, "flash_attention") == pytest.approx(1.0)
    # the loop's self time, its body, and the unnamed copy inside the loop
    assert tr.scope_seconds(out, "rnn_lstm") == pytest.approx(3.0)
    assert sum(t for e, t in out["leaf"] if not e.scope) \
        == pytest.approx(1.0)


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    tr = grid_module("trace_reduce.py")
    ev = tr.Event
    line = [ev(1.0, 2.0, "a"), ev(5.0, 6.0, "b"), ev(6.5, 9.0, "c")]
    host = [ev(0.0, 10.0, "step"), ev(2.0, 4.9, "prefetch wait"),
            ev(3.0, 3.1, "elsewhere")]
    gaps = tr.idle_gaps(line, (0.0, 10.0), host)
    assert gaps[0] == ["host: prefetch wait", pytest.approx(3.0)]
    # [0, 1], [6, 6.5] and [9, 10], summed under one name
    assert gaps[1] == ["host: step", pytest.approx(2.5)]
    assert tr.idle_gaps(line, (0.0, 10.0))[0] == ["before b",
                                                  pytest.approx(3.0)]


def test_roofline_reader_is_silent_without_its_scope():
    tr = grid_module("trace_reduce.py")
    sys.modules.setdefault("trace_reduce", tr)   # the reader imports it
    reader = grid_module("layer_metrics/flash_attention_roofline.py")

    class Model:
        @staticmethod
        def kernel_costs(cfg, traffic):
            return {"flash_attention": {"flops": 2e12, "bytes": 1e9}}

    def ctx(scope):
        line = [tr.Event(0.0, 0.5, "k", scope)]
        return {"model": Model, "cfg": {}, "traffic": {}, "chips": 1,
                "peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
                "traced": {"steps": 10},
                "trace": tr.reduce_lines([line], (0.0, 1.0))}
    # 10 steps x max(2e12/1e14, 1e9/1e12) = 0.2 s of 0.5 s measured
    assert reader.read(ctx("jit(s)/flash_attention/pallas_call")) \
        == pytest.approx(40.0)
    assert reader.read(ctx("jit(s)/dot_general")) is None   # never 0


# ---------------------------------------------------------------------------
# (b) the one line, as the driver reads it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_exactly_one_line(cell, trace):
    line = one_line(rehearse(cell, trace))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    group = MANIFEST["per_layer" if trace else "end_to_end"]
    units = {m["name"]: m["unit"] for m in group
             if "workloads" not in m or cell in m["workloads"]}
    got = {k: v["unit"] for k, v in line["metrics"].items()}
    if trace:
        # a reader with nothing to read stays silent: the CPU's trace
        # carries no scope, so a roofline may be absent; nothing else may
        assert set(got) <= set(units)
        assert {n for n in units if not n.endswith("_roofline")} <= set(got)
        dev = line["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    else:
        assert set(got) == set(units)
        assert "busy_s" not in line["device"]
    assert all(got[n] == units[n] for n in got)
    for m in line["metrics"].values():
        assert isinstance(m["value"], (int, float))
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert dev["memory_peak_bytes"] > 0
    for pair in line["compared"].values():
        assert pair["value"] <= pair["limit"]


def test_without_a_chip_and_without_rehearse_nothing_is_printed():
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, env=child_env(),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode not in (0, None)
    assert proc.stdout == ""


# ---------------------------------------------------------------------------
# (c) the emitter refuses what the contract refuses
# ---------------------------------------------------------------------------

def _good(trace: bool) -> dict:
    cell = CELLS[0]
    lastline = grid_module("lastline.py")
    values = {n: 1.5 for n in lastline.cell_metrics(MANIFEST, cell, trace)}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 8 << 30}
    if trace:
        device.update(busy_s=0.9, window_s=1.0)
    return dict(manifest=MANIFEST, workload=cell, trace=trace, correct=True,
                attempted=10, failed=0, values=values, device=device,
                compared={"loss1": {"value": 0.0, "limit": 1.0}})


def _break(kw, what):
    if what == "metric_missing":
        kw["values"].pop("setup_s")
    elif what == "metric_nan":
        kw["values"]["setup_s"] = float("nan")
    elif what == "busy_zero":
        kw["device"]["busy_s"] = 0.0
    elif what == "busy_above_window":
        kw["device"]["busy_s"] = 1.0001
    elif what == "busy_missing":
        del kw["device"]["busy_s"]
    elif what == "no_memory_peak":
        del kw["device"]["memory_peak_bytes"]
    elif what == "nothing_attempted":
        kw["attempted"] = 0
    return kw


@pytest.mark.parametrize("what,trace", [
    ("metric_missing", False), ("metric_nan", False),
    ("no_memory_peak", False), ("nothing_attempted", False),
    ("busy_zero", True), ("busy_above_window", True),
    ("busy_missing", True), ("no_memory_peak", True)])
def test_emitter_refuses(what, trace):
    lastline = grid_module("lastline.py")
    kw = _good(trace)
    m, w, t = kw.pop("manifest"), kw.pop("workload"), kw.pop("trace")
    ok = lastline.build(m, w, t, **kw)
    assert list(ok)[-1] == "compared" and ok["metrics"]
    kw = _break(_good(trace), what)
    m, w, t = kw.pop("manifest"), kw.pop("workload"), kw.pop("trace")
    with pytest.raises(lastline.Refused):
        lastline.build(m, w, t, **kw)


def test_a_refused_result_exits_nonzero_with_empty_stdout():
    code = f"""
import json, sys
sys.path.insert(0, {GRID!r})
import lastline
lastline.capture()
print("this goes to stderr")
manifest = json.load(open({os.path.join(ROOT, "BENCHMARK.json")!r}))
result = lastline.build(manifest, {CELLS[0]!r}, False, correct=True,
    attempted=5, failed=0, values={{}}, compared={{}},
    device={{"platform": "tpu", "kind": "k", "count": 1,
            "memory_peak_bytes": 1}})
lastline.emit(result)
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "Refused" in proc.stderr and "this goes to stderr" in proc.stderr


# ---------------------------------------------------------------------------
# (d) the manifest, and the files it names
# ---------------------------------------------------------------------------

def test_manifest_names_units_and_files():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({e["name"] for e in metrics}) == len(metrics)
    assert all(UNIT.match(e["unit"]) for e in metrics)
    assert all(e["better"] in ("lower", "higher") for e in metrics)
    assert all(e["source"] in ("host_clock", "device_trace")
               for e in m["end_to_end"])
    assert all(0.01 <= e["bound"] <= 0.1 for e in m["end_to_end"])
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}
    assert any("mfu" in e["name"] for e in m["per_layer"])
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {e["name"] for e in m["end_to_end"]}
    for c in configs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert os.path.isfile(os.path.join(ROOT, c["file"][:-5] + ".py"))
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
        assert all(NAME.match(k) and not re.search(r"(_dim|_rank|_size)$", k)
                   for k in c["reduced"])
    for w in m["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
        for part in (("traffic", w["traffic"] + ".json"),
                     ("limits", w["name"] + ".json")):
            assert os.path.isfile(os.path.join(GRID, *part)), part
    for e in m["per_layer"]:
        assert e["moves"] in e2e
        assert os.path.isfile(os.path.join(GRID, "layer_metrics",
                                           e["name"] + ".py"))
        for w in e.get("workloads", []):
            assert w in CELLS
    for p in m["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert all(not a.startswith("/") and ".." not in a for a in m["command"])


def test_run_py_branches_on_no_name():
    """run.py finds files by the names in the manifest; none of those
    names may appear in its code."""
    with open(RUN) as f:
        code = f.read()
    names = [e["name"] for g in ("configs", "workloads", "per_layer")
             for e in MANIFEST[g]] + [w["traffic"]
                                      for w in MANIFEST["workloads"]]
    assert [n for n in names if n in code] == []


# ---------------------------------------------------------------------------
# (e) operation counts, written out by hand
# ---------------------------------------------------------------------------

def test_flops_per_token_by_hand():
    def load(name):
        with open(os.path.join(GRID, "configs", name + ".json")) as f:
            return json.load(f), grid_module(f"configs/{name}.py")
    cfg, bert = load("bert-base")
    # a layer, forward, per token: q k v o 4 x 2 x 768^2 = 4,718,592;
    # ffn 2 x 2 x 768 x 3072 = 9,437,184; QK^T and PV 2 x 2 x 512 x 768 =
    # 1,572,864: 15,728,640. Twelve layers 188,743,680; the pooler and the
    # 2-class head once a sequence, (2 x 768^2 + 2 x 768 x 2) / 512 = 2,310.
    # Backward twice the forward: x 3.
    assert bert.flops_per_token(cfg, {"seq": 512}) == \
        3 * (12 * 15_728_640 + 2_310)
    # attention alone, a step of 32 x 512: 12 layers x 3 x 4 x 32 x 512^2
    # x 768 FLOPs; q k v o, then q k v o do dq dk dv: 12 tensors of
    # 32 x 512 x 768 bf16 a layer
    costs = bert.kernel_costs(cfg, {"batch": 32, "seq": 512})
    assert costs["flash_attention"]["flops"] == 12 * 3 * 4 * 32 * 512**2 * 768
    assert costs["flash_attention"]["bytes"] == 12 * 12 * 32 * 512 * 768 * 2
    cfg, lstm = load("lstm-lm-650")
    # two layers of i2h + h2h: 2 x 2 x (650 + 650) x 2600 = 13,520,000;
    # the head 2 x 650 x 33278 = 43,261,400; x 3
    assert lstm.flops_per_token(cfg, {"seq": 35}) == \
        3 * (13_520_000 + 43_261_400)
    costs = lstm.kernel_costs(cfg, {"batch": 1024, "seq": 35})
    assert costs["rnn_lstm"]["flops"] == 3 * 13_520_000 * 1024 * 35


# ---------------------------------------------------------------------------
# the comparison: its control and the program's faults must fail it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_comparison(cell, tmp_path):
    """calibrate.py at the tiny size: the reference put in the program's
    place in the precision the configuration states (bf16 products) stays
    inside the limits on every seed; in the precision below (fp8, the
    control) it does not. The program itself is on the CPU here, where
    bf16 sums round as they do not on the chip, some of them worse than
    fp8 products do: it is held to the ``tiny`` limits (the rehearsals
    above, and the planted faults below), and the control to
    ``reference_limits``, set between the two precisions at this size."""
    out = tmp_path / "calibrate.json"
    proc = rehearse(cell, 0, script=os.path.join(GRID, "calibrate.py"),
                    extra=["--seeds", "1,2147483660", "--controls", "2",
                           "--faults", "0", "--out", str(out)])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout == ""
    with open(os.path.join(GRID, "limits", cell + ".json")) as f:
        tiny = json.load(f)["tiny"]
    with open(out) as f:
        rows = json.load(f)["rows"]
    assert len(rows) == 2

    def over(row, kind, lim):
        return [n for n, top in lim.items() if row[kind][n][0] > top]
    for row in rows:
        assert over(row, "program", tiny["limits"]) == [], row
        assert over(row, "plain_bf16", tiny["reference_limits"]) == [], row
        assert over(row, "control_fp8", tiny["reference_limits"]), row


FAULTS = {
    # the step returns its state unchanged: parameters and optimizer state
    # are put back after every step
    "state_unchanged": """
import mxnet_tpu
from mxnet_tpu.gluon import fused_step
_step = fused_step.TrainLoop.step
def step(self, *batch, **kw):
    params = [p.data() for p in self._trainer._params]
    before = [p._data.copy() for p in params]
    loss = _step(self, *batch, **kw)
    self.synchronize()
    for p, b in zip(params, before):
        p._data = b
    for s in self.compiled_step._state_ndarrays():
        s._data = s._data * 0
    return loss
fused_step.TrainLoop.step = step
mxnet_tpu.gluon.TrainLoop.step = step
""",
    # half of the batch left out, the mean taken over the rest: the second
    # half of the rows is the first half again
    "half_batch": """
import mxnet_tpu
from mxnet_tpu.gluon import fused_step
_step = fused_step.TrainLoop.step
def step(self, *batch, **kw):
    import jax.numpy as jnp
    for b in batch:
        half = b.shape[0] // 2
        b._data = jnp.concatenate([b._data[:half], b._data[:half]])
    return _step(self, *batch, **kw)
fused_step.TrainLoop.step = step
mxnet_tpu.gluon.TrainLoop.step = step
""",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    """The rest of a run, the look for a chip skipped, with the program's
    step broken underneath: ``correct`` comes out false, and the line is
    still exactly one."""
    prelude = f"sys.path.insert(0, {ROOT!r})\n" + FAULTS[fault]
    line = one_line(rehearse(cell, 0, prelude=prelude))
    assert line["correct"] is False, line["compared"]
    assert any(p["value"] > p["limit"] for p in line["compared"].values())
