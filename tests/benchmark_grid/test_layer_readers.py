"""The per-layer readers that read the program's own streams (ISSUE 26):
the phase scopes of the step's program, the timeline's ``h2d_wait`` span
and ``runtime.compile_log()``. Each on a hand-built ``ctx``, then the
rehearsed traced line of every cell with the manifest as it stands.
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

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NEW = ["fwd_ms.train", "bwd_ms.train", "update_ms.train",
       "unphased_device_share", "input_wait_ms.train",
       "setup_trace_lower_s", "setup_compile_s", "setup_programs"]


@pytest.fixture
def grid(monkeypatch):
    """``load(name)`` for a module of benchmark/grid by path, with the
    directory importable the way ``run.py``'s own start makes it (the
    readers ``import trace_reduce`` and ``from layer_metrics import``)."""
    monkeypatch.syspath_prepend(GRID)
    for name in [m for m in sys.modules
                 if m == "trace_reduce" or m.startswith("layer_metrics")]:
        monkeypatch.delitem(sys.modules, name)

    def load(name):
        spec = importlib.util.spec_from_file_location(
            "grid_readers_" + re.sub(r"\W", "_", name),
            os.path.join(GRID, name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    return load


def read(grid, metric, ctx):
    return grid(f"layer_metrics/{metric}.py").read(ctx)


# ---------------------------------------------------------------------------
# the phase split of the device's self time
# ---------------------------------------------------------------------------

FWD = "jit(fused)/loss_and_grad/jvp(log_softmax)/fully_connected/dot_general"
BWD = "jit(fused)/loss_and_grad/transpose(jvp(log_softmax))/" \
      "fully_connected/dot_general"
RELU_BWD = "jit(fused)/loss_and_grad/transpose(loss_and_grad)/" \
           "jvp(activation_relu)/select_n"
UPDATE = "jit(fused)/optimizer_update/mul"


def trace_ctx(grid, line, steps=10, chips=1, lines=None):
    tr = grid("trace_reduce.py")
    evs = [[tr.Event(*e) for e in ln] for ln in (lines or [line])]
    return {"trace": tr.reduce_lines(evs, (0.0, 100.0)), "chips": chips,
            "traced": {"steps": steps}, "spans": []}


def test_forward_and_backward_split_by_the_transpose_part(grid):
    ctx = trace_ctx(grid, [
        (0.0, 2.0, "dot.1", FWD),
        (2.0, 5.0, "dot.2", BWD),
        (5.0, 5.5, "select.3", RELU_BWD),
        (6.0, 7.0, "fusion.4", UPDATE),
        (8.0, 8.25, "threefry.5", ""),          # a side program: no scope
        (9.0, 9.25, "copy.6", "jit(fused)/copy"),   # scoped, no phase
    ])
    assert read(grid, "fwd_ms.train", ctx) == pytest.approx(200.0)
    assert read(grid, "bwd_ms.train", ctx) == pytest.approx(350.0)
    assert read(grid, "update_ms.train", ctx) == pytest.approx(100.0)
    assert read(grid, "unphased_device_share", ctx) == \
        pytest.approx(100.0 * 0.5 / 7.0)


def test_a_fusion_counts_under_its_roots_scope(grid):
    """The SGD update fused into a dW matmul carries the matmul's
    ``op_name``: ``parse_hlo`` gives a fusion its root's scope, and the
    split then reads the whole fusion as backward."""
    tr = grid("trace_reduce.py")
    hlo = tr.parse_hlo(f"""HloModule jit_fused, is_scheduled=true

%fused_computation.9 (p0: f32[8,8]) -> f32[8,8] {{
  %p0 = f32[8,8]{{1,0}} parameter(0)
  %u = f32[8,8]{{1,0}} multiply(%p0, %p0), metadata={{op_name="{UPDATE}"}}
  ROOT %d = f32[8,8]{{1,0}} dot(%u, %u), metadata={{op_name="{BWD}"}}
}}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {{
  %x = f32[8,8]{{1,0}} parameter(0)
  ROOT %fusion.9 = f32[8,8]{{1,0}} fusion(%x), kind=kOutput, calls=%fused_computation.9
}}
""")
    ev = tr._scoped(tr.Event(0.0, 4.0, "fusion.9 fusion f32[8,8]", "",
                             "jit_fused"), hlo)
    ctx = {"trace": tr.reduce_lines([[ev]], (0.0, 10.0)), "chips": 1,
           "traced": {"steps": 4}, "spans": []}
    assert read(grid, "bwd_ms.train", ctx) == pytest.approx(1000.0)
    assert read(grid, "update_ms.train", ctx) is None
    assert read(grid, "fwd_ms.train", ctx) is None
    assert read(grid, "unphased_device_share", ctx) == 0.0


def test_a_loops_body_and_several_chips(grid):
    """A ``while`` and its body are counted once (self time), the body's
    unnamed events under the loop's scope; chips are averaged."""
    loop = "jit(fused)/loss_and_grad/jvp(rnn_lstm)/while"
    line = [(0.0, 6.0, "while.1", loop),
            (1.0, 3.0, "fusion.2", loop + "/body/dot_general"),
            (3.0, 4.0, "copy.3", ""),
            (7.0, 8.0, "fusion.4", UPDATE)]
    one = trace_ctx(grid, line, steps=2)
    assert read(grid, "fwd_ms.train", one) == pytest.approx(3000.0)
    assert read(grid, "unphased_device_share", one) == 0.0
    two = trace_ctx(grid, None, steps=2, chips=2, lines=[line, line])
    assert read(grid, "fwd_ms.train", two) == pytest.approx(3000.0)
    assert read(grid, "update_ms.train", two) == pytest.approx(500.0)


def test_phase_readers_are_silent_without_phase_scopes(grid):
    """The parent's program has no phase scope: nothing is read, and
    least of all an unphased share of 100."""
    old = trace_ctx(grid, [
        (0.0, 2.0, "dot.1", "jit(fused)/jvp(log_softmax)/dot_general"),
        (2.0, 3.0, "fusion.2", "")])
    empty = {"trace": {}, "chips": 1, "traced": {"steps": 3}, "spans": []}
    no_leaf = dict(empty, trace={"leaf": []})
    for ctx in (old, empty, no_leaf):
        for metric in ("fwd_ms.train", "bwd_ms.train", "update_ms.train",
                       "unphased_device_share"):
            assert read(grid, metric, ctx) is None, metric
    no_steps = trace_ctx(grid, [(0.0, 1.0, "dot.1", FWD)], steps=0)
    assert read(grid, "fwd_ms.train", no_steps) is None


def test_the_yardsticks_phase_names_are_the_programs(grid):
    sys.path.insert(0, ROOT)
    try:
        from mxnet_tpu.gluon import fused_step
    finally:
        sys.path.remove(ROOT)
    assert grid("layer_metrics/_phases.py").PHASES == \
        fused_step.PHASE_SCOPES


# ---------------------------------------------------------------------------
# the consumer's wait
# ---------------------------------------------------------------------------

def test_input_wait_is_the_mean_h2d_wait_span(grid):
    def span(phase, dur):
        return {"phase": phase, "step": 0, "t0": 1.0, "t1": 1.0 + dur,
                "dur": dur}
    ctx = {"spans": [span("h2d_wait", 0.001), span("dispatch", 0.5),
                     span("h2d_wait", 0.003), span("batch_fetch", 0.2)]}
    assert read(grid, "input_wait_ms.train", ctx) == pytest.approx(2.0)
    assert read(grid, "input_wait_ms.train",
                {"spans": [span("dispatch", 0.5)]}) is None
    assert read(grid, "input_wait_ms.train", {"spans": []}) is None


# ---------------------------------------------------------------------------
# set-up, from the compile log
# ---------------------------------------------------------------------------

def log_ctx(monkeypatch, events, dropped=0, window_t0=100.0):
    sys.path.insert(0, ROOT)
    try:
        from mxnet_tpu import runtime
    finally:
        sys.path.remove(ROOT)
    monkeypatch.setattr(
        runtime, "compile_log",
        lambda: {"events": [dict(zip(("phase", "fun_name", "t0", "t1"), e))
                            for e in events], "dropped": dropped})
    return {"spans": [{"phase": "dispatch", "t0": window_t0 + 0.5},
                      {"phase": "h2d_wait", "t0": window_t0}]}


LOG = [
    # an outer jit's tracing encloses its inner jits': union, not sum
    ("trace", "log_softmax", 10.5, 11.0),
    ("trace", "fused", 10.0, 14.0),
    ("lower", "jit(fused)", 14.0, 16.0),
    ("cache_read", "", 16.1, 16.9),
    ("backend_compile", "jit(fused)", 16.0, 17.0),
    ("trace", "_threefry_split", 20.0, 20.5),
    ("lower", "jit(_threefry_split)", 20.5, 20.75),
    ("backend_compile", "jit(_threefry_split)", 21.0, 21.5),
    # the window's own (a recompile) and what follows it: left out
    ("trace", "fused", 101.0, 103.0),
    ("backend_compile", "jit(fused)", 103.0, 110.0),
    # one that straddles the window's start did not end in set-up
    ("lower", "jit(late)", 99.0, 100.5),
]


def test_setup_split_is_a_union_cut_at_the_window(grid, monkeypatch):
    ctx = log_ctx(monkeypatch, LOG)
    assert read(grid, "setup_trace_lower_s", ctx) == \
        pytest.approx(6.0 + 0.75)           # a sum would read 7.25
    assert read(grid, "setup_compile_s", ctx) == pytest.approx(1.5)
    assert read(grid, "setup_programs", ctx) == 2


def test_setup_readers_are_silent_rather_than_low(grid, monkeypatch):
    metrics = ("setup_trace_lower_s", "setup_compile_s", "setup_programs")

    def silent(ctx):
        return [read(grid, m, ctx) for m in metrics] == [None] * 3
    assert not silent(log_ctx(monkeypatch, LOG))
    assert silent(log_ctx(monkeypatch, LOG, dropped=3))
    assert silent(dict(log_ctx(monkeypatch, LOG), spans=[]))
    assert silent(log_ctx(monkeypatch, LOG, window_t0=5.0))   # nothing yet
    # a program with no compile log at all: the parent commit
    ctx = log_ctx(monkeypatch, LOG)
    from mxnet_tpu import runtime
    monkeypatch.delattr(runtime, "compile_log")
    assert silent(ctx)


# ---------------------------------------------------------------------------
# the traced line of every cell, with the manifest as it stands
# ---------------------------------------------------------------------------

def test_the_manifest_appends_the_eight_metrics():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(NEW):] == NEW
    for m in MANIFEST["per_layer"][-len(NEW):]:
        assert "workloads" not in m and m["better"] == "lower"
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup_")
                              else "train_tokens_per_s")


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsed_traced_line_carries_the_new_metrics(cell):
    """The whole path on the CPU: scopes from the step's HLO text, spans
    from the timeline, the compile log from the program. The numbers are
    a CPU's (whose executor threads overlap, so self times need not even
    add up to the busy time) and are judged for presence only."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(GRID, "run.py"), "--workload", cell,
         "--rehearse", "--seed", "2147483693", "--seconds", "1",
         "--trace", "1"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.count("\n") == 1
    line = json.loads(proc.stdout)
    assert line["correct"] is True, line["compared"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    units = {m["name"]: m["unit"] for m in MANIFEST["per_layer"]}
    assert all(line["metrics"][n]["unit"] == units[n] for n in NEW)
    assert all(got[n] > 0 for n in NEW if n != "unphased_device_share")
    assert 0 <= got["unphased_device_share"] < 50
    assert got["setup_programs"] == int(got["setup_programs"]) >= 2
