"""The three readers of the held pairs (ISSUE 37): ``moe_held_pairs.train``,
``moe_pairs_growth`` and ``moe_experts_us_per_kpair`` on hand-built
``ctx``s, on the rehearsed traced line of every cell, and against
``SparseMoE.routing_stats`` at each expert cell's rehearsal preset. The
manifest's three entries are held by NAME: entries appended behind them
must not fail this file."""
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
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SMALLTHINKER = "smallthinker-21b-a3b.train-b1-s8192"
JOYAI = "joyai-llm-flash.train-b1-s4096"
NEMOTRON = "nemotron-3-nano-30b-a3b.train-b1-s4096"
EXPERT_CELLS = {SMALLTHINKER: 4, JOYAI: 2, NEMOTRON: 2}   # tiny's layers
READERS = ["moe_held_pairs.train", "moe_pairs_growth",
           "moe_experts_us_per_kpair"]


@pytest.fixture
def grid(monkeypatch):
    """``load(name)`` for a module of benchmark/grid by path, the
    directory importable as ``run.py``'s own start makes it."""
    monkeypatch.syspath_prepend(GRID)
    for name in [m for m in sys.modules
                 if m == "trace_reduce" or m.startswith("layer_metrics")]:
        monkeypatch.delitem(sys.modules, name)

    def load(name):
        spec = importlib.util.spec_from_file_location(
            "grid_counters_" + re.sub(r"\W", "_", name),
            os.path.join(GRID, name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    return load


def span(step, counters=None, phase="window"):
    e = {"phase": phase, "step": step, "t0": float(step),
         "t1": step + 0.5, "dur": 0.5}
    if counters is not None:
        e["counters"] = counters
    return e


def ctx_of(pairs_a_step, traced=2, window=None, trace=None):
    """A ctx whose step t held ``pairs_a_step[t]`` (a list of layers,
    each a list of experts; None: the record names no expert layer)."""
    spans = []
    for t, layers in enumerate(pairs_a_step, 1):
        spans.append(span(t, phase="dispatch"))
        spans.append(span(t, {} if layers is None
                          else {"moe_held_pairs": layers}))
    # the ring keeps retire order, not step order: a reader sorts
    spans.reverse()
    return {"spans": spans, "chips": 1, "trace": trace,
            "window": {"steps": len(pairs_a_step) if window is None
                       else window},
            "traced": {"steps": traced}}


def experts_trace(grid, seconds=(0.004, 0.006)):
    """A reduced trace with ``seconds`` under the ``moe_experts`` scope,
    forward and backward, and half a second under the router's."""
    tr = grid("trace_reduce.py")
    scopes = ["jit(f)/jvp(moe_experts)/gmm",
              "jit(f)/transpose(jvp(moe_experts))/tgmm"]
    leaf = [(tr.Event(i, i + 1, f"fusion.{i}", scope), t)
            for i, (scope, t) in enumerate(zip(scopes, seconds))]
    leaf.append((tr.Event(2, 3, "fusion.2", "jit(f)/moe_route/top_k"), 0.5))
    return {"leaf": leaf}


# ---------------------------------------------------------------------------
# hand-built records
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("reader", READERS)
def test_no_counters_key_is_silence(grid, reader):
    """The parent's spans: a ``window`` event without the key."""
    read = grid(f"layer_metrics/{reader}.py").read
    ctx = ctx_of([None] * 4, trace=experts_trace(grid))
    for e in ctx["spans"]:
        e.pop("counters", None)
    assert read(ctx) is None
    assert read(dict(ctx, spans=[])) is None


@pytest.mark.parametrize("reader", READERS)
def test_a_ring_that_dropped_events_is_silence(grid, reader):
    """No number beats a low one."""
    read = grid(f"layer_metrics/{reader}.py").read
    ctx = ctx_of([[[3, 5]]] * 6, window=7, trace=experts_trace(grid))
    assert read(ctx) is None


def test_an_empty_record_is_zero_pairs_and_zero_growth(grid):
    """BERT, the LSTM: the record is there and names no expert layer."""
    ctx = ctx_of([None] * 40, traced=24)
    assert grid("layer_metrics/moe_held_pairs.train.py").read(ctx) == 0.0
    assert grid("layer_metrics/moe_pairs_growth.py").read(ctx) == 0.0
    assert grid("layer_metrics/moe_experts_us_per_kpair.py").read(
        dict(ctx, trace=experts_trace(grid))) is None


def test_held_pairs_is_a_layers_mean_over_the_traced_steps(grid):
    read = grid("layer_metrics/moe_held_pairs.train.py").read
    steps = [[[10, 20], [30, 40]],      # 100 over 2 layers: 50 a layer
             [[20, 20], [30, 50]],      # 120: 60
             [[99, 99], [99, 99]]]      # past the traced steps
    assert read(ctx_of(steps, traced=2)) == 55.0
    assert read(ctx_of(steps, traced=3)) == pytest.approx((50 + 60 + 198) / 3)
    assert read(ctx_of(steps, traced=0)) is None


def test_growth_is_the_last_sixteen_over_the_first(grid):
    read = grid("layer_metrics/moe_pairs_growth.py").read
    steps = [[[100, 100]]] * 16 + [[[7, 7]]] * 30 + [[[125, 125]]] * 16
    assert read(ctx_of(steps)) == pytest.approx(25.0)
    assert read(ctx_of(steps[::-1])) == pytest.approx(-20.0)
    assert read(ctx_of([[[100, 100]]] * 40)) == 0.0
    # pairs per STEP, over all its layers
    two = [[[50], [50]]] * 16 + [[[60], [50]]] * 16
    assert read(ctx_of(two)) == pytest.approx(10.0)


def test_us_per_kpair_divides_the_scope_by_the_traced_steps_own_pairs(
        grid):
    read = grid("layer_metrics/moe_experts_us_per_kpair.py").read
    trace = experts_trace(grid)
    steps = [[[1000, 1000], [500, 500]],    # 3,000 pairs
             [[1000, 1000], [1000, 1000]],  # 4,000
             [[9, 9], [9, 9]]]
    # 10 ms under the scope over 7 thousand pairs
    assert read(ctx_of(steps, traced=2, trace=trace)) \
        == pytest.approx(1e4 / 7)
    assert read(ctx_of(steps, traced=2,
                       trace={"leaf": trace["leaf"][2:]})) is None
    assert read(ctx_of(steps, traced=2, trace=None)) is None
    assert read(ctx_of([None] * 3, trace=trace)) is None


# ---------------------------------------------------------------------------
# the manifest, by name
# ---------------------------------------------------------------------------

def test_the_manifest_holds_the_three_metrics_by_name():
    mine = {m["name"]: m for m in MANIFEST["per_layer"]
            if m["name"] in READERS}
    assert sorted(mine) == sorted(READERS)
    assert mine["moe_held_pairs.train"] == {
        "name": "moe_held_pairs.train", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "sparse experts",
        "moves": "train_tokens_per_s"}
    assert mine["moe_pairs_growth"] == {
        "name": "moe_pairs_growth", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "sparse experts",
        "moves": "step_ms_p95"}
    third = mine["moe_experts_us_per_kpair"]
    assert third["workloads"][:2] == [SMALLTHINKER, JOYAI]
    assert {k: v for k, v in third.items() if k != "workloads"} == {
        "name": "moe_experts_us_per_kpair", "unit": "us", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s"}
    for name in READERS:
        assert os.path.isfile(os.path.join(GRID, "layer_metrics",
                                           name + ".py"))


# ---------------------------------------------------------------------------
# the rehearsed traced line of every cell
# ---------------------------------------------------------------------------

def rehearse_traced(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    env.pop("XLA_FLAGS", None)      # one CPU device, as one chip
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--rehearse", "--seed",
         "2147483693", "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout)


@pytest.mark.parametrize("cell", CELLS)
def test_the_rehearsed_traced_line_carries_the_counts(cell):
    line = rehearse_traced(cell)
    assert line["correct"] is True, line["compared"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["metrics"]["moe_held_pairs.train"]["unit"] == "count"
    assert line["metrics"]["moe_pairs_growth"]["unit"] == "%"
    assert got["compiles_in_window"] == 0
    assert line["compared"]["retraces"]["value"] == 0
    if cell in EXPERT_CELLS:
        assert got["moe_held_pairs.train"] > 0
        assert -50 < got["moe_pairs_growth"] < 50
    else:
        assert got["moe_held_pairs.train"] == 0
        assert got["moe_pairs_growth"] == 0
    third = MANIFEST["per_layer"][[m["name"] for m in MANIFEST["per_layer"]]
                                  .index("moe_experts_us_per_kpair")]
    if cell in third["workloads"]:
        assert got["moe_experts_us_per_kpair"] > 0
        assert line["metrics"]["moe_experts_us_per_kpair"]["unit"] == "us"
    else:
        assert "moe_experts_us_per_kpair" not in got


# ---------------------------------------------------------------------------
# the first followed step against SparseMoE.routing_stats, exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_first_steps_pairs_are_the_sum_of_routing_stats(grid, cell,
                                                        monkeypatch):
    """``moe_held_pairs.train`` x the cell's expert layers, read at the
    first step of the cell's net at its rehearsal preset, is the sum of
    ``SparseMoE.routing_stats`` over the layers for that batch: the
    routers' inputs are caught in an eager pass over the same weights."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.gluon.nn import SparseMoE
    from mxnet_tpu.ndarray.ndarray import NDArray
    entry, = [w for w in MANIFEST["workloads"] if w["name"] == cell]
    with open(os.path.join(GRID, "configs", entry["config"] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(GRID, "traffic", entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    for preset in (cfg, traffic):
        preset.update(preset["tiny"])
    model = grid(f"configs/{entry['config']}.py")
    reference = grid("reference.py")
    net = model.build_net(cfg, traffic)
    weights = reference.make_weights(model.param_spec(cfg), 2147483693)
    params = net.collect_params()
    for name, p in params.items():
        p.set_data(NDArray(weights[name]))
    x, y = model.batches(cfg, traffic, 2147483693)[0]
    x, y = mx.nd.array(x), mx.nd.array(y)

    want, route = [], SparseMoE.route

    def caught(self, u):
        want.append(self.routing_stats(u)["pairs"].tolist())
        return route(self, u)
    with monkeypatch.context() as patched:
        patched.setattr(SparseMoE, "route", caught)
        net(x)
    assert len(want) == EXPERT_CELLS[cell]

    opt = dict(traffic["optimizer"])
    trainer = mx.gluon.Trainer(params, opt.pop("name"), opt,
                               kvstore=traffic["kvstore"])
    loop = mx.gluon.TrainLoop(net, trainer, SoftmaxCrossEntropyLoss())
    telemetry.reset()
    telemetry.enable(True)
    try:
        loop.step(x, y)
        loop.synchronize()
        spans = telemetry.timeline().events()
        # every expert layer is traced at the loss function's own level
        assert telemetry.registry().counter(
            telemetry.names.DEVICE_COUNTER_DROPPED,
            label_key="name").values() == {}
    finally:
        telemetry.enable(None)
        telemetry.reset()
    first, = [e for e in spans if e["phase"] == "window"]
    assert first["counters"] == {"moe_held_pairs": want}
    ctx = {"spans": spans, "chips": 1, "trace": None,
           "window": {"steps": 1}, "traced": {"steps": 1}}
    held = grid("layer_metrics/moe_held_pairs.train.py").read(ctx)
    assert held * len(want) == sum(map(sum, want)) > 0
