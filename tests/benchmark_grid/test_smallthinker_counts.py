"""The smallthinker-21b-a3b configuration's operation counts, written out
by hand as ``test_grid.py::test_flops_per_token_by_hand`` does for the
two older cells, and the four per-layer readers the cell brought: silent,
never 0, on a trace or a program that lacks what they read."""
import importlib.util
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GRID = os.path.join(ROOT, "benchmark", "grid")
NAME = "smallthinker-21b-a3b"
CELL = NAME + ".train-b1-s8192"
READERS = ["window_attention_roofline", "moe_experts_roofline",
           "moe_route_ms.train", "moe_ungrouped_layers"]


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
            "grid_counts_" + re.sub(r"\W", "_", name),
            os.path.join(GRID, name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    return load


@pytest.fixture
def cfg():
    with open(os.path.join(GRID, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture
def traffic():
    with open(os.path.join(GRID, "traffic", "train-b1-s8192.json")) as f:
        return json.load(f)


def test_flops_per_token_by_hand(grid, cfg, traffic):
    model = grid(f"configs/{NAME}.py")
    # a layer, forward, per token: q and o 2 x 2 x 2560 x 3584 =
    # 36,700,160; k and v 2 x 2 x 2560 x 512 = 5,242,880: 41,943,040. The
    # router 2 x 2560 x 64 = 327,680.
    proj, router = 41_943_040, 327_680
    assert proj == 2 * (2 * 2560 * 3584 + 2 * 2560 * 512)
    # scores and PV over the causal pairs a head attends to, 4 FLOPs a
    # pair and lane of 28 x 128 = 3584, a sequence of 8192: the full layer
    # 8192 x 8193 / 2 = 33,558,528 pairs; a window layer 4096 x 4097 / 2 +
    # 4096 x 4096 = 25,167,872 (every query from the 4096th on sees 4096)
    assert model.attended_pairs(8192) == 33_558_528
    assert model.attended_pairs(8192, 4096) == 25_167_872
    assert model.attended_pairs(8, 3) == 1 + 2 + 3 * 6
    assert model.attended_pairs(8, 100) == 36
    full, window = 58_727_424, 44_043_776
    assert full == 33_558_528 * 4 * 3584 // 8192
    assert window == 25_167_872 * 4 * 3584 // 8192
    # an expert 3 x 2 x 2560 x 768 = 11,796,480; 6 x 8 / 64 = 0.75 of one
    # held here for the average token: 8,847,360. The head 2 x 2560 x 18992.
    expert, head = 8_847_360, 97_239_040
    assert model.held_pairs_per_token(cfg) == 0.75
    forward = 4 * proj + 4 * router + full + 3 * window + 4 * expert + head
    assert forward == 492_570_112
    assert model.flops_per_token(cfg, traffic) == 3 * 492_570_112
    assert model.tokens_per_step(cfg, traffic) == 8192


def test_kernel_costs_by_hand(grid, cfg, traffic):
    costs = grid(f"configs/{NAME}.py").kernel_costs(cfg, traffic)
    assert set(costs) == {"flash_attention", "moe_experts"}
    # attention: forward and twice that backward over the in-window pairs
    # of one full and three window layers; per layer q, o, then q, o, do,
    # dq at 3584 wide and k, v, then k, v, dk, dv at 512 wide, 8192
    # positions, bf16
    attn = costs["flash_attention"]
    assert attn["flops"] == 3 * 4 * 3584 * (33_558_528 + 3 * 25_167_872)
    assert attn["bytes"] == 4 * 6 * 8192 * (3584 + 512) * 2
    # experts: 8192 x 0.75 = 6,144 expected pairs a layer, three products
    # of 2 x 2560 x 768 each, forward and twice that backward; 8 experts'
    # three matrices read twice in bf16, their f32 gradient written once
    moe = costs["moe_experts"]
    assert moe["flops"] == 4 * 3 * 6144 * 3 * 2 * 2560 * 768
    assert moe["bytes"] == 4 * (8 * 3 * 2560 * 768) * (2 * 2 + 4)


def test_the_file_holds_the_published_sizes_and_states_the_cut(cfg):
    published = {"hidden_size": 2560, "head_dim": 128,
                 "num_attention_heads": 28, "num_key_value_heads": 4,
                 "moe_ffn_hidden_size": 768,
                 "moe_num_active_primary_experts": 6,
                 "sliding_window_size": 4096, "rope_theta": 1500000,
                 "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
                 "vocab_size": 151936, "max_position_embeddings": 16384,
                 "moe_router_width": 64}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_rows"]
    assert [cfg[k] for k in cfg["reduced"]] == [4, 8, 18992]
    assert [cfg["published"][k] for k in cfg["reduced"]] == [52, 64, 151936]
    assert 8 * cfg["vocab_rows"] == cfg["vocab_size"]
    assert "8 chips share each layer" in cfg["reduced_why"]["deployment"]


def test_param_spec_counts_what_the_issue_reckoned(grid, cfg):
    spec = grid(f"configs/{NAME}.py").param_spec(cfg)
    size = lambda shape: int.__mul__(*shape) if len(shape) == 2 else (
        shape[0] if len(shape) == 1 else shape[0] * shape[1] * shape[2])
    total = sum(size(shape) for _, shape, _, _ in spec)
    # 4 x (attention 20,971,520 + router 163,840 + two gains 5,120 + 8
    # experts of 5,898,240) + embedding and head 2 x 18992 x 2560 + the
    # final gain
    assert total == 4 * (20_971_520 + 163_840 + 5_120 + 8 * 5_898_240) \
        + 2 * 18992 * 2560 + 2560
    assert len({name for name, *_ in spec}) == len(spec) == 4 * 10 + 3


def test_only_the_embedding_rows_leave_the_initializer_range(grid, cfg):
    """Every matrix is normal(0, initializer_range) and every gain 1 +
    that, but the embedding table: its rows are drawn at
    ``embed_initializer_range`` so that the routers read each token's own
    row and every seed gives the held experts the same share of pairs (the
    configuration's ``assumed`` says what 0.02 did to the cell)."""
    spec = grid(f"configs/{NAME}.py").param_spec(cfg)
    scales = {name: (kind, scale) for name, _, kind, scale in spec}
    assert scales.pop("embed.weight") == ("normal",
                                          cfg["embed_initializer_range"])
    assert cfg["embed_initializer_range"] == 10.0
    assert {scale for _, scale in scales.values()} == {
        cfg["initializer_range"]} == {0.02}
    assert {kind for name, (kind, _) in scales.items()
            if name.endswith("gamma")} == {"gamma"}
    assert "embed_initializer_range" in cfg["assumed"]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

class _Model:
    @staticmethod
    def kernel_costs(cfg, traffic):
        return {"flash_attention": {"flops": 2e12, "bytes": 1e9},
                "moe_experts": {"flops": 1e12, "bytes": 5e10}}


def _ctx(grid, events, steps=10):
    tr = grid("trace_reduce.py")
    line = [tr.Event(*e) for e in events]
    return {"model": _Model, "cfg": {}, "traffic": {}, "chips": 1,
            "peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
            "traced": {"steps": steps}, "spans": [], "counters": {},
            "trace": tr.reduce_lines([line], (0.0, 10.0))}


def test_readers_read_their_scopes(grid):
    pre = "jit(fused_step)/loss_and_grad/"
    ctx = _ctx(grid, [
        (0.0, 0.5, "a", pre + "jvp(flash_attention)/pallas_call"),
        (0.5, 1.0, "b", pre + "transpose(jvp(flash_attention))/pallas_call"),
        (1.0, 2.0, "c", pre + "jvp(moe_experts)/pallas_call"),
        (2.0, 2.25, "d", pre + "jvp(moe_route)/sort"),
        (2.25, 2.5, "e", pre + "transpose(jvp(moe_combine))/gather"),
        (3.0, 4.0, "f", pre + "jvp(fully_connected)/dot_general")])
    read = lambda m: grid(f"layer_metrics/{m}.py").read(ctx)
    # 10 steps x max(2e12 / 1e14, 1e9 / 1e12) = 0.2 s of 1.0 s measured
    assert read("window_attention_roofline") == pytest.approx(20.0)
    # 10 x max(0.01, 0.05): the bytes bound it; 0.5 s of 1.0 s
    assert read("moe_experts_roofline") == pytest.approx(50.0)
    assert read("moe_route_ms.train") == pytest.approx(50.0)


@pytest.mark.parametrize("metric", READERS)
def test_readers_are_silent_without_what_they_read(grid, metric):
    """The parent's program has none of these scopes and no such counter:
    nothing is read, and least of all a 0."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import names
    telemetry.registry().counter(names.MOE_DISPATCH,
                                 label_key="path")._reset()
    reader = grid(f"layer_metrics/{metric}.py")
    other = _ctx(grid, [(0.0, 1.0, "k", "jit(s)/jvp(rnn_lstm)/while")])
    empty = dict(other, trace={})
    no_leaf = dict(other, trace={"leaf": []})
    for ctx in (other, empty, no_leaf):
        assert reader.read(ctx) is None


def test_ungrouped_layers_reads_the_programs_counter(grid):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import names
    counter = telemetry.registry().counter(names.MOE_DISPATCH,
                                           label_key="path")
    counter._reset()
    reader = grid("layer_metrics/moe_ungrouped_layers.py")
    ctx = {"trace": {}, "counters": {}}
    assert reader.read(ctx) is None
    counter.inc(4, label="grouped")
    assert reader.read(ctx) == 0
    counter.inc(2, label="capacity")
    assert reader.read(ctx) == 2
    counter._reset()


def test_the_manifest_appends_the_cell_and_its_four_metrics():
    """The cell, its configuration and its four per-layer metrics are the
    last of their lists, each metric listed for this cell alone; the
    accepted ``flash_attention_roofline`` entry is as it was."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["workloads"][-1]["chips"] == 1
    assert manifest["configs"][-1]["name"] == NAME
    flash, = [m for m in manifest["per_layer"]
              if m["name"] == "flash_attention_roofline"]
    assert flash["workloads"] == ["bert-base.train-b32-s512"]
    tail = manifest["per_layer"][-len(READERS):]
    assert [m["name"] for m in tail] == READERS
    for m in tail:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(GRID, "layer_metrics",
                                           m["name"] + ".py"))
    assert {m["name"]: m["layer"] for m in tail} == {
        "window_attention_roofline": "kernels",
        "moe_experts_roofline": "kernels",
        "moe_route_ms.train": "sparse experts",
        "moe_ungrouped_layers": "sparse experts"}
