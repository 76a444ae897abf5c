"""The lfm2-24b-a2b configuration's parameter and operation counts,
written out by hand, and the two per-layer readers the cell brought:
silent, never 0, on a trace that lacks what they read. The manifest is
held by NAME: the next cell appended behind this one must not fail this
file."""
import importlib.util
import json
import math
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GRID = os.path.join(ROOT, "benchmark", "grid")
NAME = "lfm2-24b-a2b"
TRAFFIC = "train-b1-s4096"
CELL = f"{NAME}.{TRAFFIC}"
READERS = ["conv_mixer_ms.train", "short_conv_roofline"]
#: the published config.json (LiquidAI/LFM2-24B-A2B) as far as it gives
#: the model's shape
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + 9 * ["full_attention", "conv", "conv",
                                           "conv"] + ["full_attention",
                                                      "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"


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
            "grid_lfm2_" + re.sub(r"\W", "_", name),
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
    with open(os.path.join(GRID, "traffic", TRAFFIC + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# parameters, FLOPs and bytes by hand
# ---------------------------------------------------------------------------

def test_param_spec_counts_by_hand(grid, cfg):
    model = grid(f"configs/{NAME}.py")
    spec = model.param_spec(cfg)
    sizes = {name: math.prod(shape) for name, shape, _, _ in spec}
    assert len(sizes) == len(spec)
    assert model.layer_types(cfg) == ["conv", "full_attention", "conv",
                                      "conv", "conv"]
    # the short conv: W_in 2048 x 6144, the 2048 x 3 taps, W_out 2048^2
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    assert conv == 16_783_360
    # attention: q and o 2048 x 2048, k and v 2048 x 512, two gains of 64
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert attention == 10_485_888
    dense = 3 * 2048 * 11776
    assert dense == 72_351_744
    one_expert = 3 * 2048 * 1536
    assert one_expert == 9_437_184
    experts = 8 * one_expert + 64 * 2048 + 64
    assert experts == 75_628_608
    norms = 2 * 2048
    layers = [norms + conv + dense, norms + attention + experts] \
        + 3 * [norms + conv + experts]
    assert layers == [89_139_200, 86_118_592] + 3 * [92_416_064]
    table = 8192 * 2048
    assert table == 16_777_216
    total = sum(layers) + table + 2048
    assert total == 469_285_248
    assert sum(sizes.values()) == total
    by_layer = [sum(n for k, n in sizes.items() if k.startswith(f"layer{i}."))
                for i in range(5)]
    assert by_layer == layers
    assert not [k for k in sizes if k.startswith("layer5.")]
    assert not [k for k in sizes if "head" in k]          # tied
    # whole, one expert layer: 64 experts and the router
    assert 64 * one_expert + 64 * 2048 + 64 == 604_110_912   # 9.7 GB at 16 B
    # 16 B a parameter: float32 master, gradient, Adam m and v
    assert 16 * total == 7_508_563_968


def test_flops_per_token_by_hand(grid, cfg, traffic):
    model = grid(f"configs/{NAME}.py")
    f = model.forward_flops(cfg, traffic)
    # per token: W_in 2 x 2048 x 6144 and W_out 2 x 2048 x 2048
    assert f["conv_proj"] == 4096 * 33_554_432
    # q, k, v, o: 2 x 2048 x (2048 + 512 + 512 + 2048)
    assert f["attn_proj"] == 4096 * 20_971_520
    # 4096 x 4097 / 2 causal pairs, 32 heads, 2 x 64 a score and a value
    assert model.attended_pairs(4096) == 8_390_656
    assert f["attention"] == 8_390_656 * 32 * 256 == 68_736_253_952
    assert f["dense_ffn"] == 4096 * 144_703_488
    assert f["router"] == 4096 * 2 * 2048 * 64
    # 4 x 8 / 64 of a held expert for the average token: 2,048 pairs a
    # layer of the 16,384 rows the sorted list has, 256 tokens an expert
    assert model.held_pairs_per_token(cfg) == 0.5
    assert f["held_experts"] == 2048 * 3 * 2 * 2048 * 1536
    assert f["head"] == 4096 * 2 * 2048 * 8192
    forward = 4 * f["conv_proj"] + f["dense_ffn"] + f["attn_proj"] \
        + f["attention"] + 4 * (f["router"] + f["held_experts"]) + f["head"]
    # 389.0 M a token
    assert forward / 4096 == 389_025_792
    assert model.flops_per_token(cfg, traffic) == 3 * 389_025_792
    assert model.tokens_per_step(cfg, traffic) == 4096
    # 4.78 TFLOP a step, 24.3 ms at the v5e's peak; the conv mixers'
    # projections 34.5 %, the dense layer 37 %, the experts and routers 10 %
    assert round(3 * forward / 1e12, 2) == 4.78
    with open(os.path.join(GRID, "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    assert round(1e3 * 3 * forward / peaks["bf16_flops_per_s"], 1) == 24.3
    assert 0.344 < 4 * f["conv_proj"] / forward < 0.346
    assert 0.371 < f["dense_ffn"] / forward < 0.373
    assert 0.099 < 4 * (f["router"] + f["held_experts"]) / forward < 0.1


def test_kernel_costs_by_hand(grid, cfg, traffic):
    costs = grid(f"configs/{NAME}.py").kernel_costs(cfg, traffic)
    assert set(costs) == {"short_conv"}
    conv = costs["short_conv"]
    assert conv["flops"] == 0.0
    # four conv layers; forward reads [B | C | x] (6144 lanes) and writes
    # y (2048), backward reads dy (2048) and [B | C | x] and writes its
    # cotangent (6144): 22,528 lanes of 4096 positions in bf16
    assert 6144 + 2048 + 2048 + 6144 + 6144 == 22_528
    assert conv["bytes"] == 4 * 4096 * 2 * 22_528
    assert 4096 * 2 * 22_528 == 184_549_376           # 184.5 MB a layer
    with open(os.path.join(GRID, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    # bound by bytes: 0.90 ms a step at 819 GB/s
    assert round(1e3 * conv["bytes"] / peaks["hbm_bytes_per_s"], 2) == 0.90


def test_batches_cut_inputs_and_targets_from_one_stream(grid, cfg):
    model = grid(f"configs/{NAME}.py")
    traffic = {"batch": 2, "seq": 8, "pool": 3}
    pool = model.batches(cfg, traffic, 2147483659)
    again = model.batches(cfg, traffic, 2147483659)
    assert len(pool) == 3
    for (x, y), (x2, y2) in zip(pool, again):
        assert (x == x2).all() and (y == y2).all()
        assert x.shape == y.shape == (2, 8)
        assert x.dtype == y.dtype == "int32"
        assert 0 <= x.min() and max(x.max(), y.max()) < cfg["vocab_rows"]
        assert (y[:, :-1] == x[:, 1:]).all()
    assert not (pool[0][0] == pool[1][0]).all()


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------

def test_the_file_holds_every_published_key_and_states_the_cut(cfg):
    assert len(PUBLISHED["layer_types"]) == 40
    assert cfg["source"] == SOURCE
    differs = sorted(k for k, v in PUBLISHED.items() if cfg.get(k) != v)
    assert differs == ["num_dense_layers", "num_experts",
                       "num_hidden_layers"]
    assert set(differs) < set(cfg["reduced"])
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts", "vocab_rows"]
    assert [cfg[k] for k in cfg["reduced"]] == [5, 1, 8, 8192]
    assert [cfg["published"][k] for k in cfg["reduced"]] == [40, 2, 64,
                                                             65536]
    assert not [k for k in cfg["reduced"]
                if re.search(r"(_dim|_rank|_size)$", k)]
    # the published layer types whole; the built ones are its layers
    # 0, 2, 3, 4, 5: the first leading dense layer and one period
    assert len(cfg["layer_types"]) == 40
    assert cfg["layer_types"].count("full_attention") == 10
    assert cfg["built_layer_types"] == [cfg["layer_types"][i]
                                        for i in cfg["built_from_layers"]]
    assert cfg["built_from_layers"] == [0, 2, 3, 4, 5]
    assert cfg["layer_types"][2:6] == cfg["layer_types"][6:10]
    # every width is the published one
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["conv_L_cache"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["num_experts_per_tok"], cfg["moe_router_width"]) == \
        (2048, 11776, 1536, 3, 32, 8, 4, 64)
    assert 8 * cfg["vocab_rows"] == cfg["vocab_size"] == 65536
    assert cfg["moe_first_expert"] == 0
    assert "8 chips share each layer" in cfg["reduced_why"]["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["reduced_why"])
    for key in ("tied_head", "initializer_range", "embed_initializer_range",
                "router_bias_range", "conv_initializer_range",
                "rope_pairing", "qk_norm", "router", "short_conv"):
        assert key in cfg["assumed"], key
    # the tiny preset changes sizes only, never the mechanisms
    assert not set(cfg["tiny"]) & {
        "conv_L_cache", "conv_bias", "norm_eps", "norm_topk_prob",
        "use_expert_bias", "routed_scaling_factor", "rope_parameters",
        "num_dense_layers"}
    assert set(cfg["tiny"]["built_layer_types"]) == {"conv",
                                                     "full_attention"}


def test_every_leaf_is_drawn_as_the_file_says(grid, cfg):
    spec = grid(f"configs/{NAME}.py").param_spec(cfg)
    drawn = {name: (kind, scale) for name, _, kind, scale in spec}
    assert drawn.pop("embed.weight") == ("normal", 10.0)
    ends = lambda tail: [n for n in drawn if n.endswith(tail)]
    assert {drawn.pop(n) for n in ends("router_bias")} == {("normal", 0.01)}
    taps, = {drawn.pop(n) for n in ends("conv_weight")}
    assert taps == ("uniform", pytest.approx(1 / math.sqrt(3), rel=1e-15))
    assert {scale for _, scale in drawn.values()} == {0.02}
    gains = {n for n, (kind, _) in drawn.items() if kind == "gamma"}
    assert gains == {n for n in drawn if "gamma" in n}
    # two norms a layer, the q and k norms of the one attention layer,
    # the final norm
    assert len(gains) == 2 * 5 + 2 + 1
    assert {kind for n, (kind, _) in drawn.items() if n not in gains} \
        == {"normal"}


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

class _Model:
    @staticmethod
    def kernel_costs(cfg, traffic):
        return {"short_conv": {"flops": 0.0, "bytes": 2e8}}


def _ctx(grid, events, steps=10):
    tr = grid("trace_reduce.py")
    line = [tr.Event(*e) for e in events]
    return {"model": _Model, "cfg": {}, "traffic": {}, "chips": 1,
            "peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
            "traced": {"steps": steps}, "spans": [], "counters": {},
            "trace": tr.reduce_lines([line], (0.0, 10.0))}


def test_readers_read_their_scopes(grid):
    """The scope paths are the program's own (the step's optimized HLO at
    the tiny preset): ``jvp(conv_mixer)/fully_connected/..`` for the
    projections, ``jvp(conv_mixer)/gated_short_conv/short_conv/..`` for
    the gates and the conv, and ``transpose(jvp(conv_mixer))/..``
    backward."""
    pre = "jit(fused_step)/loss_and_grad/"
    ctx = _ctx(grid, [
        (0.0, 0.5, "a", pre + "jvp(conv_mixer)/fully_connected/dot_general"),
        (0.5, 1.0, "b", pre + "transpose(jvp(conv_mixer))/fully_connected/"
         "dot_general"),
        (1.0, 1.25, "c", pre + "jvp(conv_mixer)/gated_short_conv/"
         "short_conv/mul"),
        (1.25, 2.0, "d", pre + "transpose(jvp(conv_mixer))/gated_short_conv/"
         "short_conv/pad"),
        (2.0, 2.5, "e", pre + "jvp(moe_experts)/dot_general"),
        (2.5, 3.0, "f", pre + "jvp(qk_norm)/rsqrt")])
    read = lambda m: grid(f"layer_metrics/{m}.py").read(ctx)
    # 10 steps x 2e8 / 1e12 = 2 ms of the 1.0 s under the scope
    assert read("short_conv_roofline") == pytest.approx(100 * 0.002 / 1.0)
    assert read("conv_mixer_ms.train") == pytest.approx(200.0)


@pytest.mark.parametrize("metric", READERS)
def test_readers_are_silent_without_what_they_read(grid, metric):
    """The parent's program has none of these scopes: nothing is read,
    and least of all a 0."""
    reader = grid(f"layer_metrics/{metric}.py")
    other = _ctx(grid, [(0.0, 1.0, "k",
                         "jit(s)/jvp(mamba_mixer)/mamba_conv/mul")])
    empty = dict(other, trace={})
    no_leaf = dict(other, trace={"leaf": []})
    for ctx in (other, empty, no_leaf):
        assert reader.read(ctx) is None


def test_the_manifest_holds_the_cell_and_its_two_metrics_by_name():
    """Wherever they stand: a later PR appends behind them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": NAME, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "1/8" in cell["why"]
    config, = [c for c in manifest["configs"] if c["name"] == NAME]
    assert len(config["why"]) <= 200
    assert config["file"] == f"benchmark/grid/configs/{NAME}.json"
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_rows"]
    with open(os.path.join(GRID, "configs", NAME + ".json")) as f:
        assert json.load(f)["source"] == config["source"]
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == NAME] == [CELL]
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"] in READERS}
    assert sorted(mine) == sorted(READERS)
    for m in mine.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(GRID, "layer_metrics",
                                           m["name"] + ".py"))
    assert {n: (m["layer"], m["unit"], m["better"], m["source"])
            for n, m in mine.items()} == {
        "conv_mixer_ms.train": ("short-conv mixer", "ms", "lower",
                                "device_trace"),
        "short_conv_roofline": ("kernels", "%", "higher", "device_trace")}
    # no metric that was there lists this cell
    for m in manifest["per_layer"]:
        if m["name"] not in READERS:
            assert CELL not in m.get("workloads", [])
    for part in (("configs", NAME + ".json"), ("configs", NAME + ".py"),
                 ("traffic", TRAFFIC + ".json"), ("limits", CELL + ".json")):
        assert os.path.isfile(os.path.join(GRID, *part)), part
