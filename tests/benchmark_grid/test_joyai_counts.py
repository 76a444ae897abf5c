"""The joyai-llm-flash configuration's parameter and operation counts,
written out by hand, and the five per-layer readers the cell brought:
silent, never 0, on a trace or a program that lacks what they read."""
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
NAME = "joyai-llm-flash"
TRAFFIC = "train-b1-s4096"
CELL = f"{NAME}.{TRAFFIC}"
READERS = ["mla_attention_roofline", "mla_proj_ms.train",
           "moe_layer_ms.train", "mtp_ms.train", "flash_padded_calls"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
            "grid_joyai_" + re.sub(r"\W", "_", name),
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

def test_param_spec_counts_what_the_issue_reckoned(grid, cfg):
    spec = grid(f"configs/{NAME}.py").param_spec(cfg)
    sizes = {name: math.prod(shape) for name, shape, _, _ in spec}
    assert len(sizes) == len(spec)
    # latent attention: W_qa 2048 x 1536, its norm, W_qb 1536 x 32 x 192,
    # W_kva 2048 x (512 + 64), its norm, W_kvb 512 x 32 x 256, W_o 4096 x
    # 2048; with the layer's two gains
    attention = 3_145_728 + 1536 + 9_437_184 + 1_179_648 + 512 \
        + 4_194_304 + 8_388_608
    assert attention == 26_347_520
    mixer = attention + 2 * 2048
    dense = mixer + 3 * 2048 * 7168
    assert dense == 70_391_808
    # router 256 x 2048, its bias 256, the shared expert and 8 held
    # experts of 3 x 2048 x 768
    expert = mixer + 524_288 + 256 + 9 * 4_718_592
    assert expert == 69_343_488
    mtp_own = 2 * 2048 * 2048 + 3 * 2048
    assert mtp_own == 8_394_752
    tables = 2 * 16160 * 2048
    total = dense + 5 * expert + mtp_own + tables + 2048
    assert total == 491_697_408
    assert sum(sizes.values()) == total
    by_block = lambda pre: sum(n for k, n in sizes.items()
                               if k.startswith(pre))
    assert by_block("layer0.") == dense
    assert [by_block(f"layer{i}.") for i in (1, 2, 3, 4)] == [expert] * 4
    assert by_block("mtp.block.") == expert
    assert by_block("mtp.") == expert + mtp_own
    # the module has no table of its own: it reads the trunk's
    assert sorted(k for k in sizes if "embed.weight" in k
                  or "head.weight" in k) == ["embed.weight", "head.weight"]
    # 16 B a parameter: float32 master, gradient, Adam m and v
    assert 16 * total == 7_867_158_528


def test_flops_per_token_by_hand(grid, cfg, traffic):
    model = grid(f"configs/{NAME}.py")
    f = model.forward_flops(cfg, traffic)
    # per token: W_qa 2 x 2048 x 1536, W_qb 2 x 1536 x 6144, W_kva 2 x
    # 2048 x 576, W_kvb 2 x 512 x 8192, W_o 2 x 4096 x 2048
    proj = 6_291_456 + 18_874_368 + 2_359_296 + 8_388_608 + 16_777_216
    assert proj == 52_690_944 and f["proj"] == 4096 * proj
    # 4096 x 4097 / 2 causal pairs, 32 heads, 2 x 192 for a score and
    # 2 x 128 for its share of the value: 640 FLOPs a pair and head
    assert model.attended_pairs(4096) == 8_390_656
    assert f["attention"] == 8_390_656 * 32 * 640 == 171_840_634_880
    assert f["dense_ffn"] == 4096 * 3 * 2 * 2048 * 7168
    assert f["shared"] == 4096 * 9_437_184
    assert f["router"] == 4096 * 2 * 2048 * 256
    # 8 x 8 / 256 = a quarter of a held expert for the average token:
    # 1,024 pairs a layer of the 32,768 rows the sorted list has
    assert model.held_pairs_per_token(cfg) == 0.25
    assert f["held_experts"] == 1024 * 9_437_184
    assert f["mtp_proj"] == 4096 * 2 * 4096 * 2048
    assert f["head"] == 4096 * 2 * 2048 * 16160
    expert_layer = f["proj"] + f["attention"] + f["shared"] + f["router"] \
        + f["held_experts"]
    dense_layer = f["proj"] + f["attention"] + f["dense_ffn"]
    # the issue's table says 440.2 and 748.4 G, its parts rounded apart
    assert expert_layer == 440_276_090_880
    assert dense_layer == 748_439_994_368
    forward = dense_layer + 5 * expert_layer + f["mtp_proj"] + 2 * f["head"]
    assert round(forward / 1e12, 2) == 3.56
    assert model.flops_per_token(cfg, traffic) == 3.0 * forward / 4096
    assert model.tokens_per_step(cfg, traffic) == 4096
    # latent attention is about two thirds of it, the routed experts 1.4 %
    assert 0.64 < 6 * (f["proj"] + f["attention"]) / forward < 0.66
    assert 0.013 < 5 * f["held_experts"] / forward < 0.014


def test_kernel_costs_by_hand(grid, cfg, traffic):
    costs = grid(f"configs/{NAME}.py").kernel_costs(cfg, traffic)
    assert set(costs) == {"flash_attention"}
    attn = costs["flash_attention"]
    # five layers and the module's, forward and twice that backward
    assert attn["flops"] == 6 * 3 * 171_840_634_880
    assert round(attn["flops"] / 1e12, 2) == 3.09
    # per call q, k, then q, k, dq, dk at 32 x 192 and v, o, then v, o,
    # do, dv at 32 x 128, 4096 positions, bf16
    assert attn["bytes"] == 6 * 6 * 4096 * (6144 + 4096) * 2
    # the FLOPs bound it on a v5e: 15.7 ms against 3.7 ms of bytes
    with open(os.path.join(GRID, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    assert round(1e3 * attn["flops"] / peaks["bf16_flops_per_s"], 1) == 15.7
    assert attn["bytes"] / peaks["hbm_bytes_per_s"] \
        < attn["flops"] / peaks["bf16_flops_per_s"] / 4


def test_batches_cut_inputs_and_both_targets_from_one_stream(grid, cfg):
    model = grid(f"configs/{NAME}.py")
    traffic = {"batch": 2, "seq": 8, "pool": 3}
    pool = model.batches(cfg, traffic, 2147483659)
    again = model.batches(cfg, traffic, 2147483659)
    assert len(pool) == 3
    for (x, y), (x2, y2) in zip(pool, again):
        assert (x == x2).all() and (y == y2).all()
        assert x.shape == (2, 9) and y.shape == (2, 16)
        assert x.dtype == y.dtype == "int32"
        assert 0 <= x.min() and max(x.max(), y.max()) < cfg["vocab_rows"]
        # the trunk's targets are the next ids, the module's the ones after
        assert (y[:, :8] == x[:, 1:]).all()
        assert (y[:, 8:15] == x[:, 2:]).all()
    assert not (pool[0][0] == pool[1][0]).all()
    assert model.tokens_per_step(cfg, traffic) == 16


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------

def test_the_file_holds_every_catalog_key_and_states_the_cut(cfg):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "JoyAI-LLM-Flash"]
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == ["n_routed_experts", "num_hidden_layers"]
    assert set(differs) < set(cfg["reduced"])
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_rows"]
    assert [cfg[k] for k in cfg["reduced"]] == [5, 8, 16160]
    assert [cfg["published"][k] for k in cfg["reduced"]] == [40, 256, 129280]
    assert not [k for k in cfg["reduced"]
                if re.search(r"(_dim|_rank|_size)$", k)]
    assert 8 * cfg["vocab_rows"] == cfg["vocab_size"] == 129280
    assert cfg["moe_router_width"] == 256 and cfg["moe_first_expert"] == 0
    assert cfg["qk_head_dim"] == cfg["qk_nope_head_dim"] \
        + cfg["qk_rope_head_dim"] == 192
    assert "32 chips share each layer" in cfg["reduced_why"]["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["reduced_why"])
    for key in ("embed_initializer_range", "router_bias_range",
                "mtp_loss_weight", "mtp_join_order", "mtp_hidden_state",
                "initializer_range"):
        assert key in cfg["assumed"], key
    # the tiny preset changes sizes only, never the mechanisms
    assert not set(cfg["tiny"]) & {
        "scoring_func", "n_shared_experts", "num_nextn_predict_layers",
        "first_k_dense_replace", "rope_interleave", "hidden_act",
        "routed_scaling_factor", "norm_topk_prob"}


def test_only_the_rows_and_the_bias_leave_the_initializer_range(grid, cfg):
    spec = grid(f"configs/{NAME}.py").param_spec(cfg)
    scales = {name: (kind, scale) for name, _, kind, scale in spec}
    assert scales.pop("embed.weight") == ("normal", 10.0)
    biases = [n for n in scales if n.endswith("router_bias")]
    assert len(biases) == 5
    assert {scales.pop(n) for n in biases} == {("normal", 0.01)}
    assert {scale for _, scale in scales.values()} == {0.02}
    gains = {n for n, (kind, _) in scales.items() if kind == "gamma"}
    assert gains == {n for n in scales if "gamma" in n}
    assert len(gains) == 6 * 4 + 1 + 3


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

class _Model:
    @staticmethod
    def kernel_costs(cfg, traffic):
        return {"flash_attention": {"flops": 2e12, "bytes": 1e9}}


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
        (1.0, 1.5, "c", pre + "jvp(latent_proj)/fully_connected/dot_general"),
        (1.5, 1.75, "d", pre + "transpose(jvp(latent_proj))/rope/mul"),
        (2.0, 2.25, "e", pre + "jvp(moe_route)/sort"),
        (2.25, 2.5, "f", pre + "jvp(moe_experts)/ragged_dot"),
        (2.5, 2.75, "g", pre + "transpose(jvp(moe_combine))/pallas_call"),
        (2.75, 3.0, "h", pre + "jvp(shared_expert)/dot_general"),
        # the module's scopes nest: its attention, its experts
        (3.0, 3.5, "i", pre + "jvp(mtp)/flash_attention/pallas_call"),
        (3.5, 3.75, "j", pre + "transpose(jvp(mtp))/moe_experts/ragged_dot"),
        (3.75, 4.0, "k", pre + "jvp(mtp)/latent_proj/rope/mul"),
        (4.0, 5.0, "l", pre + "jvp(fully_connected)/dot_general")])
    read = lambda m: grid(f"layer_metrics/{m}.py").read(ctx)
    # 10 steps x 2e12 / 1e14 = 0.2 s of the 1.5 s under the scope, the
    # module's call among them
    assert read("mla_attention_roofline") == pytest.approx(100 * 0.2 / 1.5)
    assert read("mla_proj_ms.train") == pytest.approx(100.0)
    assert read("moe_layer_ms.train") == pytest.approx(125.0)
    assert read("mtp_ms.train") == pytest.approx(100.0)


@pytest.mark.parametrize("metric", READERS)
def test_readers_are_silent_without_what_they_read(grid, metric):
    """The parent's program has none of these scopes, and a process that
    traced no attention call has no layout to count: nothing is read,
    and least of all a 0."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import names
    telemetry.registry().counter(names.ATTENTION_MASK,
                                 label_key="kind")._reset()
    reader = grid(f"layer_metrics/{metric}.py")
    other = _ctx(grid, [(0.0, 1.0, "k", "jit(s)/jvp(rnn_lstm)/while")])
    empty = dict(other, trace={})
    no_leaf = dict(other, trace={"leaf": []})
    for ctx in (other, empty, no_leaf):
        assert reader.read(ctx) is None


def test_padded_calls_reads_the_programs_counter(grid):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import names
    layouts = telemetry.registry().counter(names.FLASH_ATTENTION_LAYOUT,
                                           label_key="layout")
    calls = telemetry.registry().counter(names.ATTENTION_MASK,
                                         label_key="kind")
    layouts._reset()
    calls._reset()
    reader = grid("layer_metrics/flash_padded_calls.py")
    ctx = {"trace": {}, "counters": {}}
    assert reader.read(ctx) is None          # no attention call traced
    calls.inc(6, label="causal")
    assert reader.read(ctx) == 0             # the XLA tier took them all
    layouts.inc(6, label="packed")
    assert reader.read(ctx) == 0
    layouts.inc(2, label="padded")
    assert reader.read(ctx) == 2
    layouts._reset()
    calls._reset()


def test_the_manifest_appends_the_cell_and_its_five_metrics():
    """The cell, its configuration and its five per-layer metrics are the
    last of their lists, each metric listed for this cell alone; the
    entries that were there are as they were."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = manifest["workloads"][-1]
    assert cell == {"name": CELL, "config": NAME, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "1/32" in cell["why"]
    config = manifest["configs"][-1]
    assert config["name"] == NAME and len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_rows"]
    assert [w["name"] for w in manifest["workloads"][:3]] == [
        "bert-base.train-b32-s512", "lstm-lm-650.train-b1024-t35",
        "smallthinker-21b-a3b.train-b1-s8192"]
    assert sum(w["chips"] for w in manifest["workloads"]) == 4
    tail = manifest["per_layer"][-len(READERS):]
    assert [m["name"] for m in tail] == READERS
    for m in tail:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(GRID, "layer_metrics",
                                           m["name"] + ".py"))
    assert {m["name"]: (m["layer"], m["unit"], m["better"], m["source"])
            for m in tail} == {
        "mla_attention_roofline": ("kernels", "%", "higher",
                                   "device_trace"),
        "mla_proj_ms.train": ("latent attention", "ms", "lower",
                              "device_trace"),
        "moe_layer_ms.train": ("sparse experts", "ms", "lower",
                               "device_trace"),
        "mtp_ms.train": ("multi-token prediction", "ms", "lower",
                         "device_trace"),
        "flash_padded_calls": ("kernels", "count", "lower",
                               "program_counter")}
    # no accepted metric's list of cells was touched
    for m in manifest["per_layer"][:-len(READERS)]:
        assert CELL not in m.get("workloads", [])
    for part in (("configs", NAME + ".json"), ("configs", NAME + ".py"),
                 ("traffic", TRAFFIC + ".json"), ("limits", CELL + ".json")):
        assert os.path.isfile(os.path.join(GRID, *part)), part
