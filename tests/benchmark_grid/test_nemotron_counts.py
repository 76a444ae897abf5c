"""The nemotron-3-nano-30b-a3b configuration's parameter and operation
counts, written out by hand, and the four per-layer readers the cell
brought: silent, never 0, on a trace or a program that lacks what they
read. The manifest is held by NAME: the next cell appended behind this one
must not fail this file."""
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
NAME = "nemotron-3-nano-30b-a3b"
TRAFFIC = "train-b1-s4096"
CELL = f"{NAME}.{TRAFFIC}"
READERS = ["ssd_scan_roofline", "mamba_layer_ms.train",
           "mamba_proj_ms.train", "moe_xla_products"]
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
            "grid_nemotron_" + re.sub(r"\W", "_", name),
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
    model = grid(f"configs/{NAME}.py")
    spec = model.param_spec(cfg)
    sizes = {name: math.prod(shape) for name, shape, _, _ in spec}
    assert len(sizes) == len(spec)
    assert model.pattern(cfg) == "MEMEM*EME"
    # Mamba-2: W_in 2688 x (4096 z + 4096 x + 2 x 8 x 128 B, C + 64 dt), the
    # conv's 6144 x 4 taps and bias, W_out 4096 x 2688, A_log, D and
    # dt_bias 64 each, the gated norm's gain, the layer's norm
    mamba = 2688 * 10304 + (6144 * 4 + 6144) + 4096 * 2688 + 192 + 4096 \
        + 2688
    assert (2688 * 10304, 4096 * 2688) == (27_697_152, 11_010_048)
    assert mamba == 38_744_896
    # attention: q 2688 x 4096, k and v 2688 x 256, o 4096 x 2688, the norm
    attention = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688
    assert attention == 23_399_040
    # experts: 8 held of 2 x 2688 x 1856, the shared one of 2 x 2688 x
    # 3712, the router 128 x 2688 and its bias, the norm
    one_expert = 2 * 2688 * 1856
    assert one_expert == 9_977_856 and 2 * 2688 * 3712 == 19_955_712
    experts = 8 * one_expert + 19_955_712 + 128 * 2688 + 128 + 2688
    assert experts == 100_125_440
    tables = 2 * 16384 * 2688
    total = 4 * mamba + attention + 4 * experts + tables + 2688
    assert total == 666_963_456
    assert sum(sizes.values()) == total
    by_layer = [sum(n for k, n in sizes.items() if k.startswith(f"layer{i}."))
                for i in range(9)]
    assert by_layer == [{"M": mamba, "*": attention, "E": experts}[kind]
                        for kind in "MEMEM*EME"]
    assert not [k for k in sizes if k.startswith("layer9.")]
    # whole, one expert layer: 128 experts beside the rest
    assert 128 * one_expert + 19_955_712 + 128 * 2688 + 128 + 2688 \
        == 1_297_468_160     # 1,297.5 M, 20.8 GB at 16 B
    # 16 B a parameter: float32 master, gradient, Adam m and v
    assert 16 * total == 10_671_415_296


def test_flops_per_token_by_hand(grid, cfg, traffic):
    model = grid(f"configs/{NAME}.py")
    f = model.forward_flops(cfg, traffic)
    # per token: W_in 2 x 2688 x 10304 and W_out 2 x 4096 x 2688
    assert f["mamba_proj"] == 4096 * (55_394_304 + 22_020_096)
    # the scan at the published chunk: 32 chunks of 128 x 129 / 2 causal
    # pairs; a pair costs 2 x 128 a group for its score (8 groups) and 2 x
    # 64 a head for its share of y (64 heads); a token 2 x 128 x 64 a head
    # into its chunk's state and as much out of the state that entered
    assert model.in_chunk_pairs(4096, 128) == 32 * 8256 == 264_192
    assert model.in_chunk_pairs(300, 128) == 2 * 8256 + 44 * 45 // 2
    scan = 264_192 * (8 * 256 + 64 * 128) + 2 * 4096 * 64 * 16_384
    assert scan == 11_295_260_672 and f["scan"] == scan
    assert model.scan_flops(cfg, 4096) == scan
    # attention: q 2688 x 4096, k, v 2688 x 256 each, o 4096 x 2688; 4096 x
    # 4097 / 2 causal pairs, 32 heads, 2 x 128 a score and 2 x 128 a value
    assert f["attn_proj"] == 4096 * 2 * 2688 * (4096 + 512 + 4096)
    assert model.attended_pairs(4096) == 8_390_656
    assert f["attention"] == 8_390_656 * 32 * 512 == 137_472_507_904
    assert f["router"] == 4096 * 2 * 2688 * 128
    assert f["shared"] == 4096 * 2 * 2 * 2688 * 3712
    # 6 x 8 / 128 of a held expert for the average token: 1,536 pairs a
    # layer of the 24,576 rows the sorted list has, 192 tokens an expert
    assert model.held_pairs_per_token(cfg) == 0.375
    assert f["held_experts"] == 1536 * 2 * 2 * 2688 * 1856
    assert f["head"] == 4096 * 2 * 2688 * 16384
    layers = model.layer_flops(cfg, traffic)
    assert layers["M"] == f["mamba_proj"] + scan == 328_384_643_072
    assert layers["*"] == 329_135_423_488
    assert layers["E"] == 196_947_738_624
    forward = 4 * layers["M"] + layers["*"] + 4 * layers["E"] + f["head"]
    assert round(forward / 1e12, 2) == 2.79
    assert model.flops_per_token(cfg, traffic) == 3.0 * forward / 4096
    assert model.tokens_per_step(cfg, traffic) == 4096
    # 8.37 TFLOP a step; the Mamba-2 layers' projections 45 % of it, the
    # scan's products 1.6 %, the held experts' 4.4 %
    assert round(3 * forward / 1e12, 2) == 8.37
    assert 0.45 < 4 * f["mamba_proj"] / forward < 0.46
    assert 0.016 < 4 * scan / forward < 0.017
    assert 0.043 < 4 * f["held_experts"] / forward < 0.045


def test_kernel_costs_by_hand(grid, cfg, traffic):
    costs = grid(f"configs/{NAME}.py").kernel_costs(cfg, traffic)
    assert set(costs) == {"ssd_scan", "flash_attention", "moe_experts"}
    scan = costs["ssd_scan"]
    # four layers, forward and twice that backward
    assert scan["flops"] == 4 * 3 * 11_295_260_672
    # x and y 4096 lanes, B and C 1024 each, dt 64, in bf16, 4096
    # positions, once forward and once more for the cotangents
    assert scan["bytes"] == 4 * 2 * 4096 * (2 * 4096 + 2 * 1024 + 64) * 2
    # bytes bound it on a v5e by a little: 0.82 ms against 0.69 ms
    with open(os.path.join(GRID, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    assert round(1e3 * scan["bytes"] / peaks["hbm_bytes_per_s"], 2) == 0.82
    assert round(1e3 * scan["flops"] / peaks["bf16_flops_per_s"], 2) == 0.69
    # ONE attention layer: QK^T and PV (2 x 2 x 128 a pair) over the
    # causal pairs of 32 heads, three times; q, o, do, dq at 32 x 128 and
    # k, v, dk, dv at 2 x 128, six passes in bf16
    attn = costs["flash_attention"]
    assert attn["flops"] == 3 * (4096 * 4097 // 2) * 32 * 4 * 128
    assert attn["bytes"] == 6 * 4096 * (32 + 2) * 128 * 2
    # FOUR expert layers: two products of 2 x 2688 x 1856 a pair over the
    # expected 1,536 pairs, three times; the 8 held experts' two matrices
    # read twice in bf16, their float32 gradient written once
    experts = costs["moe_experts"]
    assert experts["flops"] == 4 * 3 * 1536 * 2 * 2 * 2688 * 1856
    assert experts["bytes"] == 4 * 8 * 2 * 2688 * 1856 * (2 * 2 + 4)
    # the shared roofline reader takes either: FLOPs bound attention
    # (2.09 ms against 0.26), bytes the experts (1.87 against 3.12)
    ms = lambda cost: (
        round(1e3 * cost["flops"] / peaks["bf16_flops_per_s"], 2),
        round(1e3 * cost["bytes"] / peaks["hbm_bytes_per_s"], 2))
    assert ms(attn) == (2.09, 0.26) and ms(experts) == (1.87, 3.12)


def test_the_reference_moves_to_the_host_and_build_net_moves_back(
        grid, cfg, monkeypatch):
    """On a TPU ``loss_sum`` makes the host's CPU the default device;
    ``build_net`` puts back what it found, a caller's own setting too, and
    touches nothing where nothing was moved."""
    import jax
    model = grid(f"configs/{NAME}.py")
    tiny = dict(cfg, **cfg["tiny"])
    dot = lambda spec, a, b: None
    own = jax.devices("cpu")[1]
    for before in (None, own):
        jax.config.update("jax_default_device", before)
        try:
            model.build_net(tiny, {})                 # nothing moved yet
            assert jax.config.jax_default_device is before
            model.loss_sum(tiny, dot)                 # not a TPU: stays
            assert jax.config.jax_default_device is before
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            model.loss_sum(tiny, dot)
            model.loss_sum(tiny, dot)                 # a second follow
            assert jax.config.jax_default_device == jax.devices("cpu")[0]
            monkeypatch.undo()
            model.build_net(tiny, {})
            assert jax.config.jax_default_device is before
            assert model._DEFAULT_DEVICE_BEFORE == []
        finally:
            jax.config.update("jax_default_device", None)


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
    assert model.tokens_per_step(cfg, traffic) == 16


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------

def test_the_file_holds_every_catalog_key_and_states_the_cut(cfg):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == ["n_routed_experts", "num_hidden_layers"]
    assert set(differs) < set(cfg["reduced"])
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_rows"]
    assert [cfg[k] for k in cfg["reduced"]] == [9, 8, 16384]
    assert [cfg["published"][k] for k in cfg["reduced"]] == [52, 128,
                                                             131072]
    assert not [k for k in cfg["reduced"]
                if re.search(r"(_dim|_rank|_size)$", k)]
    # the published pattern whole; its first nine characters are built
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert [cfg["hybrid_override_pattern"].count(c) for c in "ME*"] == \
        [23, 23, 6]
    assert cfg["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert 8 * cfg["vocab_rows"] == cfg["vocab_size"] == 131072
    assert cfg["moe_router_width"] == 128 and cfg["moe_first_expert"] == 0
    assert cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        == cfg["expand"] * cfg["hidden_size"] - 1280 == 4096
    assert "16 chips share each layer" in cfg["reduced_why"]["deployment"]
    assert set(cfg["reduced"]) <= set(cfg["reduced_why"])
    for key in ("attention_positions", "gated_norm", "dt_clamp",
                "initializer_range", "embed_initializer_range",
                "router_bias_range", "conv_initializer_range",
                "A_log_range", "dt_bias_range", "recompute",
                "reference_device"):
        assert key in cfg["assumed"], key
    assert "mamba_recompute" not in cfg       # one rung, no key
    # the tiny preset changes sizes only, never the mechanisms
    assert not set(cfg["tiny"]) & {
        "mlp_hidden_act", "mamba_hidden_act", "n_shared_experts",
        "routed_scaling_factor", "norm_topk_prob", "conv_kernel",
        "use_conv_bias", "layer_norm_epsilon"}
    assert set(cfg["tiny"]["hybrid_override_pattern"]) == set("ME*")


def test_every_leaf_is_drawn_as_the_file_says(grid, cfg):
    spec = grid(f"configs/{NAME}.py").param_spec(cfg)
    drawn = {name: (kind, scale) for name, _, kind, scale in spec}
    assert drawn.pop("embed.weight") == ("normal", 10.0)
    ends = lambda tail: [n for n in drawn if n.endswith(tail)]
    assert {drawn.pop(n) for n in ends("router_bias")} == {("normal", 0.01)}
    assert {drawn.pop(n) for n in ends("conv_weight") + ends("conv_bias")} \
        == {("uniform", 0.5)}
    assert {drawn.pop(n) for n in ends("A_log")} == {("uniform", 1.39)}
    assert {drawn.pop(n) for n in ends("dt_bias")} == {("uniform", 1.0)}
    assert {scale for _, scale in drawn.values()} == {0.02}
    gains = {n for n, (kind, _) in drawn.items() if kind == "gamma"}
    assert gains == {n for n in drawn
                     if "gamma" in n or n.endswith(".D")}
    # nine layer norms, four gated norms and four D, the final norm
    assert len(gains) == 9 + 4 + 4 + 1
    # A in [0.25, 4.0]; softplus(+-1) in [0.31, 1.31]
    assert round(math.exp(-1.39), 2) == 0.25
    assert round(math.exp(1.39), 1) == 4.0
    assert [round(math.log1p(math.exp(b)), 2) for b in (-1, 1)] == [0.31,
                                                                    1.31]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

class _Model:
    @staticmethod
    def kernel_costs(cfg, traffic):
        return {"ssd_scan": {"flops": 2e12, "bytes": 1e9}}


def _ctx(grid, events, steps=10):
    tr = grid("trace_reduce.py")
    line = [tr.Event(*e) for e in events]
    return {"model": _Model, "cfg": {}, "traffic": {}, "chips": 1,
            "peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
            "traced": {"steps": steps}, "spans": [], "counters": {},
            "trace": tr.reduce_lines([line], (0.0, 10.0))}


def test_readers_read_their_scopes(grid):
    """The scope paths are the program's own (the step's optimized HLO at
    the tiny preset): forward ``jvp(mamba_mixer)/<scope>/..``, and what
    the segment's checkpoint makes again in the backward
    ``transpose(jvp(mamba_mixer))/../checkpoint/<scope>/..``."""
    pre = "jit(fused_step)/loss_and_grad/"
    again = pre + "transpose(jvp(mamba_mixer))/loss_and_grad/" \
        "jvp(mamba_mixer)/checkpoint/"
    ctx = _ctx(grid, [
        (0.0, 0.5, "a", pre + "jvp(mamba_mixer)/mamba_proj/"
         "fully_connected/dot_general"),
        (0.5, 1.0, "b", pre + "transpose(jvp(mamba_mixer))/mamba_proj/"
         "fully_connected/dot_general"),
        (1.0, 1.5, "c", pre + "jvp(mamba_mixer)/ssd_scan/"
         "bcgrts,bcsgrp->bctgrp/dot_general"),
        (1.5, 2.0, "d", again + "ssd_scan/while/body/"
         "dynamic_update_slice"),
        (2.0, 2.25, "e", again + "rematted_computation/mamba_conv/mul"),
        (2.25, 2.5, "f", pre + "jvp(mamba_mixer)/mamba_norm/rsqrt"),
        (2.5, 3.0, "g", pre + "jvp(moe_experts)/ragged-dot"),
        (3.0, 4.0, "h", pre + "jvp(fully_connected)/dot_general")])
    read = lambda m: grid(f"layer_metrics/{m}.py").read(ctx)
    # 10 steps x 2e12 / 1e14 = 0.2 s of the 1.0 s under the scope, the
    # backward's second making of the forward among them
    assert read("ssd_scan_roofline") == pytest.approx(100 * 0.2 / 1.0)
    assert read("mamba_layer_ms.train") == pytest.approx(250.0)
    assert read("mamba_proj_ms.train") == pytest.approx(100.0)


@pytest.mark.parametrize("metric", READERS)
def test_readers_are_silent_without_what_they_read(grid, metric):
    """The parent's program has none of these scopes, and a process that
    traced no expert layer has no product to count: nothing is read, and
    least of all a 0."""
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


def test_xla_products_reads_the_programs_counters(grid, cfg):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import names
    products = telemetry.registry().counter(names.MOE_GROUPED_DOT,
                                            label_key="tier")
    layers = telemetry.registry().counter(names.MOE_DISPATCH,
                                          label_key="path")
    products._reset()
    layers._reset()
    reader = grid("layer_metrics/moe_xla_products.py")
    ctx = {"trace": {}, "counters": {}, "cfg": cfg}
    assert reader.read(ctx) is None          # no expert layer traced
    # the step traced twice: four expert layers each time, two products a
    # layer by lax.ragged_dot
    layers.inc(8, label="grouped")
    products.inc(16, label="xla")
    assert reader.read(ctx) == 8
    # a configuration that names no pattern of layers: silent
    assert reader.read(dict(ctx, cfg={})) is None
    # the kernels take them all: five sites a layer, none by XLA
    products._reset()
    products.inc(40, label="pallas")
    assert reader.read(ctx) == 0
    products._reset()
    layers._reset()


def test_the_manifest_holds_the_cell_and_its_four_metrics_by_name():
    """Wherever they stand: a later PR appends behind them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": NAME, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "1/16" in cell["why"]
    config, = [c for c in manifest["configs"] if c["name"] == NAME]
    assert len(config["why"]) <= 200
    assert config["file"] == f"benchmark/grid/configs/{NAME}.json"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_rows"]
    with open(os.path.join(GRID, "configs", NAME + ".json")) as f:
        assert json.load(f)["source"] == config["source"]
    # no other cell runs this configuration, and it asks for one chip
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
        "ssd_scan_roofline": ("kernels", "%", "higher", "device_trace"),
        "mamba_layer_ms.train": ("state-space mixer", "ms", "lower",
                                 "device_trace"),
        "mamba_proj_ms.train": ("state-space mixer", "ms", "lower",
                                "device_trace"),
        "moe_xla_products": ("sparse experts", "count", "lower",
                             "program_counter")}
    # no metric that was there lists this cell
    for m in manifest["per_layer"]:
        if m["name"] not in READERS:
            assert CELL not in m.get("workloads", [])
    for part in (("configs", NAME + ".json"), ("configs", NAME + ".py"),
                 ("traffic", TRAFFIC + ".json"), ("limits", CELL + ".json")):
        assert os.path.isfile(os.path.join(GRID, *part)), part


def test_follow_one_makes_one_follow_a_process(tmp_path):
    """follow_one.py at the tiny size, the route the full-size controls of
    ``limits/<cell>.json`` took: the float32 follow, then in processes of
    their own the bf16 witness and the fp8 control against it; the tiny
    ``reference_limits`` stand between their first gradients."""
    import subprocess
    with open(os.path.join(GRID, "limits", CELL + ".json")) as f:
        limit = json.load(f)["tiny"]["reference_limits"]["grad1"]

    def follow(precision, against=None):
        out = tmp_path / f"{precision}.json"
        argv = [sys.executable, os.path.join(GRID, "follow_one.py"),
                "--workload", CELL, "--seed", "1", "--precision", precision,
                "--out", str(out), "--rehearse"]
        if against:
            argv += ["--against", str(against)]
        proc = subprocess.run(
            argv, cwd=ROOT, capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3"))
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert proc.stdout == ""
        with open(out) as f:
            assert len(json.load(f)["loss"]) == 3
        said = [line[line.index("{"):] for line in proc.stderr.splitlines()
                if line.startswith("[grid") and '"readings"' in line]
        return out, [json.loads(line) for line in said]
    exact, said = follow("f32")
    assert said == []
    _, (bf16,) = follow("bf16", exact)
    _, (fp8,) = follow("fp8", exact)
    assert bf16["readings"]["grad1"][0] < limit < fp8["readings"]["grad1"][0]
    assert set(fp8["compared"]) == {"grad1", "change3"}
    assert fp8["precision"] == "fp8" and fp8["seed"] == 1
