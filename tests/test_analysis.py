"""mx.analysis checker tests: golden known-bad programs, each producing
exactly the expected finding — the analyzers are load-bearing for tier-1
(test_fused_step / test_zero_shard assert through them), so THEY need
regression coverage of both directions: known-bad programs must fire the
right rule, known-good programs must stay silent.
"""
import os
import textwrap

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu.analysis import guard as tguard
from mxnet_tpu.analysis.hlo import (parse_hlo, parse_replica_groups,
                                    parse_shape_elements)
from mxnet_tpu.analysis.lint import (filter_allowed, lint_function,
                                     lint_source)
from mxnet_tpu.analysis.program import (dtype_drift_scan, expect_mode,
                                        host_transfer_scan)
from mxnet_tpu.analysis.report import ProgramReport
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.gluon import loss as gloss


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

_CANNED_HLO = """\
HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {2}: (1, {}, may-alias) }, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

ENTRY %main (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %all-reduce = f32[8]{0} all-reduce(f32[8]{0} %p0), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, to_apply=%add
  %dynamic-slice = f32[1]{0} dynamic-slice(f32[8]{0} %all-reduce, s32[] %pid), dynamic_slice_sizes={1}
  %all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p1), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %add.9 = f32[8]{0} add(f32[8]{0} %all-reduce.1, f32[8]{0} %p1)
  %reduce-scatter = f32[1]{0} reduce-scatter(f32[8]{0} %p1), channel_id=3, replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add
  %all-gather = f32[8]{0} all-gather(f32[1]{0} %reduce-scatter), channel_id=4, replica_groups=[1,8]<=[8], dimensions={0}
}
"""


def test_hlo_parser_aliases_and_ops():
    mod = parse_hlo(_CANNED_HLO, num_devices=8)
    assert mod.input_output_alias == [(0, 0), (2, 1)]
    assert mod.ops["all-reduce"].opcode == "all-reduce"
    assert mod.ops["all-reduce"].elements == 8
    assert mod.consumers("all-reduce")[0].opcode == "dynamic-slice"


def test_hlo_replica_group_forms():
    iota = parse_replica_groups("replica_groups=[2,4]<=[8]", 8)
    assert iota == [(0, 1, 2, 3), (4, 5, 6, 7)]
    expl = parse_replica_groups("replica_groups={{0,1},{2,3}}", 4)
    assert expl == [(0, 1), (2, 3)]
    t = parse_replica_groups("replica_groups=[4,2]<=[2,4]T(1,0)", 8)
    assert t == [(0, 4), (1, 5), (2, 6), (3, 7)]


def test_hlo_shape_elements():
    assert parse_shape_elements("f32[4,4]{1,0}") == (16, "f32", 64)
    n, dt, b = parse_shape_elements("(f32[2]{0}, bf16[8]{0})")
    assert (n, dt, b) == (10, "f32", 2 * 4 + 8 * 2)


def test_census_classifies_decomposed_reduce_scatter():
    """The CPU backend's all-reduce + 1/N dynamic-slice pattern counts
    as a (decomposed) reduce_scatter; a consumed-in-full all-reduce
    stays an all_reduce."""
    census = analysis.collective_census(_CANNED_HLO, num_devices=8)
    kinds = census.by_kind
    assert kinds["reduce_scatter"] == 2    # 1 literal + 1 decomposed
    assert kinds["all_reduce"] == 1        # consumed in full -> genuine
    assert kinds["all_gather"] == 1
    dec = [op for op in census.ops if op.decomposed]
    assert len(dec) == 1 and dec[0].name == "all-reduce"


# ---------------------------------------------------------------------------
# golden known-bad programs
# ---------------------------------------------------------------------------

def test_known_bad_leaked_host_callback():
    """A pure_callback smuggled into the step: the jaxpr scan must
    report exactly one host-transfer finding."""
    def leaky(x):
        y = jax.pure_callback(
            lambda a: onp.asarray(a) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y.sum()

    jaxpr = jax.make_jaxpr(leaky)(jnp.ones((4,)))
    findings = host_transfer_scan(jaxpr)
    assert len(findings) == 1
    assert findings[0].rule == "host-transfer"
    assert "callback" in findings[0].message
    # known-good twin: no callback, no finding
    clean = jax.make_jaxpr(lambda x: (x * 2).sum())(jnp.ones((4,)))
    assert host_transfer_scan(clean) == []


def test_known_bad_broken_donation():
    """Donation broken by a dtype-changing output: jax silently DROPS
    the unusable donation at lowering — the audit catches it because
    the caller's expectation (2 donated) exceeds what XLA aliased."""
    def f(x, y):
        return x.astype(jnp.float16), x + y   # x's donation unusable

    import warnings
    lowered = jax.jit(f, donate_argnums=(0, 1)).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8)))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")      # jax's donation warning
        report = analysis.analyze_lowered(lowered, expected_donated=2)
    assert not report.donation.ok
    assert report.donation.aliased == 1
    rules = [f.rule for f in report.findings]
    assert "donation-copy" in rules
    # known-good twin: shape/dtype-preserving update aliases both
    g = jax.jit(lambda x, y: (x + 1, y * 2), donate_argnums=(0, 1))
    rep2 = analysis.analyze_lowered(
        g.lower(jnp.ones((8, 8)), jnp.ones((8, 8))), expected_donated=2)
    assert rep2.donation.ok and rep2.donation.aliased == 2


def test_known_bad_accidental_f64_upcast():
    """f32 -> f64 widening is an error-severity drift, never blessed."""
    with jax.enable_x64():
        jaxpr = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64).sum())(
                jnp.ones((4,), jnp.float32))
    findings = dtype_drift_scan(jaxpr)
    assert any(f.rule == "dtype-drift" and f.severity == "error"
               and "float64" in f.message for f in findings)


def test_known_bad_bf16_widening_and_blessing():
    """bf16 -> f32 widening: flagged by default, blessed under the
    multi-precision master list."""
    jaxpr = jax.make_jaxpr(
        lambda x: x.astype(jnp.float32) * 2.0)(
            jnp.ones((4,), jnp.bfloat16))
    flagged = dtype_drift_scan(jaxpr)
    assert len(flagged) == 1 and not flagged[0].blessed
    blessed = dtype_drift_scan(
        jaxpr, blessed=[("bfloat16", "float32")])
    assert len(blessed) == 1 and blessed[0].blessed


def test_known_bad_allreduce_where_reduce_scatter_expected():
    """A zero-sharded-claiming program whose gradients actually
    all-reduce (replicated update): expect_mode must flag the missing
    reduce-scatter/all-gather AND the unit-sized all-reduce."""
    hlo = textwrap.dedent("""\
    HloModule jit_bad, is_scheduled=true, entry_computation_layout={(f32[1024]{0})->f32[1024]{0}}

    ENTRY %main (p0: f32[1024]) -> f32[1024] {
      %p0 = f32[1024]{0} parameter(0)
      %all-reduce = f32[1024]{0} all-reduce(f32[1024]{0} %p0), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
      %add.1 = f32[1024]{0} add(f32[1024]{0} %all-reduce, f32[1024]{0} %p0)
    }
    """)
    report = ProgramReport(mode="zero")
    report.collectives = analysis.collective_census(hlo, num_devices=8)
    report.meta["unit_sizes"] = [1024]
    expect_mode(report, mode="zero", axis=None)
    rules = sorted({f.rule for f in report.findings})
    assert rules == ["collective-mismatch", "per-param-allreduce"]
    assert not report.ok


# ---------------------------------------------------------------------------
# analyze_step + compile_step wiring
# ---------------------------------------------------------------------------

def _tiny_setup(bs=8):
    onp.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = mx.nd.array(onp.random.randn(bs, 8).astype("float32"))
    y = mx.nd.array(onp.random.randint(0, 4, size=(bs,)).astype("int32"))
    net(x)
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9},
                      kvstore=None)
    return net, trainer, loss_blk, x, y


def test_analyze_step_plain_fused_clean():
    net, trainer, loss_blk, x, y = _tiny_setup()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    step(x, y)
    report = step.analyze(x, y)
    assert report.mode == "fused"
    assert report.ok, report.summary()
    assert report.collectives.ops == []
    d = report.donation
    assert d.expected == 8 and d.aliased == 8 and not d.copied
    assert d.donated_bytes > 0
    assert report.n_traces == 1          # analysis lower is not a retrace
    assert step.n_traces == 1
    # cached per bucket: second call returns the same object
    assert step.analyze(x, y) is report


def test_analyze_step_eager_reports_not_compiled():
    net, trainer, loss_blk, x, y = _tiny_setup()

    def hostile(a, b):
        out = net(a)
        _ = out.asnumpy().sum()
        return loss_blk(out, b)

    step = trainer.compile_step(hostile)
    step(x, y)
    assert step.mode == "eager"
    report = step.analyze(x, y)
    assert any(f.rule == "not-compiled" for f in report.findings)
    assert report.ok          # warn severity: no hard failure


def test_compile_step_analyze_report_mode():
    net, trainer, loss_blk, x, y = _tiny_setup()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b),
                                analyze="report")
    step(x, y)
    assert step.analysis_report is not None
    assert step.analysis_report.ok


def test_compile_step_analyze_env_default(monkeypatch):
    monkeypatch.setenv("MXNET_ANALYSIS", "report")
    net, trainer, loss_blk, x, y = _tiny_setup()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    step(x, y)
    assert step.analysis_report is not None


def test_compile_step_analyze_raise_on_host_callback():
    """analyze='raise': a loss_fn smuggling a host callback into the
    (otherwise traceable) program raises after the first step.
    jax.debug.print is the canonical culprit — it traces fine (unlike
    pure_callback under JVP, which would demote to eager and be caught
    by the transfer guard instead) but plants a per-step host callback
    in the compiled program."""
    net, trainer, loss_blk, x, y = _tiny_setup()

    def leaky(a, b):
        out = net(a)
        jax.debug.print("activations {}", out._data.sum())
        return loss_blk(out, b)

    step = trainer.compile_step(leaky, analyze="raise")
    with pytest.raises(MXNetError, match="host"):
        step(x, y)


def test_explain_retrace_shapes():
    net, trainer, loss_blk, x, y = _tiny_setup(bs=8)
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    step(x, y)
    assert "only one program" in step.explain_retrace()
    x2 = mx.nd.array(onp.random.randn(4, 8).astype("float32"))
    y2 = mx.nd.array(onp.random.randint(0, 4, size=(4,))
                     .astype("int32"))
    step(x2, y2)
    assert step.n_traces == 2
    why = step.explain_retrace()
    assert "shapes" in why and "(8, 8)" in why and "(4, 8)" in why


# ---------------------------------------------------------------------------
# transfer guard
# ---------------------------------------------------------------------------

def test_transfer_guard_raise_inside_scope():
    a = mx.nd.array(onp.ones((3,), "float32"))
    with pytest.raises(MXNetError, match="device->host sync"):
        with tguard.transfer_guard("raise"):
            a.asnumpy()
    a.asnumpy()                          # outside the scope: fine


def test_transfer_guard_log_records_events():
    tguard.clear_events()
    a = mx.nd.array(onp.ones((3,), "float32"))
    with tguard.transfer_guard("log"):
        a.asnumpy()
        float(a.sum())                   # item() -> asnumpy() funnel
    kinds = [k for k, _ in tguard.events()]
    assert kinds.count("asnumpy") == 2   # one per sync, no double count
    tguard.clear_events()


def test_transfer_guard_allow_transfers():
    a = mx.nd.array(onp.ones((3,), "float32"))
    with tguard.transfer_guard("raise"):
        with tguard.allow_transfers("blessed"):
            a.asnumpy()                  # no raise


def test_transfer_guard_env_catches_planted_asnumpy(monkeypatch):
    """The acceptance path: MXNET_TRANSFER_GUARD=raise + a planted
    .asnumpy() in a compiled region -> MXNetError naming the sync, from
    inside the step call."""
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    net, trainer, loss_blk, x, y = _tiny_setup()

    def hostile(a, b):
        out = net(a)
        _ = out.asnumpy().sum()          # the plant
        return loss_blk(out, b)

    step = trainer.compile_step(hostile)
    with pytest.raises(MXNetError, match="asnumpy"):
        step(x, y)


def test_transfer_guard_env_log_keeps_training(monkeypatch):
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "log")
    tguard.clear_events()
    net, trainer, loss_blk, x, y = _tiny_setup()

    def hostile(a, b):
        out = net(a)
        _ = out.asnumpy().sum()
        return loss_blk(out, b)

    step = trainer.compile_step(hostile)
    step(x, y)                           # falls back to eager, trains
    assert step.mode == "eager"
    assert any(k == "asnumpy" for k, _ in tguard.events())
    tguard.clear_events()


def test_transfer_guard_clean_step_quiet(monkeypatch):
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    net, trainer, loss_blk, x, y = _tiny_setup()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    for _ in range(2):
        step(x, y)                       # no spurious flags
    assert step.mode == "fused"


# ---------------------------------------------------------------------------
# source lint
# ---------------------------------------------------------------------------

def _lint(body: str):
    src = ("class B:\n"
           "    def forward(self, x, mask=None):\n"
           + textwrap.indent(textwrap.dedent(body), "        "))
    return lint_source(src, filename="snippet.py")


def test_lint_catches_each_rule():
    assert [f.rule for f in _lint("v = x.asnumpy()\nreturn x\n")] \
        == ["MXA001"]
    assert [f.rule for f in _lint("s = float(x.sum())\nreturn x\n")] \
        == ["MXA002"]
    assert [f.rule for f in _lint(
        "if x.sum() > 0:\n    x = x * 2\nreturn x\n")] == ["MXA003"]
    assert [f.rule for f in _lint(
        "import numpy as np\nn = np.random.uniform()\nreturn x\n")] \
        == ["MXA004"]


def test_lint_static_conditions_not_flagged():
    assert _lint("if x.shape[0] > 2:\n    x = x + 1\nreturn x\n") == []
    assert _lint("if mask is not None:\n    x = x + mask\nreturn x\n") \
        == []
    assert _lint("if len(x) > 1:\n    x = x + 1\nreturn x\n") == []


def test_lint_taint_propagates_through_assignment():
    fs = _lint("y = x * 2\nz = y + 1\nif z.min() < 0:\n"
               "    z = -z\nreturn z\n")
    assert [f.rule for f in fs] == ["MXA003"]


def test_lint_inline_allow_blesses():
    fs = _lint("v = x.asnumpy()  # mx-lint: allow=MXA001\nreturn x\n")
    assert len(fs) == 1 and fs[0].blessed
    assert filter_allowed(fs, []) == []


def test_lint_function_on_live_loss_fn():
    def bad_loss(out, label):
        s = out.asnumpy().sum()
        return out.sum() + s

    fs = lint_function(bad_loss)
    assert [f.rule for f in fs] == ["MXA001"]
    assert os.path.basename(__file__).replace(".pyc", ".py") \
        in fs[0].where


def test_lint_cli_roundtrip(tmp_path):
    from mxnet_tpu.analysis.lint import main as lint_main
    p = tmp_path / "m.py"
    p.write_text("class B:\n    def forward(self, x):\n"
                 "        return x.asnumpy()\n")
    assert lint_main([str(p)]) == 1
    ok = tmp_path / "ok.py"
    ok.write_text("class B:\n    def forward(self, x):\n"
                  "        return x * 2\n")
    assert lint_main([str(ok)]) == 0


def test_report_to_dict_and_summary():
    net, trainer, loss_blk, x, y = _tiny_setup()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    step(x, y)
    report = step.analyze(x, y)
    d = report.to_dict()
    assert d["mode"] == "fused" and d["n_traces"] == 1
    assert d["donated_bytes"] > 0 and d["findings"] == []
    s = report.summary()
    assert "donation" in s and "collectives" in s
