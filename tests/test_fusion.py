"""Fusion-census tests (mx.analysis.fusion — the arXiv:2301.13062
ideal-fusion audit): nested-fusion HLO parsing, the FLOP/boundary
models, golden known-bad programs (planted stranded transpose, planted
large f32 boundary materialization), the compute-/memory-bound
classification, the MXA005 unroll lint rule, and the per-leg baseline
regression gate over the checked-in tests/fixtures/fusion_baselines.json
(the tier-1 ``lint``-marked sweep at the bottom).
"""
import json
import os
import textwrap

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.analysis import fusion as afusion
from mxnet_tpu.analysis.hlo import parse_hlo
from mxnet_tpu.analysis.lint import lint_source
from mxnet_tpu.analysis.program import dtype_drift_scan, expect_mode, \
    host_transfer_scan
from mxnet_tpu.analysis.report import ProgramReport
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, nn, rnn
from mxnet_tpu.gluon import loss as gloss

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
BASELINES = os.path.join(FIXTURES, "fusion_baselines.json")


# ---------------------------------------------------------------------------
# nested-fusion HLO parsing
# ---------------------------------------------------------------------------

_NESTED_HLO = textwrap.dedent("""\
HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[64,64]{1,0})->f32[64,64]{1,0}}

%region_0.9 (Arg_0.10: f32[], Arg_1.11: f32[]) -> f32[] {
  %Arg_0.10 = f32[] parameter(0)
  %Arg_1.11 = f32[] parameter(1)
  ROOT %add.12 = f32[] add(f32[] %Arg_0.10, f32[] %Arg_1.11)
}

%fused_computation (param_0.1: f32[64,64]) -> f32[64,64] {
  %param_0.1 = f32[64,64]{1,0} parameter(0)
  %tanh.1 = f32[64,64]{1,0} tanh(f32[64,64]{1,0} %param_0.1)
  %convert.3 = f64[64,64]{1,0} convert(f32[64,64]{1,0} %tanh.1)
  %convert.4 = f32[64,64]{1,0} convert(f64[64,64]{1,0} %convert.3)
  ROOT %add.1 = f32[64,64]{1,0} add(f32[64,64]{1,0} %convert.4, f32[64,64]{1,0} %param_0.1)
}

ENTRY %main.1 (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  %dot.1 = f32[64,64]{1,0} dot(f32[64,64]{1,0} %p0, f32[64,64]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %tanh_add_fusion = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %dot.1), kind=kLoop, calls=%fused_computation
  %constant.2 = f32[] constant(0)
  ROOT %reduce-window.1 = f32[64,64]{1,0} reduce-window(f32[64,64]{1,0} %tanh_add_fusion, f32[] %constant.2), window={size=1x1}, to_apply=%region_0.9
}
""")


def test_parser_builds_computations():
    mod = parse_hlo(_NESTED_HLO)
    assert set(mod.computations) == {"region_0.9", "fused_computation",
                                     "main.1"}
    assert mod.entry == "main.1"
    assert mod.computations["main.1"].is_entry
    # ops attached to their computation
    assert mod.ops["tanh.1"].computation == "fused_computation"
    assert mod.ops["dot.1"].computation == "main.1"
    assert mod.ops["add.12"].computation == "region_0.9"


def test_parser_links_fusion_bodies():
    mod = parse_hlo(_NESTED_HLO)
    fop = mod.ops["tanh_add_fusion"]
    assert fop.fusion_kind == "loop"
    assert fop.called == {"calls": ["fused_computation"]}
    body = [o.name for o in mod.fused_ops(fop)]
    assert body == ["param_0.1", "tanh.1", "convert.3", "convert.4",
                    "add.1"]
    # parent attribution from a body op back to its fusion
    assert mod.parent_fusion(mod.ops["tanh.1"]).name == "tanh_add_fusion"
    assert mod.parent_fusion(mod.ops["dot.1"]) is None


def test_parser_schedulable_vs_kernel_internal():
    mod = parse_hlo(_NESTED_HLO)
    sched = {c.name for c in mod.schedulable_computations()}
    assert sched == {"main.1"}
    assert mod.computations["fused_computation"].kernel_internal
    assert mod.computations["region_0.9"].kernel_internal  # to_apply
    # ROOT detection
    assert mod.ops["reduce-window.1"].is_root
    assert not mod.ops["dot.1"].is_root


def test_parser_typed_operands():
    mod = parse_hlo(_NESTED_HLO)
    dot = mod.ops["dot.1"]
    assert dot.operand_types == ["f32[64,64]{1,0}", "f32[64,64]{1,0}"]
    assert dot.operand_bytes(0) == 64 * 64 * 4
    # reduce-window's scalar init operand
    rw = mod.ops["reduce-window.1"]
    assert rw.operand_bytes(1) == 4


def test_parser_while_bodies_are_schedulable():
    hlo = textwrap.dedent("""\
    HloModule jit_loop, is_scheduled=true, entry_computation_layout={(s32[])->s32[]}

    %while_body (param.1: s32[]) -> s32[] {
      %param.1 = s32[] parameter(0)
      %constant.1 = s32[] constant(1)
      ROOT %add.1 = s32[] add(s32[] %param.1, s32[] %constant.1)
    }

    %while_cond (param.0: s32[]) -> pred[] {
      %param.0 = s32[] parameter(0)
      %constant.2 = s32[] constant(8)
      ROOT %compare.1 = pred[] compare(s32[] %param.0, s32[] %constant.2), direction=LT
    }

    ENTRY %main.1 (p0: s32[]) -> s32[] {
      %p0 = s32[] parameter(0)
      ROOT %while.1 = s32[] while(s32[] %p0), condition=%while_cond, body=%while_body
    }
    """)
    mod = parse_hlo(hlo)
    w = mod.ops["while.1"]
    assert w.called == {"condition": ["while_cond"],
                       "body": ["while_body"]}
    sched = {c.name for c in mod.schedulable_computations()}
    assert sched == {"main.1", "while_body", "while_cond"}


# ---------------------------------------------------------------------------
# FLOP model
# ---------------------------------------------------------------------------

def test_flop_model_dot_exact():
    mod = parse_hlo(_NESTED_HLO)
    # [64,64] @ [64,64]: 2*M*N*K
    assert afusion.op_flops(mod.ops["dot.1"]) == 2 * 64 * 64 * 64


def test_flop_model_convolution():
    line = ("  %convolution.1 = f32[1,8,8,4]{3,2,1,0} convolution("
            "f32[1,8,8,2]{3,2,1,0} %p0, f32[3,3,2,4]{3,2,1,0} %k), "
            "window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f")
    mod = parse_hlo("ENTRY %main.1 (p0: f32[1,8,8,2]) -> f32[1,8,8,4] "
                    "{\n" + line + "\n}\n")
    conv = mod.ops["convolution.1"]
    # 3*3*2 MACs per output element (kernel elems / out features)
    assert afusion.op_flops(conv) == 2 * (8 * 8 * 4) * (3 * 3 * 2)


def test_flop_model_fusion_sums_body():
    mod = parse_hlo(_NESTED_HLO)
    fop = mod.ops["tanh_add_fusion"]
    # tanh + 2 converts + add, 64*64 elements each
    assert afusion.op_flops(fop, mod) == 4 * 64 * 64


# ---------------------------------------------------------------------------
# ideal-fusion diff: golden known-bad programs
# ---------------------------------------------------------------------------

def _stranded_hlo(transposed=True):
    """Two loop fusions with a transpose (known-bad) or a direct edge
    (known-good twin) between them."""
    mid = ("  %transpose.7 = f32[512,512]{1,0} transpose(f32[512,512]"
           "{1,0} %scale_fusion), dimensions={1,0}\n"
           if transposed else "")
    feed = "%transpose.7" if transposed else "%scale_fusion"
    return textwrap.dedent("""\
    HloModule jit_bad, is_scheduled=true, entry_computation_layout={(f32[512,512]{1,0})->f32[512,512]{1,0}}

    %fused_computation (param_0.1: f32[512,512]) -> f32[512,512] {
      %param_0.1 = f32[512,512]{1,0} parameter(0)
      %constant.1 = f32[] constant(2)
      %broadcast.1 = f32[512,512]{1,0} broadcast(f32[] %constant.1), dimensions={}
      ROOT %multiply.1 = f32[512,512]{1,0} multiply(f32[512,512]{1,0} %param_0.1, f32[512,512]{1,0} %broadcast.1)
    }

    %fused_computation.1 (param_0.2: f32[512,512]) -> f32[512,512] {
      %param_0.2 = f32[512,512]{1,0} parameter(0)
      %tanh.1 = f32[512,512]{1,0} tanh(f32[512,512]{1,0} %param_0.2)
      ROOT %add.1 = f32[512,512]{1,0} add(f32[512,512]{1,0} %tanh.1, f32[512,512]{1,0} %param_0.2)
    }

    ENTRY %main.1 (p0: f32[512,512]) -> f32[512,512] {
      %p0 = f32[512,512]{1,0} parameter(0)
      %scale_fusion = f32[512,512]{1,0} fusion(f32[512,512]{1,0} %p0), kind=kLoop, calls=%fused_computation
    """) + mid + (
        "  ROOT %tanh_add_fusion = f32[512,512]{1,0} fusion(f32[512,512]"
        "{1,0} " + feed + "), kind=kLoop, calls=%fused_computation.1\n"
        "}\n")


def test_known_bad_stranded_transpose_between_fusions():
    report = afusion.fusion_census(_stranded_hlo(True))
    assert len(report.stranded) == 1
    s = report.stranded[0]
    assert s.opcode == "transpose" and s.bytes == 512 * 512 * 4
    assert s.producer == "scale_fusion"
    assert s.consumers == ["tanh_add_fusion"]
    assert any(f.rule == "stranded-op" for f in report.findings)
    # known-good twin: direct fusion->fusion edge, nothing stranded
    clean = afusion.fusion_census(_stranded_hlo(False))
    assert clean.stranded == []
    assert not any(f.rule == "stranded-op" for f in clean.findings)


def test_stranded_floor_suppresses_scalar_glue():
    report = afusion.fusion_census(_stranded_hlo(True),
                                   stranded_floor_bytes=512 * 512 * 4 + 1)
    assert report.stranded == []


_BIG_BOUNDARY_HLO = textwrap.dedent("""\
HloModule jit_big, is_scheduled=true, entry_computation_layout={(f32[2048,2048]{1,0})->f32[2048,2048]{1,0}}

%fused_computation (param_0.1: f32[2048,2048]) -> f32[2048,2048] {
  %param_0.1 = f32[2048,2048]{1,0} parameter(0)
  ROOT %exp.1 = f32[2048,2048]{1,0} exponential(f32[2048,2048]{1,0} %param_0.1)
}

%fused_computation.1 (param_0.2: f32[2048,2048], param_1.2: f32[2048,2048]) -> f32[2048,2048] {
  %param_0.2 = f32[2048,2048]{1,0} parameter(0)
  %param_1.2 = f32[2048,2048]{1,0} parameter(1)
  ROOT %add.1 = f32[2048,2048]{1,0} add(f32[2048,2048]{1,0} %param_0.2, f32[2048,2048]{1,0} %param_1.2)
}

ENTRY %main.1 (p0: f32[2048,2048]) -> f32[2048,2048] {
  %p0 = f32[2048,2048]{1,0} parameter(0)
  %dot.1 = f32[2048,2048]{1,0} dot(f32[2048,2048]{1,0} %p0, f32[2048,2048]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %exp_fusion = f32[2048,2048]{1,0} fusion(f32[2048,2048]{1,0} %dot.1), kind=kLoop, calls=%fused_computation
  ROOT %add_fusion = f32[2048,2048]{1,0} fusion(f32[2048,2048]{1,0} %exp_fusion, f32[2048,2048]{1,0} %dot.1), kind=kOutput, calls=%fused_computation.1
}
""")


def test_known_bad_large_boundary_materialization():
    report = afusion.fusion_census(_BIG_BOUNDARY_HLO)
    # ranked: the 16 MiB dot output (2 consumers) first
    assert report.boundaries[0].name == "dot.1"
    assert report.boundaries[0].bytes == 2048 * 2048 * 4
    assert report.boundary_bytes == 2 * 2048 * 2048 * 4
    bf = [f for f in report.findings if f.rule == "fusion-boundary"]
    assert bf and "dot.1" in bf[0].where
    # fusion kinds parsed: one kLoop + one kOutput
    assert report.by_kind() == {"dot": 1, "loop": 1, "output": 1}


def test_bound_classification_against_ridge():
    report = afusion.fusion_census(_BIG_BOUNDARY_HLO)
    dot = [k for k in report.kernels if k.kind == "dot"][0]
    # 2048^3 matmul: intensity ~341 flop/byte, above the ~180 ridge
    assert dot.bound() == "compute"
    loop = [k for k in report.kernels if k.kind == "loop"][0]
    assert loop.bound() == "memory"
    # flop-weighted: the dot dominates
    assert report.compute_bound_pct > 99.0
    # ridge override flips the classification
    assert dot.bound(ridge=1e9) == "memory"


def test_report_roundtrips_to_dict():
    report = afusion.fusion_census(_BIG_BOUNDARY_HLO)
    d = report.to_dict()
    assert d["n_fusions"] == 2 and d["n_kernels"] == 3
    assert d["boundary_bytes"] == report.boundary_bytes
    assert d["kernels"][0]["bound"] in ("compute", "memory")
    brief = report.brief()
    assert set(brief) == {"n_fusions", "stranded_ops", "boundary_bytes",
                          "compute_bound_pct"}
    assert "fusions=2" in report.summary_line()
    assert "dot.1" in report.table()


# ---------------------------------------------------------------------------
# fused-body visibility for the other HLO scans (satellite)
# ---------------------------------------------------------------------------

def test_dtype_drift_hlo_fallback_sees_inside_fusions():
    """A widening f32->f64 convert XLA pulled into a fusion body: the
    jaxpr-less scan must find it and name the kernel it hides in."""
    findings = dtype_drift_scan(None, hlo_text=_NESTED_HLO)
    wide = [f for f in findings if "float64" in f.message]
    assert len(wide) == 1
    assert wide[0].severity == "error"
    assert "inside fusion %tanh_add_fusion" in wide[0].where
    # the f64->f32 narrowing twin is free: not flagged
    assert all("float64 -> float32" not in f.message for f in findings)


def test_host_transfer_scan_attributes_fusion_body():
    hlo = textwrap.dedent("""\
    HloModule jit_leak, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

    %fused_computation (param_0.1: f32[8]) -> f32[8] {
      %param_0.1 = f32[8]{0} parameter(0)
      ROOT %custom-call.1 = f32[8]{0} custom-call(f32[8]{0} %param_0.1), custom_call_target="xla_python_cpu_callback"
    }

    ENTRY %main.1 (p0: f32[8]) -> f32[8] {
      %p0 = f32[8]{0} parameter(0)
      ROOT %cb_fusion = f32[8]{0} fusion(f32[8]{0} %p0), kind=kCustom, calls=%fused_computation
    }
    """)
    findings = host_transfer_scan(None, hlo)
    assert len(findings) == 1
    assert "inside fusion %cb_fusion" in findings[0].where


# ---------------------------------------------------------------------------
# expect_mode fusion pack
# ---------------------------------------------------------------------------

def test_expect_mode_escalates_stranded_ops():
    report = ProgramReport(mode="fused")
    report.fusion = afusion.fusion_census(_stranded_hlo(True))
    expect_mode(report, mode="fused")
    errs = [f for f in report.findings
            if f.rule == "stranded-op" and f.severity == "error"]
    assert len(errs) == 1 and "transpose" in errs[0].message
    assert not report.ok
    # clean program: no escalation
    clean = ProgramReport(mode="fused")
    clean.fusion = afusion.fusion_census(_stranded_hlo(False))
    expect_mode(clean, mode="fused")
    assert clean.ok


# ---------------------------------------------------------------------------
# baseline regression gate
# ---------------------------------------------------------------------------

def _report_for(n_fusions=10, stranded=0, boundary=1000):
    rep = afusion.FusionReport(boundary_bytes=boundary)
    for i in range(n_fusions):
        rep.kernels.append(afusion.FusionKernel(
            name=f"f{i}", kind="loop", computation="main", n_ops=2,
            op_census={"add": 2}, flops=10, bytes_in=8, bytes_out=8))
    for i in range(stranded):
        rep.stranded.append(afusion.StrandedOp(
            name=f"s{i}", opcode="transpose", bytes=8192,
            producer="f0", consumers=["f1"], computation="main"))
    return rep


def test_baseline_gate_passes_in_band():
    base = {"leg": {"n_fusions": 10, "stranded_ops": 0,
                    "boundary_bytes": 1000, "tol_pct": 25}}
    assert afusion.check_baseline(_report_for(), base, "leg") == []
    # within band: 12 fusions (band = 10 +- max(1, 2.5) = +-3 -> 2)
    assert afusion.check_baseline(_report_for(n_fusions=12), base,
                                  "leg") == []
    # fewer boundary bytes is an improvement, not a violation
    assert afusion.check_baseline(_report_for(boundary=100), base,
                                  "leg") == []


def test_baseline_gate_flags_regressions():
    base = {"leg": {"n_fusions": 10, "stranded_ops": 0,
                    "boundary_bytes": 1000, "tol_pct": 25}}
    # fusion count left the band (either direction)
    bad = afusion.check_baseline(_report_for(n_fusions=20), base, "leg")
    assert [f.rule for f in bad] == ["fusion-regression"]
    assert all(f.severity == "error" for f in bad)
    bad = afusion.check_baseline(_report_for(n_fusions=2), base, "leg")
    assert [f.rule for f in bad] == ["fusion-regression"]
    # new stranded op
    bad = afusion.check_baseline(_report_for(stranded=1), base, "leg")
    assert len(bad) == 1 and "stranded" in bad[0].message
    # boundary bytes beyond +tol
    bad = afusion.check_baseline(_report_for(boundary=1500), base, "leg")
    assert len(bad) == 1 and "boundary" in bad[0].message
    # unknown leg: warn, not error (the gate must not invent baselines)
    miss = afusion.check_baseline(_report_for(), base, "other")
    assert len(miss) == 1 and miss[0].severity == "warn"


def test_baseline_from_env(monkeypatch, tmp_path):
    monkeypatch.delenv("MXNET_FUSION_BASELINE", raising=False)
    assert afusion.baseline_from_env() is None
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"mlp": {"n_fusions": 5}}))
    monkeypatch.setenv("MXNET_FUSION_BASELINE", str(p))
    baselines, leg = afusion.baseline_from_env()
    assert baselines == {"mlp": {"n_fusions": 5}} and leg is None
    monkeypatch.setenv("MXNET_FUSION_BASELINE", f"{p}:mlp")
    baselines, leg = afusion.baseline_from_env()
    assert leg == "mlp"


# ---------------------------------------------------------------------------
# real compiled programs (the ISSUE 9 acceptance path)
# ---------------------------------------------------------------------------

def _mlp_leg():
    onp.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = mx.nd.array(onp.random.randn(8, 8).astype("float32"))
    y = mx.nd.array(onp.random.randint(0, 4, size=(8,)).astype("int32"))
    net(x)
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9},
                      kvstore=None)
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    return step, x, y


class _WordLM(mx.gluon.HybridBlock):
    """examples/train_lstm_lm.py's architecture at tiny dims — the
    worst-MFU BENCH leg's shape (Embedding -> fused LSTM -> Dense)."""

    def __init__(self, vocab, embed, hidden):
        super().__init__()
        self.emb = nn.Embedding(vocab, embed)
        self.lstm = rnn.LSTM(hidden, num_layers=1, layout="NTC")
        self.head = nn.Dense(vocab, flatten=False)

    def forward(self, tokens):
        return self.head(self.lstm(self.emb(tokens)))


def _lstm_leg():
    onp.random.seed(0)
    vocab = 16
    lm = _WordLM(vocab, 8, 16)
    lm.initialize()
    x = mx.nd.array(onp.random.randint(0, vocab, size=(4, 8))
                    .astype("int32"))
    y = mx.nd.array(onp.random.randint(0, vocab, size=(4, 8))
                    .astype("int32"))
    lm(x)
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    trainer = Trainer(lm.collect_params(), "adam",
                      {"learning_rate": 5e-3}, kvstore=None)
    step = trainer.compile_step(lambda a, b: loss_blk(lm(a), b))
    return step, x, y


def test_analyze_populates_fusion_report():
    step, x, y = _mlp_leg()
    step(x, y)
    report = step.analyze(x, y)
    fr = report.fusion
    assert fr is not None and fr.n_fusions > 0
    assert fr.stranded == []          # the fused MLP step is clean
    assert fr.boundary_bytes > 0
    assert report.ok, report.summary()
    assert report.to_dict()["fusion"]["n_fusions"] == fr.n_fusions
    assert "fusion" in report.summary()
    # fusion_report() is the cached census off the same bucket
    assert step.fusion_report(x, y) is fr


def test_fusion_gauges_published():
    step, x, y = _mlp_leg()
    step(x, y)
    fr = step.fusion_report(x, y)
    assert telemetry.value(telemetry.names.FUSION_REGIONS) \
        == fr.n_fusions
    assert telemetry.value(telemetry.names.FUSION_BOUNDARY_BYTES) \
        == fr.boundary_bytes
    assert telemetry.value(telemetry.names.FUSION_STRANDED) == 0


def test_fusion_report_none_on_eager():
    onp.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    x = mx.nd.array(onp.random.randn(8, 8).astype("float32"))
    y = mx.nd.array(onp.random.randint(0, 4, size=(8,)).astype("int32"))
    net(x)
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                 kvstore=None)

    def hostile(a, b):
        out = net(a)
        _ = out.asnumpy().sum()          # demotes the step to eager
        return loss_blk(out, b)

    estep = tr.compile_step(hostile)
    estep(x, y)
    assert estep.mode == "eager"
    assert estep.fusion_report(x, y) is None


def test_analyze_raise_enforces_injected_baseline(monkeypatch, tmp_path):
    """The gate wired through compile_step(analyze='raise'): a baseline
    that demands far fewer fusions than the program has must fail the
    first step with a fusion-regression error."""
    p = tmp_path / "tight.json"
    p.write_text(json.dumps(
        {"mlp": {"n_fusions": 1, "stranded_ops": 0,
                 "boundary_bytes": 1, "tol_pct": 0}}))
    monkeypatch.setenv("MXNET_FUSION_BASELINE", f"{p}:mlp")
    onp.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = mx.nd.array(onp.random.randn(8, 8).astype("float32"))
    y = mx.nd.array(onp.random.randint(0, 4, size=(8,)).astype("int32"))
    net(x)
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9},
                      kvstore=None)
    rstep = trainer.compile_step(lambda a, b: loss_blk(net(a), b),
                                 analyze="raise")
    with pytest.raises(MXNetError, match="fusion"):
        rstep(x, y)


def test_analyze_passes_on_checked_in_baseline(monkeypatch):
    monkeypatch.setenv("MXNET_FUSION_BASELINE", f"{BASELINES}:mlp")
    step, x, y = _mlp_leg()
    step(x, y)
    report = step.analyze(x, y)
    assert not [f for f in report.findings
                if f.rule == "fusion-regression"], report.summary()
    assert report.ok


# ---------------------------------------------------------------------------
# MXA005: unrolled-loop source lint
# ---------------------------------------------------------------------------

def _lint(body: str):
    src = ("class B:\n"
           "    def forward(self, x, mask=None):\n"
           + textwrap.indent(textwrap.dedent(body), "        "))
    return lint_source(src, filename="snippet.py")


def test_mxa005_flags_shape_derived_range():
    fs = _lint("outs = []\n"
               "for i in range(x.shape[0]):\n"
               "    outs.append(x * i)\n"
               "return outs\n")
    assert [f.rule for f in fs] == ["MXA005"]
    assert "unroll" in fs[0].message and fs[0].severity == "warn"


def test_mxa005_flags_iterating_traced_array():
    fs = _lint("acc = x * 0\nfor row in x:\n    acc = acc + row\n"
               "return acc\n")
    assert "MXA005" in [f.rule for f in fs]


def test_mxa005_skips_literal_and_non_tensor_loops():
    # literal range: visibly small and static
    assert _lint("for i in range(3):\n    x = x + i\nreturn x\n") == []
    # dynamic range but no tensor work in the body
    assert _lint("n = 0\nfor i in range(self.depth):\n    n += i\n"
                 "return x\n") == []


def test_mxa005_inline_allow_blesses():
    fs = _lint("for i in range(x.shape[0]):  # mx-lint: allow=MXA005\n"
               "    x = x + i\nreturn x\n")
    assert len(fs) == 1 and fs[0].blessed


def test_mxa005_scans_unroll_methods_only_for_unrolling():
    """``unroll`` methods are scanned for MXA005 but NOT the other
    rules — their config-flag args would false-flag MXA003."""
    src = textwrap.dedent("""\
    class Cell:
        def unroll(self, length, inputs, merge_outputs=None):
            if merge_outputs:
                inputs = inputs * 1
            outs = []
            for i in range(length):
                outs.append(inputs * i)
            return outs
    """)
    fs = lint_source(src, filename="cell.py")
    assert [f.rule for f in fs] == ["MXA005"]


def test_mxa005_fires_on_the_reference_unroller(lint_allowlist):
    """The known-present sentinel: RecurrentCell.unroll IS a Python
    unroller and must keep firing MXA005 (blessed in the allowlist) —
    if it vanishes, the rule or the blessing is stale."""
    from mxnet_tpu.analysis.lint import filter_allowed, lint_path
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = lint_path(os.path.join(repo, "mxnet_tpu", "gluon", "rnn"))
    hits = [f for f in findings if f.rule == "MXA005"]
    assert hits, "RecurrentCell.unroll no longer fires MXA005"
    assert filter_allowed(hits, lint_allowlist) == [], \
        "rnn unroller MXA005 findings must be blessed in the allowlist"


# ---------------------------------------------------------------------------
# tier-1 baseline sweep (lint-marked, like the source-lint sweep)
# ---------------------------------------------------------------------------

@pytest.mark.lint
@pytest.mark.parametrize("leg,builder", [("mlp", _mlp_leg),
                                         ("lstm", _lstm_leg)])
def test_fusion_baseline_sweep(leg, builder):
    """The regression gate over the checked-in baselines: each leg's
    compiled program must hold its fusion posture (count band, zero new
    stranded ops, boundary bytes within tolerance). A jax bump that
    legitimately shifts these fails HERE — refresh the fixture in the
    same PR with the diff explained (docs/ANALYSIS.md)."""
    step, x, y = builder()
    step(x, y)
    fr = step.fusion_report(x, y)
    assert fr is not None and fr.n_fusions > 0, \
        f"[{leg}] no fusion census for a compiled step"
    baselines = afusion.load_baselines(BASELINES)
    findings = afusion.check_baseline(fr, baselines, leg)
    assert findings == [], (
        f"[{leg}] fusion posture regressed vs "
        f"tests/fixtures/fusion_baselines.json "
        f"(measured: {fr.brief()}):\n"
        + "\n".join(f"  {f}" for f in findings))


@pytest.mark.lint
def test_fusion_baseline_sweep_lstm_kernel(monkeypatch):
    """The lstm leg compiled with the Pallas kernel layer forced to
    its interpret tier (MXNET_PALLAS=on): the kernel-path program is
    gated by its own checked-in baseline so a regression in the
    kernelized program fails tier-1 just like the XLA path. (The raw
    interpret-mode boundary_bytes are NOT comparable to the XLA leg's
    — the interpret harness carries whole buffers through its grid
    while-loops; the kernel's actual HBM win is pinned as the strict
    backward-residual ratchet in tests/test_kernels.py.)"""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    step, x, y = _lstm_leg()
    step(x, y)
    fr = step.fusion_report(x, y)
    assert fr is not None and fr.n_fusions > 0
    baselines = afusion.load_baselines(BASELINES)
    findings = afusion.check_baseline(fr, baselines, "lstm_kernel")
    assert findings == [], (
        f"[lstm_kernel] fusion posture regressed "
        f"(measured: {fr.brief()}):\n"
        + "\n".join(f"  {f}" for f in findings))


# ---------------------------------------------------------------------------
# custom-call FLOP estimators (PR 10 satellite: kernel legs stop
# under-counting in the bound classification)
# ---------------------------------------------------------------------------

_CUSTOM_CALL_HLO = """\
HloModule cc_test

ENTRY %main {
  %p0 = f32[16,512,64]{2,1,0} parameter(0)
  %p1 = f32[16,512,64]{2,1,0} parameter(1)
  %p2 = f32[16,512,64]{2,1,0} parameter(2)
  %cc = f32[16,512,64]{2,1,0} custom-call(%p0, %p1, %p2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/flash_fwd/_flash_kernel"}
  %xw = f32[8,4,512]{2,1,0} parameter(3)
  %wh = f32[512,128]{1,0} parameter(4)
  %sc = f32[8,4,128]{2,1,0} custom-call(%xw, %wh), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/rnn/_fwd_kernel"}
  %un = f32[16,512,64]{2,1,0} custom-call(%p0), custom_call_target="SomeUnknownTarget"
  ROOT %t = (f32[16,512,64]{2,1,0}, f32[8,4,128]{2,1,0}, f32[16,512,64]{2,1,0}) tuple(%cc, %sc, %un)
}
"""


def test_custom_call_flops_builtin_estimators():
    """Flash-attention and rnn-scan custom calls get real FLOP
    estimates (matched on the kernel function name in the op_name
    metadata); unknown custom calls stay at 0 — compute_bound_pct no
    longer under-counts kernel legs."""
    fr = afusion.fusion_census(_CUSTOM_CALL_HLO)
    by_name = {k.name: k for k in fr.kernels}
    assert by_name["cc"].flops == 4 * 16 * 512 * 512 * 64
    assert by_name["cc"].bound() == "compute"
    assert by_name["sc"].flops == 2 * 8 * 4 * 512 * 128 \
        + 10 * 8 * 4 * 512
    assert by_name["un"].flops == 0
    assert fr.compute_bound_pct > 0


@pytest.mark.parametrize("q_dims,k_dims,kernel,want", [
    # BERT-base's layer, 32 x 12 heads of 64 over 512 tokens: the same
    # count whether the kernel reads (B*H, S, D) or (B, S, H*D)
    ((384, 512, 64), (384, 512, 64), "_flash_kernel",
     4 * 32 * 12 * 512 * 512 * 64),
    ((32, 512, 768), (32, 512, 768), "_flash_kernel",
     4 * 32 * 12 * 512 * 512 * 64),
    ((32, 512, 768), (32, 512, 768), "_flash_bwd_fused_kernel",
     10 * 32 * 12 * 512 * 512 * 64),
    # cross-length, packed: Sq 128 against Sk 2048
    ((8, 128, 768), (8, 2048, 768), "_flash_bwd_dkv_kernel",
     8 * 8 * 12 * 128 * 2048 * 64),
], ids=["fwd-bh_s_d", "fwd-b_s_hd", "bwd-fused-b_s_hd", "bwd-dkv-cross"])
def test_flash_flops_count_both_layouts(q_dims, k_dims, kernel, want):
    from mxnet_tpu.analysis.hlo import parse_hlo
    shape = lambda dims: "bf16[%s]{2,1,0}" % ",".join(map(str, dims))
    mod = parse_hlo(f"""\
HloModule flash_layouts

ENTRY %main {{
  %q = {shape(q_dims)} parameter(0)
  %k = {shape(k_dims)} parameter(1)
  %v = {shape(k_dims)} parameter(2)
  ROOT %cc = {shape(q_dims)} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={{op_name="jit(step)/flash_attention/{kernel}"}}
}}
""")
    assert afusion.op_flops(mod.ops["cc"], mod) == want


def test_register_custom_call_flops_hook():
    """The public hook: a registered estimator applies by substring
    match, re-registering a name replaces it, and an estimator that
    raises degrades to 0 (a census must never die)."""
    from mxnet_tpu.analysis.hlo import parse_hlo
    mod = parse_hlo(_CUSTOM_CALL_HLO)
    op = mod.ops["un"]
    try:
        afusion.register_custom_call_flops(
            "my_kernel", lambda op, mod=None: 1234,
            match="someunknowntarget")
        assert afusion.op_flops(op, mod) == 1234
        afusion.register_custom_call_flops(
            "my_kernel", lambda op, mod=None: 5678,
            match="someunknowntarget")
        assert afusion.op_flops(op, mod) == 5678
        afusion.register_custom_call_flops(
            "my_kernel", lambda op, mod=None: 1 / 0,
            match="someunknowntarget")
        assert afusion.op_flops(op, mod) == 0
    finally:
        afusion._CUSTOM_CALL_FLOPS[:] = [
            e for e in afusion._CUSTOM_CALL_FLOPS
            if e[0] != "my_kernel"]
