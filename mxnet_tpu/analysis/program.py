"""Program lint: static analysis of the compiled train step.

Value-level tests prove a step computes the right numbers; this pass
proves the PROGRAM is the right program — the invariants PRs 1-3 built
(one reduce-scatter per unit instead of N all-reduces, buffers actually
donated, no host round-trip per step, bf16 staying bf16 outside blessed
fp32 masters) are asserted against the jaxpr and the optimized HLO that
XLA actually scheduled, the analysis practice of arXiv:2301.13062 and
the sharded-update contract of arXiv:2004.13336 turned into a checker.

Entry points:

- :func:`analyze_step` — lower+compile a ``CompiledTrainStep``'s program
  for one example batch (no optimizer counts advance) and run every
  checker; returns a :class:`~.report.ProgramReport`.
- :func:`analyze_lowered` — the same checkers over any ``jax.stages.
  Lowered`` (bench sidecars, golden known-bad programs in tests).
- :func:`collective_census` — HLO-text census alone.
- :func:`expect_mode` — mode-specific invariant pack (plain-fused,
  zero-sharded, dp=1) appended as findings; what the tier-1 fixtures
  assert.

CPU-backend note: XLA:CPU has no native reduce-scatter thunk — its
``reduce-scatter-decomposer`` pass rewrites every reduce-scatter into
all-reduce + dynamic-slice BEFORE the final text we read.  The census
re-classifies that pattern (an all-reduce whose only real consumers
slice exactly a 1/group_size shard) as ``reduce_scatter`` with
``decomposed=True``, so zero-shard assertions hold on the 8-device
virtual CPU mesh and on real TPU slices alike.
"""
from __future__ import annotations

import logging
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .hlo import HloModule, HloOp, parse_hlo
from .report import (CollectiveOp, CollectiveStats, DonationAudit, Finding,
                     ProgramReport)

__all__ = ["collective_census", "donation_audit", "host_transfer_scan",
           "dtype_drift_scan", "analyze_lowered", "analyze_step",
           "expect_mode", "explain_signature_diff"]

_LOG = logging.getLogger("mxnet_tpu.analysis")

_COLLECTIVE_KINDS = {
    "all-reduce": "all_reduce", "all-reduce-start": "all_reduce",
    "all-gather": "all_gather", "all-gather-start": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "reduce-scatter-start": "reduce_scatter",
    "collective-permute": "collective_permute",
    "collective-permute-start": "collective_permute",
    "all-to-all": "all_to_all",
    "all-to-all-start": "all_to_all",
}

# host-transfer primitives at the jaxpr level (jax's callback family) and
# custom-call targets at the HLO level
_HOST_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "python_callback",
    "callback", "outside_call", "host_callback_call",
}
_HOST_CUSTOM_CALL_MARKERS = (
    "callback", "xla_python", "HostTransfer", "tpu_host",
)
_HOST_OPCODES = {"infeed", "outfeed", "send", "recv", "send-done",
                 "recv-done"}

# dtype widths for drift direction checks
_WIDTH = {"bool": 0, "int8": 1, "uint8": 1, "bfloat16": 2, "float16": 2,
          "int16": 2, "uint16": 2, "float32": 4, "int32": 4, "uint32": 4,
          "float64": 8, "int64": 8, "uint64": 8}


# ---------------------------------------------------------------------------
# collective census
# ---------------------------------------------------------------------------

def _axes_for_groups(groups, mesh) -> Tuple[str, ...]:
    """Which mesh axes a collective's replica groups span.

    For each axis of the mesh, the set of device groups that vary only
    that axis is precomputed; a collective whose groups partition the
    devices the same way is attributed to that axis.  Groups spanning
    several axes at once report every axis whose extent they cover."""
    if not groups or mesh is None:
        return ()
    try:
        import numpy as onp
        dev_ids = onp.array([d.id for d in mesh.devices.flat]).reshape(
            mesh.devices.shape)
        axis_names = list(mesh.axis_names)
        got = {frozenset(g) for g in groups}
        matched = []
        for i, ax in enumerate(axis_names):
            # groups that vary ONLY axis i: move axis i last, flatten rest
            moved = onp.moveaxis(dev_ids, i, -1)
            want = {frozenset(int(x) for x in grp)
                    for grp in moved.reshape(-1, dev_ids.shape[i])}
            if got == want:
                return (ax,)
            # collective spanning axis i among others (its groups are
            # unions of axis-i groups)
            if all(any(w <= g for g in got) for w in want):
                matched.append(ax)
        return tuple(matched)
    except Exception:       # pragma: no cover - defensive
        return ()


def _classify_decomposed(mod: HloModule, op: HloOp, group: int) -> bool:
    """True when ``op`` (an all-reduce) is the CPU decomposition of a
    reduce-scatter: every real consumer takes exactly a 1/group shard
    (dynamic-slice by partition id, usually fused).

    Transparent consumers (get-tuple-element / bitcast / copy) are
    followed recursively with THEIR OWN element counts — XLA's
    all-reduce combiner merges bucketed gradient all-reduces into one
    variadic tuple all-reduce whose direct consumers are only GTEs, and
    judging those at the tuple's total element count would misclassify
    the combined op as a plain all-reduce (2(n-1)/n wire pricing, a 2x
    overcount of the decomposed reduce-scatter's (n-1)/n)."""
    if group <= 1 or op.elements == 0 or op.elements % group:
        return False
    sliced = 0

    def walk(name: str, elements: int, depth: int) -> bool:
        nonlocal sliced
        if elements == 0 or elements % group:
            return False
        shard = elements // group
        consumers = mod.consumers(name)
        if not consumers:
            # a dangling transparent hop vetoes nothing; a dangling
            # all-reduce result is not a reduce-scatter
            return depth > 0
        for c in consumers:
            if c.opcode in ("dynamic-slice", "fusion") and \
                    c.elements == shard:
                # a consumer producing exactly the 1/group shard is the
                # partition-id dynamic-slice (usually fused into the
                # shard-local compute that follows it)
                sliced += 1
            elif c.opcode in ("get-tuple-element", "bitcast", "copy") \
                    and depth < 4:
                if not walk(c.name, c.elements, depth + 1):
                    return False
            else:
                return False
        return True

    return walk(op.name, op.elements, 0) and sliced > 0


def collective_census(hlo_text: str, mesh=None,
                      num_devices: Optional[int] = None) -> CollectiveStats:
    """Count and classify every collective in an optimized HLO dump.

    ``mesh`` (a ``jax.sharding.Mesh`` or this framework's ``DeviceMesh``)
    enables per-axis attribution of replica groups."""
    jmesh = getattr(mesh, "mesh", mesh)   # DeviceMesh wraps .mesh
    if num_devices is None:
        num_devices = int(jmesh.devices.size) if jmesh is not None else 1
    mod = parse_hlo(hlo_text, num_devices=num_devices)
    stats = CollectiveStats()
    for op in mod.ops.values():
        kind = _COLLECTIVE_KINDS.get(op.opcode)
        if kind is None:
            continue
        groups = op.replica_groups
        group_size = len(groups[0]) if groups else num_devices
        axes = _axes_for_groups(groups, jmesh)
        decomposed = False
        if kind == "all_reduce" and \
                _classify_decomposed(mod, op, group_size):
            kind, decomposed = "reduce_scatter", True
        stats.ops.append(CollectiveOp(
            kind=kind, name=op.name, elements=op.elements,
            dtype=op.dtype or "?", axes=axes, group_size=group_size,
            operand_count=max(1, len(op.operands)),
            decomposed=decomposed))
    return stats


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------

def donation_audit(stablehlo_text: str, compiled_text: str,
                   memory_stats=None,
                   expected: Optional[int] = None) -> DonationAudit:
    """Compare donation DECLARED at the jax level against aliasing XLA
    actually performed.  A declared-but-unaliased input is a silent copy
    per step (the regression class test_fused_step's writeback test can't
    see — numerics stay right, HBM pays double)."""
    audit = DonationAudit(expected=expected)
    declared_params: List[int] = []
    # lowered StableHLO marks donated args per-parameter:
    #   %arg0: tensor<..> {jax.buffer_donor = true}   (jax >= 0.4.30)
    #   %arg1: tensor<..> {tf.aliasing_output = 1}    (pre-decided alias)
    # the annotation block belongs to ONE argument — stop the match at
    # the next argument (comma) so a donor deep in the list is never
    # credited to an earlier undonated arg
    for m in re.finditer(r"%arg(\d+): [^,{]*\{[^{}]*?"
                         r"(jax\.buffer_donor = true"
                         r"|tf\.aliasing_output = \d+)",
                         stablehlo_text or ""):
        declared_params.append(int(m.group(1)))
    audit.declared = len(declared_params)
    mod = parse_hlo(compiled_text or "")
    audit.aliased_params = sorted(p for _, p in mod.input_output_alias)
    audit.aliased = len(audit.aliased_params)
    if declared_params:
        aliased = set(audit.aliased_params)
        audit.copied = [p for p in declared_params if p not in aliased]
    if memory_stats is not None:
        audit.donated_bytes = int(
            getattr(memory_stats, "alias_size_in_bytes", 0))
    return audit


# ---------------------------------------------------------------------------
# host-transfer scan (jaxpr + HLO)
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr) -> Iterable:
    """All eqns of a (Closed)Jaxpr, recursing into sub-jaxprs (pjit,
    scan, cond, while, remat...)."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jx.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _iter_eqns(sub)


def _subjaxprs(v):
    from jax.extend.core import Jaxpr, ClosedJaxpr
    if isinstance(v, (Jaxpr, ClosedJaxpr)):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


def _eqn_where(eqn) -> str:
    try:
        frame = eqn.source_info.traceback.frames[0]
        return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        return ""


def host_transfer_scan(closed_jaxpr, hlo_text: str = "") -> List[Finding]:
    """Host callbacks / infeed / outfeed inside the step program — each
    one is a device->host (or host->device) synchronization per call."""
    findings: List[Finding] = []
    if closed_jaxpr is not None:
        for eqn in _iter_eqns(closed_jaxpr):
            name = eqn.primitive.name
            if name in _HOST_PRIMITIVES or "callback" in name:
                cb = eqn.params.get("callback", None)
                findings.append(Finding(
                    checker="program", rule="host-transfer",
                    message=f"host callback primitive `{name}` inside the "
                            "compiled step" +
                            (f" (callback={cb!r})" if cb else ""),
                    where=_eqn_where(eqn)))
    mod = parse_hlo(hlo_text or "")

    def _where(op):
        # an op XLA already pulled into a fusion body is still a host
        # round-trip per step — name the kernel it hides in
        parent = mod.parent_fusion(op)
        return f"{op.name} (inside fusion %{parent.name})" if parent \
            else op.name

    for op in mod.ops.values():
        if op.opcode in _HOST_OPCODES:
            findings.append(Finding(
                checker="program", rule="host-transfer",
                message=f"`{op.opcode}` op in the optimized program",
                where=_where(op)))
        elif op.opcode == "custom-call" and op.custom_call_target and \
                any(k in op.custom_call_target
                    for k in _HOST_CUSTOM_CALL_MARKERS):
            findings.append(Finding(
                checker="program", rule="host-transfer",
                message="host-callback custom-call "
                        f"`{op.custom_call_target}`",
                where=_where(op)))
    return findings


# ---------------------------------------------------------------------------
# dtype drift
# ---------------------------------------------------------------------------

_HLO_DTYPE_NAMES = {"bf16": "bfloat16", "f16": "float16",
                    "f32": "float32", "f64": "float64"}


def _dtype_drift_scan_hlo(hlo_text: str, blessed) -> List[Finding]:
    """HLO-level widening-``convert`` scan — the fallback when no
    jaxpr is available (canned programs, lowered-only analysis).
    Walks EVERY computation, so converts XLA already pulled into a
    fusion body are seen and attributed to their kernel."""
    mod = parse_hlo(hlo_text or "")
    findings: List[Finding] = []
    for op in mod.ops.values():
        if op.opcode != "convert":
            continue
        src_t = op.operand_types[0] if op.operand_types else None
        src = _HLO_DTYPE_NAMES.get(
            (src_t or "").split("[", 1)[0])
        dst = _HLO_DTYPE_NAMES.get(op.dtype or "")
        if not src or not dst:
            continue
        if _WIDTH.get(dst, 0) <= _WIDTH.get(src, 0):
            continue
        is_blessed = (src, dst) in blessed and dst != "float64"
        parent = mod.parent_fusion(op)
        findings.append(Finding(
            checker="program", rule="dtype-drift",
            severity="error" if dst == "float64" else "warn",
            blessed=is_blessed,
            message=f"widening convert {src} -> {dst} in the optimized "
                    "program" + (" (blessed by the multi-precision "
                                 "master list)" if is_blessed else ""),
            where=f"{op.name} (inside fusion %{parent.name})" if parent
            else op.name))
    return findings


def dtype_drift_scan(closed_jaxpr,
                     blessed: Optional[Sequence[Tuple[str, str]]] = None,
                     hlo_text: str = "") -> List[Finding]:
    """Unexpected widening ``convert_element_type`` chains.

    Narrowing (f32->bf16 AMP casts) is free; widening silently doubles
    activation/state HBM and MXU time.  ``blessed`` lists (src, dst)
    dtype-name pairs that are intentional — the multi-precision master
    list blesses ('bfloat16','float32')/('float16','float32') because
    fp32 masters are the POINT of that mode.  f32->f64 is never blessed
    (nothing in this framework wants f64).

    The jaxpr (pre-optimization) sees every convert, fused or not;
    when no jaxpr is available the scan falls back to the optimized
    HLO's ``convert`` ops — walking fusion BODIES too, which the old
    entry-only reading silently skipped once XLA fused a convert."""
    blessed = {tuple(b) for b in (blessed or ())}
    findings: List[Finding] = []
    if closed_jaxpr is None:
        return _dtype_drift_scan_hlo(hlo_text, blessed)
    for eqn in _iter_eqns(closed_jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        try:
            src = str(eqn.invars[0].aval.dtype)
            dst = str(eqn.params.get("new_dtype"))
        except Exception:
            continue
        if src not in _WIDTH or dst not in _WIDTH:
            continue
        if _WIDTH[dst] <= _WIDTH[src]:
            continue
        if not (src.startswith(("float", "bfloat"))
                and dst.startswith(("float", "bfloat"))):
            continue   # integer index promotions are not drift
        is_blessed = (src, dst) in blessed and dst != "float64"
        findings.append(Finding(
            checker="program", rule="dtype-drift",
            severity="error" if dst == "float64" else "warn",
            blessed=is_blessed,
            message=f"widening convert {src} -> {dst} in the compiled "
                    "step" + (" (blessed by the multi-precision master "
                              "list)" if is_blessed else ""),
            where=_eqn_where(eqn)))
    return findings


# ---------------------------------------------------------------------------
# whole-program analysis
# ---------------------------------------------------------------------------

def analyze_lowered(lowered, mesh=None, expected_donated=None,
                    blessed_dtypes=None, mode: str = "?",
                    compiled=None, jaxpr=None) -> ProgramReport:
    """Run every program checker over a ``jax.stages.Lowered`` (and its
    compiled executable — compiled here when not supplied).  Pass the
    ``jaxpr`` (from ``jax.make_jaxpr`` of the same function+args) to
    enable the jaxpr-level checks (host callbacks, dtype drift)."""
    report = ProgramReport(mode=mode)
    try:
        stablehlo = lowered.as_text()
    except Exception:               # pragma: no cover - defensive
        stablehlo = ""
    if compiled is None:
        compiled = lowered.compile()
    try:
        hlo_text = compiled.as_text()
    except Exception:               # pragma: no cover - defensive
        hlo_text = ""
    try:
        mem = compiled.memory_analysis()
        mem = mem[0] if isinstance(mem, (list, tuple)) else mem
    except Exception:               # pragma: no cover - defensive
        mem = None
    if mem is not None:
        try:
            from ..telemetry.memory import MemoryReport
            report.memory = MemoryReport.from_compiled(compiled).to_dict()
        except Exception:           # pragma: no cover - defensive
            report.memory = None
    report.collectives = collective_census(hlo_text, mesh=mesh)
    if hlo_text:
        try:
            from . import sharding as _sharding
            report.sharding = _sharding.audit_sharding(
                hlo_text, census=report.collectives, mesh=mesh,
                stablehlo=stablehlo)
            _sharding.publish(report.sharding)
        except Exception:       # pragma: no cover - defensive
            _LOG.debug("sharding audit failed", exc_info=True)
    report.donation = donation_audit(stablehlo, hlo_text, mem,
                                     expected=expected_donated)
    report.host_transfers = host_transfer_scan(jaxpr, hlo_text)
    report.dtype_drift = dtype_drift_scan(jaxpr, blessed=blessed_dtypes,
                                          hlo_text=hlo_text)
    if hlo_text:
        try:
            from . import fusion as _fusion
            report.fusion = _fusion.fusion_census(hlo_text)
            report.findings.extend(report.fusion.findings)
            env = _fusion.baseline_from_env()
            if env is not None:
                baselines, leg = env
                report.findings.extend(_fusion.check_baseline(
                    report.fusion, baselines, leg or mode))
            _fusion.publish(report.fusion)
        except Exception:       # pragma: no cover - defensive
            _LOG.debug("fusion census failed", exc_info=True)
    if hlo_text:
        try:
            from . import overlap as _overlap
            report.overlap = _overlap.overlap_census(
                hlo_text, mesh=mesh)
            report.findings.extend(report.overlap.findings)
            env = _overlap.baseline_from_env()
            if env is not None:
                baselines, leg = env
                report.findings.extend(_overlap.check_baseline(
                    report.overlap, baselines, leg or mode))
            _overlap.publish(report.overlap)
        except Exception:       # pragma: no cover - defensive
            _LOG.debug("overlap census failed", exc_info=True)
    for p in report.donation.copied:
        report.add(Finding(
            checker="program", rule="donation-copy",
            message=f"input #{p} was declared donated but XLA did not "
                    "alias it — a full buffer copy every step",
            where=f"param {p}"))
    if expected_donated is not None and \
            report.donation.aliased < expected_donated:
        report.add(Finding(
            checker="program", rule="donation-copy",
            message=f"only {report.donation.aliased} of "
                    f"{expected_donated} param/state buffers aliased — "
                    "donation fell back to copies",
            where="input_output_alias"))
    return report


def _trace_jaxpr(fn, *args, **kwargs):
    import jax
    try:
        return jax.make_jaxpr(fn)(*args, **kwargs)
    except Exception:               # pragma: no cover - defensive
        return None


def analyze_step(step, *args, batch_size=None, **kwargs) -> ProgramReport:
    """Lower + compile one ``CompiledTrainStep`` entry for this example
    batch (no optimizer counts advance, the live weights are untouched)
    and run the full program lint.  The result is cached on the step's
    shape-bucket entry — repeated calls are free."""
    info = step.lower_entry(*args, batch_size=batch_size, **kwargs)
    if info is None:
        report = ProgramReport(mode=step.mode or "eager")
        report.n_traces = step.n_traces
        report.add(Finding(
            checker="program", rule="not-compiled", severity="warn",
            message="step runs on the eager tape path "
                    f"({step.mode!r}); there is no compiled program to "
                    "lint — the transfer guard (MXNET_TRANSFER_GUARD) "
                    "still covers its hot loop"))
        return report
    if info.get("report") is not None:
        return info["report"]
    report = analyze_lowered(
        info["lowered"], mesh=info.get("mesh"),
        expected_donated=info.get("expected_donated"),
        blessed_dtypes=info.get("blessed_dtypes"),
        mode=info.get("mode", "?"), jaxpr=info.get("jaxpr"))
    report.n_traces = step.n_traces
    report.meta.update({k: v for k, v in info.items()
                        if k in ("mode", "axis", "unit_sizes", "n_params",
                                 "n_state_leaves")})
    expect_mode(report)
    info["report"] = report
    return report


# ---------------------------------------------------------------------------
# mode expectations (the tier-1 contract)
# ---------------------------------------------------------------------------

def mode_spec_pack(mode: str, axis: Optional[str] = None,
                   unit_sizes=()) -> Optional["object"]:
    """The declarative :class:`~.sharding.SpecPack` behind one compiled
    mode's historical expectations — ``expect_mode`` is now a thin
    dispatcher over these (docs/ANALYSIS.md "Sharding analysis"):

    - ``zero``: >=1 reduce_scatter and >=1 all_gather on the dp axis,
      ZERO all-reduces carrying exactly one shard unit's gradient (a
      unit-sized all-reduce means the reduce-scatter transformation of
      arXiv:2004.13336 regressed to replicate-everywhere), weight
      re-replication gathers declared by their padded unit sizes so
      any OTHER big gather is an implicit reshard.
    - ``fused-mesh``: the dp gradient reduction must exist.
    - ``fused`` dp=1 / ``predict``: no collectives at all (warn).
    """
    from . import sharding as _sharding
    R = _sharding.CollectiveRule
    units = frozenset(int(u) for u in (unit_sizes or ()))
    if mode == "zero":
        rules = [
            R("reduce_scatter", axis=axis, min_count=1,
              rule_id="collective-mismatch"),
            R("all_gather", axis=axis, min_count=1,
              rule_id="collective-mismatch"),
        ]
        if units:
            rules.append(R("all_reduce", axis=axis, max_count=0,
                           elements=units,
                           rule_id="per-param-allreduce"))
        return _sharding.SpecPack(
            name="zero-dp",
            description="ZeRO-1 sharded update (reduce-scatter grads, "
                        "shard-local update, all-gather weights)",
            axes=(axis,) if axis else (),
            rules=tuple(rules),
            declared=(
                # the batch/loss psums and the numerics-stat psums are
                # reductions the step declares
                R("all_reduce", axis=axis),
                # weight re-replication: all-gathers whose payload is a
                # padded shard unit
                R("all_gather", axis=axis, elements=units or None),
            ),
            # reshards surface as warnings + the baseline gate; no hard
            # budget — XLA legitimately gathers small activations
            # instead of psumming weight grads when that moves less
            max_reshard_bytes=None,
            state_axis=axis)
    if mode == "fused-mesh":
        return _sharding.SpecPack(
            name="fused-mesh-dp",
            description="mesh-aware fused step (replicated params, "
                        "dp-sharded batch, in-program grad psum)",
            axes=(axis,) if axis else (),
            rules=(R(("all_reduce", "reduce_scatter"), axis=axis,
                     min_count=1, rule_id="collective-mismatch"),),
            declared=(R("all_reduce", axis=axis),
                      R("reduce_scatter", axis=axis)),
            max_reshard_bytes=None)
    if mode in ("fused", "predict"):
        what = "single-device fused step" if mode == "fused" \
            else "serving predict program"
        return _sharding.SpecPack(
            name=f"{mode}-single",
            description=f"{what} (no partitioning expected)",
            rules=(R("*", max_count=0, rule_id="collective-mismatch",
                     severity="warn"),))
    return None


def expect_mode(report: ProgramReport, mode: Optional[str] = None,
                axis: Optional[str] = None) -> ProgramReport:
    """Append the per-mode structural invariants as findings.

    The historical fused/zero/predict expectations are now declarative
    :class:`~.sharding.SpecPack` s (:func:`mode_spec_pack`) enforced
    through :func:`~.sharding.expect_spec` — which also runs the
    implicit-reshard audit against the pack's declared collectives and
    the sharded-state byte budget, and re-checks the
    ``MXNET_SHARDING_BASELINE`` regression gate.  Every mode: all
    declared donations aliased, no host transfers.
    """
    from . import sharding as _sharding
    mode = mode or report.mode
    axis = axis or report.meta.get("axis")
    pack = mode_spec_pack(mode, axis=axis,
                          unit_sizes=report.meta.get("unit_sizes") or ())
    if pack is not None:
        _sharding.expect_spec(report, pack)
    audit = report.sharding
    if audit is not None:
        env = _sharding.baseline_from_env()
        if env is not None:
            baselines, leg = env
            report.findings.extend(_sharding.check_baseline(
                audit, baselines, leg or mode))
        _sharding.publish(audit)
    # fusion pack (every compiled mode): the optimized program must
    # have NO fusable elementwise/broadcast/convert op stranded between
    # two fusions above the size floor — each one is two avoidable HBM
    # round-trips per step the value-level tests cannot see
    # (arXiv:2301.13062; the fusion census produces the evidence)
    fr = report.fusion
    if mode in ("fused", "fused-mesh", "zero", "predict") \
            and fr is not None and fr.stranded:
        worst = fr.stranded[0]
        report.add(Finding(
            checker="fusion", rule="stranded-op",
            message=f"{len(fr.stranded)} fusable op(s) above the "
                    f"{fr.stranded_floor} B floor stranded between "
                    f"fusions in the {mode} step (worst: "
                    f"`{worst.opcode}` {worst.bytes} B at {worst.name})"
                    " — the ideal-fusion contract regressed",
            where=worst.name))
    return report


# ---------------------------------------------------------------------------
# retrace accounting
# ---------------------------------------------------------------------------

_SIG_FIELDS = ("train_mode", "arg_treedef", "static_spec", "nd_mask",
               "shapes_dtypes", "numerics_mode")


def explain_signature_diff(old, new) -> str:
    """Human-readable diff of two CompiledTrainStep cache keys — WHY the
    second one retraced."""
    if old is None:
        return "first trace (no prior signature to compare)"
    parts = []
    for i, fieldname in enumerate(_SIG_FIELDS):
        a = old[i] if i < len(old) else None
        b = new[i] if i < len(new) else None
        if a == b:
            continue
        if fieldname == "shapes_dtypes":
            a, b = list(a or ()), list(b or ())
            n = max(len(a), len(b))
            diffs = []
            for j in range(n):
                sa = a[j] if j < len(a) else None
                sb = b[j] if j < len(b) else None
                if sa != sb:
                    diffs.append(f"arg[{j}]: {sa} -> {sb}")
            parts.append("traced argument shapes/dtypes changed ("
                         + "; ".join(diffs[:6])
                         + ("; ..." if len(diffs) > 6 else "") + ")")
        elif fieldname == "arg_treedef":
            parts.append(f"argument STRUCTURE changed ({a} -> {b})")
        elif fieldname == "static_spec":
            parts.append("non-array (static) argument values changed — "
                         "each distinct value compiles its own program")
        elif fieldname == "nd_mask":
            parts.append("NDArray-vs-raw-array argument mix changed")
        else:
            parts.append(f"{fieldname} changed ({a} -> {b})")
    return "; ".join(parts) if parts else \
        "signatures identical (cache eviction, not a retrace trigger)"
