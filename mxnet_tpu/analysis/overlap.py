"""Exposed-communication analysis over the optimized-HLO schedule.

The sharding cost model (analysis/sharding.py) prices every collective
in seconds, but a priced collective only costs wall-clock time where
nothing computes while it is on the wire.  This pass walks the
compiler's FINAL kernel schedule (optimized dumps carry
``is_scheduled=true`` — text order is the schedule) and measures, per
collective, how much independent compute the scheduler placed inside
its *overlap window*:

* async pairs (``all-reduce-start``/``-done`` etc., TPU/GPU dumps) —
  the window is exactly the scheduler's explicit start..done span;
* synchronous collectives (XLA:CPU has no async pairs) — the window is
  the dependency slack ``(last producer .. first consumer that NEEDS
  the bytes)``: the span in which a latency-hiding runtime could run
  the transfer asynchronously without reordering the schedule.
  Zero-FLOP data movement (pads, slices, converts, concatenations, GTE
  plumbing) does not end a window — the scheduler pins those right
  behind the collective, but they carry no deadline; the walk follows
  them to the first flops-bearing kernel or collective.  A value that
  reaches the outputs without any such consumer (new weights gathered
  straight into the root tuple) has program completion as its
  deadline, so everything scheduled after the collective can hide it.

Kernels inside the window that do NOT transitively depend on the
collective (forward taint through operands) could hide it; their
roofline seconds (the fusion census's FLOP/byte model) are credited
against the collective's wire seconds (ring model over the
``BandwidthProfile``).  Whatever is left is **exposed** comm:

    exposed_s = max(0, comm_s - hide_s)        per collective
    overlap_fraction = 1 - sum(exposed) / sum(comm)

The monolithic serial ZeRO step (``zero.bucket_bytes <= 0``: one
packed collective payload over every unit) measures fraction ~0 —
every kernel after the reduce-scatter depends on it, and nothing but
zero-FLOP writeback slices trails the weight all-gather (the only
residual hider is the nanoseconds-scale loss tail the scheduler may
park after it).  The
bucketed step (gluon/fused_step.py) measures fraction > 0 — bucket
k's all-gather is independent of bucket k+1's optimizer update by
construction, and the scheduler demonstrably interleaves them.
Consumer chains through plumbing are followed transparently when
locating the first real consumer; the taint walk still treats them as
dependency edges, so ordering stays exact.

Like the fusion/sharding passes this one is an observer: parse or
model failures degrade to an empty report, never exceptions.
"""
from __future__ import annotations

import json
import logging
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .hlo import HloModule, HloOp, parse_hlo
from .report import CollectiveOp, Finding

_LOG = logging.getLogger(__name__)

__all__ = [
    "CollectiveWindow", "OverlapReport", "overlap_census",
    "load_baselines", "check_baseline", "baseline_from_env", "publish",
]

#: async collective start opcodes -> their matching done opcode (the
#: scheduler's explicit overlap region on backends that emit them)
_ASYNC_DONE = {
    "all-reduce-start": "all-reduce-done",
    "all-gather-start": "all-gather-done",
    "reduce-scatter-start": "reduce-scatter-done",
    "collective-permute-start": "collective-permute-done",
    "all-to-all-start": "all-to-all-done",
    "async-start": "async-done",
}
_DONE_OPCODES = frozenset(_ASYNC_DONE.values())

#: data plumbing followed when locating a collective's first REAL
#: consumer (the taint walk still sees these as dependency edges)
_TRANSPARENT_OPCODES = frozenset(
    {"get-tuple-element", "bitcast", "copy", "tuple", "opt-barrier"})

#: pure data-movement opcodes: a kernel whose body holds ONLY these
#: re-routes bytes — it carries no compute deadline for a collective's
#: result and cannot hide wire time behind arithmetic either (the
#: fusion census prices element copies as FLOPs, so the flops field
#: alone cannot make this call)
_MOVEMENT_OPCODES = frozenset({
    "bitcast", "broadcast", "concatenate", "constant", "convert",
    "copy", "dynamic-slice", "dynamic-update-slice",
    "get-tuple-element", "iota", "pad", "parameter", "reshape",
    "reverse", "slice", "transpose", "tuple", "opt-barrier"})


@dataclass
class CollectiveWindow:
    """One collective's overlap accounting on the schedule."""
    name: str
    kind: str
    axis: str
    comm_s: float
    hide_s: float
    exposed_s: float
    n_hiders: int
    window: Tuple[int, int]
    computation: str = "?"
    is_async: bool = False

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "kind": self.kind, "axis": self.axis,
                "comm_s": self.comm_s, "hide_s": self.hide_s,
                "exposed_s": self.exposed_s, "n_hiders": self.n_hiders,
                "window": list(self.window), "is_async": self.is_async}


@dataclass
class OverlapReport:
    """Exposed-vs-total communication posture of one program."""
    windows: List[CollectiveWindow] = field(default_factory=list)
    per_axis_total_s: Dict[str, float] = field(default_factory=dict)
    per_axis_exposed_s: Dict[str, float] = field(default_factory=dict)
    total_comm_s: float = 0.0
    exposed_comm_s: float = 0.0
    n_async: int = 0
    #: the dump carried ``is_scheduled=true`` (when False, text order
    #: merely approximates the schedule)
    scheduled: bool = False
    profile: str = "cpu"
    findings: List[Finding] = field(default_factory=list)

    @property
    def n_collectives(self) -> int:
        return len(self.windows)

    @property
    def overlap_fraction(self) -> float:
        """Share of modeled comm seconds hidden behind independent
        compute (0 = fully exposed/serial, 1 = fully hidden)."""
        if self.total_comm_s <= 0.0:
            return 0.0
        return max(0.0, 1.0 - self.exposed_comm_s / self.total_comm_s)

    def brief(self) -> Dict[str, Any]:
        return {"exposed_comm_s": self.exposed_comm_s,
                "total_comm_s": self.total_comm_s,
                "overlap_fraction": self.overlap_fraction,
                "n_collectives": self.n_collectives,
                "n_async": self.n_async}

    def to_dict(self) -> Dict[str, Any]:
        d = self.brief()
        d.update({
            "scheduled": self.scheduled, "profile": self.profile,
            "per_axis_total_s": dict(self.per_axis_total_s),
            "per_axis_exposed_s": dict(self.per_axis_exposed_s),
            "windows": [w.to_dict() for w in self.windows[:24]],
        })
        return d

    def summary_line(self) -> str:
        return (f"exposed={self.exposed_comm_s:.3e}s of "
                f"{self.total_comm_s:.3e}s comm "
                f"(fraction={self.overlap_fraction:.2f}, "
                f"{self.n_collectives} collectives, "
                f"{self.n_async} async)")

    def table_str(self, top: int = 16) -> str:
        lines = [f"{'collective':<30s}{'kind':<18s}{'axis':<6s}"
                 f"{'comm s':>11s}{'hide s':>11s}{'exposed s':>11s}"
                 f"{'hiders':>7s}"]
        rows = sorted(self.windows, key=lambda w: -w.exposed_s)[:top]
        for w in rows:
            lines.append(
                f"{w.name[:28]:<30s}{w.kind:<18s}{w.axis:<6s}"
                f"{w.comm_s:>11.3e}{w.hide_s:>11.3e}"
                f"{w.exposed_s:>11.3e}{w.n_hiders:>7d}")
        for ax in sorted(self.per_axis_total_s):
            lines.append(
                f"  axis {ax!r}: exposed "
                f"{self.per_axis_exposed_s.get(ax, 0.0):.3e} s of "
                f"{self.per_axis_total_s[ax]:.3e} s")
        lines.append("  " + self.summary_line())
        return "\n".join(lines)


def _kernel_tables(hlo_text: str):
    """``(seconds, movement)`` over every kernel in the schedule:
    roofline seconds by op name (the fusion census's FLOP/byte model
    over the checked-in roofline constants), and the set of
    movement-only kernel names — fusions whose whole body is data
    movement.  Those neither hide comm (crediting element copies as
    compute would let plumbing mask wire time) nor impose a deadline
    on a collective's result."""
    from . import fusion as _fusion
    secs: Dict[str, float] = {}
    movement: set = set()
    try:
        rep = _fusion.fusion_census(hlo_text)
    except Exception:            # pragma: no cover - defensive
        _LOG.debug("fusion census for overlap failed", exc_info=True)
        return secs, movement
    flops_s = _fusion.BENCH_ROOFLINE_TFLOPS * 1e12
    bytes_s = _fusion.HBM_BANDWIDTH_GBPS * 1e9
    for k in rep.kernels:
        if all(oc in _MOVEMENT_OPCODES for oc in k.op_census):
            movement.add(k.name)
            continue
        if k.flops <= 0:
            continue
        secs[k.name] = max(k.flops / flops_s,
                           k.boundary_bytes / bytes_s)
    return secs, movement


def _first_real_consumer_pos(mod: HloModule, op: HloOp,
                             pos: Dict[str, int],
                             movement: set) -> Optional[int]:
    """Schedule position of the first consumer that actually NEEDS the
    collective's result: arithmetic compute or another collective.
    Data movement (GTE/bitcast/copy/tuple plumbing, but also pads,
    slices, converts and whole movement-only fusions) is followed
    transparently: the scheduler pins those right behind the
    collective, yet they only re-route bytes and represent no deadline
    a latency-hiding runtime would have to meet.  ``None`` when the
    value only escapes through such plumbing (e.g. straight into the
    root tuple)."""
    best: Optional[int] = None
    seen = {op.name}
    frontier = [op.name]
    for _ in range(10):
        nxt: List[str] = []
        for name in frontier:
            for c in mod.consumers(name):
                if c.name in seen:
                    continue
                seen.add(c.name)
                if (c.name in movement
                        or c.opcode in _MOVEMENT_OPCODES):
                    nxt.append(c.name)
                elif c.name in pos:
                    best = pos[c.name] if best is None \
                        else min(best, pos[c.name])
        if not nxt:
            break
        frontier = nxt
    return best


def _window_for(mod: HloModule, op: HloOp, order: List[str],
                pos: Dict[str, int],
                movement: set) -> Tuple[int, int, bool]:
    """(start, end, is_async) overlap window of one collective, as
    schedule positions exclusive of the endpoints."""
    p = pos[op.name]
    if op.opcode in _ASYNC_DONE:
        done = _ASYNC_DONE[op.opcode]
        end = p + 1
        for c in mod.consumers(op.name):
            if c.opcode == done and c.name in pos:
                end = max(end, pos[c.name])
        return p, end, True
    start = -1
    for src in op.operands:
        if src in pos:
            start = max(start, pos[src])
    end = _first_real_consumer_pos(mod, op, pos, movement)
    if end is None:
        # the value reaches the outputs without any compute needing it
        # (e.g. new weights all-gathered straight into the root tuple):
        # its deadline is program completion, so every independent
        # kernel scheduled AFTER the collective can hide it.  An
        # end-of-schedule resharding collective self-corrects — nothing
        # trails it, so it stays fully exposed.
        end = len(order)
    return start, max(end, p + 1), False


def _tainted_in_window(mod: HloModule, op: HloOp, order: List[str],
                       pos: Dict[str, int], end: int) -> set:
    """Names in ``(pos(op), end)`` transitively dependent on ``op`` —
    one forward pass in schedule order (valid schedules place every
    consumer after its producer)."""
    tainted = {op.name}
    for i in range(pos[op.name] + 1, min(end, len(order))):
        o = mod.ops.get(order[i])
        if o is not None and any(s in tainted for s in o.operands):
            tainted.add(o.name)
    return tainted


def overlap_census(hlo_text: str, mesh=None,
                   num_devices: Optional[int] = None,
                   profile=None) -> OverlapReport:
    """Measure exposed (non-overlapped) communication seconds per mesh
    axis on one optimized-HLO schedule.

    ``mesh`` enables per-axis attribution (same contract as
    ``collective_census``); ``profile`` is a ``BandwidthProfile``
    (default: the active ``MXNET_SHARDING_BANDWIDTH`` profile)."""
    from . import program as _program
    from . import sharding as _sharding

    report = OverlapReport()
    try:
        jmesh = getattr(mesh, "mesh", mesh)
        if num_devices is None:
            num_devices = int(jmesh.devices.size) \
                if jmesh is not None else 1
        profile = profile or _sharding.bandwidth_profile()
        report.profile = profile.name
        mod = parse_hlo(hlo_text, num_devices=num_devices)
        report.scheduled = mod.is_scheduled
        census = _program.collective_census(
            hlo_text, mesh=mesh, num_devices=num_devices)
        by_name: Dict[str, CollectiveOp] = \
            {c.name: c for c in census.ops}
        kernel_s, movement = _kernel_tables(hlo_text)
        for comp in mod.schedulable_computations():
            order = comp.op_names
            pos = {n: i for i, n in enumerate(order)}
            for name in order:
                op = mod.ops.get(name)
                if op is None:
                    continue
                cop = by_name.get(name)
                if cop is None:
                    if op.opcode not in _ASYNC_DONE:
                        continue
                    # async starts the census's sync grammar missed:
                    # account them with an unattributed record
                    cop = CollectiveOp(
                        kind=op.opcode.replace("-start", "")
                        .replace("-", "_"),
                        name=name, elements=op.elements,
                        dtype=op.dtype or "?", axes=(),
                        group_size=num_devices, operand_count=1)
                if op.opcode in _DONE_OPCODES:
                    continue
                wire = _sharding.collective_wire_bytes(cop)
                gbps = profile.gbps(cop.axes)
                comm_s = wire / (gbps * 1e9) if gbps > 0 else 0.0
                start, end, is_async = _window_for(mod, op, order, pos,
                                                   movement)
                tainted = _tainted_in_window(mod, op, order, pos, end)
                hide_s, n_hiders = 0.0, 0
                for i in range(max(0, start + 1), min(end, len(order))):
                    hname = order[i]
                    if hname == name or hname in tainted:
                        continue
                    other = mod.ops.get(hname)
                    if other is not None and (
                            other.name in by_name
                            or other.opcode in _ASYNC_DONE
                            or other.opcode in _DONE_OPCODES):
                        continue    # comm can't hide comm
                    s = kernel_s.get(hname, 0.0)
                    if s > 0.0:
                        hide_s += s
                        n_hiders += 1
                exposed = max(0.0, comm_s - hide_s)
                ax = cop.axes[0] if cop.axes else "?"
                report.windows.append(CollectiveWindow(
                    name=name, kind=cop.kind, axis=ax, comm_s=comm_s,
                    hide_s=hide_s, exposed_s=exposed,
                    n_hiders=n_hiders, window=(start, end),
                    computation=comp.name, is_async=is_async))
                report.n_async += 1 if is_async else 0
                report.total_comm_s += comm_s
                report.exposed_comm_s += exposed
                report.per_axis_total_s[ax] = \
                    report.per_axis_total_s.get(ax, 0.0) + comm_s
                report.per_axis_exposed_s[ax] = \
                    report.per_axis_exposed_s.get(ax, 0.0) + exposed
    except Exception:            # pragma: no cover - defensive
        _LOG.debug("overlap census failed", exc_info=True)
    report.windows.sort(key=lambda w: -w.exposed_s)
    return report


# ---------------------------------------------------------------------------
# baseline regression gate
# ---------------------------------------------------------------------------

def load_baselines(path: str) -> Dict[str, Any]:
    """Per-leg overlap baselines: ``{leg: {exposed_comm_s,
    overlap_fraction, tol_pct}}`` (``_comment`` keys ignored)."""
    with open(path, "r", encoding="utf-8") as fh:
        raw = json.load(fh)
    return {k: v for k, v in raw.items() if not k.startswith("_")}


def check_baseline(report: OverlapReport, baselines: Dict[str, Any],
                   leg: str) -> List[Finding]:
    """Diff a program's overlap posture against a checked-in baseline.

    Both bands are one-sided regressions: ``exposed_comm_s`` may only
    GROW by tol_pct over the captured posture (less exposure is an
    improvement), and ``overlap_fraction`` may only FALL below the
    captured fraction by tol_pct (relative) or 0.05 (absolute floor —
    fractions near 0 need an absolute band).  Violations are
    error-severity ``overlap-regression`` findings so
    ``analyze='raise'`` fails fast on a change that re-serializes
    hidden communication (docs/ANALYSIS.md refresh workflow)."""
    base = baselines.get(leg)
    findings: List[Finding] = []
    if base is None:
        findings.append(Finding(
            checker="overlap", rule="overlap-regression",
            severity="warn",
            message=f"no overlap baseline for leg {leg!r} — add it to "
                    "the baselines file (docs/ANALYSIS.md)",
            where=leg))
        return findings
    tol = float(base.get("tol_pct", 50.0)) / 100.0
    e_base = float(base.get("exposed_comm_s", 0.0))
    # exposed seconds near zero need an absolute floor too (1 us)
    e_band = max(e_base * (1.0 + tol), e_base + 1e-6)
    if report.exposed_comm_s > e_band:
        findings.append(Finding(
            checker="overlap", rule="overlap-regression",
            message=f"[{leg}] exposed comm {report.exposed_comm_s:.3e}"
                    f" s exceeds baseline {e_base:.3e} s by more than "
                    f"{base.get('tol_pct', 50.0)}% — communication "
                    "this program used to hide behind compute is "
                    "exposed wall-clock again (docs/PERF_NOTES.md "
                    "\"Communication overlap\")",
            where=leg))
    f_base = base.get("overlap_fraction")
    if f_base is not None:
        f_floor = min(float(f_base) * (1.0 - tol),
                      float(f_base) - 0.05)
        if report.overlap_fraction < f_floor:
            findings.append(Finding(
                checker="overlap", rule="overlap-regression",
                message=f"[{leg}] overlap fraction "
                        f"{report.overlap_fraction:.3f} fell below "
                        f"baseline {float(f_base):.3f} — the schedule "
                        "stopped interleaving collectives with "
                        "independent compute; investigate, then "
                        "refresh the baseline if intentional "
                        "(docs/ANALYSIS.md)",
                where=leg))
    return findings


def baseline_from_env() -> Optional[tuple]:
    """``MXNET_OVERLAP_BASELINE=<path>[:<leg>]`` → (baselines dict,
    leg-or-None); None when unset or unreadable (logged, never
    raises)."""
    spec = os.environ.get("MXNET_OVERLAP_BASELINE")
    if not spec:
        return None
    path, leg = spec, None
    if ":" in spec and not os.path.exists(spec):
        path, leg = spec.rsplit(":", 1)
    try:
        return load_baselines(path), leg
    except Exception as e:       # pragma: no cover - defensive
        _LOG.warning("MXNET_OVERLAP_BASELINE=%r unreadable (%s: %s)",
                     spec, type(e).__name__, e)
        return None


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def publish(report: OverlapReport):
    """Refresh the exposed-comm gauges from one census (the latest
    analyzed program wins — one step program is live at a time)."""
    try:
        from ..telemetry import names as tn
        from ..telemetry import registry as treg
        reg = treg()
        for ax in report.per_axis_exposed_s:
            reg.gauge(tn.SHARDING_EXPOSED_COMM).set(
                report.per_axis_exposed_s[ax], label=ax)
        reg.gauge(tn.OVERLAP_FRACTION).set(report.overlap_fraction)
    except Exception:            # pragma: no cover - defensive
        _LOG.debug("overlap gauge publish failed", exc_info=True)
