"""Fusion census: static audit of XLA's fusion decisions in the
optimized HLO, after the method of "Operator Fusion in XLA: Analysis
and Evaluation" (arXiv:2301.13062).

The BENCH legs put LSTM at MFU 0.17 and ResNet at 0.275 against the
measured roofline — and the first question for any MFU gap is *where
does the program touch HBM that it didn't have to*.  XLA answers it
implicitly through fusion: everything inside one fusion kernel streams
through registers/VMEM, everything AT a kernel boundary is written to
and re-read from HBM.  This pass makes those boundaries inspectable
and regression-testable:

1. **Fusion graph** (:func:`fusion_census`): every ``fusion`` op (and
   every standalone compute kernel — dot, convolution, reduce,
   custom-call, …) in the *schedulable* computations (entry + while
   bodies + conditional branches; fusion bodies execute inside one
   kernel and are walked, not scheduled), with its kind
   (loop/input/output/custom), an opcode census of its body, a FLOP
   estimate, and the bytes it reads/writes at its boundary.
2. **Ideal-fusion diff**: (a) *stranded ops* — unfused elementwise /
   broadcast / convert / transpose ops sitting between two fusions
   above a size floor, each one two avoidable HBM round-trips per
   step; (b) *boundary materializations* — intermediates crossing a
   kernel boundary, ranked by bytes, flagged above a floor; (c)
   per-kernel **arithmetic intensity** (FLOPs / boundary bytes)
   classified compute- vs memory-bound against the measured BENCH
   roofline ridge point.
3. **Regression gate** (:func:`check_baseline`): checked-in per-leg
   baselines (``tests/fixtures/fusion_baselines.json``) with tolerance
   bands over {fusion count, stranded count, boundary bytes} — a jax
   bump or model edit that silently degrades fusion fails the tier-1
   sweep (and ``analyze='raise'`` under ``MXNET_FUSION_BASELINE``)
   instead of surfacing as an MFU drop three PRs later.

FLOP numbers are *estimates* from shapes (2·M·K·N dots, window-sized
convs, element-count elementwise) — good for ranking and bound
classification, not for billing. Boundary bytes inside while bodies
count once, not per trip (trip counts are not in the HLO text).
Sharded programs: a partitioned module (entry ``*_spmd``) already has
per-shard shapes and counts unchanged; an UNpartitioned
``num_partitions>1`` module still carries global shapes with
``sharding=`` annotations, and every annotated op's FLOPs/bytes are
divided by its tile factor so bound classification and the census
totals the MFU gauge sanity-checks against stay per-shard
(:func:`_shard_divisors`).
"""
from __future__ import annotations

import json
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Union

from .hlo import HloModule, HloOp, parse_hlo, parse_shape_elements
from .report import Finding

__all__ = ["FusionKernel", "StrandedOp", "Boundary", "FusionReport",
           "fusion_census", "op_flops", "register_custom_call_flops",
           "load_baselines", "check_baseline", "baseline_from_env",
           "publish", "STRANDED_FLOOR_BYTES", "BOUNDARY_FLOOR_BYTES",
           "RIDGE_FLOPS_PER_BYTE"]

_LOG = logging.getLogger("mxnet_tpu.analysis")

#: BENCH_r05 measured matmul roofline (TFLOP/s, TPU v5 lite) and the
#: chip's HBM bandwidth (GB/s, public spec) — their ratio is the
#: roofline ridge point that splits compute- from memory-bound kernels
BENCH_ROOFLINE_TFLOPS = 147.8
HBM_BANDWIDTH_GBPS = 819.0
RIDGE_FLOPS_PER_BYTE = BENCH_ROOFLINE_TFLOPS * 1e12 / \
    (HBM_BANDWIDTH_GBPS * 1e9)

#: byte floor below which a stranded op is scalar glue, not a finding
STRANDED_FLOOR_BYTES = 4096
#: byte floor above which a boundary materialization earns a finding
BOUNDARY_FLOOR_BYTES = 1 << 20

# opcodes XLA's fusion passes can absorb for free — an entry-level op
# from this set between two fusions is a missed fusion, not a kernel.
# `copy` is deliberately NOT here: optimized-HLO copies are buffer
# assignment / donation artifacts, not fusion misses.
_FUSABLE_OPCODES = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "abs", "negate", "exponential", "exponential-minus-one", "log",
    "log-plus-one", "tanh", "logistic", "sqrt", "rsqrt", "cbrt",
    "power", "sign", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "sine", "cosine", "tan", "atan2", "compare",
    "select", "clamp", "and", "or", "xor", "not",
    "shift-left", "shift-right-arithmetic", "shift-right-logical",
    "remainder", "is-finite", "convert", "broadcast", "transpose",
    "reshape", "reverse", "slice", "concatenate", "pad", "iota",
})

# elementwise opcodes for the FLOP model: ~1 flop per output element
_EW_FLOP_OPCODES = _FUSABLE_OPCODES | {"copy", "map", "select-and-scatter",
                                       "dynamic-slice",
                                       "dynamic-update-slice"}

# standalone ops that ARE kernels of their own at a schedulable level
# (the fusion graph's non-fusion nodes)
_KERNEL_OPCODES = frozenset({
    "dot", "convolution", "custom-call", "reduce", "reduce-window",
    "sort", "scatter", "gather", "cholesky", "triangular-solve", "fft",
    "rng", "rng-bit-generator", "topk",
})

# data-free plumbing: resolve through these when walking producer /
# consumer adjacency (they move no bytes)
_TRANSPARENT_OPCODES = frozenset({
    "get-tuple-element", "tuple", "bitcast", "copy-start", "copy-done",
})

# never "intermediates": inputs, module outputs, scalar immediates
_NON_MATERIAL_OPCODES = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id",
})


# ---------------------------------------------------------------------------
# FLOP model
# ---------------------------------------------------------------------------

def _dims_of(type_str: Optional[str]) -> List[int]:
    if not type_str:
        return []
    m = re.search(r"\[([\d,]*)\]", type_str)
    if not m or not m.group(1):
        return []
    return [int(d) for d in m.group(1).split(",") if d]


def _prod(dims: List[int]) -> int:
    out = 1
    for d in dims:
        out *= d
    return out


# custom-call FLOP estimators: without these every hand-written kernel
# (flash attention today, the ops/kernels layer's scan/optimizer/norm
# kernels tomorrow) counts ZERO FLOPs in the census — its arithmetic
# intensity degenerates to 0, it classifies memory-bound, and
# compute_bound_pct under-counts the very kernels written to be
# compute-dense. Matchers are substrings tested against the op's full
# HLO line (Mosaic kernels all share the `tpu_custom_call` target; the
# kernel function name survives in the op_name metadata).
_CUSTOM_CALL_FLOPS: List[tuple] = []


def register_custom_call_flops(name: str, fn, match: Optional[str] = None):
    """Register a FLOP estimator for custom-call kernels.

    ``fn(op: HloOp, mod: HloModule|None) -> int`` runs when ``match``
    (default: ``name``) appears in the custom-call's HLO line (target
    or metadata op_name). First match in registration order wins on
    overlap; re-registering an existing ``name`` replaces it
    (idempotent module reloads)."""
    key = (match or name).lower()
    for i, (n, _, _) in enumerate(_CUSTOM_CALL_FLOPS):
        if n == name:
            _CUSTOM_CALL_FLOPS[i] = (name, key, fn)
            return
    _CUSTOM_CALL_FLOPS.append((name, key, fn))


def _custom_call_flops(op: HloOp, mod: Optional[HloModule]) -> int:
    line = op.line.lower()
    for _, key, fn in _CUSTOM_CALL_FLOPS:
        if key in line:
            try:
                return int(fn(op, mod))
            except Exception:      # estimator bug must not kill a census
                _LOG.debug("custom-call flop estimator failed for %s",
                           op.name, exc_info=True)
                return 0
    return 0


def _operand_dims(op: HloOp, mod: Optional[HloModule],
                  i: int) -> List[int]:
    """Dims of operand ``i``: from the inline operand type when the
    HLO carries it, else resolved through the producing op."""
    if i < len(op.operand_types) and op.operand_types[i]:
        return _dims_of(op.operand_types[i])
    if mod is not None and i < len(op.operands):
        prod = mod.ops.get(op.operands[i])
        if prod is not None:
            return _dims_of(prod.type_str)
    return []


def _flash_fwd_flops(op: HloOp, mod=None) -> int:
    # two (Sq x Sk x D) matmuls a head, from q and k as the kernel
    # addresses them (ops/attention.py _Tiles): (B*H, S, D) at the
    # head's own width, or the projections' (B, S, H*D): rows x last
    # dimension is B*H*D either way, so one product counts both. Only a
    # head width padded in HBM (80 -> 128) still counts its zero lanes.
    q = _operand_dims(op, mod, 0)
    k = _operand_dims(op, mod, 1)
    if len(q) < 3 or len(k) < 3:
        return 0
    return 4 * q[0] * q[1] * k[1] * q[2]


def _flash_bwd_flops(factor: int):
    def fn(op: HloOp, mod=None) -> int:
        base = _flash_fwd_flops(op, mod)
        return base // 4 * factor
    return fn


def _rnn_scan_flops(op: HloOp, mod=None) -> int:
    # xw (T, N, G*H) + resident w_hh (G*H, H): T h2h matmuls + gates
    xw = _operand_dims(op, mod, 0)
    if len(xw) < 3:
        return 0
    t, n, gh = xw[0], xw[1], xw[2]
    w = next((d for d in (_operand_dims(op, mod, i)
                          for i in range(1, len(op.operands)))
              if len(d) == 2 and d[0] == gh), None)
    h = w[1] if w else gh
    return 2 * t * n * gh * h + 10 * t * n * gh


def _elementwise_flops(per_element: int):
    def fn(op: HloOp, mod=None) -> int:
        widest = max((_prod(_operand_dims(op, mod, i))
                      for i in range(len(op.operands))), default=0)
        return per_element * max(op.elements, widest)
    return fn


# the built-in kernel layer (ops/attention.py + ops/kernels/)
register_custom_call_flops("flash_attention_fwd", _flash_fwd_flops,
                           match="_flash_kernel")
register_custom_call_flops("flash_attention_bwd_dq",
                           _flash_bwd_flops(6), match="_flash_bwd_dq")
register_custom_call_flops("flash_attention_bwd_dkv",
                           _flash_bwd_flops(8), match="_flash_bwd_dkv")
register_custom_call_flops("flash_attention_bwd_fused",
                           _flash_bwd_flops(10),
                           match="_flash_bwd_fused")
register_custom_call_flops("rnn_scan_fwd", _rnn_scan_flops,
                           match="_fwd_kernel")
register_custom_call_flops("rnn_scan_bwd", _rnn_scan_flops,
                           match="_bwd_kernel")
register_custom_call_flops("opt_update", _elementwise_flops(10),
                           match="_opt_kernel")
register_custom_call_flops("layernorm_fwd", _elementwise_flops(8),
                           match="_ln_fwd_kernel")
register_custom_call_flops("layernorm_bwd", _elementwise_flops(12),
                           match="_ln_bwd_kernel")
register_custom_call_flops("bias_gelu_fwd", _elementwise_flops(15),
                           match="_bg_fwd_kernel")
register_custom_call_flops("bias_gelu_bwd", _elementwise_flops(18),
                           match="_bg_bwd_kernel")


def op_flops(op: HloOp, mod: Optional[HloModule] = None) -> int:
    """Estimated FLOPs of one HLO op from its line's shapes.

    dot: 2 · out_elements · contracted_size (contracting dims parsed
    from the line); convolution: 2 · out_elements · kernel_elems /
    out_features (dim_labels parsed); reduce/reduce-window: input
    elements; elementwise: output elements; fusion: sum over its body
    (``mod`` required to resolve the body). Unknown opcodes: 0."""
    if op.opcode == "fusion":
        if mod is None:
            return 0
        return sum(op_flops(b, mod) for b in mod.fused_ops(op)
                   if b.opcode != "fusion")
    if op.opcode == "dot":
        lhs_dims = _dims_of(op.operand_types[0]
                            if op.operand_types else None)
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
        contracted = 1
        if lhs_dims and m and m.group(1):
            for i in (int(x) for x in m.group(1).split(",") if x):
                if i < len(lhs_dims):
                    contracted *= lhs_dims[i]
        return 2 * op.elements * max(1, contracted)
    if op.opcode == "convolution":
        k_dims = _dims_of(op.operand_types[1]
                          if len(op.operand_types) > 1 else None)
        k_elems = 1
        for d in k_dims:
            k_elems *= d
        out_features = 1
        m = re.search(r"dim_labels=\w+_(\w+)->", op.line)
        if m and k_dims:
            o_at = m.group(1).find("o")
            if 0 <= o_at < len(k_dims):
                out_features = max(1, k_dims[o_at])
        return 2 * op.elements * max(1, k_elems // out_features)
    if op.opcode in ("reduce", "reduce-window"):
        in_bytes = op.operand_bytes(0)
        if in_bytes is not None and op.operand_types[0]:
            return parse_shape_elements(op.operand_types[0])[0]
        return op.elements
    if op.opcode == "custom-call":
        return _custom_call_flops(op, mod)
    if op.opcode in _EW_FLOP_OPCODES:
        return op.elements
    return 0


# ---------------------------------------------------------------------------
# report structures
# ---------------------------------------------------------------------------

@dataclass
class FusionKernel:
    """One kernel in the schedulable program: a ``fusion`` op (kind
    loop/input/output/custom) or a standalone compute op (kind = its
    opcode: dot, convolution, custom-call, …)."""
    name: str
    kind: str
    computation: str
    n_ops: int
    op_census: Dict[str, int]
    flops: int
    bytes_in: int
    bytes_out: int

    @property
    def boundary_bytes(self) -> int:
        return self.bytes_in + self.bytes_out

    @property
    def intensity(self) -> float:
        """Arithmetic intensity: FLOPs per HBM boundary byte."""
        return self.flops / self.boundary_bytes \
            if self.boundary_bytes else 0.0

    def bound(self, ridge: float = RIDGE_FLOPS_PER_BYTE) -> str:
        return "compute" if self.intensity >= ridge else "memory"

    def to_dict(self, ridge: float = RIDGE_FLOPS_PER_BYTE):
        return {"name": self.name, "kind": self.kind,
                "computation": self.computation, "n_ops": self.n_ops,
                "op_census": dict(self.op_census), "flops": self.flops,
                "bytes_in": self.bytes_in, "bytes_out": self.bytes_out,
                "intensity": round(self.intensity, 4),
                "bound": self.bound(ridge)}


@dataclass
class StrandedOp:
    """An unfused fusable op between two fusions: XLA materializes its
    input AND its output to HBM where either neighbor fusion could
    have absorbed it."""
    name: str
    opcode: str
    bytes: int
    producer: str           # the upstream fusion/kernel
    consumers: List[str]    # downstream fusions
    computation: str

    def to_dict(self):
        return {"name": self.name, "opcode": self.opcode,
                "bytes": self.bytes, "producer": self.producer,
                "consumers": list(self.consumers),
                "computation": self.computation}


@dataclass
class Boundary:
    """One intermediate tensor materialized at a kernel boundary
    (written to HBM by its producer, read back by each consumer)."""
    name: str
    opcode: str
    bytes: int
    consumers: List[str]
    computation: str

    def to_dict(self):
        return {"name": self.name, "opcode": self.opcode,
                "bytes": self.bytes, "consumers": list(self.consumers),
                "computation": self.computation}


@dataclass
class FusionReport:
    """Everything the fusion census measured about ONE optimized
    program, plus the ideal-diff findings."""
    kernels: List[FusionKernel] = field(default_factory=list)
    stranded: List[StrandedOp] = field(default_factory=list)
    boundaries: List[Boundary] = field(default_factory=list)
    boundary_bytes: int = 0
    stranded_floor: int = STRANDED_FLOOR_BYTES
    boundary_floor: int = BOUNDARY_FLOOR_BYTES
    ridge: float = RIDGE_FLOPS_PER_BYTE
    findings: List[Finding] = field(default_factory=list)

    @property
    def fusions(self) -> List[FusionKernel]:
        return [k for k in self.kernels
                if k.kind in ("loop", "input", "output", "custom")]

    @property
    def n_fusions(self) -> int:
        return len(self.fusions)

    @property
    def n_kernels(self) -> int:
        return len(self.kernels)

    @property
    def total_flops(self) -> int:
        return sum(k.flops for k in self.kernels)

    @property
    def compute_bound_pct(self) -> float:
        """FLOP-weighted share (0–100) of kernels whose arithmetic
        intensity clears the roofline ridge point."""
        total = self.total_flops
        if not total:
            return 0.0
        cb = sum(k.flops for k in self.kernels
                 if k.bound(self.ridge) == "compute")
        return round(100.0 * cb / total, 2)

    def by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for k in self.kernels:
            out[k.kind] = out.get(k.kind, 0) + 1
        return out

    def brief(self) -> Dict[str, Any]:
        """The four headline numbers (ProgramReport.to_dict / the BENCH
        json's per-leg fusion posture)."""
        return {"n_fusions": self.n_fusions,
                "stranded_ops": len(self.stranded),
                "boundary_bytes": self.boundary_bytes,
                "compute_bound_pct": self.compute_bound_pct}

    def to_dict(self):
        return {
            "n_fusions": self.n_fusions,
            "n_kernels": self.n_kernels,
            "by_kind": self.by_kind(),
            "stranded_ops": len(self.stranded),
            "boundary_bytes": self.boundary_bytes,
            "compute_bound_pct": self.compute_bound_pct,
            "stranded": [s.to_dict() for s in self.stranded[:16]],
            "top_boundaries": [b.to_dict()
                               for b in self.boundaries[:16]],
            "kernels": [k.to_dict(self.ridge) for k in self.kernels],
        }

    def summary_line(self) -> str:
        return (f"fusions={self.n_fusions} kernels={self.n_kernels} "
                f"stranded={len(self.stranded)} "
                f"boundary_bytes={self.boundary_bytes} "
                f"compute_bound={self.compute_bound_pct}%")

    def table(self, top: int = 24) -> str:
        """Human-readable kernel table (tools/diagnose.py --fusion)."""
        rows = sorted(self.kernels, key=lambda k: -k.flops)[:top]
        lines = [f"{'kernel':<42s}{'kind':<8s}{'ops':>4s}{'flops':>12s}"
                 f"{'bound B':>10s}{'fl/B':>8s}  bound"]
        for k in rows:
            census = ",".join(f"{o}x{n}" for o, n in sorted(
                k.op_census.items(), key=lambda kv: -kv[1])[:3])
            lines.append(
                f"{k.name[:40]:<42s}{k.kind:<8s}{k.n_ops:>4d}"
                f"{k.flops:>12d}{k.boundary_bytes:>10d}"
                f"{k.intensity:>8.2f}  {k.bound(self.ridge)}"
                + (f"  [{census}]" if census else ""))
        if len(self.kernels) > top:
            lines.append(f"  ... {len(self.kernels) - top} more kernels")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the census
# ---------------------------------------------------------------------------

def _resolve_through(mod: HloModule, name: str, downstream: bool,
                     _depth: int = 0) -> List[HloOp]:
    """Real neighbors of an op, looking through data-free plumbing
    (get-tuple-element / tuple / bitcast)."""
    if _depth > 8:
        return []
    out: List[HloOp] = []
    if downstream:
        neigh = mod.consumers(name)
    else:
        op = mod.ops.get(name)
        neigh = [mod.ops[o] for o in (op.operands if op else ())
                 if o in mod.ops]
    for n in neigh:
        if n.opcode in _TRANSPARENT_OPCODES:
            out.extend(_resolve_through(mod, n.name, downstream,
                                        _depth + 1))
        else:
            out.append(n)
    return out


def _shard_divisors(mod: HloModule):
    """Per-op byte/FLOP divisor for SPMD-sharded modules.

    The optimized HLO of a partitioned program (entry ``*_spmd``)
    already has PER-SHARD shapes — divisor 1 everywhere.  A
    ``num_partitions>1`` module the partitioner has NOT rewritten
    (pre-partitioning dumps, Shardy-style annotated modules, canned
    test programs) still carries GLOBAL logical shapes with
    ``sharding=`` annotations: counting those at face value overcounts
    FLOPs and boundary bytes by the tile factor, misclassifies
    memory-bound kernels as compute-bound, and inflates the census
    totals the MFU gauge is sanity-checked against.  Here every
    annotated op contributes its ``shard_count``; unannotated ops stay
    at 1 (conservative — only provably-sharded work is scaled)."""
    if mod.num_partitions <= 1 or mod.spmd_partitioned:
        return lambda op: 1
    from .sharding import parse_op_sharding
    cache: Dict[str, int] = {}

    def divisor(op: HloOp) -> int:
        f = cache.get(op.name)
        if f is not None:
            return f
        f = 1
        if op.sharding:
            sh = parse_op_sharding(op.sharding)
            if sh is not None and sh.kind == "tiled":
                f = max(1, sh.shard_count)
        cache[op.name] = f
        return f

    return divisor


def _kernel_of(mod: HloModule, op: HloOp) -> Optional[str]:
    """The kernel an op's data lives in at a schedulable level: the op
    itself when it IS a kernel (fusion / standalone compute), else
    None (it is a loose op or plumbing)."""
    if op.opcode == "fusion" or op.opcode in _KERNEL_OPCODES:
        return op.name
    return None


def fusion_census(hlo: Union[str, HloModule],
                  stranded_floor_bytes: int = STRANDED_FLOOR_BYTES,
                  boundary_floor_bytes: int = BOUNDARY_FLOOR_BYTES,
                  ridge_flops_per_byte: float = RIDGE_FLOPS_PER_BYTE) \
        -> FusionReport:
    """Audit fusion boundaries in one optimized HLO program.

    ``hlo`` is the ``compiled.as_text()`` dump (or an already-parsed
    :class:`HloModule`). Returns a :class:`FusionReport`; never raises
    on malformed text (an analyzer must not take down the run it
    observes) — unparseable programs yield an empty report."""
    mod = parse_hlo(hlo) if isinstance(hlo, str) else hlo
    report = FusionReport(stranded_floor=stranded_floor_bytes,
                          boundary_floor=boundary_floor_bytes,
                          ridge=ridge_flops_per_byte)
    sched = {c.name for c in mod.schedulable_computations()}
    if not sched:      # headerless canned snippets: treat all as entry
        sched = {None}
    shard_div = _shard_divisors(mod)

    for op in mod.ops.values():
        if op.computation not in sched and sched != {None}:
            continue
        # per-shard correction: global-shape sharded modules divide by
        # the op's tile factor (partitioned modules divide by 1)
        div = shard_div(op)
        op_bytes = op.bytes // div
        # --- kernel nodes: fusions + standalone compute ops ----------
        if op.opcode == "fusion":
            body = mod.fused_ops(op)
            census: Dict[str, int] = {}
            for b in body:
                if b.opcode in ("parameter", "constant"):
                    continue
                census[b.opcode] = census.get(b.opcode, 0) + 1
            bytes_in = 0
            for i in range(len(op.operands)):
                bytes_in += op.operand_bytes(i) or 0
            report.kernels.append(FusionKernel(
                name=op.name, kind=op.fusion_kind or "loop",
                computation=op.computation or "?",
                n_ops=sum(census.values()), op_census=census,
                flops=op_flops(op, mod) // div, bytes_in=bytes_in // div,
                bytes_out=op_bytes))
        elif op.opcode in _KERNEL_OPCODES:
            bytes_in = 0
            for i in range(len(op.operands)):
                bytes_in += op.operand_bytes(i) or 0
            report.kernels.append(FusionKernel(
                name=op.name,
                kind="custom-call" if op.opcode == "custom-call"
                else op.opcode,
                computation=op.computation or "?",
                n_ops=1, op_census={op.opcode: 1},
                flops=op_flops(op, mod) // div, bytes_in=bytes_in // div,
                bytes_out=op_bytes))

        # --- boundary materializations -------------------------------
        if op.opcode in _NON_MATERIAL_OPCODES or op.bytes == 0:
            continue
        consumers = [c for c in _resolve_through(mod, op.name, True)
                     if c.computation == op.computation]
        if not consumers or op.is_root:
            continue             # module/computation output, not a
            # boundary between two kernels
        report.boundary_bytes += op_bytes
        report.boundaries.append(Boundary(
            name=op.name, opcode=op.opcode, bytes=op_bytes,
            consumers=[c.name for c in consumers],
            computation=op.computation or "?"))

        # --- stranded fusable ops ------------------------------------
        if op.opcode in _FUSABLE_OPCODES and \
                op_bytes >= stranded_floor_bytes:
            producers = _resolve_through(mod, op.name, False)
            fused_prod = [p for p in producers
                          if p.opcode == "fusion"]
            fused_cons = [c for c in consumers
                          if c.opcode == "fusion"]
            if fused_prod and fused_cons:
                report.stranded.append(StrandedOp(
                    name=op.name, opcode=op.opcode, bytes=op_bytes,
                    producer=fused_prod[0].name,
                    consumers=[c.name for c in fused_cons],
                    computation=op.computation or "?"))

    report.boundaries.sort(key=lambda b: -b.bytes)
    report.stranded.sort(key=lambda s: -s.bytes)

    for s in report.stranded[:8]:
        report.findings.append(Finding(
            checker="fusion", rule="stranded-op", severity="warn",
            message=f"unfused `{s.opcode}` ({s.bytes} B) stranded "
                    f"between fusion `{s.producer}` and "
                    f"{len(s.consumers)} downstream fusion(s) — two "
                    "avoidable HBM round-trips per step "
                    "(arXiv:2301.13062 ideal-fusion diff)",
            where=s.name))
    for b in report.boundaries[:5]:
        if b.bytes < boundary_floor_bytes:
            break
        report.findings.append(Finding(
            checker="fusion", rule="fusion-boundary", severity="warn",
            message=f"kernel boundary materializes {b.bytes} B of "
                    f"`{b.opcode}` output to HBM (read back by "
                    f"{len(b.consumers)} consumer(s)) — candidates "
                    "for fusion or recomputation",
            where=b.name))
    return report


# ---------------------------------------------------------------------------
# baseline regression gate
# ---------------------------------------------------------------------------

def load_baselines(path: str) -> Dict[str, Any]:
    """Per-leg fusion baselines: ``{leg: {n_fusions, stranded_ops,
    boundary_bytes, tol_pct}}`` (``_comment`` keys ignored)."""
    with open(path, "r", encoding="utf-8") as fh:
        raw = json.load(fh)
    return {k: v for k, v in raw.items() if not k.startswith("_")}


def check_baseline(report: FusionReport, baselines: Dict[str, Any],
                   leg: str) -> List[Finding]:
    """Diff a program's fusion posture against a checked-in baseline.

    Bands: ``n_fusions`` must stay within ±tol_pct (min ±1 — fusion
    counts move both ways when XLA repartitions, either direction is a
    posture change to re-baseline consciously); ``stranded_ops`` and
    ``boundary_bytes`` are one-sided — fewer/less is an improvement,
    more than baseline (+tol for bytes) is a regression.  Every
    violation is an error-severity ``fusion-regression`` finding, so
    ``analyze='raise'`` fails fast (docs/ANALYSIS.md documents the
    refresh workflow for legitimate jax-upgrade shifts)."""
    base = baselines.get(leg)
    findings: List[Finding] = []
    if base is None:
        findings.append(Finding(
            checker="fusion", rule="fusion-regression", severity="warn",
            message=f"no fusion baseline for leg {leg!r} — add it to "
                    "the baselines file (docs/ANALYSIS.md)",
            where=leg))
        return findings
    tol = float(base.get("tol_pct", 25.0)) / 100.0
    n_base = int(base.get("n_fusions", 0))
    band = max(1, int(round(n_base * tol)))
    if abs(report.n_fusions - n_base) > band:
        findings.append(Finding(
            checker="fusion", rule="fusion-regression",
            message=f"[{leg}] fusion count {report.n_fusions} left the "
                    f"baseline band {n_base}±{band} — XLA's fusion "
                    "partitioning changed; investigate, then refresh "
                    "the baseline if intentional (docs/ANALYSIS.md)",
            where=leg))
    s_base = int(base.get("stranded_ops", 0))
    if len(report.stranded) > s_base:
        worst = report.stranded[0]
        findings.append(Finding(
            checker="fusion", rule="fusion-regression",
            message=f"[{leg}] {len(report.stranded)} stranded op(s) vs "
                    f"baseline {s_base} — new unfused op(s) between "
                    f"fusions (worst: `{worst.opcode}` {worst.bytes} B "
                    f"at {worst.name})",
            where=leg))
    b_base = int(base.get("boundary_bytes", 0))
    if b_base and report.boundary_bytes > b_base * (1.0 + tol):
        findings.append(Finding(
            checker="fusion", rule="fusion-regression",
            message=f"[{leg}] materialized boundary bytes "
                    f"{report.boundary_bytes} exceed baseline {b_base} "
                    f"by more than {base.get('tol_pct', 25.0)}% — the "
                    "program round-trips more intermediate data "
                    "through HBM than it used to",
            where=leg))
    return findings


def baseline_from_env() -> Optional[tuple]:
    """``MXNET_FUSION_BASELINE=<path>[:<leg>]`` → (baselines dict,
    leg-or-None); None when unset or unreadable (logged, never
    raises)."""
    spec = os.environ.get("MXNET_FUSION_BASELINE")
    if not spec:
        return None
    path, leg = spec, None
    if ":" in spec and not os.path.exists(spec):
        path, leg = spec.rsplit(":", 1)
    try:
        return load_baselines(path), leg
    except Exception as e:       # pragma: no cover - defensive
        _LOG.warning("MXNET_FUSION_BASELINE=%r unreadable (%s: %s)",
                     spec, type(e).__name__, e)
        return None


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def publish(report: FusionReport):
    """Refresh the ``mx_fusion_*`` gauges from one census (the latest
    analyzed program wins — one step program is live at a time)."""
    try:
        from ..telemetry import names as tn
        from ..telemetry import registry as treg
        reg = treg()
        reg.gauge(tn.FUSION_REGIONS).set(report.n_fusions)
        reg.gauge(tn.FUSION_STRANDED).set(len(report.stranded))
        reg.gauge(tn.FUSION_BOUNDARY_BYTES).set(report.boundary_bytes)
        reg.gauge(tn.FUSION_COMPUTE_BOUND).set(
            report.compute_bound_pct / 100.0)
    except Exception:            # pragma: no cover - defensive
        _LOG.debug("fusion gauge publish failed", exc_info=True)
