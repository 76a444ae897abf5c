"""SPMD sharding static analysis: sharding-flow audit, implicit-reshard
detection, per-mesh-axis communication cost model, spec invariant packs.

The collective census (PR 4) can say *which* collectives a compiled
program runs and on which mesh axes; it cannot say whether the program's
sharding matches the user's INTENT, or what the communication costs.
This pass closes both gaps, the checker spine the unified sharding
frontend (`compile_step(mesh=, spec=)`) will stand on — built before the
refactor the same way the PR 9 fusion census preceded the PR 10 kernel
layer:

1. **Sharding-flow audit** (:func:`sharding_table`): GSPMD
   ``sharding={...}`` annotations on the optimized HLO's entry
   parameters / outputs / annotated ops (and ``mhlo.sharding`` attrs on
   the StableHLO side) parsed into structured :class:`OpSharding`
   objects — iota tile assignments (``devices=[2,2]<=[4]``, with
   ``T(...)`` source transposes), explicit device lists, partial
   replication (``last_tile_dim_replicate``), ``replicated`` /
   ``manual`` / ``maximal``, and tuple shardings — resolved against the
   mesh's axis names into PartitionSpec-shaped per-dim axis tuples.
   The result is the per-parameter/per-activation sharding table of the
   entry computation: what layout each buffer ACTUALLY got.
2. **Implicit-reshard detection** (:func:`implicit_reshards`):
   SPMD-partitioner-inserted all-gathers / all-to-alls /
   collective-permutes that are not implied by the declared spec (a
   ``P("dp", None)`` input silently gathered to replicated before a
   matmul), ranked by wire bytes moved per step, each naming the
   producing and consuming op.  "Implied" is declarative: a
   :class:`SpecPack` blesses the collectives its parallelism pattern is
   SUPPOSED to run (ZeRO's reduce-scatter + weight all-gather, MoE's
   two all-to-alls, the pipeline/ring ppermutes); everything else above
   the byte floor is a reshard the user did not ask for.
3. **Per-axis communication cost model** (:func:`comm_cost`): every
   collective costed in estimated seconds from ring-algorithm wire
   bytes over a per-axis bandwidth profile — ICI vs DCN vs the measured
   CPU fallback, the machine profile checked in next to the fusion
   census's roofline constants (``MXNET_SHARDING_BANDWIDTH``
   overrides).  This upgrades the PR 4 census from counting to costing
   and publishes the ``mx_sharding_*`` gauges.
4. **``expect_spec`` invariant packs** (:class:`SpecPack`,
   :func:`expect_spec`): ``expect_mode``'s fused/zero/predict
   expectations generalized to declarative packs over arbitrary
   mesh+PartitionSpec layouts — each pack asserts its collective
   signature (min/max per kind×axis), zero implicit reshards above its
   floor, and its sharded-state byte budget (table-derived: params laid
   out on the pack's state axis must actually be ~1/N per replica).
   Packs for the five existing parallelism paths register from their
   home modules (dp/ZeRO here in analysis/program.py's expect_mode,
   tp + sequence-parallel ring attention from ops/attention.py,
   expert-parallel from ops/moe.py, pipeline from parallel/pipeline.py).
5. **Baseline regression gate** (:func:`check_baseline`): checked-in
   per-leg ``{implicit_reshards, reshard_bytes}`` baselines
   (``tests/fixtures/sharding_baselines.json``) enforced by the tier-1
   sweep and by ``MXNET_SHARDING_BASELINE=<path>[:<leg>]`` inside any
   ``analyze()`` — a jax bump or model edit that silently starts
   gathering a sharded tensor fails fast instead of surfacing as a
   step-time regression three PRs later.

Like every analyzer here: parsing failures degrade to unresolved
fields, never exceptions — an analyzer must not take down the run it
observes.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from .hlo import HloModule, HloOp, parse_hlo, parse_shape_elements
from .report import CollectiveOp, CollectiveStats, Finding

__all__ = [
    "OpSharding", "parse_op_sharding", "ParamSharding", "ShardingTable",
    "sharding_table", "stablehlo_shardings", "Reshard",
    "implicit_reshards", "BandwidthProfile", "bandwidth_profile",
    "collective_wire_bytes", "CommCost", "comm_cost", "CollectiveRule",
    "SpecPack", "register_spec_pack", "get_spec_pack", "spec_packs",
    "expect_spec", "ShardingAudit", "audit_sharding", "publish",
    "load_baselines", "check_baseline", "baseline_from_env",
    "RESHARD_FLOOR_BYTES", "ICI_BANDWIDTH_GBPS", "DCN_BANDWIDTH_GBPS",
    "CPU_BANDWIDTH_GBPS",
]

_LOG = logging.getLogger("mxnet_tpu.analysis")

#: byte floor below which an undeclared collective is scalar glue
#: (partition-id bookkeeping, loss/metric gathers), not a reshard
#: finding — same spirit as the fusion census's stranded floor
RESHARD_FLOOR_BYTES = 4096

#: per-link bandwidth profile, checked in next to the fusion census's
#: roofline constants (fusion.BENCH_ROOFLINE_TFLOPS / HBM 819 GB/s):
#: ICI = one inter-chip ring link of the BENCH_r05 machine (TPU v5
#: lite, public spec ~200 GB/s per chip; one ring direction), DCN = the
#: data-center NIC path pods cross between slices (~200 Gbit/s), CPU =
#: the measured host-loopback fallback the 8-device virtual mesh
#: actually moves bytes over.  Estimates rank and budget — they are not
#: a network simulator (MXNET_SHARDING_BANDWIDTH overrides).
ICI_BANDWIDTH_GBPS = 180.0
DCN_BANDWIDTH_GBPS = 25.0
CPU_BANDWIDTH_GBPS = 10.0

_LINK_GBPS = {"ici": ICI_BANDWIDTH_GBPS, "dcn": DCN_BANDWIDTH_GBPS,
              "cpu": CPU_BANDWIDTH_GBPS}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: collective kinds the SPMD partitioner inserts to MOVE data between
#: layouts (vs reduce it) — the implicit-reshard candidates.  A healthy
#: all-reduce is a declared reduction (grad psum, loss mean); gathers /
#: exchanges / permutes not named by the spec pack are layout changes
#: the user did not ask for.
RESHARD_KINDS = ("all_gather", "all_to_all", "collective_permute")


# ---------------------------------------------------------------------------
# OpSharding: the GSPMD sharding-annotation grammar
# ---------------------------------------------------------------------------

_DEVICES_RE = re.compile(
    r"devices=\[([\d,]+)\]"                       # tile dims
    r"(?:<=\[([\d,]+)\](?:T\(([\d,]+)\))?"        # iota [+ transpose]
    r"|([\d][\d,\s]*))?")                         # | explicit id list
_LAST_TILE_REPL_RE = re.compile(r"last_tile_dim_replicate")
_LAST_TILE_DIMS_RE = re.compile(r"last_tile_dims=\{([^}]*)\}")
_MAXIMAL_RE = re.compile(r"maximal.*?device=(\d+)|\{(\d+)\}")


@dataclass
class OpSharding:
    """One parsed GSPMD sharding annotation.

    ``kind``: ``replicated`` | ``tiled`` | ``manual`` | ``maximal`` |
    ``tuple`` | ``unknown``.  For ``tiled``, ``tile_dims`` holds the
    full tile-assignment shape (INCLUDING any trailing replication /
    manual subgroup dims — ``n_subgroup_dims`` of them) and
    ``device_order`` the flattened device ids in assignment order.
    ``spec`` is filled by :meth:`resolve`: one entry per TENSOR dim —
    ``None`` (unsharded), an axis name, or a tuple of axis names."""
    kind: str
    raw: str = ""
    tile_dims: Tuple[int, ...] = ()
    n_subgroup_dims: int = 0
    device_order: Optional[Tuple[int, ...]] = None
    maximal_device: Optional[int] = None
    parts: Optional[List["OpSharding"]] = None      # tuple shardings
    spec: Optional[Tuple[Any, ...]] = None          # resolved vs mesh

    @property
    def data_tile_dims(self) -> Tuple[int, ...]:
        """Tile dims that partition TENSOR data (subgroup dims — the
        ``last_tile_dim_replicate`` replication dim, ``last_tile_dims``
        manual dims — stripped)."""
        if self.n_subgroup_dims:
            return self.tile_dims[:-self.n_subgroup_dims]
        return self.tile_dims

    @property
    def shard_count(self) -> int:
        """Shards the data is split into (1 for replicated/manual)."""
        n = 1
        for d in self.data_tile_dims:
            n *= d
        return n

    def local_shape(self, global_shape: Sequence[int]) -> Tuple[int, ...]:
        """Per-shard shape of a ``global_shape`` tensor under this
        sharding (ceil-divided, as GSPMD pads)."""
        dims = self.data_tile_dims
        out = []
        for i, g in enumerate(global_shape):
            t = dims[i] if i < len(dims) else 1
            out.append(-(-int(g) // max(1, t)))
        return tuple(out)

    def global_shape(self, local_shape: Sequence[int]) -> Tuple[int, ...]:
        """Global logical shape reconstructed from a per-shard shape
        (exact when the global dim divided evenly; an upper bound
        otherwise — GSPMD pads the last shard)."""
        dims = self.data_tile_dims
        out = []
        for i, l in enumerate(local_shape):
            t = dims[i] if i < len(dims) else 1
            out.append(int(l) * max(1, t))
        return tuple(out)

    def resolve(self, mesh) -> Optional[Tuple[Any, ...]]:
        """Fill ``spec`` with the mesh axis (or axis tuple) each tensor
        dim is sharded over, by matching the tile assignment's device
        order against the mesh's device-id array.  ``None`` when the
        assignment doesn't correspond to this mesh (wrong world, or an
        explicit order no axis permutation explains)."""
        self.spec = _resolve_spec(self, mesh)
        return self.spec

    def describe(self) -> str:
        if self.kind == "tiled":
            if self.spec is not None:
                parts = []
                for s in self.spec:
                    if s is None:
                        parts.append("-")
                    elif isinstance(s, tuple):
                        parts.append("(" + ",".join(s) + ")")
                    else:
                        parts.append(str(s))
                body = "P(" + ", ".join(parts) + ")"
            else:
                body = "tiled" + str(list(self.data_tile_dims))
            if self.n_subgroup_dims:
                body += "+partial"
            return body
        if self.kind == "tuple":
            return "(" + ", ".join(p.describe()
                                   for p in (self.parts or [])) + ")"
        return self.kind

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "tile_dims": list(self.tile_dims),
                "shard_count": self.shard_count,
                "spec": [list(s) if isinstance(s, tuple) else s
                         for s in self.spec] if self.spec is not None
                else None,
                "describe": self.describe()}


def parse_op_sharding(text: Optional[str]) -> Optional[OpSharding]:
    """Parse one ``sharding={...}`` / ``mhlo.sharding`` annotation body.

    Accepts the braces-included raw attr (``{devices=[2,2]<=[4]}``) or
    its bare contents; tuple shardings (``{{replicated}, {devices=...}}``)
    return kind ``tuple`` with ``parts``.  Unrecognized text degrades to
    kind ``unknown``, never raises."""
    if not text:
        return None
    body = text.strip()
    if body.startswith("{") and body.endswith("}"):
        body = body[1:-1].strip()
    if body.startswith("{"):
        # tuple-of-shardings: split top-level {...} groups
        parts, depth, start = [], 0, None
        for i, ch in enumerate(body):
            if ch == "{":
                if depth == 0:
                    start = i
                depth += 1
            elif ch == "}":
                depth -= 1
                if depth == 0 and start is not None:
                    sub = parse_op_sharding(body[start:i + 1])
                    if sub is not None:
                        parts.append(sub)
        return OpSharding(kind="tuple", raw=text, parts=parts)
    if body == "replicated":
        return OpSharding(kind="replicated", raw=text)
    if body.startswith("manual"):
        return OpSharding(kind="manual", raw=text)
    if body.startswith("maximal") or re.fullmatch(r"\d+", body):
        m = _MAXIMAL_RE.search(body)
        dev = None
        if m:
            dev = int(m.group(1) or m.group(2))
        return OpSharding(kind="maximal", raw=text, maximal_device=dev)
    m = _DEVICES_RE.search(body)
    if m is None:
        return OpSharding(kind="unknown", raw=text)
    tile_dims = tuple(int(d) for d in m.group(1).split(",") if d)
    order: Optional[Tuple[int, ...]] = None
    n = 1
    for d in tile_dims:
        n *= d
    if m.group(2):                                    # iota form
        try:
            import numpy as onp
            src = [int(x) for x in m.group(2).split(",") if x]
            ids = onp.arange(int(onp.prod(src))).reshape(src)
            if m.group(3):
                perm = [int(x) for x in m.group(3).split(",") if x]
                ids = ids.transpose(perm)
            order = tuple(int(x) for x in ids.reshape(-1))
        except Exception:                # pragma: no cover - defensive
            order = None
    elif m.group(4):                                  # explicit list
        order = tuple(int(x) for x in
                      m.group(4).replace(" ", "").split(",") if x != "")
    if order is not None and len(order) != n:
        order = None
    subgroups = 0
    if _LAST_TILE_REPL_RE.search(body):
        subgroups = 1
    ltd = _LAST_TILE_DIMS_RE.search(body)
    if ltd:
        subgroups = max(subgroups,
                        len([x for x in ltd.group(1).split(",") if x]))
    return OpSharding(kind="tiled", raw=text, tile_dims=tile_dims,
                      n_subgroup_dims=subgroups, device_order=order)


def _mesh_coords(mesh):
    """{device_id: (coord per mesh axis)} + axis names/sizes, for any
    DeviceMesh / jax Mesh; None when unavailable."""
    jmesh = getattr(mesh, "mesh", mesh)
    if jmesh is None:
        return None
    try:
        import numpy as onp
        dev_ids = onp.array([d.id for d in jmesh.devices.flat]).reshape(
            jmesh.devices.shape)
        axis_names = list(jmesh.axis_names)
        coords: Dict[int, Tuple[int, ...]] = {}
        for idx in onp.ndindex(dev_ids.shape):
            coords[int(dev_ids[idx])] = tuple(int(i) for i in idx)
        return coords, axis_names, dev_ids.shape
    except Exception:                    # pragma: no cover - defensive
        return None


def _resolve_spec(sh: OpSharding, mesh) -> Optional[Tuple[Any, ...]]:
    if sh.kind != "tiled" or sh.device_order is None:
        return None
    info = _mesh_coords(mesh)
    if info is None:
        return None
    coords, axis_names, axis_sizes = info
    if any(i not in coords for i in sh.device_order):
        return None                      # annotation from another world
    try:
        import numpy as onp
        assignment = onp.array(sh.device_order).reshape(sh.tile_dims)
        n_axes = len(axis_names)
        # per-tile-dim: which mesh-axis coordinates vary along it
        spec: List[Any] = []
        varies = []                      # [dim][axis] -> bool
        for dim in range(len(sh.tile_dims)):
            moved = onp.moveaxis(assignment, dim, -1).reshape(
                -1, sh.tile_dims[dim])
            v = [False] * n_axes
            for row in moved:
                base = coords[int(row[0])]
                for dev in row[1:]:
                    c = coords[int(dev)]
                    for a in range(n_axes):
                        if c[a] != base[a]:
                            v[a] = True
            varies.append(v)
        for dim in range(len(sh.data_tile_dims)):
            t = sh.tile_dims[dim]
            if t == 1:
                spec.append(None)
                continue
            axes = tuple(axis_names[a] for a in range(n_axes)
                         if varies[dim][a]
                         # an axis belongs to ONE tensor dim; exclude
                         # axes that also vary along another data dim
                         and not any(varies[d2][a]
                                     for d2 in range(
                                         len(sh.data_tile_dims))
                                     if d2 != dim))
            ext = 1
            for ax in axes:
                ext *= int(axis_sizes[axis_names.index(ax)])
            if not axes or ext != t:
                spec.append(None)        # unresolvable against this mesh
            elif len(axes) == 1:
                spec.append(axes[0])
            else:
                spec.append(axes)
        return tuple(spec)
    except Exception:                    # pragma: no cover - defensive
        return None


# ---------------------------------------------------------------------------
# sharding-flow audit: the per-buffer sharding table
# ---------------------------------------------------------------------------

_PARAM_IDX_RE = re.compile(r"parameter\((\d+)\)")
# StableHLO:  %arg0: tensor<8x16xf32> ... mhlo.sharding = "{...}"
_MHLO_ARG_RE = re.compile(
    r"%arg(\d+):\s*tensor<((?:\d+x)*)([a-z][a-z0-9]*)>"
    r"[^)]*?mhlo\.sharding\s*=\s*\"([^\"]+)\"")


@dataclass
class ParamSharding:
    """One entry-computation buffer's resolved layout."""
    index: int
    name: str                            # op_name metadata (jax label)
    role: str                            # parameter | output | op
    local_shape: Tuple[int, ...]
    global_shape: Tuple[int, ...]
    dtype: str
    bytes_local: int
    bytes_global: int
    sharding: Optional[OpSharding]

    @property
    def describe(self) -> str:
        return self.sharding.describe() if self.sharding else "?"

    def to_dict(self) -> Dict[str, Any]:
        return {"index": self.index, "name": self.name, "role": self.role,
                "local_shape": list(self.local_shape),
                "global_shape": list(self.global_shape),
                "dtype": self.dtype, "bytes_local": self.bytes_local,
                "bytes_global": self.bytes_global,
                "sharding": self.sharding.to_dict()
                if self.sharding else None}


@dataclass
class ShardingTable:
    """Per-parameter/per-activation sharding of one entry computation."""
    params: List[ParamSharding] = field(default_factory=list)
    outputs: List[ParamSharding] = field(default_factory=list)
    annotated: List[ParamSharding] = field(default_factory=list)
    num_partitions: int = 1
    mesh_axes: Tuple[str, ...] = ()

    @property
    def rows(self) -> List[ParamSharding]:
        return self.params + self.outputs + self.annotated

    def digest(self) -> str:
        """Stable fingerprint of the program's layout decisions — two
        captures with the same digest shard every buffer identically."""
        h = hashlib.sha1()
        for r in sorted(self.rows, key=lambda r: (r.role, r.index,
                                                  r.name)):
            h.update(f"{r.role}:{r.index}:{r.name}:{r.dtype}:"
                     f"{r.local_shape}:"
                     f"{r.sharding.raw if r.sharding else '-'}"
                     .encode())
        return h.hexdigest()[:12]

    def sharded_bytes(self, axis: str) -> Tuple[int, int]:
        """(local, global) bytes summed over params whose resolved spec
        names ``axis`` — the table-derived state footprint a spec
        pack's byte budget checks."""
        loc = glob = 0
        for r in self.params:
            spec = r.sharding.spec if r.sharding else None
            if not spec:
                continue
            hit = any(s == axis or (isinstance(s, tuple) and axis in s)
                      for s in spec)
            if hit:
                loc += r.bytes_local
                glob += r.bytes_global
        return loc, glob

    def to_dict(self) -> Dict[str, Any]:
        return {"num_partitions": self.num_partitions,
                "mesh_axes": list(self.mesh_axes),
                "digest": self.digest(),
                "params": [r.to_dict() for r in self.params],
                "outputs": [r.to_dict() for r in self.outputs],
                "annotated": [r.to_dict() for r in self.annotated]}

    def table_str(self, top: int = 32) -> str:
        short = {"parameter": "param", "output": "out", "op": "op"}
        lines = [f"{'#':>3s} {'role':<7s}{'buffer':<34s}{'dtype':<7s}"
                 f"{'local':<16s}{'global':<16s}layout"]
        for r in self.rows[:top]:
            lines.append(
                f"{r.index:>3d} {short.get(r.role, r.role):<7s}"
                f"{r.name[:32]:<34s}"
                f"{r.dtype:<7s}{str(list(r.local_shape)):<16s}"
                f"{str(list(r.global_shape)):<16s}{r.describe}")
        if len(self.rows) > top:
            lines.append(f"  ... {len(self.rows) - top} more buffers")
        return "\n".join(lines)


def stablehlo_shardings(text: str) -> Dict[int, Tuple[Tuple[int, ...],
                                                      str, OpSharding]]:
    """``mhlo.sharding`` annotations of a lowered StableHLO module:
    {arg index: (GLOBAL shape, dtype, OpSharding)} — StableHLO is
    pre-partitioning, so its shapes are the global logical ones."""
    out: Dict[int, Tuple[Tuple[int, ...], str, OpSharding]] = {}
    for m in _MHLO_ARG_RE.finditer(text or ""):
        idx = int(m.group(1))
        dims = tuple(int(d) for d in m.group(2).split("x") if d)
        if idx in out:
            continue                     # first mention wins
        sh = parse_op_sharding(m.group(4))
        if sh is not None:
            out[idx] = (dims, m.group(3), sh)
    return out


def _shape_of(type_str: str) -> Tuple[int, ...]:
    m = re.search(r"\[([\d,]*)\]", type_str or "")
    if not m or not m.group(1):
        return ()
    return tuple(int(d) for d in m.group(1).split(",") if d)


def sharding_table(hlo: Union[str, HloModule], mesh=None,
                   stablehlo: str = "") -> ShardingTable:
    """Build the sharding-flow table of one optimized program.

    Entry parameters and the entry ROOT (with their ``sharding=``
    attrs), plus any annotated non-parameter op, resolved against
    ``mesh`` when given.  ``stablehlo`` (the lowered pre-partitioning
    text) supplies exact global shapes where available; otherwise
    global = local x tile dims."""
    mod = parse_hlo(hlo) if isinstance(hlo, str) else hlo
    jmesh = getattr(mesh, "mesh", mesh)
    table = ShardingTable(num_partitions=mod.num_partitions,
                          mesh_axes=tuple(jmesh.axis_names)
                          if jmesh is not None else ())
    mhlo = stablehlo_shardings(stablehlo)
    entry = mod.computations.get(mod.entry or "")
    names = entry.op_names if entry is not None else list(mod.ops)
    for op_name in names:
        op = mod.ops.get(op_name)
        if op is None:
            continue
        sh = parse_op_sharding(op.sharding) if op.sharding else None
        if sh is not None and mesh is not None:
            sh.resolve(mesh)
        local = _shape_of(op.type_str)
        if op.opcode == "parameter":
            im = _PARAM_IDX_RE.search(op.line)
            idx = int(im.group(1)) if im else len(table.params)
            glob = None
            if idx in mhlo:
                glob = mhlo[idx][0]
                if sh is None:
                    sh = mhlo[idx][2]
                    if mesh is not None:
                        sh.resolve(mesh)
            if glob is None:
                glob = sh.global_shape(local) if sh else local
            gelems = 1
            for d in glob:
                gelems *= d
            table.params.append(ParamSharding(
                index=idx, name=op.op_name or op.name, role="parameter",
                local_shape=local, global_shape=tuple(glob),
                dtype=op.dtype or "?", bytes_local=op.bytes,
                bytes_global=gelems * _DTYPE_BYTES.get(op.dtype or "f32",
                                                       4),
                sharding=sh))
        elif op.is_root:
            glob = sh.global_shape(local) if sh else local
            table.outputs.append(ParamSharding(
                index=0, name=op.op_name or op.name, role="output",
                local_shape=local, global_shape=tuple(glob),
                dtype=op.dtype or "?", bytes_local=op.bytes,
                bytes_global=op.bytes * (sh.shard_count if sh else 1),
                sharding=sh))
        elif sh is not None:
            glob = sh.global_shape(local)
            table.annotated.append(ParamSharding(
                index=len(table.annotated), name=op.op_name or op.name,
                role="op", local_shape=local, global_shape=tuple(glob),
                dtype=op.dtype or "?", bytes_local=op.bytes,
                bytes_global=op.bytes * sh.shard_count, sharding=sh))
    table.params.sort(key=lambda r: r.index)
    return table


# ---------------------------------------------------------------------------
# per-axis communication cost model
# ---------------------------------------------------------------------------

class BandwidthProfile:
    """Per-mesh-axis link bandwidth, GB/s.

    Built from a spec string (``MXNET_SHARDING_BANDWIDTH``): a bare link
    kind (``ici`` | ``dcn`` | ``cpu``) or GB/s number applies to every
    axis; ``axis=kind_or_GBps`` entries override per axis
    (``"dp=ici,pp=dcn"`` models a two-slice pod).  Default: ``ici`` on
    TPU backends, the measured ``cpu`` fallback elsewhere."""

    def __init__(self, default_gbps: float,
                 axis_gbps: Optional[Dict[str, float]] = None,
                 name: str = "custom"):
        self.default_gbps = float(default_gbps)
        self.axis_gbps = dict(axis_gbps or {})
        self.name = name

    def gbps(self, axes: Sequence[str] = ()) -> float:
        for ax in axes or ():
            if ax in self.axis_gbps:
                return self.axis_gbps[ax]
        return self.default_gbps

    @staticmethod
    def _term(term: str) -> Optional[float]:
        term = term.strip().lower()
        if term in _LINK_GBPS:
            return _LINK_GBPS[term]
        try:
            return float(term)
        except ValueError:
            return None

    @classmethod
    def parse(cls, spec: str) -> "BandwidthProfile":
        default = None
        axis: Dict[str, float] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" in part:
                ax, val = part.split("=", 1)
                g = cls._term(val)
                if g is not None:
                    if ax.strip() in ("default", "*"):
                        default = g
                    else:
                        axis[ax.strip()] = g
            else:
                g = cls._term(part)
                if g is not None:
                    default = g
        if default is None:
            default = _default_link_gbps()
        return cls(default, axis, name=spec)


def _default_link_gbps() -> float:
    try:
        import jax
        backend = jax.default_backend()
    except Exception:                    # pragma: no cover - defensive
        backend = "cpu"
    return ICI_BANDWIDTH_GBPS if backend == "tpu" else CPU_BANDWIDTH_GBPS


def bandwidth_profile(spec: Optional[str] = None) -> BandwidthProfile:
    """The active profile: ``spec`` > ``MXNET_SHARDING_BANDWIDTH`` env >
    backend default (ICI on TPU, measured CPU fallback elsewhere)."""
    spec = spec if spec is not None \
        else os.environ.get("MXNET_SHARDING_BANDWIDTH")
    if spec:
        return BandwidthProfile.parse(spec)
    g = _default_link_gbps()
    name = "ici" if g == ICI_BANDWIDTH_GBPS else "cpu"
    return BandwidthProfile(g, name=name)


def collective_wire_fraction(kind: str, group_size: int,
                             decomposed: bool = False) -> float:
    """Ring-model wire traffic as a FRACTION of the census record's
    payload bytes.  Costing through this fraction prices collectives
    per payload byte rather than per op, so N bucketed collectives of B
    bytes each cost the same as one collective of N*B bytes — bucketing
    the ZeRO gradient for overlap must not inflate the modeled cost."""
    n = max(1, group_size)
    if n == 1:
        return 0.0
    if kind == "all_gather":
        return (n - 1) / n
    if kind == "reduce_scatter":
        if decomposed:                    # payload = full input
            return (n - 1) / n
        return float(n - 1)               # payload = the 1/n shard
    if kind == "all_reduce":
        return 2 * (n - 1) / n
    if kind == "all_to_all":
        return (n - 1) / n
    return 1.0                            # collective_permute: one hop


def collective_wire_bytes(op: CollectiveOp) -> int:
    """Ring-algorithm bytes each participant moves over its link for
    one collective, from the census record's RESULT payload.

    all_gather: result is the full gathered buffer -> (n-1)/n x result.
    reduce_scatter: result is the 1/n shard -> (n-1) x result ((n-1)/n
    of the full input; a DECOMPOSED record's payload is the full
    all-reduce result, so (n-1)/n x payload).  all_reduce: ring
    reduce-scatter + all-gather = 2(n-1)/n x payload.  all_to_all:
    (n-1)/n of the buffer changes shards.  collective_permute: the
    whole payload moves one hop."""
    n = max(1, op.group_size)
    b = op.elements * _DTYPE_BYTES.get(op.dtype, 4)
    if n == 1:
        return 0
    if op.kind == "all_gather":
        return b * (n - 1) // n
    if op.kind == "reduce_scatter":
        if op.decomposed:                 # payload = full input
            return b * (n - 1) // n
        return b * (n - 1)                # payload = the 1/n shard
    if op.kind == "all_reduce":
        return 2 * b * (n - 1) // n
    if op.kind == "all_to_all":
        return b * (n - 1) // n
    if op.kind == "collective_permute":
        return b
    return b


@dataclass
class CommCost:
    """Estimated per-step communication cost of one program's census."""
    per_op: List[Dict[str, Any]] = field(default_factory=list)
    per_axis_s: Dict[str, float] = field(default_factory=dict)
    per_axis_bytes: Dict[str, int] = field(default_factory=dict)
    total_s: float = 0.0
    total_bytes: int = 0
    profile: str = "cpu"

    def to_dict(self) -> Dict[str, Any]:
        return {"total_s": self.total_s, "total_bytes": self.total_bytes,
                "per_axis_s": dict(self.per_axis_s),
                "per_axis_bytes": dict(self.per_axis_bytes),
                "profile": self.profile,
                "per_op": self.per_op[:24]}

    def table_str(self, top: int = 12) -> str:
        lines = [f"{'collective':<28s}{'kind':<20s}{'axis':<8s}"
                 f"{'wire B':>12s}{'est s':>12s}"]
        for r in sorted(self.per_op, key=lambda r: -r["seconds"])[:top]:
            lines.append(f"{r['name'][:26]:<28s}{r['kind']:<20s}"
                         f"{(r['axes'][0] if r['axes'] else '?'):<8s}"
                         f"{r['wire_bytes']:>12d}{r['seconds']:>12.3e}")
        for ax in sorted(self.per_axis_s):
            lines.append(f"  axis {ax!r}: {self.per_axis_bytes[ax]} B, "
                         f"~{self.per_axis_s[ax]:.3e} s/step")
        return "\n".join(lines)


def comm_cost(census: CollectiveStats,
              profile: Optional[BandwidthProfile] = None) -> CommCost:
    """Cost every collective in a census against the bandwidth profile
    — the per-axis estimate that turns the PR 4 census from counting
    into costing (arXiv:1909.09756's first-order pod-scaling
    question).

    Seconds are priced PER PAYLOAD BYTE (``collective_wire_fraction``
    x payload / bandwidth), not per op — N bucketed collectives of B
    bytes each sum to the cost of one collective of N*B bytes, so the
    overlap-motivated bucketing of the ZeRO gradient leaves the modeled
    comm budget unchanged (the ``wire_bytes`` per-op records keep the
    floor-divided integer form pinned by the ring-formula goldens)."""
    profile = profile or bandwidth_profile()
    cost = CommCost(profile=profile.name)
    for op in census.ops:
        wire = collective_wire_bytes(op)
        payload = op.elements * _DTYPE_BYTES.get(op.dtype, 4)
        frac = collective_wire_fraction(
            op.kind, op.group_size, op.decomposed)
        gbps = profile.gbps(op.axes)
        sec = payload * frac / (gbps * 1e9) if gbps > 0 else 0.0
        ax = op.axes[0] if op.axes else "?"
        cost.per_op.append({"name": op.name, "kind": op.kind,
                            "axes": list(op.axes), "wire_bytes": wire,
                            "seconds": sec})
        cost.per_axis_s[ax] = cost.per_axis_s.get(ax, 0.0) + sec
        cost.per_axis_bytes[ax] = cost.per_axis_bytes.get(ax, 0) + wire
        cost.total_s += sec
        cost.total_bytes += wire
    cost.per_op.sort(key=lambda r: -r["seconds"])
    return cost


# ---------------------------------------------------------------------------
# implicit-reshard detection
# ---------------------------------------------------------------------------

@dataclass
class CollectiveRule:
    """One declared/asserted collective pattern of a spec pack.

    ``kind`` is the census kind (a tuple allows alternatives — "a
    gradient reduction is an all_reduce OR a reduce_scatter"; ``"*"``
    matches every collective); ``axis`` restricts to collectives whose
    replica groups span that mesh axis (None = any); ``elements``
    restricts payload element counts (the zero pack declares its weight
    all-gathers by their padded unit sizes so anything ELSE gathering is
    a reshard); ``min_count``/``max_count`` make the rule an assertion
    (0/None = declaration only — blessed, not required).  ``rule_id``
    and ``severity`` control the finding a violation emits —
    ``expect_mode``'s packs keep the historical ``collective-mismatch``
    / ``per-param-allreduce`` ids the tier-1 fixtures assert on."""
    kind: Union[str, Tuple[str, ...]]
    axis: Optional[str] = None
    min_count: int = 0
    max_count: Optional[int] = None
    elements: Optional[frozenset] = None
    rule_id: str = "spec-mismatch"
    severity: str = "error"

    @property
    def kinds(self) -> Tuple[str, ...]:
        return (self.kind,) if isinstance(self.kind, str) \
            else tuple(self.kind)

    def matches(self, op: CollectiveOp) -> bool:
        if "*" not in self.kinds and op.kind not in self.kinds:
            return False
        if self.axis is not None and op.axes and \
                self.axis not in op.axes:
            return False
        if self.elements is not None and \
                op.elements not in self.elements:
            return False
        return True

    def describe_kind(self) -> str:
        return "|".join(self.kinds)


@dataclass
class Reshard:
    """One SPMD-partitioner-inserted layout change the declared spec
    did not imply."""
    name: str
    kind: str
    axes: Tuple[str, ...]
    group_size: int
    elements: int
    dtype: str
    payload_bytes: int
    wire_bytes: int
    seconds: float
    producer: str = ""
    consumers: Tuple[str, ...] = ()

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "kind": self.kind,
                "axes": list(self.axes), "group_size": self.group_size,
                "elements": self.elements, "dtype": self.dtype,
                "payload_bytes": self.payload_bytes,
                "wire_bytes": self.wire_bytes, "seconds": self.seconds,
                "producer": self.producer,
                "consumers": list(self.consumers)}


def _neighbors(mod: Optional[HloModule], name: str):
    """(producer, consumers) of a collective, looking through
    get-tuple-element/tuple/bitcast plumbing."""
    if mod is None or name not in mod.ops:
        return "", ()
    transparent = {"get-tuple-element", "tuple", "bitcast"}
    op = mod.ops[name]
    producer = ""
    for o in op.operands:
        p = mod.ops.get(o)
        seen = 0
        while p is not None and p.opcode in transparent and seen < 8:
            p = mod.ops.get(p.operands[0]) if p.operands else None
            seen += 1
        if p is not None and p.opcode not in ("constant", "parameter"):
            producer = p.name
            break
        if p is not None and not producer:
            producer = p.name
    cons: List[str] = []
    stack = [name]
    seen = 0
    while stack and seen < 32:
        cur = stack.pop()
        seen += 1
        for c in mod.consumers(cur):
            if c.opcode in transparent:
                stack.append(c.name)
            else:
                cons.append(c.name)
    return producer, tuple(dict.fromkeys(cons))


def implicit_reshards(census: CollectiveStats,
                      mod: Optional[HloModule] = None,
                      declared: Sequence[CollectiveRule] = (),
                      floor_bytes: int = RESHARD_FLOOR_BYTES,
                      profile: Optional[BandwidthProfile] = None) \
        -> List[Reshard]:
    """Collectives that MOVE data (all-gather / all-to-all /
    collective-permute) yet match no declared rule and clear the byte
    floor — ranked by wire bytes, each naming its producing and
    consuming ops.  A ``P("dp", None)`` input silently gathered to
    replicated before a matmul shows up here with the gather's full
    byte count."""
    profile = profile or bandwidth_profile()
    out: List[Reshard] = []
    for op in census.ops:
        if op.kind not in RESHARD_KINDS:
            continue
        if any(r.matches(op) for r in declared):
            continue
        payload = op.elements * _DTYPE_BYTES.get(op.dtype, 4)
        if payload < floor_bytes:
            continue
        wire = collective_wire_bytes(op)
        gbps = profile.gbps(op.axes)
        producer, consumers = _neighbors(mod, op.name)
        out.append(Reshard(
            name=op.name, kind=op.kind, axes=op.axes,
            group_size=op.group_size, elements=op.elements,
            dtype=op.dtype, payload_bytes=payload, wire_bytes=wire,
            seconds=wire / (gbps * 1e9) if gbps > 0 else 0.0,
            producer=producer, consumers=consumers))
    out.sort(key=lambda r: -r.wire_bytes)
    return out


# ---------------------------------------------------------------------------
# spec invariant packs
# ---------------------------------------------------------------------------

@dataclass
class SpecPack:
    """Declarative invariant pack for one mesh+PartitionSpec layout.

    ``rules`` are asserted (min/max collective counts per kind x axis);
    ``declared`` adds blessing-only patterns; both bless their matches
    for reshard detection.  ``max_reshard_bytes`` bounds the total wire
    bytes of implicit reshards above ``reshard_floor`` (0 = none
    allowed; None = report reshards as warnings only and leave
    regression protection to the baseline gate — the mode packs use
    None because XLA legitimately trades small activation gathers
    against gradient reductions at its own discretion).
    ``state_axis`` arms the table-derived byte budget:
    params resolved onto that axis must sum to <= global/N x
    (1 + ``state_pad_tol``) per replica — the sharded-state contract of
    arXiv:2004.13336, checked structurally."""
    name: str
    description: str = ""
    axes: Tuple[str, ...] = ()
    rules: Tuple[CollectiveRule, ...] = ()
    declared: Tuple[CollectiveRule, ...] = ()
    reshard_floor: int = RESHARD_FLOOR_BYTES
    max_reshard_bytes: Optional[int] = 0
    state_axis: Optional[str] = None
    state_pad_tol: float = 0.5

    def all_declared(self) -> Tuple[CollectiveRule, ...]:
        return tuple(self.rules) + tuple(self.declared)


_SPEC_PACKS: Dict[str, SpecPack] = {}


def register_spec_pack(pack: SpecPack) -> SpecPack:
    """Register (or replace — idempotent module reloads) a pack in the
    process-wide catalog. Parallelism paths register their own pack
    next to their implementation (ops/attention.py, ops/moe.py,
    parallel/pipeline.py)."""
    _SPEC_PACKS[pack.name] = pack
    return pack


def get_spec_pack(name: str) -> SpecPack:
    from ..base import MXNetError
    if name not in _SPEC_PACKS:
        raise MXNetError(
            f"no spec pack {name!r} registered; known: "
            f"{sorted(_SPEC_PACKS)} (docs/ANALYSIS.md 'Sharding "
            "analysis')")
    return _SPEC_PACKS[name]


def spec_packs() -> Dict[str, SpecPack]:
    return dict(_SPEC_PACKS)


def expect_spec(report, pack: Union[SpecPack, str], mod=None, mesh=None,
                hlo_text: str = "") -> List[Finding]:
    """Assert one pack's invariants against a ProgramReport (or a bare
    CollectiveStats) and append the findings.

    Checks, in order: the collective signature (every rule's min/max
    count per kind x axis), implicit reshards above the pack floor
    (bounded by ``max_reshard_bytes``), and the sharded-state byte
    budget from the report's sharding table.  Returns the findings it
    appended."""
    if isinstance(pack, str):
        pack = get_spec_pack(pack)
    census = getattr(report, "collectives", report)
    audit = getattr(report, "sharding", None)
    if audit is not None:
        mod = mod if mod is not None else audit.mod
        mesh = mesh if mesh is not None else audit.mesh
    findings: List[Finding] = []
    # --- collective signature -----------------------------------------
    for rule in pack.rules:
        hits = [op for op in census.ops if rule.matches(op)]
        n = len(hits)
        where = f"{rule.describe_kind()}@{rule.axis or '*'}"
        if n < rule.min_count:
            findings.append(Finding(
                checker="sharding", rule=rule.rule_id,
                severity=rule.severity,
                message=f"[{pack.name}] expected >= {rule.min_count} "
                        f"`{rule.describe_kind()}` on axis "
                        f"{rule.axis!r}, found {n} — the "
                        f"{pack.description or pack.name} collective "
                        f"signature regressed "
                        f"(census: {census.by_kind})",
                where=where))
        if rule.max_count is not None and n > rule.max_count:
            if rule.elements is not None:
                msg = (f"[{pack.name}] {n} "
                       f"`{rule.describe_kind()}`(s) carry exactly a "
                       "declared unit's payload "
                       f"({sorted(set(o.elements for o in hits))} "
                       "elements) — the sharded update is paying "
                       "replicated reductions")
                where = ", ".join(o.name for o in hits[:4])
            else:
                msg = (f"[{pack.name}] {n} `{rule.describe_kind()}` "
                       f"on axis {rule.axis!r} exceed the declared "
                       f"maximum {rule.max_count} — the program runs "
                       f"collectives the spec did not imply "
                       f"(census: {census.by_kind})")
            findings.append(Finding(
                checker="sharding", rule=rule.rule_id,
                severity=rule.severity, message=msg, where=where))
    # --- implicit reshards --------------------------------------------
    if mod is None and hlo_text:
        mod = parse_hlo(hlo_text)
    reshards = implicit_reshards(census, mod=mod,
                                 declared=pack.all_declared(),
                                 floor_bytes=pack.reshard_floor)
    if audit is not None:
        audit.reshards = reshards
        audit.reshard_floor = pack.reshard_floor
        audit.pack = pack.name
    total = sum(r.wire_bytes for r in reshards)
    for r in reshards[:8]:
        findings.append(Finding(
            checker="sharding", rule="implicit-reshard", severity="warn",
            message=f"[{pack.name}] SPMD partitioner inserted "
                    f"`{r.kind}` of {r.payload_bytes} B "
                    f"({r.wire_bytes} B on the wire, "
                    f"~{r.seconds:.2e} s) on axis "
                    f"{r.axes[0] if r.axes else '?'} not implied by the "
                    f"declared spec — produced by `{r.producer or '?'}`"
                    f", consumed by "
                    f"{', '.join(r.consumers[:3]) or '?'}",
            where=r.name))
    if pack.max_reshard_bytes is not None and \
            total > pack.max_reshard_bytes:
        worst = reshards[0]
        findings.append(Finding(
            checker="sharding", rule="implicit-reshard",
            message=f"[{pack.name}] {len(reshards)} implicit reshard(s) "
                    f"move {total} B/step above the "
                    f"{pack.reshard_floor} B floor (budget "
                    f"{pack.max_reshard_bytes} B) — worst: "
                    f"`{worst.kind}` {worst.payload_bytes} B at "
                    f"{worst.name} (producer `{worst.producer or '?'}`)",
            where=worst.name))
    # --- sharded-state byte budget ------------------------------------
    if pack.state_axis and audit is not None and \
            audit.table is not None and mesh is not None:
        jmesh = getattr(mesh, "mesh", mesh)
        try:
            n = int(dict(jmesh.shape).get(pack.state_axis, 0))
        except Exception:                # pragma: no cover - defensive
            n = 0
        loc, glob = audit.table.sharded_bytes(pack.state_axis)
        if n >= 2 and glob:
            budget = int(glob / n * (1.0 + pack.state_pad_tol))
            if loc > budget:
                findings.append(Finding(
                    checker="sharding", rule="state-budget",
                    message=f"[{pack.name}] buffers sharded on "
                            f"{pack.state_axis!r} hold {loc} B per "
                            f"replica, over the ~1/{n} budget "
                            f"{budget} B (global {glob} B) — the "
                            "sharded-state contract regressed toward "
                            "replication",
                    where=f"axis {pack.state_axis}"))
    if hasattr(report, "add"):
        for f in findings:
            report.add(f)
    return findings


# ---------------------------------------------------------------------------
# whole-program audit + report plumbing
# ---------------------------------------------------------------------------

@dataclass
class ShardingAudit:
    """Everything the sharding analysis measured about ONE program:
    the flow table, the (pack-aware) implicit reshards, and the comm
    cost.  ``ProgramReport.sharding`` carries one of these."""
    table: Optional[ShardingTable] = None
    reshards: List[Reshard] = field(default_factory=list)
    cost: Optional[CommCost] = None
    reshard_floor: int = RESHARD_FLOOR_BYTES
    pack: Optional[str] = None
    #: parse/mesh context for pack re-audits (expect_mode) — not
    #: serialized
    mod: Optional[HloModule] = field(default=None, repr=False)
    mesh: Any = field(default=None, repr=False)

    @property
    def reshard_bytes(self) -> int:
        return sum(r.wire_bytes for r in self.reshards)

    def brief(self) -> Dict[str, Any]:
        """The headline numbers, as one flat dict."""
        return {"implicit_reshards": len(self.reshards),
                "reshard_bytes": self.reshard_bytes,
                "comm_cost_est_s": self.cost.total_s if self.cost
                else 0.0,
                "sharding_table_digest": self.table.digest()
                if self.table else None}

    def to_dict(self) -> Dict[str, Any]:
        d = self.brief()
        d["pack"] = self.pack
        d["per_axis_cost_s"] = dict(self.cost.per_axis_s) \
            if self.cost else {}
        d["reshards"] = [r.to_dict() for r in self.reshards[:16]]
        d["table"] = self.table.to_dict() if self.table else None
        return d

    def summary_line(self) -> str:
        return (f"params={len(self.table.params) if self.table else 0} "
                f"reshards={len(self.reshards)} "
                f"reshard_bytes={self.reshard_bytes} "
                f"comm~{self.cost.total_s if self.cost else 0.0:.2e}s "
                f"digest={self.table.digest() if self.table else '-'}")


def audit_sharding(hlo: Union[str, HloModule],
                   census: Optional[CollectiveStats] = None, mesh=None,
                   stablehlo: str = "",
                   declared: Sequence[CollectiveRule] = (),
                   floor_bytes: int = RESHARD_FLOOR_BYTES,
                   profile: Optional[BandwidthProfile] = None) \
        -> ShardingAudit:
    """Run the full sharding analysis over one optimized program:
    flow table + implicit reshards (against ``declared``, typically a
    pack's blessings) + comm cost.  Never raises."""
    try:
        mod = parse_hlo(hlo) if isinstance(hlo, str) else hlo
        if census is None:
            from .program import collective_census
            census = collective_census(
                hlo if isinstance(hlo, str) else "", mesh=mesh)
        profile = profile or bandwidth_profile()
        return ShardingAudit(
            table=sharding_table(mod, mesh=mesh, stablehlo=stablehlo),
            reshards=implicit_reshards(census, mod=mod,
                                       declared=declared,
                                       floor_bytes=floor_bytes,
                                       profile=profile),
            cost=comm_cost(census, profile=profile),
            reshard_floor=floor_bytes, mod=mod, mesh=mesh)
    except Exception:                    # pragma: no cover - defensive
        _LOG.debug("sharding audit failed", exc_info=True)
        return ShardingAudit()


# ---------------------------------------------------------------------------
# baseline regression gate
# ---------------------------------------------------------------------------

def load_baselines(path: str) -> Dict[str, Any]:
    """Per-leg sharding baselines: ``{leg: {implicit_reshards,
    reshard_bytes, tol_pct}}`` (``_comment`` keys ignored)."""
    with open(path, "r", encoding="utf-8") as fh:
        raw = json.load(fh)
    return {k: v for k, v in raw.items() if not k.startswith("_")}


def check_baseline(audit: ShardingAudit, baselines: Dict[str, Any],
                   leg: str) -> List[Finding]:
    """Diff one program's reshard posture against a checked-in
    baseline.  Both bands are one-sided — fewer reshards / fewer bytes
    is an improvement; more is an error-severity ``sharding-regression``
    finding, so ``analyze='raise'`` fails fast
    (docs/ANALYSIS.md documents the refresh workflow)."""
    base = baselines.get(leg)
    findings: List[Finding] = []
    if base is None:
        findings.append(Finding(
            checker="sharding", rule="sharding-regression",
            severity="warn",
            message=f"no sharding baseline for leg {leg!r} — add it to "
                    "the baselines file (docs/ANALYSIS.md)",
            where=leg))
        return findings
    tol = float(base.get("tol_pct", 25.0)) / 100.0
    r_base = int(base.get("implicit_reshards", 0))
    if len(audit.reshards) > r_base:
        worst = audit.reshards[0] if audit.reshards else None
        detail = (f" (worst: `{worst.kind}` {worst.payload_bytes} B "
                  f"at {worst.name})") if worst else ""
        findings.append(Finding(
            checker="sharding", rule="sharding-regression",
            message=f"[{leg}] {len(audit.reshards)} implicit reshard(s) "
                    f"vs baseline {r_base} — the partitioner now moves "
                    f"data the spec does not imply{detail}",
            where=leg))
    b_base = int(base.get("reshard_bytes", 0))
    if audit.reshard_bytes > max(b_base * (1.0 + tol),
                                 b_base + audit.reshard_floor):
        findings.append(Finding(
            checker="sharding", rule="sharding-regression",
            message=f"[{leg}] implicit-reshard wire bytes "
                    f"{audit.reshard_bytes} exceed baseline {b_base} by "
                    f"more than {base.get('tol_pct', 25.0)}% — more "
                    "data resharded per step than the captured posture",
            where=leg))
    return findings


def baseline_from_env() -> Optional[tuple]:
    """``MXNET_SHARDING_BASELINE=<path>[:<leg>]`` -> (baselines dict,
    leg-or-None); None when unset or unreadable (logged, never
    raises)."""
    spec = os.environ.get("MXNET_SHARDING_BASELINE")
    if not spec:
        return None
    path, leg = spec, None
    if ":" in spec and not os.path.exists(spec):
        path, leg = spec.rsplit(":", 1)
    try:
        return load_baselines(path), leg
    except Exception as e:               # pragma: no cover - defensive
        _LOG.warning("MXNET_SHARDING_BASELINE=%r unreadable (%s: %s)",
                     spec, type(e).__name__, e)
        return None


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def publish(audit: ShardingAudit):
    """Refresh the ``mx_sharding_*`` gauges from one audit (the latest
    analyzed program wins — one step program is live at a time)."""
    try:
        from ..telemetry import names as tn
        from ..telemetry import registry as treg
        reg = treg()
        reg.gauge(tn.SHARDING_RESHARDS).set(len(audit.reshards))
        reg.gauge(tn.SHARDING_RESHARD_BYTES).set(audit.reshard_bytes)
        if audit.cost is not None:
            g_cost = reg.gauge(tn.SHARDING_COMM_COST)
            g_bytes = reg.gauge(tn.SHARDING_COLLECTIVE_BYTES)
            for ax, sec in audit.cost.per_axis_s.items():
                g_cost.set(sec, label=ax)
            for ax, b in audit.cost.per_axis_bytes.items():
                g_bytes.set(b, label=ax)
    except Exception:                    # pragma: no cover - defensive
        _LOG.debug("sharding gauge publish failed", exc_info=True)
