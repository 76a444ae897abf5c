"""Fused whole-train-step compilation (``Trainer.compile_step``).

The reference MXNet fuses the UPDATE side of training (multi-tensor
``multi_sgd_*`` kernels, ``update_on_kvstore``) but still pays an
imperative dispatch per op and a host boundary between backward and the
optimizer. Here the canonical Gluon loop

    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(batch_size)

compiles into ONE donated-buffer XLA program per input-shape bucket:
forward (via the same functional binding the CachedOp uses —
``block.ParamBinding``), ``jax.value_and_grad`` of the summed loss over
the parameter pytree (the seed-ones equivalent of ``loss.backward()``),
gradient rescale/clip, the data-parallel reduction (a no-op/psum XLA
inserts for single-process stores; host ``pushpull_list`` between two
programs for dist stores), and the optimizer's ``_rule`` — the idiom the
fusion literature shows dominates TPU efficiency (arXiv:2301.13062) and
that enables in-graph weight-update optimization (arXiv:2004.13336).

Contracts:

- **Traced hyperparameters.** lr/wd/update-count/rescale_grad (and the
  clip bound) enter the program as traced arguments packed in small host
  arrays — ``trainer.learning_rate = x``, a scheduler tick, or a new
  ``step(batch_size)`` NEVER retrace. One compile per input-shape bucket
  (LRU-capped by ``MXNET_FUSED_STEP_CACHE_SIZE``, like the CachedOp's
  ``_jit_lru``).
- **Donation.** Weight and optimizer-state buffers are donated
  (``donate_argnums``) so XLA updates them in place in HBM; after each
  call the results are written back INTO the same ``Parameter._data``
  and state NDArray handles (``Parameter._write_fused``), so handles
  users hold from ``param.data()`` stay valid. Raw ``jax.Array`` objects
  captured from ``param.data()._data`` before a step are invalidated by
  donation — snapshot via ``asnumpy()``/``copy`` instead.
- **Transparent fallback.** Sparse-grad or multi-precision parameters,
  ``update_on_kvstore`` stores, and blocks whose forward cannot trace
  (host-side numpy, data-dependent Python control flow) fall back to the
  eager record/backward/step loop with identical numerics.
- **ZeRO-1 sharded update.** When a ``DeviceMesh`` with a data-parallel
  axis is active (``parallel.make_mesh``), the redundant replicated
  weight update is cross-replica sharded (arXiv:2004.13336): gradients
  are constrained to a flat 1/N-per-replica layout (XLA's weight-update
  sharding pass turns the gradient all-reduce into a reduce-scatter),
  the optimizer rule runs on each replica's shard, and the new weights
  all-gather back to replicated. Optimizer state (momenta, Adam moments,
  fp32 master copies of multi-precision params) lives permanently
  sharded via ``NamedSharding`` — per-replica state memory drops ~N×.
  Parameters smaller than ``MXNET_ZERO_SHARD_MIN_SIZE`` elements bucket
  into one fused shard per dtype so tiny tensors don't pay a collective
  each. See ``_ZeroShardPlan``.
- **Numerics instrumentation.** ``numerics='global'|'per_layer'``
  (``MXNET_NUMERICS``) threads auxiliary on-device statistics through
  the same program — global grad/param norms, update/weight ratio,
  per-dtype non-finite counts, per-layer norms — as pure reductions of
  values the step already computes: params/loss stay BIT-EXACT vs
  numerics=off, and under ZeRO the reductions are psum-composed from
  the flat shards so every replica reports true global norms
  (telemetry/numerics.py; docs/OBSERVABILITY.md "numerics").
- **Device counters.** What an op emits while the loss function is
  traced (``telemetry/device_counters.py``: the pairs a router gave its
  held experts) leaves every step program the same way, numerics on or
  off: the program's LAST output is one aux pytree with a ``numerics``
  and a ``counters`` part, each only where there is one. A program with
  neither returns an empty pytree there, no output of the lowered
  program, so its text is what it was without the channel.
"""
from __future__ import annotations

import contextlib
import logging
import os
from collections import OrderedDict
from typing import Any, Callable, Optional

import numpy as onp

import jax
import jax.numpy as jnp

from .. import _tape
from ..analysis import guard as _tguard
from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from ..ndarray.random import next_key, push_trace_key, pop_trace_key
from ..telemetry import device_counters as _dcounters
from ..testing.faults import fault_point
from .block import UNTRACEABLE_ERRORS, ParamBinding, _TRACED

__all__ = ["CompiledTrainStep", "TrainLoop"]

_LOG = logging.getLogger("mxnet_tpu.fused_step")

#: the ``jax.named_scope`` every op of a step program sits under, so a
#: device trace splits into forward+backward / update / ZeRO packing and
#: collectives / numerics aux (docs/OBSERVABILITY.md "Phase scopes").
#: Trace-time metadata only: what runs is the same program.
PHASE_LOSS_AND_GRAD = "loss_and_grad"
PHASE_OPTIMIZER_UPDATE = "optimizer_update"
PHASE_GRAD_REDUCE = "grad_reduce"
PHASE_NUMERICS = "numerics"
PHASE_SCOPES = (PHASE_LOSS_AND_GRAD, PHASE_OPTIMIZER_UPDATE, PHASE_GRAD_REDUCE,
                PHASE_NUMERICS)

_TELEM = None


def _telemetry():
    global _TELEM
    if _TELEM is None:
        from .. import telemetry as _t
        _TELEM = _t
    return _TELEM


# elastic device-loss detection (elastic/detect.py), lazily cached the
# same way — classifies failures escaping the step-dispatch seam
_EDET = None


def _edetect():
    global _EDET
    if _EDET is None:
        from ..elastic import detect as _d
        _EDET = _d
    return _EDET

_ARRAY_TYPES = (NDArray, onp.ndarray, jax.Array)


def _place_on_mesh(mesh, axis: str, d):
    """Mesh input layout (batch-shard dim0 when divisible, else
    replicate) — shared with the device prefetcher via
    ``parallel.mesh.place_on_mesh``."""
    from ..parallel.mesh import place_on_mesh
    return place_on_mesh(mesh, axis, d)


def _zero_min_size() -> int:
    """ZeRO bucket floor (elements): ``MXNET_ZERO_SHARD_MIN_SIZE`` when
    set and parseable, else 2048; at least 1.  A param of fewer
    elements joins the fused small-param bucket instead of getting its
    own reduce-scatter + all-gather pair; any packing is numerically
    identical (the update is elementwise over the flat shards)."""
    try:
        return max(1, int(os.environ.get("MXNET_ZERO_SHARD_MIN_SIZE",
                                         "2048")))
    except ValueError:
        return 2048


def _zero_bucket_bytes() -> int:
    """ZeRO gradient communication bucket size (bytes):
    ``MXNET_ZERO_BUCKET_BYTES`` when set and parseable, else 4 MiB.
    0 (or less) selects the monolithic serial baseline (one collective
    payload over every unit: backward -> reduce-scatter -> update ->
    all-gather with no independent compute left to hide the wire
    time)."""
    try:
        return max(0, int(os.environ.get("MXNET_ZERO_BUCKET_BYTES",
                                         str(4 << 20))))
    except ValueError:
        return 4 << 20


def zero_bucket_schedule(units, bucket_bytes: int):
    """Partition ZeRO unit indices into size-bounded communication
    buckets, in REVERSE unit order — backward produces the LAST
    layer's gradients first, so the first bucket's reduce-scatter can
    launch while earlier layers' backward compute still runs
    (reverse-topological grad availability, arXiv:1909.09756's
    compute/comm overlap checklist).  A bucket's units concatenate into
    ONE flat collective payload (parallel/collectives.py
    ``reduce_scatter_bucketed``), so buckets never mix update dtypes.
    ``bucket_bytes <= 0`` returns the fewest possible buckets (one per
    contiguous update-dtype run, usually one total): the monolithic
    serial baseline."""

    def _ub(u):
        try:
            return int(u["padded"]) * onp.dtype(u["upd_dtype"]).itemsize
        except Exception:    # pragma: no cover - defensive
            return int(u["padded"]) * 4

    serial = bucket_bytes is None or int(bucket_bytes) <= 0
    bucket_bytes = None if serial else int(bucket_bytes)
    order = range(len(units)) if serial else reversed(range(len(units)))
    buckets, cur, cur_b, cur_dt = [], [], 0, None
    for k in order:
        u = units[k]
        ub = _ub(u)
        # forward dtype AND update dtype must both be uniform within a
        # bucket: the packed forward buffer is in forward dtype, the
        # collective payload in update dtype
        dt = (str(u["upd_dtype"]), str(u["dtypes"][0]))
        if cur and (dt != cur_dt or
                    (not serial and cur_b + ub > bucket_bytes)):
            buckets.append(cur)
            cur, cur_b = [], 0
        cur.append(k)
        cur_b += ub
        cur_dt = dt
    if cur:
        buckets.append(cur)
    return buckets


def _analysis_mode(requested: Optional[str]) -> Optional[str]:
    """Normalize the ``analyze=`` kwarg / MXNET_ANALYSIS env setting to
    one of None | 'report' | 'warn' | 'raise'."""
    v = requested if requested is not None \
        else os.environ.get("MXNET_ANALYSIS")
    if v is None or v is False:
        return None
    if v is True:
        return "warn"
    v = str(v).strip().lower()
    if v in ("", "0", "off", "false", "no", "none"):
        return None
    if v in ("1", "report"):
        return "report"
    if v in ("warn", "log"):
        return "warn"
    if v in ("raise", "error", "strict"):
        return "raise"
    _LOG.warning("unknown analysis mode %r (MXNET_ANALYSIS); "
                 "treating as 'warn'", v)
    return "warn"


class _ZeroShardPlan:
    """Host-side layout of the ZeRO-1 sharded weight update
    (arXiv:2004.13336 "Automatic Cross-Replica Sharding of Weight Update
    in Data-Parallel Training").

    Trainable parameters map to UNITS:

    - every parameter with flat size >= ``MXNET_ZERO_SHARD_MIN_SIZE``
      (and every multi-precision parameter) is its own unit;
    - smaller parameters concatenate into one bucket unit per dtype, so
      tiny tensors share a single reduce-scatter/all-gather instead of
      paying one collective each (their hyperparameters pack into
      per-element vectors — ``Optimizer.pack_shard_hparams``).

    Each unit is a flat buffer zero-padded to a multiple of the dp-axis
    size; its optimizer state (and the fp32 master copy of a
    multi-precision unit) lives as ``NamedSharding``-partitioned arrays,
    1/N per replica. Weights stay replicated for the forward; inside the
    compiled step the flat gradient is constrained to the sharded layout
    (XLA's weight-update-sharding pass converts the gradient all-reduce
    into a reduce-scatter feeding it), the elementwise optimizer rule
    runs shard-locally, and the new weights are constrained back to
    replicated (an all-gather).
    """

    def __init__(self, trainer, mesh, axis: str):
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.mesh import zero_shard_pad
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.shape[axis])
        self.shard = NamedSharding(mesh.mesh, PartitionSpec(axis))
        self.repl = NamedSharding(mesh.mesh, PartitionSpec())
        opt = trainer._optimizer
        params = trainer._params
        min_size = _zero_min_size()

        raw_units = []
        small: "dict[str, list]" = {}
        for j, p in enumerate(params):
            d = p._data._data
            mp = opt.multi_precision and d.dtype in (jnp.float16,
                                                     jnp.bfloat16)
            if mp or int(d.size) >= min_size:
                raw_units.append((tuple([j]), mp))
            else:
                small.setdefault(str(d.dtype), []).append(j)
        for js in small.values():
            raw_units.append((tuple(js), False))

        self.units = []
        self.states = []       # per unit: tuple of flat sharded NDArrays
        self.masters = []      # flat sharded fp32 masters (mp units only)
        self.master_slot = {}  # unit index -> slot in self.masters
        for members, mp in raw_units:
            shapes = tuple(tuple(params[j]._data._data.shape)
                           for j in members)
            dtypes = tuple(params[j]._data._data.dtype for j in members)
            sizes = tuple(int(onp.prod(s)) if s else 1 for s in shapes)
            total = int(sum(sizes))
            self.units.append(dict(
                members=members, shapes=shapes, dtypes=dtypes, sizes=sizes,
                total=total, padded=zero_shard_pad(total, self.n_shards),
                mp=mp, upd_dtype=jnp.float32 if mp else dtypes[0]))
        restored = getattr(trainer, "_restored_masters", {})
        for k, unit in enumerate(self.units):
            if unit["mp"]:
                j = unit["members"][0]
                if j in restored:
                    # checkpoint resume: the saved fp32 master carries
                    # low-order bits the fp16 weight lost — recasting
                    # would break bit-exact resume (checkpoint/state.py)
                    master = jnp.asarray(restored.pop(j), jnp.float32)
                else:
                    master = params[j]._data._data.astype(jnp.float32)
                self.master_slot[k] = len(self.masters)
                self.masters.append(NDArray(self._flat_shard(
                    master.reshape(-1), unit["padded"])))
            self.states.append(tuple(
                NDArray(x) for x in self._unit_state_leaves(trainer, unit)))

    # ---------------- layout helpers ----------------
    def _flat_shard(self, flat, padded: int):
        n = int(flat.shape[0])
        if n != padded:
            flat = jnp.pad(flat, (0, padded - n))
        return jax.device_put(flat, self.shard)

    def _unit_state_leaves(self, trainer, unit):
        """Create (or adopt from the Updater) each member's optimizer
        state, then concatenate + pad + shard per state slot."""
        opt = trainer._optimizer
        params = trainer._params
        per_member = []
        for j, shape in zip(unit["members"], unit["shapes"]):
            p = params[j]
            src = NDArray(jnp.asarray(p._data._data, jnp.float32)) \
                if unit["mp"] else p.data()
            st = trainer._updater.states.get(j)
            if not (isinstance(st, tuple)
                    and all(isinstance(s, NDArray)
                            and tuple(s.shape) == shape for s in st)):
                st = opt.create_state(j, src)
            per_member.append(tuple(s._data.reshape(-1) for s in st))
        counts = {len(m) for m in per_member}
        if len(counts) != 1:
            raise MXNetError(
                "zero-shard: optimizer state leaf count differs across "
                f"bucket members ({sorted(counts)})")
        leaves = []
        for li in range(counts.pop()):
            flats = [m[li] for m in per_member]
            flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
            leaves.append(self._flat_shard(flat, unit["padded"]))
        return leaves

    # ---------------- per-step host work ----------------
    def pack_hparams(self, opt, lrs, wds, ts):
        """Per-unit hyperparameters: scalars for single-param units,
        per-element packed vectors for buckets."""
        ulrs, uwds, uts = [], [], []
        for unit in self.units:
            m = unit["members"]
            if len(m) == 1:
                ulrs.append(onp.float32(lrs[m[0]]))
                uwds.append(onp.float32(wds[m[0]]))
                uts.append(onp.int32(ts[m[0]]))
            else:
                lv, wv, tv = opt.pack_shard_hparams(
                    lrs, wds, ts, list(m), list(unit["sizes"]),
                    unit["padded"])
                ulrs.append(lv)
                uwds.append(wv)
                uts.append(tv)
        return tuple(ulrs), tuple(uwds), tuple(uts)

    def place_leaf(self, d):
        return _place_on_mesh(self.mesh, self.axis, d)

    # ---------------- observability ----------------
    @staticmethod
    def _per_replica_bytes(a) -> int:
        """Addressable-shard bytes — delegates to the ONE accounting
        helper the buffer census uses (telemetry/memory.py), so
        ``state_bytes_per_replica`` and the census ``optimizer`` pool
        agree byte-for-byte by construction."""
        from ..telemetry.memory import device_bytes
        return device_bytes(a)

    def state_bytes_per_replica(self) -> int:
        """PER-REPLICA bytes of the sharded state + masters; every
        buffer walked is (re-)filed in the census ``optimizer`` pool —
        the walk IS the registration (one accounting path)."""
        c = _telemetry().memory.census()
        total = 0
        for st in self.states:
            for s in st:
                c.register("optimizer", s)
                total += self._per_replica_bytes(s._data)
        for m in self.masters:
            c.register("optimizer", m)
            total += self._per_replica_bytes(m._data)
        return total


def _aux_outputs(numerics, counters) -> dict:
    """A step program's last output: the parts it has, none empty."""
    parts = {"numerics": numerics, "counters": counters}
    return {k: v for k, v in parts.items() if v}


def _infer_batch_size(traced) -> int:
    for leaf in traced:
        d = leaf._data if isinstance(leaf, NDArray) else leaf
        if getattr(d, "ndim", 0) >= 1:
            return int(d.shape[0])
    return 1


class CompiledTrainStep:
    """One callable = one full training step, compiled.

    Built by ``Trainer.compile_step(loss_fn)``. ``loss_fn(*batch)`` is
    ordinary imperative Gluon code returning a loss NDArray; calling the
    step runs forward+backward+allreduce+update and returns the loss.
    Gradient semantics match ``loss.backward()`` (seed ones — the summed
    loss is differentiated) followed by ``trainer.step(batch_size)``.
    """

    def __init__(self, trainer, loss_fn: Callable, donate: bool = True,
                 train_mode: bool = True, zero_shard: Optional[bool] = None,
                 zero_axis: str = "dp", mesh=None,
                 analyze: Optional[str] = None,
                 numerics: Optional[str] = None):
        self._trainer = trainer
        self._loss_fn = loss_fn
        self._donate = donate
        self._train = train_mode
        self._mode: Optional[str] = None   # None→undecided, 'fused'|'eager'
        self._lru: "OrderedDict[Any, dict]" = OrderedDict()
        self._trace_signatures: set = set()
        self._sig_history: list = []   # bucket keys in trace order
        self._n_traces = 0
        self._steps_done = 0
        # opt-in program lint after the first step (docs/ANALYSIS.md);
        # default comes from MXNET_ANALYSIS
        self._analyze = _analysis_mode(analyze)
        self._analysis_report = None
        # in-program numerics instrumentation (docs/OBSERVABILITY.md
        # "numerics"): None | 'global' | 'per_layer'; default from
        # MXNET_NUMERICS. Part of the bucket signature — switching mode
        # compiles a fresh instrumented program.
        self._numerics = _telemetry().numerics.mode(numerics)
        self._pending_aux = None    # the last step's StepAux
        # ZeRO-1 sharded update: None = auto (on when a mesh with a
        # `zero_axis` axis is active), True = required, False = off
        self._zero_requested = zero_shard
        self._zero_axis = zero_axis
        self._zero_mesh = mesh
        self._zero_ok: Optional[tuple] = None   # (mesh, axis) once decided
        self._zero: Optional[_ZeroShardPlan] = None
        self._census_done = False
        self._plain_mesh: Optional[tuple] = None  # mesh-aware plain mode
        self._mesh_prepared = False

        # dedup while preserving order: tied params may appear twice in a
        # collected dict; bind each object once
        seen: set = set()
        self._all_params = []
        for p in trainer._all_params:
            if id(p) not in seen:
                seen.add(id(p))
                self._all_params.append(p)
        pos = {id(p): i for i, p in enumerate(self._all_params)}
        # trainer._params (grad_req != null) carry the optimizer indices
        self._trainable_pos = [pos[id(p)] for p in trainer._params]
        # the checkpoint stack finds zero-sharded state through this
        trainer._register_compiled(self)

    # ---------------- introspection ----------------
    @property
    def n_traces(self) -> int:
        """Distinct compiled step programs built so far (the retrace
        counter tests assert on — trace-time side effect, stable under
        jit-cache eviction)."""
        return self._n_traces

    @property
    def mode(self) -> Optional[str]:
        return self._mode

    @property
    def zero_sharded(self) -> bool:
        """True when the ZeRO-1 sharded weight update is active."""
        return self._zero is not None or self._zero_ok is not None

    @property
    def analysis_report(self):
        """The ProgramReport from the last opt-in ``analyze=`` run (or
        ``None``)."""
        return self._analysis_report

    # ---------------- numerics instrumentation ----------------
    @property
    def numerics(self) -> Optional[str]:
        """Active numerics mode: None (off) | 'global' | 'per_layer'."""
        return self._numerics

    def set_numerics(self, mode: Optional[str]):
        """Switch the numerics instrumentation mode ('off'/None,
        'global', 'per_layer'). The mode is part of the bucket
        signature, so the next call compiles a fresh program for its
        shape bucket; existing buckets stay cached."""
        self._numerics = _telemetry().numerics.mode(mode or "off")

    def take_aux(self):
        """Pop the :class:`~mxnet_tpu.telemetry.StepAux` of the most
        recent step: its numerics record and the counters its ops
        emitted, either part None; None where the program returned
        neither. The TrainLoop pushes this into the dispatch window
        alongside the loss, so both are read sync-free at the blessed
        retire."""
        aux, self._pending_aux = self._pending_aux, None
        return aux

    def take_numerics(self):
        """Pop the :class:`~mxnet_tpu.telemetry.StepNumerics` record of
        the most recent step (None when numerics is off), for windowless
        callers: hand it to ``telemetry.numerics.monitor()`` or read
        :meth:`numerics_values` directly. The step's device counters go
        with it, unread."""
        aux = self.take_aux()
        return None if aux is None else aux.numerics

    def numerics_values(self) -> Optional[dict]:
        """Convenience synchronous read of the last step's numerics:
        pops the pending record, publishes it through the monitor
        (gauges + divergence anomalies + forensics, as a window retire
        would), and returns the host values dict — or None when
        numerics is off / no step ran. This BLOCKS on the step's
        program; prefer the TrainLoop's window path in hot loops."""
        rec = self.take_numerics()
        if rec is None:
            return None
        return _telemetry().numerics.monitor().observe_retire(
            self._steps_done, rec)

    def explain_retrace(self) -> str:
        """WHY the most recent retrace happened: a component-wise diff
        of the last two program cache keys (shape-bucket signatures) —
        new traced shapes/dtypes, changed static argument values,
        changed argument structure (analysis/program.py)."""
        if not self._sig_history:
            return "no program traced yet"
        if len(self._sig_history) < 2:
            return "only one program traced (no retrace to explain)"
        from ..analysis.program import explain_signature_diff
        return explain_signature_diff(self._sig_history[-2],
                                      self._sig_history[-1])

    def input_placement(self) -> Optional[Callable]:
        """The host→device placement this step applies to its input
        leaves: ``place(x)`` device_puts a raw array with the step's
        exact ``NamedSharding`` (dp-sharded batch on a mesh, replicated
        otherwise), or ``None`` when the step runs single-device (plain
        default-device placement suffices). The device prefetcher
        (``gluon.data.DevicePrefetcher`` / ``TrainLoop.prefetch``) stages
        upcoming batches through this so the host→device copy overlaps
        the previous step's compute instead of serializing inside jit
        dispatch."""
        from ..parallel.mesh import current_mesh, place_on_mesh
        mesh = axis = None
        if self._zero_ok is not None:
            mesh, axis = self._zero_ok
        elif self._plain_mesh is not None:
            mesh, axis = self._plain_mesh
        else:
            m = self._zero_mesh or current_mesh()
            a = self._zero_axis
            if m is not None and a in m.axis_names \
                    and m.shape[a] >= 2:
                mesh, axis = m, a
        if mesh is None:
            return None
        return lambda d, _m=mesh, _a=axis: place_on_mesh(_m, _a, d)

    def optimizer_state_bytes(self) -> int:
        """PER-REPLICA bytes of optimizer state (momenta/moments + fp32
        master copies). Under the ZeRO-1 sharded mode each replica holds
        1/N of every state buffer; in the plain fused and eager modes
        state is fully replicated — the ratio between the two is the
        memory the sharded update frees (~N× for Adam). Accounting is
        the census's own ``telemetry.memory.device_bytes`` and every
        buffer walked is (re-)filed in the census ``optimizer`` pool,
        so this number and ``census().live_bytes_by_pool()['optimizer']``
        agree byte-for-byte (tests/test_memory.py pins it)."""
        if self._zero is not None:
            return self._zero.state_bytes_per_replica()
        c = _telemetry().memory.census()
        total = 0
        for s in self._state_ndarrays():
            c.register("optimizer", s)
            total += _ZeroShardPlan._per_replica_bytes(s._data)
        return total

    def optimizer_state_buffers(self) -> list:
        """The live device buffers :meth:`optimizer_state_bytes` counts
        (moments + fp32 masters), as ``jax.Array``s — their
        ``addressable_shards`` say which devices really hold the ZeRO
        shards (chip_smoke.py's ``dp`` phase reads them)."""
        return [s._data for s in self._state_ndarrays()]

    def _state_ndarrays(self) -> list:
        """Every optimizer-state NDArray handle: the ZeRO plan's flat
        shards and masters, else the Updater's per-parameter states."""
        if self._zero is not None:
            return [s for st in self._zero.states for s in st] \
                + list(self._zero.masters)
        return [s for st in self._trainer._updater.states.values()
                for s in jax.tree_util.tree_leaves(
                    st, is_leaf=lambda x: isinstance(x, NDArray))
                if isinstance(s, NDArray)]

    def memory_report(self, *args, batch_size: Optional[int] = None,
                      **kwargs):
        """Static HBM footprint of the compiled step program
        (:class:`~mxnet_tpu.telemetry.MemoryReport`): per shape-bucket
        ``memory_analysis()`` — argument/output/temp/generated-code
        bytes, donated alias bytes, peak estimate.

        With a batch: that bucket's report (lower+compile once, cached
        on the bucket entry; the AOT executable is reused when
        :meth:`aot_compile` already built it). With NO arguments: the
        field-wise max over every bucket analyzed so far (buckets run
        one at a time, so the worst bucket is the run's headroom), or
        ``None`` when none was. Eager mode: ``None`` — there is no
        compiled program to attribute. Split (dist-store) mode covers
        the grad program only. Each report also refreshes the
        ``mx_hbm_compiled_bytes{component}`` / ``mx_hbm_peak_estimate_
        bytes`` gauges and registers with the OOM forensics, so a
        post-mortem dump names every bucket's static peak."""
        t = _telemetry()
        if not args and not kwargs:
            reports = [e["memory"] for e in self._lru.values()
                       if e.get("memory") is not None]
            return t.memory.MemoryReport.merge(reports) if reports \
                else None
        if self._mode is None:
            self._mode = self._decide_mode()
        if self._mode != "fused":
            return None
        entry, _ = self._entry_for(args, kwargs)
        if entry.get("memory") is not None:
            return entry["memory"]
        compiled = entry.get("exe")
        if compiled is None:
            info = self.lower_entry(*args, batch_size=batch_size,
                                    **kwargs)
            if info is None:
                return None
            compiled = info["lowered"].compile()
        report = t.memory.MemoryReport.from_compiled(compiled)
        entry["memory"] = report
        n_buckets = sum(1 for e in self._lru.values()
                        if e.get("memory") is not None)
        t.memory.register_compiled_report(
            f"{self._mode}:bucket{n_buckets}", report)
        self._publish_hbm()
        return report

    def _publish_hbm(self):
        """``mx_hbm_*`` gauges = field-wise max over analyzed buckets."""
        t = _telemetry()
        reports = [e["memory"] for e in self._lru.values()
                   if e.get("memory") is not None]
        if not reports:
            return
        merged = t.memory.MemoryReport.merge(reports)
        reg = t.registry()
        g = reg.gauge(t.names.HBM_COMPILED_BYTES)
        for field in merged.FIELDS:
            g.set(getattr(merged, field),
                  label=field.replace("_bytes", ""))
        reg.gauge(t.names.HBM_PEAK_BYTES).set(merged.peak_bytes)

    def _register_census(self):
        """File the step's long-lived device buffers in the live-buffer
        census (telemetry/memory.py): parameters under ``params``,
        optimizer state/masters under ``optimizer``. Weakref-based and
        idempotent — one call after the first step covers the whole run
        because writeback rebinds ``_data`` INSIDE the same handles."""
        try:
            c = _telemetry().memory.census()
            for p in self._all_params:
                if p._data is not None:
                    c.register("params", p._data)
            for s in self._state_ndarrays():
                c.register("optimizer", s)
        except Exception:        # pragma: no cover - census must never
            _LOG.debug("census registration failed", exc_info=True)
            return                  # kill a step; retry next call
        self._census_done = True

    # ---------------- mode decision ----------------
    def _decide_mode(self) -> str:
        tr = self._trainer
        if not tr._kv_initialized:
            # single-process in-program stores need no kvstore at all —
            # seeding one would alias param buffers that donation later
            # invalidates. Dist stores DO need init (for pushpull_list).
            kind = tr._kvstore_kind
            needs_kv = kind is not None and (
                not isinstance(kind, str) or "dist" in kind)
            if needs_kv:
                tr._init_kvstore()
            else:
                tr._update_on_kvstore = False
        if tr._update_on_kvstore:
            return "eager"   # optimizer lives on the store: cannot fuse
        for p in self._all_params:
            if p._data is None:
                return "eager"   # deferred shapes: eager forward infers
            if p.stype != "default" or p._grad_stype != "default":
                return "eager"   # sparse storage/grad: lazy row path
        zero = self._resolve_zero()
        opt = self._trainer._optimizer
        if not zero and opt.multi_precision and any(
                p._data._data.dtype in (jnp.float16, jnp.bfloat16)
                for p in self._trainer._params):
            # master-weight states fuse only via the sharded update
            # (the zero plan owns flat fp32 masters); plain mode: eager
            return "eager"
        return "fused"

    def _resolve_zero(self) -> bool:
        """Decide whether the ZeRO-1 sharded update applies: a mesh with
        the dp axis must be active, the optimizer rule elementwise, and
        the kvstore's reduction must both live in-program AND advertise
        the reduce-scatter decomposition. A valid mesh whose update is
        gated off (opt-out, non-elementwise optimizer) still runs the
        PLAIN fused mode mesh-aware — params replicated, batch sharded,
        psum in-program."""
        from ..parallel.mesh import current_mesh
        mesh = self._zero_mesh or current_mesh()
        axis = self._zero_axis
        mesh_ok = (mesh is not None and axis in mesh.axis_names
                   and mesh.shape[axis] >= 2)
        if mesh_ok:
            self._plain_mesh = (mesh, axis)
        reason = None
        if self._zero_requested is False:
            return False
        if not mesh_ok:
            reason = f"no active mesh with a {axis!r} axis of size >= 2"
        else:
            opt = self._trainer._optimizer
            kv = self._trainer._kvstore
            if not getattr(opt, "elementwise_update", False):
                reason = (f"{type(opt).__name__} update is not elementwise "
                          "(cannot run on flat shards)")
            elif self._host_allreduce():
                reason = "kvstore reduction cannot live in-program"
            elif kv is not None and not getattr(
                    kv, "in_program_reduce_scatter", True):
                reason = "kvstore does not advertise the reduce-scatter path"
        if reason is not None:
            if self._zero_requested:
                raise MXNetError(f"compile_step(zero_shard=True): {reason}")
            return False
        self._zero_ok = (mesh, axis)
        return True

    @contextlib.contextmanager
    def _mesh_scope(self):
        """The step's own mesh, active for every call: a caller that
        has left its ``with make_mesh(...)`` block still traces (jit
        keys its cache on the mesh context) and dispatches kernels (the
        gate reads the active mesh) as on the first call."""
        from ..parallel.mesh import current_mesh
        pair = self._zero_ok or self._plain_mesh
        if pair is None or current_mesh() is pair[0]:
            yield
        else:
            with pair[0]:
                yield

    def _host_allreduce(self) -> bool:
        kv = self._trainer._kvstore
        # unknown custom stores default to the conservative host path
        return kv is not None and not getattr(kv, "in_program_reduce",
                                              False)

    # ---------------- call ----------------
    def __call__(self, *args, batch_size: Optional[int] = None, **kwargs):
        # the whole step is a transfer-guard hot region: with
        # MXNET_TRANSFER_GUARD=log|raise any device->host sync in here —
        # a .asnumpy() in the loss_fn concretizing the trace, a silent
        # per-step sync on the eager fallback — logs its stack or raises
        with _tguard.hot_scope("CompiledTrainStep.step"):
            # device-lost seam (elastic/detect.py), alongside the OOM
            # seams inside _guarded_call: an escaping PjRt device loss
            # gets exactly one device_lost anomaly before it propagates
            with _edetect().device_lost_guard(
                    "CompiledTrainStep.step (compile/dispatch)",
                    step=self._steps_done + 1):
                # chaos-harness seam bracketing step dispatch — OUTSIDE
                # the first-call eager fallback (_guarded_call's try),
                # so an injected loss propagates to the elastic
                # supervisor instead of demoting the program to eager
                fault_point("step.dispatch", "before")
                out = self._guarded_call(args, kwargs, batch_size)
                fault_point("step.dispatch", "after")
        if self._analyze is not None and self._analysis_report is None:
            self._run_analysis(args, kwargs, batch_size)
        return out

    def _guarded_call(self, args, kwargs, batch_size):
        if self._mode is None:
            self._mode = self._decide_mode()
        t = _telemetry()
        if self._mode == "eager":
            if self._numerics:
                _LOG.warning(
                    "compile_step: numerics instrumentation requires "
                    "the fused path (this program runs eager); disabled"
                    " — MXNET_INSPECT_NAN=1 is the eager-mode guard")
                self._numerics = None
            with t.memory.oom_guard("CompiledTrainStep.step (eager)",
                                    step=self._steps_done + 1):
                out = self._eager_call(args, kwargs, batch_size)
            if not self._census_done:
                self._register_census()
            return out
        opt = self._trainer._optimizer
        # first call: the trace may fail AFTER hyperparameter counts were
        # advanced — snapshot so the eager fallback replays step 1 as
        # step 1 (Adam's bias correction depends on t)
        snapshot = (opt.num_update, dict(opt._index_update_count)) \
            if not self._steps_done else None
        try:
            # the OOM seam: a RESOURCE_EXHAUSTED at compile or dispatch
            # writes its ranked post-mortem BEFORE the fallback/raise
            # machinery sees it (telemetry/memory.py)
            with t.memory.oom_guard("CompiledTrainStep.step (compile/"
                                    "dispatch)",
                                    step=self._steps_done + 1), \
                    self._mesh_scope():
                out = self._fused_call(args, kwargs, batch_size)
        except UNTRACEABLE_ERRORS as e:
            # only a loss that cannot be TRACED demotes; a lowering,
            # Mosaic/XLA compile or runtime error propagates — on an
            # accelerator it would otherwise run (and be timed) as
            # per-op eager dispatch under the fused step's name
            if self._steps_done:
                raise   # the program is proven; this is a genuine error
            _LOG.warning(
                "compile_step: loss is not traceable (%s: %s); falling "
                "back to the eager tape path", type(e).__name__, e)
            opt.num_update, opt._index_update_count = \
                snapshot[0], snapshot[1]
            self._mode = "eager"
            return self._eager_call(args, kwargs, batch_size)
        self._steps_done += 1
        if not self._census_done:
            self._register_census()
        return out

    step = __call__

    def _run_analysis(self, args, kwargs, batch_size):
        """Post-first-step program lint (``analyze=``/MXNET_ANALYSIS):
        'report' stores the ProgramReport, 'warn' also logs findings,
        'raise' raises on error-severity findings."""
        from ..analysis import program as _aprog
        from ..analysis.lint import lint_function
        try:
            report = _aprog.analyze_step(self, *args,
                                         batch_size=batch_size, **kwargs)
        except MXNetError:
            raise
        except Exception as e:   # analysis must not kill a healthy run
            _LOG.warning("compile_step: program analysis failed "
                         "(%s: %s); skipping", type(e).__name__, e)
            self._analysis_report = False
            return
        try:
            # the source lint explains WHY a step fell back to eager
            # (the .asnumpy() line) alongside the program findings
            report.findings.extend(lint_function(self._loss_fn))
        except Exception:        # pragma: no cover - defensive
            pass
        self._analysis_report = report
        if self._analyze == "warn" and not report.ok:
            _LOG.warning("compile_step program analysis:\n%s",
                         report.summary())
        elif self._analyze == "raise":
            report.raise_if_findings()

    # ---------------- eager fallback ----------------
    def _eager_call(self, args, kwargs, batch_size):
        from .. import autograd
        wrap = lambda a: a if isinstance(a, NDArray) or not isinstance(
            a, (onp.ndarray, jax.Array)) else NDArray(a)   # noqa: E731
        args = tuple(wrap(a) for a in args)
        kwargs = {k: wrap(v) for k, v in kwargs.items()}
        with autograd.record(train_mode=self._train):
            loss = self._loss_fn(*args, **kwargs)
        _tape.backward([loss])
        if batch_size is None:
            batch_size = _infer_batch_size(
                [a for a in args if isinstance(a, NDArray)])
        self._trainer.step(batch_size)
        self._steps_done += 1
        return loss

    # ---------------- fused path ----------------
    def _flatten(self, args, kwargs):
        all_leaves, arg_treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda t: isinstance(t, NDArray))
        traced = [l for l in all_leaves if isinstance(l, _ARRAY_TYPES)]
        static_spec = tuple(_TRACED if isinstance(l, _ARRAY_TYPES) else l
                            for l in all_leaves)
        nd_mask = tuple(isinstance(l, NDArray) for l in traced)
        return traced, arg_treedef, static_spec, nd_mask

    @staticmethod
    def _cache_cap() -> int:
        try:
            return int(os.environ.get("MXNET_FUSED_STEP_CACHE_SIZE", "0"))
        except ValueError:
            return 0

    def _entry_for(self, args, kwargs):
        traced, arg_treedef, static_spec, nd_mask = self._flatten(
            args, kwargs)
        shapes = tuple(
            (tuple((l._data if isinstance(l, NDArray) else l).shape),
             str((l._data if isinstance(l, NDArray) else l).dtype))
            for l in traced)
        sig = (self._train, arg_treedef, static_spec, nd_mask, shapes,
               self._numerics)
        entry = self._lru.get(sig)
        if entry is None:
            entry = self._build_bucket(arg_treedef, static_spec, nd_mask)
            t = _telemetry()
            t.registry().counter(t.names.COMPILE_RETRACES).inc()
            self._lru[sig] = entry
            self._trace_signatures.add(sig)
            self._sig_history.append(sig)
            cap = self._cache_cap()
            while cap > 0 and len(self._lru) > cap:
                self._lru.popitem(last=False)
        else:
            self._lru.move_to_end(sig)
        return entry, traced

    def _build_bucket(self, arg_treedef, static_spec, nd_mask) -> dict:
        params = self._all_params
        loss_fn = self._loss_fn
        train = self._train
        t_pos = tuple(self._trainable_pos)
        opt_fn = self._trainer._optimizer.fused_step_fn()
        donate = (0, 1) if self._donate else ()
        step_self = self

        # in-program numerics aux (docs/OBSERVABILITY.md "numerics"):
        # scalar reductions of values the program already computes —
        # the update dataflow itself is untouched, so numerics=on is
        # bit-exact on params/loss vs off
        numerics = self._numerics
        if numerics and self._host_allreduce():
            _LOG.warning(
                "compile_step: numerics instrumentation is not wired "
                "for the split (host-allreduce) mode; disabled")
            numerics = None
        nxm = _telemetry().numerics if numerics else None
        if numerics:
            # trainable-param dtypes are static at build time (fused
            # mode guarantees materialized shapes)
            grad_dtype_groups: "dict[str, list]" = {}
            for j, p in enumerate(self._trainer._params):
                grad_dtype_groups.setdefault(
                    str(p._data._data.dtype), []).append(j)

        def run_loss(pds, traced_leaves, key):
            it = iter(NDArray(l) if m else l
                      for l, m in zip(traced_leaves, nd_mask))
            leaves = [next(it) if s is _TRACED else s for s in static_spec]
            args, kwargs = jax.tree_util.tree_unflatten(arg_treedef, leaves)
            binding = ParamBinding(params, pds)
            push_trace_key(key)
            prev_r = _tape.set_recording(False)
            prev_s = _tape.set_taping_suspended(True)
            prev_t = _tape.set_training(train)
            try:
                with binding, _dcounters.collect() as emitted:
                    out = loss_fn(*args, **kwargs)
            finally:
                _tape.set_recording(prev_r)
                _tape.set_taping_suspended(prev_s)
                _tape.set_training(prev_t)
                pop_trace_key()
            l = out._data if isinstance(out, NDArray) else jnp.asarray(out)
            # differentiate the SUM: identical to loss.backward() seeding
            # ones over the per-sample loss vector
            return jnp.sum(l), (l, binding.state, emitted)

        def stack_counters(emitted):
            # under the numerics phase, which the trace readers know
            with jax.named_scope(PHASE_NUMERICS):
                return _dcounters.stacked(emitted)

        def grad_part(pds, traced_leaves, key):
            with jax.named_scope(PHASE_LOSS_AND_GRAD):
                (_, (l, state, emitted)), grads = jax.value_and_grad(
                    run_loss, has_aux=True)(tuple(pds), traced_leaves, key)
            gs = tuple(grads[i] for i in t_pos)
            return l, state, gs, stack_counters(emitted)

        if self._zero is not None:
            # ZeRO-1 sharded update: grads constrained to the flat
            # 1/N-per-replica layout (XLA converts the allreduce into a
            # reduce-scatter feeding it), elementwise rule on each
            # replica's shard against permanently-sharded state, new
            # weights constrained back to replicated (all-gather).
            # The elementwise rule goes through the Pallas fused
            # multi-tensor update kernel when the MXNET_PALLAS gate
            # selects it (ops/kernels/opt_update.py; bit-exact vs the
            # XLA chain, pinned by tests) — one kernel per flat unit
            # instead of a per-op elementwise chain.
            from ..ops.kernels.opt_update import \
                kernel_step_fn as _opt_kfn
            opt_kernel_fn = _opt_kfn(self._trainer._optimizer)
            if opt_kernel_fn is not None:
                opt_fn = opt_kernel_fn
            plan = self._zero
            shard, repl = plan.shard, plan.repl
            units = plan.units
            mslot = plan.master_slot
            wsc = jax.lax.with_sharding_constraint

            def _flat_cat(arrs):
                flats = [a.reshape(-1) for a in arrs]
                return flats[0] if len(flats) == 1 \
                    else jnp.concatenate(flats)

            def _padded(v, padded):
                n = v.shape[0]
                return v if n == padded else jnp.pad(v, (0, padded - n))

            # comm bucketing (docs/PERF_NOTES.md "Communication
            # overlap"): the flat units are grouped into size-bounded
            # buckets in reverse-topological grad order and each bucket
            # concatenates into ONE reduce-scatter / shard update / ONE
            # all-gather (parallel/collectives.py). Overlap then falls
            # out of real data dependencies — bucket k's collectives
            # depend only on bucket k's units, so other buckets'
            # backward/update compute is free to hide the wire time —
            # with nothing for XLA's simplifier or scheduler to defeat
            # (barriers and value-ties both die before the final
            # schedule). Per-unit elementwise math is untouched and the
            # packing is pure routing, so ANY bucketing (including the
            # serial single-bucket baseline) is bit-exact vs any other.
            bucket_bytes = _zero_bucket_bytes()
            buckets = zero_bucket_schedule(units, bucket_bytes)
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel.collectives import allgather_bucketed
            nsh = plan.n_shards
            shard2d = NamedSharding(
                plan.mesh.mesh, PartitionSpec(plan.axis, None))

            def _unpack_bucket(buf, idx):
                """Per-unit padded flats out of an interleaved
                (n_shards, S) bucket buffer — comm-free slices on the
                free axis whether the buffer is sharded or replicated
                (parallel/collectives.py layout)."""
                outs, off = [], 0
                for k in idx:
                    s = units[k]["padded"] // nsh
                    outs.append(buf[:, off:off + s].reshape(
                        units[k]["padded"]))
                    off += s
                return outs

            def _scatter_members(dst, k, flat, to_pds=True):
                """Write unit k's member views of a flat buffer into
                ``dst`` — at the param positions (``to_pds``) or at the
                trainable-slot positions."""
                u = units[k]
                off = 0
                for j, shp, n in zip(u["members"], u["shapes"],
                                     u["sizes"]):
                    dst[t_pos[j] if to_pds else j] = \
                        flat[off:off + n].reshape(shp)
                    off += n

            def pack_buckets(pds):
                """Per-bucket interleaved (n_shards, S) forward weight
                buffers (forward dtype — buckets are dtype-uniform)."""
                bufs = []
                for idx in buckets:
                    rows = [
                        _padded(_flat_cat(
                            [pds[t_pos[j]]
                             for j in units[k]["members"]]),
                            units[k]["padded"]).reshape(
                                nsh, units[k]["padded"] // nsh)
                        for k in idx]
                    buf = rows[0] if len(rows) == 1 \
                        else jnp.concatenate(rows, axis=1)
                    # pin the PRIMAL pack replicated: the params are
                    # already replicated, so re-materializing them in
                    # run_loss_bufs must stay comm-free slicing.
                    # Without the pin GSPMD may shard the pack (its
                    # cotangent wants P(axis)) and then pay per-param
                    # gather chains to rebuild the forward weights
                    bufs.append(wsc(buf, repl))
                return tuple(bufs)

            def run_loss_bufs(bufs, pds, traced_leaves, key):
                """run_loss with the trainable params re-materialized
                from the packed bucket buffers.  Differentiating w.r.t.
                ``bufs`` (not ``pds``) makes autodiff ACCUMULATE each
                bucket's gradient into one flat packed buffer, so the
                pending cross-replica sum covers the whole bucket and
                GSPMD lowers it as ONE reduce-scatter per bucket —
                reducing per-param grads first and concatenating after
                would materialize one collective per unit instead."""
                pds = list(pds)
                for bi, idx in enumerate(buckets):
                    for k, flat in zip(idx,
                                       _unpack_bucket(bufs[bi], idx)):
                        _scatter_members(pds, k, flat)
                return run_loss(tuple(pds), traced_leaves, key)

            def zero_fused(pds, sts, masters, traced_leaves, ulrs, uwds,
                           uts, rescale, clip, key):
                step_self._n_traces += 1
                with jax.named_scope(PHASE_GRAD_REDUCE):
                    packed = pack_buckets(pds)
                with jax.named_scope(PHASE_LOSS_AND_GRAD):
                    (_, (l, state, emitted)), grad_bufs = \
                        jax.value_and_grad(run_loss_bufs, has_aux=True)(
                            packed, pds, traced_leaves, key)
                n_units = len(units)
                ws_u = [None] * n_units
                for k, u in enumerate(units):
                    if u["mp"]:
                        wflat = masters[mslot[k]]   # persistent fp32 shard
                    else:
                        with jax.named_scope(PHASE_GRAD_REDUCE):
                            wflat = wsc(_padded(_flat_cat(
                                [pds[t_pos[j]] for j in u["members"]]),
                                u["padded"]), shard)
                    ws_u[k] = wflat
                gs_u = [None] * n_units
                new_ws = [None] * n_units
                new_sts_u = [None] * n_units
                fulls = [None] * n_units
                for bi, idx in enumerate(buckets):
                    # ONE reduce-scatter for the whole bucket: the
                    # packed gradient buffer is a single pending
                    # cross-replica sum, and the shard2d constraint
                    # turns it into one collective whose per-unit
                    # shards slice out comm-free
                    gbuf = grad_bufs[bi]
                    upd = units[idx[0]]["upd_dtype"]
                    # the constraint is applied to the FLAT view (row d
                    # of the interleaved layout = contiguous slice d of
                    # the flat buffer): GSPMD lowers a 1-D P(axis) pin
                    # on a pending sum as the clean reduce-scatter /
                    # all-reduce + partition-id-slice pattern the
                    # zero-dp program checks assert on
                    with jax.named_scope(PHASE_GRAD_REDUCE):
                        if gbuf.dtype != upd:
                            gbuf = gbuf.astype(upd)
                        gbuf = wsc(gbuf.reshape(-1), shard).reshape(
                            nsh, -1)
                        b_gs = _unpack_bucket(gbuf, idx)
                    for k, g in zip(idx, b_gs):
                        gs_u[k] = g
                    with jax.named_scope(PHASE_OPTIMIZER_UPDATE):
                        bw, bst = opt_fn(
                            tuple(ws_u[k] for k in idx), tuple(b_gs),
                            tuple(ulrs[k] for k in idx),
                            tuple(uwds[k] for k in idx),
                            tuple(uts[k] for k in idx),
                            rescale, clip,
                            tuple(sts[k] for k in idx))
                    for k, w, st in zip(idx, bw, bst):
                        new_ws[k] = w
                        new_sts_u[k] = st
                    # ONE all-gather for the bucket's new weights.  The
                    # inner shard2d pin keeps the update output sharded
                    # so the `repl` constraint gathers the RESULT once —
                    # without it GSPMD propagates `repl` into the
                    # update's last elementwise op and all-gathers both
                    # of its operands instead
                    with jax.named_scope(PHASE_GRAD_REDUCE):
                        b_fulls = allgather_bucketed(
                            list(bw), nsh,
                            constrain=lambda b: wsc(wsc(b, shard2d),
                                                    repl))
                    for k, f in zip(idx, b_fulls):
                        fulls[k] = f
                new_pds = list(state)
                new_masters = [None] * len(mslot)
                with jax.named_scope(PHASE_GRAD_REDUCE):
                    for k, u in enumerate(units):
                        full = fulls[k]
                        off = 0
                        for j, shp, n, dt in zip(u["members"], u["shapes"],
                                                 u["sizes"], u["dtypes"]):
                            new_pds[t_pos[j]] = \
                                full[off:off + n].reshape(shp).astype(dt)
                            off += n
                        if u["mp"]:
                            new_masters[mslot[k]] = wsc(new_ws[k], shard)
                    # pin the state outputs to the sharded layout: the
                    # replicated all-gather consumer above must not make
                    # GSPMD replicate the persistent buffers on the way
                    # out
                    new_sts = tuple(tuple(wsc(s, shard) for s in st)
                                    for st in new_sts_u)
                numerics_aux = None
                if numerics:
                    with jax.named_scope(PHASE_NUMERICS):
                        gs_log = ()
                        if numerics == "per_layer":
                            # logical per-param grads, sliced back out
                            # of the packed pre-scatter buffers
                            # (materializes the full gradient — the
                            # documented per-layer cost)
                            gs_log = [None] * len(t_pos)
                            for bi, idx in enumerate(buckets):
                                for k, flat in zip(
                                        idx, _unpack_bucket(grad_bufs[bi],
                                                            idx)):
                                    _scatter_members(gs_log, k, flat,
                                                     to_pds=False)
                        numerics_aux = zero_aux(ws_u, gs_u, new_ws, gs_log,
                                                rescale)
                return (tuple(new_pds), new_sts, tuple(new_masters), l,
                        _aux_outputs(numerics_aux, stack_counters(emitted)))

            def zero_aux(ws_u, gs_u, new_ws, gs, rescale):
                """Numerics aux from the flat 1/N-per-replica unit
                buffers: each sumsq/count is a shard-local reduction
                GSPMD psums on the dp axis, so every replica reports
                the exact GLOBAL statistic without materializing a
                replicated gradient (zero padding is finite/zero and
                never skews anything)."""
                r2 = jnp.square(jnp.asarray(rescale, jnp.float32))
                aux = {
                    "grad_sq": r2 * sum(nxm.sumsq(g) for g in gs_u),
                    "param_sq": sum(nxm.sumsq(w) for w in ws_u),
                    "upd_sq": sum(
                        nxm.sumsq(nw.astype(jnp.float32)
                                  - w.astype(jnp.float32))
                        for nw, w in zip(new_ws, ws_u)),
                }
                by_dt: "dict[str, list]" = {}
                for k, u in enumerate(units):
                    by_dt.setdefault(str(u["dtypes"][0]), []).append(k)
                aux["nonfinite"] = {
                    dt: sum(nxm.nonfinite_count(gs_u[k]) for k in ks)
                    for dt, ks in sorted(by_dt.items())}
                if numerics == "per_layer":
                    # per-parameter norms consume the LOGICAL grads —
                    # under ZeRO this can force XLA to materialize the
                    # full gradient it would otherwise reduce-scatter
                    # away (the documented per-layer cost)
                    aux["layer_grad_sq"] = jnp.stack(
                        [r2 * nxm.sumsq(g) for g in gs])
                drifts = []
                for k, u in enumerate(units):
                    if u["mp"]:
                        d = new_ws[k]
                        q = d.astype(u["dtypes"][0]).astype(jnp.float32)
                        drifts.append(jnp.max(
                            jnp.abs(d - q) / (jnp.abs(d) + 1e-8)))
                if drifts:
                    aux["master_drift"] = drifts[0] if len(drifts) == 1 \
                        else jnp.max(jnp.stack(drifts))
                return aux

            donate_z = (0, 1, 2) if self._donate else ()
            return {"kind": "zero",
                    "fn": jax.jit(zero_fused, donate_argnums=donate_z),
                    "exe": None, "flops": None, "numerics": numerics,
                    "probe": grad_part}

        if self._host_allreduce():
            # split mode (dist stores): program A computes loss+grads+
            # functional state; the kvstore's bucketed pushpull_list runs
            # between programs; program B is the donated fused update.
            grad_fn = jax.jit(grad_part)

            def update(ws, sts, lrs, wds, ts, rescale, clip, gs):
                step_self._n_traces += 1
                with jax.named_scope(PHASE_OPTIMIZER_UPDATE):
                    return opt_fn(ws, gs, lrs, wds, ts, rescale, clip,
                                  sts)

            return {"kind": "split", "grad": grad_fn,
                    "update": jax.jit(update, donate_argnums=donate),
                    "exe": None, "flops": None, "numerics": None,
                    "probe": grad_part}

        def fused_aux(ws, gs, new_ws, rescale):
            """Numerics aux for the plain fused modes: reductions of
            the grads/weights the update already holds. On a dp mesh
            (params replicated, batch sharded) GSPMD composes each
            reduction with the gradient psum, so the norms are global
            there too."""
            r2 = jnp.square(jnp.asarray(rescale, jnp.float32))
            gsq = [nxm.sumsq(g) for g in gs]
            aux = {
                "grad_sq": r2 * sum(gsq),
                "param_sq": sum(nxm.sumsq(w) for w in ws),
                "upd_sq": sum(
                    nxm.sumsq(nw.astype(jnp.float32)
                              - w.astype(jnp.float32))
                    for nw, w in zip(new_ws, ws)),
                "nonfinite": {
                    dt: sum(nxm.nonfinite_count(gs[j]) for j in js)
                    for dt, js in sorted(grad_dtype_groups.items())},
            }
            if numerics == "per_layer":
                aux["layer_grad_sq"] = jnp.stack([r2 * s for s in gsq])
            return aux

        # NOT named ``fused`` as before PR 26: the persistent compile
        # cache keys a program by its ops and its module's name
        # (``jit_<this function>``) and leaves op names out, so under the
        # old name a step with no Pallas call in it (the LSTM LM) was
        # served the executable an unscoped build had cached, whose HLO
        # text — what a trace's attribution is read from — carries the
        # old op_names and no phase (PERF.md section 6, PR 26)
        def fused_step(pds, sts, traced_leaves, lrs, wds, ts, rescale,
                       clip, key):
            step_self._n_traces += 1
            l, state, gs, counters = grad_part(pds, traced_leaves, key)
            ws = tuple(pds[i] for i in t_pos)
            with jax.named_scope(PHASE_OPTIMIZER_UPDATE):
                new_ws, new_sts = opt_fn(ws, gs, lrs, wds, ts, rescale,
                                         clip, sts)
            new_pds = list(state)   # BN-stat rebinds + identity for rest
            for j, i in enumerate(t_pos):
                new_pds[i] = new_ws[j]
            numerics_aux = None
            if numerics:
                with jax.named_scope(PHASE_NUMERICS):
                    numerics_aux = fused_aux(ws, gs, new_ws, rescale)
            return (tuple(new_pds), new_sts, l,
                    _aux_outputs(numerics_aux, counters))

        return {"kind": "fused",
                "fn": jax.jit(fused_step, donate_argnums=donate),
                "exe": None, "flops": None, "numerics": numerics,
                "probe": grad_part}

    def _ensure_states(self):
        updater = self._trainer._updater
        for i, p in enumerate(self._trainer._params):
            if i not in updater.states:
                updater.states[i] = \
                    self._trainer._optimizer.create_state_multi_precision(
                        i, p.data())
        return [updater.states[i]
                for i in range(len(self._trainer._params))]

    def _scalars(self, batch_size):
        tr = self._trainer
        opt = tr._optimizer
        opt.rescale_grad = tr._scale / batch_size
        lrs, wds, ts = opt.begin_fused_step(
            list(range(len(tr._params))))
        rescale = onp.float32(opt.rescale_grad)
        clip = onp.float32(opt.clip_gradient
                           if opt.clip_gradient is not None else 0.0)
        return lrs, wds, ts, rescale, clip

    def _prepare_zero(self):
        """Materialize the zero plan: replicate weights on the mesh and
        build the flat sharded state/master buffers."""
        mesh, axis = self._zero_ok
        repl_sharding = mesh.sharding()
        for p in self._all_params:
            p._write_fused(jax.device_put(p._data._data, repl_sharding))
        self._zero = _ZeroShardPlan(self._trainer, mesh, axis)

    def _zero_call(self, entry, traced, batch_size):
        plan = self._zero
        pds = tuple(p._data._data for p in self._all_params)
        sts = tuple(tuple(s._data for s in st) for st in plan.states)
        masters = tuple(m._data for m in plan.masters)
        leaf_datas = tuple(plan.place_leaf(
            l._data if isinstance(l, NDArray) else l) for l in traced)
        lrs, wds, ts, rescale, clip = self._scalars(batch_size)
        ulrs, uwds, uts = plan.pack_hparams(self._trainer._optimizer,
                                            lrs, wds, ts)
        key = next_key()
        new_pds, new_sts, new_masters, l, auxd = entry["fn"](
            pds, sts, masters, leaf_datas, ulrs, uwds, uts, rescale, clip,
            key)
        # writeback: same handles, new buffers (donation contract); the
        # state/master handles stay sharded across steps
        for p, nw in zip(self._all_params, new_pds):
            p._write_fused(nw)
        for st, ns in zip(plan.states, new_sts):
            for s, n in zip(st, ns):
                s._data = n
        for m, nm in zip(plan.masters, new_masters):
            m._data = nm
        self._stash_aux(entry, auxd, leaf_datas, batch_size, key)
        return NDArray(l)

    def _fused_call(self, args, kwargs, batch_size):
        if self._zero_ok is not None and self._zero is None:
            self._prepare_zero()
        elif self._plain_mesh is not None and not self._mesh_prepared:
            # mesh-aware PLAIN mode (zero gated off): params replicate on
            # the mesh so dp-sharded batches psum in-program
            mesh, _ = self._plain_mesh
            repl_sharding = mesh.sharding()
            for p in self._all_params:
                p._write_fused(jax.device_put(p._data._data, repl_sharding))
            self._mesh_prepared = True
        entry, traced = self._entry_for(args, kwargs)
        if batch_size is None:
            batch_size = _infer_batch_size(traced)
        if entry["kind"] == "zero":
            return self._zero_call(entry, traced, batch_size)
        states = self._ensure_states()
        for st in states:
            if not (isinstance(st, tuple) and all(
                    isinstance(s, NDArray) for s in st)):
                raise MXNetError(
                    "compile_step: optimizer state is not a flat NDArray "
                    "tuple (multi-precision?); eager path required")
        pds = tuple(p._data._data for p in self._all_params)
        sts = tuple(tuple(s._data for s in st) for st in states)
        leaf_datas = tuple(l._data if isinstance(l, NDArray) else l
                           for l in traced)
        if self._mesh_prepared:
            mesh, axis = self._plain_mesh
            leaf_datas = tuple(_place_on_mesh(mesh, axis, d)
                               for d in leaf_datas)
        lrs, wds, ts, rescale, clip = self._scalars(batch_size)
        key = next_key()

        if entry["kind"] == "split":
            l, state, gs, counters = entry["grad"](pds, leaf_datas, key)
            auxd = _aux_outputs(None, counters)
            # land gradients on the Parameter grad handles and reuse the
            # Trainer's own reduction machinery (bucketed pushpull_list)
            tr = self._trainer
            for p, g in zip(tr._params, gs):
                p.grad()._data = g
            tr._allreduce_grads()
            gs = tuple(p.grad()._data for p in tr._params)
            ws = tuple(pds[i] for i in self._trainable_pos)
            new_ws, new_sts = entry["update"](ws, sts, lrs, wds, ts,
                                              rescale, clip, gs)
            new_pds = list(state)
            for j, i in enumerate(self._trainable_pos):
                new_pds[i] = new_ws[j]
        else:
            fn = entry["exe"] or entry["fn"]
            new_pds, new_sts, l, auxd = fn(
                pds, sts, leaf_datas, lrs, wds, ts, rescale, clip, key)

        # writeback: same handles, new buffers (donation contract)
        for p, nw in zip(self._all_params, new_pds):
            p._write_fused(nw)
        for st, ns in zip(states, new_sts):
            for s, n in zip(st, ns):
                s._data = n
        self._stash_aux(entry, auxd, leaf_datas, batch_size, key)
        return NDArray(l)

    # ---------------- aux plumbing ----------------
    def _stash_aux(self, entry, auxd, leaf_datas, batch_size, key):
        """Wrap the program's aux output in this step's ``StepAux`` for
        the dispatch window, or leave None where the program has neither
        part. The numerics part becomes a StepNumerics record: small
        device scalars (still async), the host-side lr/loss-scale
        context, and the one-shot NaN-origin forensic closure over the
        CAPTURED input batch + RNG key. Holding the leaf refs keeps at
        most window-depth input batches alive — the price of being able
        to replay the faulting batch. The counters part stays the device
        arrays it is. Must never kill a step."""
        self._pending_aux = None
        if not auxd:
            return
        t = _telemetry()
        try:
            rec = None
            if "numerics" in auxd:
                rec = t.numerics.StepNumerics(
                    mode=entry["numerics"], raw=auxd["numerics"],
                    param_names=self._numerics_param_names(),
                    context=self._numerics_context(batch_size),
                    forensic=self._make_forensic(entry, leaf_datas, key))
            self._pending_aux = t.StepAux(rec, auxd.get("counters"))
        except Exception:        # pragma: no cover - defensive
            _LOG.warning("aux stash failed", exc_info=True)

    def _numerics_param_names(self):
        """UNIQUE trainable-parameter names in trainer._params order:
        the collect_params dict keys where available (Parameter.name
        alone is 'weight'/'bias' and collides across blocks)."""
        names = getattr(self, "_numerics_names", None)
        if names is None:
            tr = self._trainer
            by_id = {id(p): n for p, n in zip(tr._all_params,
                                              tr._param_names)}
            names = [by_id.get(id(p), p.name) for p in tr._params]
            self._numerics_names = names
        return names

    def _numerics_context(self, batch_size):
        opt = self._trainer._optimizer
        ctx = opt.hparam_snapshot()
        ctx["batch_size"] = batch_size
        ctx["step_in_program"] = self._steps_done + 1
        scaler = getattr(self._trainer, "_amp_loss_scaler", None)
        ctx["loss_scale"] = float(scaler.loss_scale) \
            if scaler is not None else None
        ctx["mode"] = "zero" if self._zero is not None else "fused"
        return ctx

    def _make_forensic(self, entry, leaf_datas, key):
        step_self = self

        def run(step_tag):
            return step_self._numerics_forensics(entry, leaf_datas, key,
                                                 step_tag)
        return run

    def _numerics_forensics(self, entry, leaf_datas, key, step_tag):
        """NaN-origin forensics, run ONCE per non-finite episode and
        OUTSIDE the hot loop (the monitor calls this under a blessed
        allow_transfers region when the ``nonfinite_grad`` anomaly
        fires): re-execute this bucket's loss+grad computation on the
        captured batch under ``jax.debug_nans``/``debug_infs`` to name
        the first non-finite-producing primitive, then once more plain
        (no donation) for the ranked per-layer norm table. Params are
        the CURRENT handles — the faulting step's pre-update weights
        were donated away — so the replay chases the batch, not the
        exact weight state (recorded in the dump)."""
        t = _telemetry()
        probe = entry.get("probe")
        if probe is None:
            return None
        pds = tuple(p._data._data for p in self._all_params)
        info = {"params_at": "retire (post-update handles)"}
        info["offending_op"] = t.numerics.localize_nonfinite(
            lambda: probe(pds, leaf_datas, key))
        try:
            l, _state, gs, _ = jax.jit(probe)(pds, leaf_datas, key)
            lv = onp.asarray(l, dtype="float64")
            info["loss"] = float(lv.mean())
            layers = []
            for name, p, g in zip(self._numerics_param_names(),
                                  self._trainer._params, gs):
                ga = onp.asarray(jnp.asarray(g, jnp.float32),
                                 dtype="float64")
                nf = int((~onp.isfinite(ga)).sum())
                finite = ga[onp.isfinite(ga)]
                gnorm = float(onp.sqrt((finite ** 2).sum()))
                pa = onp.asarray(
                    jnp.asarray(p._data._data, jnp.float32),
                    dtype="float64")
                layers.append({
                    "param": name,
                    "shape": list(ga.shape),
                    "dtype": str(g.dtype),
                    "grad_norm": gnorm,
                    "param_norm": float(onp.linalg.norm(pa)),
                    "nonfinite": nf,
                })
            layers.sort(key=lambda d: (-d["nonfinite"], -d["grad_norm"]))
            info["layers"] = layers
        except Exception as e:
            info["reexec_error"] = f"{type(e).__name__}: {e}"
        return info

    # ---------------- program analysis (mx.analysis) ----------------
    def analyze(self, *args, batch_size: Optional[int] = None, **kwargs):
        """Run the program lint over this batch's shape bucket and
        return the :class:`~mxnet_tpu.analysis.ProgramReport` —
        collective census, donation audit, host transfers, dtype drift,
        fusion census (docs/ANALYSIS.md).  Does not advance optimizer
        counts."""
        from ..analysis.program import analyze_step
        return analyze_step(self, *args, batch_size=batch_size, **kwargs)

    def fusion_report(self, *args, batch_size: Optional[int] = None,
                      **kwargs):
        """Fusion census of this batch bucket's OPTIMIZED program
        (:class:`~mxnet_tpu.analysis.fusion.FusionReport`): every
        fusion/compute kernel with its op census, FLOP estimate and
        boundary bytes, the stranded-op ideal-fusion diff, and the
        compute-/memory-bound classification against the BENCH roofline
        ridge (docs/ANALYSIS.md "Fusion census").  ``None`` on the
        eager path — there is no compiled program to audit.  Cached
        with the bucket's :meth:`analyze` report."""
        report = self.analyze(*args, batch_size=batch_size, **kwargs)
        return getattr(report, "fusion", None)

    def sharding_report(self, *args, batch_size: Optional[int] = None,
                        **kwargs):
        """SPMD sharding audit of this batch bucket's OPTIMIZED program
        (:class:`~mxnet_tpu.analysis.sharding.ShardingAudit`): the
        per-buffer sharding table, implicit reshards ranked by wire
        bytes against this mode's spec pack, and the per-mesh-axis
        communication cost estimate (docs/ANALYSIS.md "Sharding
        analysis").  ``None`` on the eager path.  Cached with the
        bucket's :meth:`analyze` report."""
        report = self.analyze(*args, batch_size=batch_size, **kwargs)
        return getattr(report, "sharding", None)

    def lower_entry(self, *args, batch_size: Optional[int] = None,
                    **kwargs):
        """Lower this batch bucket's program for static analysis.

        Returns a dict with the ``jax.stages.Lowered``, the traced
        jaxpr, and the layout facts the checkers need (mesh/axis,
        expected donated buffer count, shard-unit sizes, blessed dtype
        conversions) — or ``None`` on the eager path, where there is no
        program to lower.  Live weights and optimizer counts are
        untouched; the retrace counter is restored (an analysis lower
        is not a training retrace).  Cached per bucket."""
        if self._mode is None:
            self._mode = self._decide_mode()
        if self._mode != "fused":
            return None
        if self._zero_ok is not None and self._zero is None:
            self._prepare_zero()
        elif self._plain_mesh is not None and not self._mesh_prepared:
            mesh, _ = self._plain_mesh
            repl_sharding = mesh.sharding()
            for p in self._all_params:
                p._write_fused(jax.device_put(p._data._data,
                                              repl_sharding))
            self._mesh_prepared = True
        entry, traced = self._entry_for(args, kwargs)
        if entry.get("analysis") is not None:
            return entry["analysis"]
        if batch_size is None:
            batch_size = _infer_batch_size(traced)
        opt = self._trainer._optimizer
        n = len(self._trainer._params)
        blessed = []
        try:
            from .. import amp as _amp
            amp_on = _amp.is_enabled()
        except Exception:            # pragma: no cover - defensive
            amp_on = False
        if opt.multi_precision or amp_on:
            # the multi-precision master list: fp32 masters/islands are
            # the POINT of these modes, widening to f32 is intentional
            blessed = [("bfloat16", "float32"), ("float16", "float32")]
        rescale = onp.float32(1.0 / batch_size)
        clip = onp.float32(0.0)
        key = next_key()
        zeros = onp.zeros(n, onp.float32)
        ones = onp.ones(n, onp.int32)
        n_traces_before = self._n_traces
        try:
            if entry["kind"] == "zero":
                plan = self._zero
                pds = tuple(p._data._data for p in self._all_params)
                sts = tuple(tuple(s._data for s in st)
                            for st in plan.states)
                masters = tuple(m._data for m in plan.masters)
                leaf = tuple(plan.place_leaf(
                    l._data if isinstance(l, NDArray) else l)
                    for l in traced)
                ulrs, uwds, uts = plan.pack_hparams(opt, zeros, zeros,
                                                    ones)
                fargs = (pds, sts, masters, leaf, ulrs, uwds, uts,
                         rescale, clip, key)
                lowered = entry["fn"].lower(*fargs)
                jaxpr = self._safe_jaxpr(entry["fn"], fargs)
                n_state = sum(len(st) for st in sts)
                unit_sizes = sorted({u["padded"] for u in plan.units}
                                    | {u["total"] for u in plan.units})
                info = dict(
                    kind="zero", mode="zero", lowered=lowered,
                    jaxpr=jaxpr, mesh=plan.mesh, axis=plan.axis,
                    expected_donated=(len(pds) + n_state + len(masters))
                    if self._donate else None,
                    unit_sizes=unit_sizes, n_params=len(pds),
                    n_state_leaves=n_state, blessed_dtypes=blessed,
                    report=None)
            else:
                states = self._ensure_states()
                pds = tuple(p._data._data for p in self._all_params)
                sts = tuple(tuple(s._data for s in st) for st in states)
                leaf = tuple(l._data if isinstance(l, NDArray) else l
                             for l in traced)
                if self._mesh_prepared:
                    mesh, axis = self._plain_mesh
                    leaf = tuple(_place_on_mesh(mesh, axis, d)
                                 for d in leaf)
                if entry["kind"] == "split":
                    fargs = (pds, leaf, key)
                    lowered = entry["grad"].lower(*fargs)
                    jaxpr = self._safe_jaxpr(entry["grad"], fargs)
                    info = dict(kind="split", mode="split",
                                lowered=lowered, jaxpr=jaxpr, mesh=None,
                                axis=None, expected_donated=None,
                                unit_sizes=[], n_params=len(pds),
                                n_state_leaves=0,
                                blessed_dtypes=blessed, report=None)
                else:
                    fargs = (pds, sts, leaf, zeros, zeros, ones, rescale,
                             clip, key)
                    lowered = entry["fn"].lower(*fargs)
                    jaxpr = self._safe_jaxpr(entry["fn"], fargs)
                    mesh = axis = None
                    mode = "fused"
                    if self._mesh_prepared:
                        mesh, axis = self._plain_mesh
                        mode = "fused-mesh"
                    n_state = sum(len(st) for st in sts)
                    info = dict(
                        kind="fused", mode=mode, lowered=lowered,
                        jaxpr=jaxpr, mesh=mesh, axis=axis,
                        expected_donated=(len(pds) + n_state)
                        if self._donate else None,
                        unit_sizes=sorted({int(d.size) for d in pds}),
                        n_params=len(pds), n_state_leaves=n_state,
                        blessed_dtypes=blessed, report=None)
        finally:
            # lowering re-runs the traced python (n_traces side effect):
            # an analysis lower is not a training retrace
            self._n_traces = n_traces_before
        entry["analysis"] = info
        return info

    @staticmethod
    def _safe_jaxpr(fn, fargs):
        try:
            return jax.make_jaxpr(fn)(*fargs)
        except Exception:            # pragma: no cover - defensive
            return None

    # ---------------- AOT ----------------
    def aot_compile(self, *args, batch_size: Optional[int] = None,
                    **kwargs):
        """Lower + compile the step for this batch's shape bucket ahead
        of time and pin the executable, so the timed loop never pays a
        second jit compile; returns XLA's flop count for the ONE program
        the chip runs per step (or None where cost_analysis is
        unavailable). Does not advance optimizer counts."""
        if self._mode is None:
            self._mode = self._decide_mode()
        if self._mode != "fused" or self._host_allreduce() \
                or self._zero_ok is not None:
            # zero mode: jit-compiles on first step; AOT flop pinning is
            # not wired for the sharded signature yet
            return None
        entry, traced = self._entry_for(args, kwargs)
        if entry["exe"] is not None:
            return entry["flops"]
        if batch_size is None:
            batch_size = _infer_batch_size(traced)
        states = self._ensure_states()
        pds = tuple(p._data._data for p in self._all_params)
        sts = tuple(tuple(s._data for s in st) for st in states)
        leaf_datas = tuple(l._data if isinstance(l, NDArray) else l
                           for l in traced)
        n = len(self._trainer._params)
        lrs = onp.zeros(n, onp.float32)
        wds = onp.zeros(n, onp.float32)
        ts = onp.ones(n, onp.int32)
        rescale = onp.float32(1.0 / batch_size)
        clip = onp.float32(0.0)
        key = next_key()
        exe = entry["fn"].lower(pds, sts, leaf_datas, lrs, wds, ts,
                                rescale, clip, key).compile()
        entry["exe"] = exe
        try:
            ca = exe.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            f = float(ca.get("flops", 0.0))
            entry["flops"] = f if f > 0 else None
        except Exception:        # pragma: no cover - platform-dependent
            entry["flops"] = None
        return entry["flops"]


class TrainLoop:
    """Convenience wrapper for the canonical (net, loss, trainer) triple:

        loop = gluon.TrainLoop(net, trainer, loss_block)
        for x, y in batches:
            loss = loop.step(x, y)     # ONE compiled XLA program

    ``step(*inputs, label)`` feeds all but the last array to ``net`` and
    the last to the loss block, through ``Trainer.compile_step`` — the
    framework-level replacement for hand-rolled jitted train steps.

    **Async dispatch** (docs/PERF_NOTES.md "async engine"): ``step()``
    returns IMMEDIATELY with an async loss NDArray — JAX arrays are
    futures, and the loop never forces them. A bounded in-flight window
    (``mx.engine.DispatchWindow``, size ``MXNET_INFLIGHT_STEPS`` /
    ``inflight=``, default 2; ``NaiveEngine`` forces 0) reproduces the
    reference engine's ``PushAsync``/``WaitForVar`` discipline: the host
    dispatches ahead of the device and blocks only when the window
    fills, on the OLDEST step's loss. A step that faulted raises at its
    own retire — named by step number — not at a later sync with the
    wrong traceback. ``synchronize()`` drains the window;
    ``engine_stats()`` reports pushes/retires/max-pending plus the last
    prefetcher's input-wait stats. The whole ``step()`` body is a
    transfer-guard hot region: with ``MXNET_TRANSFER_GUARD=raise`` any
    host sync OTHER than the blessed window retire (and checkpoint
    snapshots) raises.

    **Device input prefetch**: ``for x, y in loop.prefetch(batches):``
    stages upcoming host batches onto the device with the step's exact
    sharding on a background thread, overlapping the host→device copy
    with the previous step's compute (gluon/data/prefetcher.py).

    **Numerics observability** (``numerics=`` / ``MXNET_NUMERICS``,
    docs/OBSERVABILITY.md "numerics"): the compiled step's in-program
    grad/param health statistics (global grad norm, update/weight
    ratio, non-finite counts, per-layer norms) ride the dispatch
    window alongside each loss and surface as ``mx_numerics_*`` series
    plus divergence anomalies at the blessed retire — zero extra host
    syncs; a non-finite gradient triggers one NaN-origin forensic
    re-execution and an atomic post-mortem dump.

    **Preemption safety** (``checkpoint_dir=...``): the loop owns a
    ``mx.checkpoint.TrainCheckpointManager`` — on construction it
    auto-resumes from the newest VALID checkpoint (params, fused/ZeRO
    optimizer state, update counters, RNG; corrupt ones are skipped
    with a warning), every ``checkpoint_every`` steps it snapshots
    device state synchronously and commits the write atomically on a
    background thread (serialization overlaps the next steps), and it
    keeps the newest ``keep_last`` checkpoints. A run killed at ANY
    instant — including mid-commit — restarts from the last published
    checkpoint and replays forward bit-exactly (docs/ROBUSTNESS.md).
    A failed background write surfaces on the next ``step()``/``wait()``.
    """

    def __init__(self, net, trainer, loss, donate: bool = True,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: Optional[int] = None,
                 keep_last: int = 3, async_checkpoint: bool = True,
                 resume: bool = True, inflight: Optional[int] = None,
                 numerics: Optional[str] = None):
        from .. import engine as _engine
        self._net = net
        self._loss = loss
        self._trainer = trainer
        self._step = trainer.compile_step(self._loss_fn, donate=donate,
                                          numerics=numerics)
        self._window = _engine.DispatchWindow(max_inflight=inflight,
                                              what="TrainLoop step")
        self._prefetcher = None
        self._global_step = 0
        t = _telemetry()
        self._m_steps = t.registry().counter(t.names.TRAIN_STEPS)
        self._every = checkpoint_every
        self._manager = None
        if checkpoint_dir is not None:
            from ..checkpoint.manager import TrainCheckpointManager
            self._manager = TrainCheckpointManager(
                checkpoint_dir, keep_last=keep_last,
                async_save=async_checkpoint)
            if resume:
                meta = self._manager.restore_latest(
                    trainer=trainer, net=net, strict=False)
                if meta is not None:
                    self._global_step = int(meta.get("step", 0))
                    _LOG.info("TrainLoop resumed at step %d from %s",
                              self._global_step, checkpoint_dir)

    def _loss_fn(self, *batch):
        *inputs, label = batch
        out = self._net(*inputs)
        return self._loss(out, label)

    def step(self, *batch, batch_size: Optional[int] = None):
        try:
            return self._step_impl(batch, batch_size)
        except (KeyboardInterrupt, SystemExit) as intr:
            # an interrupt mid-hot-loop used to abandon the dispatch
            # window (in-flight steps and their deferred errors silently
            # dropped) — drain it, surface the earliest faulted step's
            # error, and leave a final checkpoint behind
            fault = self._interrupt_cleanup()
            if fault is not None:
                raise fault from intr
            raise

    def _step_impl(self, batch, batch_size):
        # the WHOLE pipelined iteration is a transfer-guard hot region
        # (nested inside CompiledTrainStep's own scope this is a no-op):
        # the window retire below and the checkpoint snapshot are the
        # only blessed syncs; anything else — a float(loss) leaking in,
        # a per-step metric asnumpy — is flagged/raised when
        # MXNET_TRANSFER_GUARD is armed
        with _tguard.hot_scope("TrainLoop.step"):
            t = _telemetry()
            step_no = self._global_step + 1
            if t.active():
                # dispatch span + the XProf bridge: the span holds this
                # StepTraceAnnotation (not a second ``mx:dispatch`` one),
                # which groups the step's device kernels under the same
                # step number the host spans carry, so the merged trace
                # aligns host phases with XLA execution
                with t.timeline().span(
                        "dispatch", step=step_no,
                        annotation=jax.profiler.StepTraceAnnotation(
                            "mx_train_step", step_num=step_no)):
                    loss = self._step(*batch, batch_size=batch_size)
            else:
                loss = self._step(*batch, batch_size=batch_size)
            self._global_step = step_no
            self._m_steps.inc()
            d = loss._data if isinstance(loss, NDArray) else loss
            # the step's aux (numerics record, device counters) rides
            # the window with the loss and is read at the blessed
            # retire — sync-free
            self._window.push(d, tag=self._global_step,
                              aux=self._step.take_aux())
            if self._manager is not None and self._every and \
                    self._global_step % self._every == 0:
                with _tguard.allow_transfers("checkpoint snapshot"):
                    self.save_checkpoint()
        return loss

    __call__ = step

    def _interrupt_cleanup(self):
        """KeyboardInterrupt/SIGTERM landed in the hot loop: drain the
        window (a deferred async failure in it is the REAL story — the
        earliest faulted step's error is returned for the caller to
        propagate instead of the bare interrupt) and, when a checkpoint
        manager is attached, commit a final checkpoint so the
        interrupted run resumes from where it actually stopped."""
        fault = None
        try:
            self._window.drain()
        except BaseException as e:
            fault = e
            try:
                self._window.abandon()
            except Exception:    # pragma: no cover - defensive
                pass
        if self._manager is not None:
            try:
                with _tguard.allow_transfers("interrupt final checkpoint"):
                    self._manager.save(self._global_step,
                                       trainer=self._trainer,
                                       net=self._net, block=True)
            except Exception:
                _LOG.warning("final checkpoint on interrupt failed",
                             exc_info=True)
        return fault

    # ---------------- async engine surface ----------------
    def synchronize(self):
        """Drain the in-flight dispatch window — ``WaitForVar`` on every
        outstanding step. Deferred async errors surface here attributed
        to the step that faulted."""
        self._window.drain()

    def discard_inflight(self):
        """Recovery-path window cleanup (``mx.elastic``): retire the
        in-flight steps that still complete, then discard everything
        after the first failure — their results died with the device;
        the newest checkpoint is the source of truth. Returns
        ``(retired, discarded_tags)``."""
        return self._window.drain_partial()

    def prefetch(self, batches, depth: Optional[int] = None):
        """Wrap a host batch iterable in a device prefetcher staged with
        THIS loop's input sharding (dp-sharded batch on a mesh,
        replicated otherwise)::

            for x, y in loop.prefetch(loader):
                loop.step(x, y)

        The host→device copy of batch N+1 overlaps step N's compute.
        ``depth`` bounds staged batches (``MXNET_DEVICE_PREFETCH``,
        default 2). Stats land in :meth:`engine_stats`."""
        from .data.prefetcher import DevicePrefetcher
        self._prefetcher = DevicePrefetcher(
            batches, depth=depth, place=self._step.input_placement())
        return self._prefetcher

    def engine_stats(self) -> dict:
        """Dispatch/prefetch observability: the in-flight window size and
        its push/retire counters, plus the last :meth:`prefetch`
        iterator's input-wait numbers (tools/diagnose.py --engine)."""
        s = dict(self._window.stats)
        s["inflight_window"] = self._window.max_inflight
        s["pending"] = len(self._window)
        if self._prefetcher is not None:
            s.update(self._prefetcher.stats)
        return s

    # ---------------- checkpointing ----------------
    def save_checkpoint(self, block: Optional[bool] = None):
        """Snapshot now (at ``global_step``); async unless
        ``block=True``. No-op without ``checkpoint_dir``."""
        if self._manager is None:
            raise MXNetError(
                "TrainLoop was built without checkpoint_dir=")
        self._manager.save(self._global_step, trainer=self._trainer,
                           net=self._net, block=block)

    def wait(self):
        """Drain the in-flight checkpoint write (re-raising its error);
        call before exiting so the newest snapshot is durable."""
        if self._manager is not None:
            self._manager.wait()

    @property
    def global_step(self) -> int:
        return self._global_step

    @property
    def checkpoint_manager(self):
        return self._manager

    @property
    def compiled_step(self) -> CompiledTrainStep:
        return self._step

    @property
    def trainer(self):
        return self._trainer
