"""AOT-compiled inference executables (``serving.CompiledPredictor``).

The training half of this framework compiles the whole train step into
one donated-buffer XLA program (gluon/fused_step.py); this is the
INFERENCE counterpart, the full-program-compilation discipline of the
Julia-to-TPU work (arXiv:1810.09868) applied to the serving path:

- **One program per shape bucket.** The forward runs ONCE under trace
  (taping suspended, ``autograd`` recording off, ``train_mode=False``)
  through the same functional ``ParamBinding`` the fused step uses, and
  the resulting program is AOT-lowered and compiled
  (:meth:`CompiledPredictor.aot_compile` / :meth:`warmup`) so the hot
  loop never pays a jit compile. The persistent compile cache warm-starts
  the executables across process restarts — a restarted replica serves
  its first request from the disk cache instead of re-paying XLA.
- **Params resident on device.** Parameters are passed by handle every
  call — the same device buffers, no per-request host→device copy and
  no donation (inference reuses them; nothing is consumed). INT8
  predictors close their quantized weights over the trace as XLA
  constants.
- **Bucketed batch shapes.** ``bucket_sizes`` quantizes the leading
  batch dimension; :meth:`bucket_for` / :meth:`pad_to_bucket` pad a
  partial batch up to the next bucket (zero rows, sliced away by the
  caller) so N concurrent request sizes hit a handful of compiled
  programs instead of N. The :class:`~mxnet_tpu.serving.DynamicBatcher`
  coalesces concurrent requests INTO these buckets.
- **Same static-analysis gates as training.** :meth:`analyze` runs the
  full program lint (collective census, host-transfer scan, dtype
  drift, fusion census) over the serving program; :meth:`memory_report`
  attributes its HBM; ``expect_mode`` knows the ``predict`` contract
  (no collectives on a single device, no stranded fusable ops).
- **Sync-free dispatch.** :meth:`predict` returns ASYNC NDArrays — the
  host never reads the result; the response-side sync belongs to
  whoever consumes it (the batcher's window retire, or the client's
  ``.asnumpy()``). The whole call is a transfer-guard hot region:
  ``MXNET_TRANSFER_GUARD=raise`` turns any stray host sync inside it
  into an error (docs/SERVING.md).
"""
from __future__ import annotations

import logging
import time
from collections import OrderedDict
from typing import Any, Callable, Optional, Sequence

import numpy as onp

import jax
import jax.numpy as jnp

from .. import _tape
from ..analysis import guard as _tguard
from ..base import MXNetError
from ..gluon.block import UNTRACEABLE_ERRORS, ParamBinding, _TRACED
from ..gluon.fused_step import _analysis_mode
from ..ndarray.ndarray import NDArray
from ..ndarray.random import next_key, push_trace_key, pop_trace_key

__all__ = ["CompiledPredictor", "DEFAULT_BUCKETS", "predictor_for"]

_LOG = logging.getLogger("mxnet_tpu.serving")

_TELEM = None


def _telemetry():
    global _TELEM
    if _TELEM is None:
        from .. import telemetry as _t
        _TELEM = _t
    return _TELEM


# elastic device-loss detection (elastic/detect.py), lazily reached so
# a lost device escaping the predictor call gets its exactly-one
# device_lost anomaly (the ServingSupervisor's recovery trigger)
_EDET = None


def _edetect():
    global _EDET
    if _EDET is None:
        from ..elastic import detect as _d
        _EDET = _d
    return _EDET


#: default leading-dim shape buckets: powers of two up to 64 — small
#: enough that a replica compiles them all at startup, coarse enough
#: that the compile cache keys on a handful of programs
DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64)

_ARRAY_TYPES = (NDArray, onp.ndarray, jax.Array)


def _data_of(leaf):
    return leaf._data if isinstance(leaf, NDArray) else leaf


def _pad_rows(d, bucket: int):
    """Zero-pad a leaf's leading dim up to ``bucket`` rows (host-side
    for host arrays, an async device op for device arrays — never a
    sync)."""
    raw = _data_of(d)
    n = int(raw.shape[0])
    if n == bucket:
        return d
    if isinstance(raw, onp.ndarray):
        pad = onp.zeros((bucket - n,) + raw.shape[1:], raw.dtype)
        out = onp.concatenate([raw, pad], axis=0)
    else:
        pad = jnp.zeros((bucket - n,) + tuple(raw.shape[1:]), raw.dtype)
        out = jnp.concatenate([raw, pad], axis=0)
    return NDArray(out) if isinstance(d, NDArray) else out


class CompiledPredictor:
    """One callable = the whole forward pass, AOT-compiled per shape
    bucket.

    ``net`` must be initialized with materialized shapes (run one eager
    forward first — the model-zoo constructors' usual discipline).

        pred = mx.serving.CompiledPredictor(net)
        pred.warmup(example_row)          # AOT-compile every bucket
        out = pred.predict(x)             # async NDArray, no host sync
    """

    def __init__(self, net, bucket_sizes: Optional[Sequence[int]] = None,
                 analyze: Optional[str] = None):
        self._net = net
        sizes = tuple(sorted({int(b) for b in
                              (bucket_sizes or DEFAULT_BUCKETS)}))
        if not sizes or sizes[0] < 1:
            raise MXNetError("bucket_sizes must be positive integers, "
                             f"got {bucket_sizes!r}")
        self.bucket_sizes = sizes
        self._mode: Optional[str] = None   # None→undecided, 'fused'|'eager'
        self._lru: "OrderedDict[Any, dict]" = OrderedDict()
        self._n_traces = 0
        self._requests_done = 0
        self._analyze = _analysis_mode(analyze)
        self._analysis_report = None
        # measured per-micro-batch service time from the warmup()
        # execution; a DynamicBatcher seeds its admission EWMA from it
        # so deadline shedding works from request 1 (no cold-start
        # blindness). None until warmup ran.
        self.service_time_seed_s: Optional[float] = None
        # params with materialized data, bound functionally per call —
        # the same handles every time (resident on device); quantized
        # blocks own no Parameters and close their weights over the trace
        self._params = [p for p in net.collect_params().values()
                        if p._data is not None]
        if any(p._data is None for p in net.collect_params().values()):
            raise MXNetError(
                "CompiledPredictor needs materialized parameter shapes — "
                "run one eager forward (net(example)) before wrapping")

    # ---------------- introspection ----------------
    @property
    def n_traces(self) -> int:
        """Distinct compiled bucket programs built so far (what the
        bucket-retrace tests assert on)."""
        return self._n_traces

    @property
    def mode(self) -> Optional[str]:
        return self._mode

    @property
    def analysis_report(self):
        return self._analysis_report

    # ---------------- bucketing ----------------
    def bucket_for(self, rows: int) -> int:
        """Smallest configured bucket >= ``rows``."""
        for b in self.bucket_sizes:
            if rows <= b:
                return b
        raise MXNetError(
            f"request of {rows} rows exceeds the largest shape bucket "
            f"({self.bucket_sizes[-1]}); raise bucket_sizes= or split "
            "the request")

    def pad_to_bucket(self, *args):
        """Pad every array leaf's leading dim up to the next bucket.
        Returns ``(padded_args, rows)`` — ``rows`` is the valid-row
        count (the mask): outputs beyond it are padding and must be
        sliced away."""
        leaves, treedef = jax.tree_util.tree_flatten(
            args, is_leaf=lambda t: isinstance(t, NDArray))
        rows = None
        for l in leaves:
            if isinstance(l, _ARRAY_TYPES) and \
                    getattr(_data_of(l), "ndim", 0) >= 1:
                rows = int(_data_of(l).shape[0])
                break
        if rows is None:
            raise MXNetError("pad_to_bucket: no array leaf with a "
                             "leading batch dim")
        bucket = self.bucket_for(rows)
        padded = [_pad_rows(l, bucket)
                  if isinstance(l, _ARRAY_TYPES) and
                  getattr(_data_of(l), "ndim", 0) >= 1 else l
                  for l in leaves]
        return jax.tree_util.tree_unflatten(treedef, padded), rows

    # ---------------- bucket cache ----------------
    def _flatten(self, args, kwargs):
        all_leaves, arg_treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda t: isinstance(t, NDArray))
        traced = [l for l in all_leaves if isinstance(l, _ARRAY_TYPES)]
        static_spec = tuple(_TRACED if isinstance(l, _ARRAY_TYPES) else l
                            for l in all_leaves)
        nd_mask = tuple(isinstance(l, NDArray) for l in traced)
        return traced, arg_treedef, static_spec, nd_mask

    def _entry_for(self, args, kwargs):
        traced, arg_treedef, static_spec, nd_mask = self._flatten(
            args, kwargs)
        shapes = tuple((tuple(_data_of(l).shape), str(_data_of(l).dtype))
                       for l in traced)
        sig = (arg_treedef, static_spec, nd_mask, shapes)
        entry = self._lru.get(sig)
        if entry is None:
            entry = self._build_bucket(arg_treedef, static_spec, nd_mask)
            t = _telemetry()
            t.registry().counter(t.names.COMPILE_RETRACES).inc()
            self._lru[sig] = entry
        else:
            self._lru.move_to_end(sig)
        return entry, traced

    def _build_bucket(self, arg_treedef, static_spec, nd_mask) -> dict:
        net = self._net
        params = self._params
        pred_self = self
        entry: dict = {"exe": None, "flops": None, "out_tree": None,
                       "analysis": None, "memory": None}

        def run(pds, traced_leaves, key):
            pred_self._n_traces += 1
            it = iter(NDArray(l) if m else l
                      for l, m in zip(traced_leaves, nd_mask))
            leaves = [next(it) if s is _TRACED else s
                      for s in static_spec]
            args, kwargs = jax.tree_util.tree_unflatten(arg_treedef,
                                                        leaves)
            binding = ParamBinding(params, pds)
            push_trace_key(key)
            # the inference fast path: taping SUSPENDED (no autograd
            # graph), recording off, eval mode — the forward is a pure
            # function of (params, inputs)
            prev_r = _tape.set_recording(False)
            prev_s = _tape.set_taping_suspended(True)
            prev_t = _tape.set_training(False)
            try:
                with binding:
                    out = net(*args, **kwargs)
            finally:
                _tape.set_recording(prev_r)
                _tape.set_taping_suspended(prev_s)
                _tape.set_training(prev_t)
                pop_trace_key()
            out_leaves, out_tree = jax.tree_util.tree_flatten(
                out, is_leaf=lambda t: isinstance(t, NDArray))
            entry["out_tree"] = out_tree
            return tuple(_data_of(l) if isinstance(l, _ARRAY_TYPES)
                         else jnp.asarray(l) for l in out_leaves)

        entry["fn"] = jax.jit(run)
        return entry

    # ---------------- call ----------------
    def predict(self, *args, **kwargs):
        """Dispatch one (bucketed) batch; returns the net's output
        structure with ASYNC NDArray leaves — no host sync happens in
        here (the transfer guard enforces it when armed). Inputs must
        already be bucket-shaped; pair with :meth:`pad_to_bucket` or
        the :class:`~mxnet_tpu.serving.DynamicBatcher`."""
        with _tguard.hot_scope("CompiledPredictor.predict"), \
                _edetect().device_lost_guard("CompiledPredictor.predict"):
            if self._mode is None:
                self._mode = "fused"
            if self._mode == "eager":
                out = self._eager_call(args, kwargs)
            else:
                try:
                    out = self._fused_call(args, kwargs)
                except UNTRACEABLE_ERRORS as e:
                    # only an untraceable forward demotes; lowering,
                    # compile and runtime errors propagate
                    if self._requests_done:
                        raise   # proven program: a genuine error
                    _LOG.warning(
                        "CompiledPredictor: forward is not traceable "
                        "(%s: %s); falling back to the eager forward",
                        type(e).__name__, e)
                    self._mode = "eager"
                    out = self._eager_call(args, kwargs)
            self._requests_done += 1
        if self._analyze is not None and self._analysis_report is None:
            self._run_analysis(args, kwargs)
        return out

    __call__ = predict

    def _fused_call(self, args, kwargs):
        entry, traced = self._entry_for(args, kwargs)
        pds = tuple(p._data._data for p in self._params)
        leaf_datas = tuple(_data_of(l) for l in traced)
        fn = entry["exe"] or entry["fn"]
        datas = fn(pds, leaf_datas, next_key())
        return jax.tree_util.tree_unflatten(
            entry["out_tree"], [NDArray(d) for d in datas])

    def _eager_call(self, args, kwargs):
        prev_r = _tape.set_recording(False)
        prev_t = _tape.set_training(False)
        try:
            return self._net(*args, **kwargs)
        finally:
            _tape.set_recording(prev_r)
            _tape.set_training(prev_t)

    # ---------------- AOT ----------------
    def aot_compile(self, *args, **kwargs):
        """Lower + compile this batch's bucket ahead of time and pin
        the executable (warm-started from the persistent compile cache);
        returns XLA's flop count for the program, or None where
        cost_analysis is unavailable."""
        if self._mode == "eager":
            return None
        entry, traced = self._entry_for(args, kwargs)
        if entry["exe"] is not None:
            return entry["flops"]
        pds = tuple(p._data._data for p in self._params)
        leaf_datas = tuple(_data_of(l) for l in traced)
        n_before = self._n_traces
        try:
            exe = entry["fn"].lower(pds, leaf_datas, next_key()).compile()
        finally:
            # an AOT lower re-runs the traced python; the live jit call
            # for the same bucket will trace once more — count ONE
            # program per bucket, not the analysis artifacts
            self._n_traces = n_before
        self._n_traces += 1
        self._mode = "fused"
        entry["exe"] = exe
        try:
            ca = exe.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            f = float(ca.get("flops", 0.0))
            entry["flops"] = f if f > 0 else None
        except Exception:        # pragma: no cover - platform-dependent
            entry["flops"] = None
        return entry["flops"]

    def warmup(self, *example, buckets: Optional[Sequence[int]] = None):
        """AOT-compile every shape bucket from one example request
        (a 1-row batch): each bucket's program is lowered + compiled
        before traffic arrives, so no live request ever pays a compile.
        Returns ``{bucket_size: flops}``."""
        out = {}
        last_padded = None
        for b in (buckets or self.bucket_sizes):
            padded = tuple(
                _pad_rows(l, b) if isinstance(l, _ARRAY_TYPES) and
                getattr(_data_of(l), "ndim", 0) >= 1 else l
                for l in example)
            out[b] = self.aot_compile(*padded)
            last_padded = padded
        # time ONE execution of the largest warmed bucket (compile
        # already paid above): the measured micro-batch service time
        # seeds the DynamicBatcher's admission EWMA, so deadline-based
        # shedding projects honestly from the very first request
        if last_padded is not None and example:
            try:
                t0 = time.perf_counter()
                res = self.predict(*last_padded)
                jax.block_until_ready([
                    _data_of(l) for l in jax.tree_util.tree_leaves(
                        res, is_leaf=lambda t: isinstance(t, NDArray))
                    if isinstance(l, _ARRAY_TYPES)])
                self.service_time_seed_s = time.perf_counter() - t0
            except Exception:    # pragma: no cover - warmup is advisory
                _LOG.debug("warmup timing execution failed",
                           exc_info=True)
        return out

    # ---------------- static analysis ----------------
    def lower_entry(self, *args, batch_size: Optional[int] = None,
                    **kwargs):
        """Lower this bucket's program for static analysis — the same
        artifact contract as ``CompiledTrainStep.lower_entry`` so the
        program lint (analysis/program.py) runs unchanged over serving
        programs. No retrace is counted; live params are untouched."""
        if self._mode == "eager":
            return None
        entry, traced = self._entry_for(args, kwargs)
        if entry.get("analysis") is not None:
            return entry["analysis"]
        pds = tuple(p._data._data for p in self._params)
        leaf_datas = tuple(_data_of(l) for l in traced)
        key = next_key()
        blessed = []
        if any(str(d.dtype) in ("bfloat16", "float16") for d in pds):
            # low-precision predictors keep norm layers in f32 by
            # design (amp.convert_hybrid_block) — widening back is
            # intentional there
            blessed = [("bfloat16", "float32"), ("float16", "float32")]
        n_before = self._n_traces
        try:
            fargs = (pds, leaf_datas, key)
            lowered = entry["fn"].lower(*fargs)
            try:
                jaxpr = jax.make_jaxpr(entry["fn"])(*fargs)
            except Exception:    # pragma: no cover - defensive
                jaxpr = None
        finally:
            self._n_traces = n_before
        info = dict(kind="predict", mode="predict", lowered=lowered,
                    jaxpr=jaxpr, mesh=None, axis=None,
                    expected_donated=None, unit_sizes=[],
                    n_params=len(pds), n_state_leaves=0,
                    blessed_dtypes=blessed, report=None)
        entry["analysis"] = info
        return info

    def analyze(self, *args, **kwargs):
        """Full program lint of this bucket's serving program
        (:class:`~mxnet_tpu.analysis.ProgramReport`): collective census
        (a single-device predict program must have none), host-transfer
        scan, dtype drift, fusion census — the same gates the training
        step passes (docs/ANALYSIS.md)."""
        from ..analysis.program import analyze_step
        return analyze_step(self, *args, **kwargs)

    def fusion_report(self, *args, **kwargs):
        report = self.analyze(*args, **kwargs)
        return getattr(report, "fusion", None)

    def memory_report(self, *args, **kwargs):
        """Static HBM footprint of this bucket's compiled program
        (:class:`~mxnet_tpu.telemetry.MemoryReport`); with no arguments,
        the field-wise max over every bucket analyzed so far."""
        t = _telemetry()
        if not args and not kwargs:
            reports = [e["memory"] for e in self._lru.values()
                       if e.get("memory") is not None]
            return t.memory.MemoryReport.merge(reports) if reports \
                else None
        if self._mode == "eager":
            return None
        entry, _ = self._entry_for(args, kwargs)
        if entry.get("memory") is not None:
            return entry["memory"]
        compiled = entry.get("exe")
        if compiled is None:
            info = self.lower_entry(*args, **kwargs)
            if info is None:
                return None
            compiled = info["lowered"].compile()
        report = t.memory.MemoryReport.from_compiled(compiled)
        entry["memory"] = report
        n_buckets = sum(1 for e in self._lru.values()
                        if e.get("memory") is not None)
        t.memory.register_compiled_report(
            f"predict:bucket{n_buckets}", report)
        return report

    def _run_analysis(self, args, kwargs):
        try:
            report = self.analyze(*args, **kwargs)
        except Exception as e:   # analysis must not kill serving
            _LOG.warning("CompiledPredictor: program analysis failed "
                         "(%s: %s); skipping", type(e).__name__, e)
            self._analysis_report = False
            return
        self._analysis_report = report
        if self._analyze == "warn" and not report.ok:
            _LOG.warning("CompiledPredictor program analysis:\n%s",
                         report.summary())
        elif self._analyze == "raise":
            report.raise_if_findings()


def predictor_for(net, dtype: str = "float32", calib_data=None,
                  calib_mode: str = "naive",
                  bucket_sizes: Optional[Sequence[int]] = None,
                  **kwargs) -> CompiledPredictor:
    """Build a predictor at the requested serving precision, reusing
    the training stack's conversion paths (docs/SERVING.md):

    - ``float32``/``fp32`` — the net as-is;
    - ``bfloat16``/``bf16``/``float16`` — ``amp.convert_hybrid_block``
      casts non-norm parameters down (norm layers stay f32);
    - ``int8`` — ``contrib.quantization.quantize_net`` calibrates on
      ``calib_data`` (required) and swaps Dense/Conv children for the
      INT8 MXU kernels.

    Conversion mutates ``net`` in place (the reference conversion
    contract); pass a copy to keep an f32 original.
    """
    d = dtype.lower()
    if d in ("float32", "fp32", "f32"):
        pass
    elif d in ("bfloat16", "bf16", "float16", "fp16"):
        from .. import amp as _amp
        _amp.convert_hybrid_block(
            net, "bfloat16" if d.startswith("b") else "float16")
    elif d == "int8":
        if calib_data is None:
            raise MXNetError("int8 serving needs calib_data= batches "
                             "for range calibration")
        from ..contrib.quantization import quantize_net
        quantize_net(net, calib_data, calib_mode=calib_mode)
    else:
        raise MXNetError(f"unknown serving dtype {dtype!r} (float32, "
                         "bfloat16, float16, int8)")
    return CompiledPredictor(net, bucket_sizes=bucket_sizes, **kwargs)
