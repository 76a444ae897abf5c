"""Device-side input prefetch: overlap host→device copy with compute.

The threaded ``DataLoader`` pipeline overlaps DECODE with training, but
the final host→device transfer still happened synchronously inside jit
dispatch — the TPU idled on the PCIe/ICI copy every step. This stage
closes that gap (the top non-model optimization of the MLPerf TPU-pod
work, arXiv:1909.09756; reference analog: ``iter_prefetcher.h`` +
``PrefetchingIter``, generalized to place ON the accelerator):

- a bounded background thread pulls batches from any host iterable and
  ``jax.device_put``s them ahead of time — with the train step's EXACT
  ``NamedSharding`` when a mesh is active (dp-sharded batch dim,
  replicated otherwise), so the fused step's input-layout check passes
  them through untouched;
- ``device_put`` is itself async: the producer thread only *enqueues*
  transfers, the PjRt runtime streams them while the chip runs step N;
- the consumer side records how long it actually waited on input
  (``input_wait_ms``) and how often the staging queue was empty on
  arrival (``starvation_count``) — the two numbers that tell a profiler
  whether input is hidden or the bottleneck.

Wiring: ``DataLoader(..., device=..., prefetch_to_device=k)`` or
``TrainLoop.prefetch(batches)`` (which supplies the step's placement).
``MXNET_DEVICE_PREFETCH`` sets the default staging depth (2); 0 disables
the background thread (placement still happens, inline).
"""
from __future__ import annotations

import os
import queue
import threading
import time
from typing import Callable, Optional

import numpy as onp

import jax

from ...analysis.threads import mx_lock, register_queue
from ...base import MXNetError
from ...ndarray.ndarray import NDArray

__all__ = ["DevicePrefetcher", "default_prefetch_depth"]

_DONE = object()

_TELEM = None


def _telemetry():
    global _TELEM
    if _TELEM is None:
        from ... import telemetry as _t
        _TELEM = _t
    return _TELEM


def default_prefetch_depth(default: int = 2) -> int:
    try:
        v = int(os.environ.get("MXNET_DEVICE_PREFETCH", str(default)))
    except ValueError:
        return default
    return max(0, v)


class _Raised:
    """Producer-side exception carrier: re-raised at the consumer."""

    def __init__(self, exc):
        self.exc = exc


class DevicePrefetcher:
    """Bounded background host→device staging over any batch iterable.

    ``place`` is the per-leaf placement (``CompiledTrainStep
    .input_placement()`` — the step's NamedSharding); when ``None``,
    leaves go to ``device`` (a ``Context``, ``jax.Device``, or ``None``
    for the process default). A ``DeviceMesh`` may be passed as
    ``mesh=`` (with ``axis=``) instead of an explicit ``place``.

    Iterating yields batches with the same structure and handle types as
    the source (NDArray in → NDArray out), already device-resident.
    Stats (cumulative across iterations): ``prefetch_batches``,
    ``input_wait_ms``, ``starvation_count``, ``prefetch_depth``.
    """

    def __init__(self, source, depth: Optional[int] = None,
                 place: Optional[Callable] = None, device=None,
                 mesh=None, axis: str = "dp", timeout: float = 120.0):
        self._source = source
        self._depth = default_prefetch_depth() if depth is None \
            else max(0, int(depth))
        self._timeout = timeout
        if place is None and mesh is not None:
            from ...parallel.mesh import place_on_mesh
            place = lambda d, _m=mesh, _a=axis: place_on_mesh(_m, _a, d)  # noqa: E731
        self._place_leaf = place
        self._device = self._resolve_device(device) if place is None \
            else None
        self.stats = {"prefetch_depth": self._depth,
                      "prefetch_batches": 0, "input_wait_ms": 0.0,
                      "starvation_count": 0}
        # stats is a public dict read while the producer thread runs;
        # every mutation goes through this lock so a reader (monitor
        # thread, test assertion) never sees a torn update
        self._stats_mu = mx_lock("data.prefetch.stats")
        t = _telemetry()
        reg = t.registry()
        self._m_batches = reg.counter(t.names.PREFETCH_BATCHES)
        self._m_starved = reg.counter(t.names.PREFETCH_STARVATION)
        self._m_wait = reg.counter(t.names.PREFETCH_INPUT_WAIT)

    @staticmethod
    def _resolve_device(device):
        if device is None or device is True:
            return None   # process-default placement
        if isinstance(device, jax.Device):
            return device
        jd = getattr(device, "jax_device", None)   # mx.Context
        if jd is not None:
            return jd() if callable(jd) else jd
        raise MXNetError(
            f"device= must be a Context, jax.Device, or None; "
            f"got {type(device).__name__}")

    # ---------------- placement ----------------
    def _put(self, d):
        if self._place_leaf is not None:
            return self._place_leaf(d)
        if self._device is None:
            return jax.device_put(d)
        return jax.device_put(d, self._device)

    def _track(self, staged):
        """File one staged device buffer in the census ``prefetch`` pool
        (weakref — it leaves the pool when the consumer drops the
        batch; the early-break release test counts on this)."""
        try:
            _telemetry().memory.census().register("prefetch", staged)
        except Exception:        # pragma: no cover - census must never
            pass                 # kill the producer thread
        return staged

    def _stage_batch(self, batch, ordinal):
        """One whole batch through :meth:`_stage`, bracketed by the
        chaos-harness ``prefetch.stage`` fault point (one hit per BATCH,
        not per leaf — device_put staging is the third seam a mid-run
        device revocation can land on) and the device-lost detector."""
        from ...testing.faults import fault_point
        fault_point("prefetch.stage", "before")
        try:
            staged = self._stage(batch)
        except BaseException as e:
            from ...elastic import detect as _edet
            _edet.maybe_record_device_lost(e, "prefetch staging",
                                           step=ordinal)
            raise
        fault_point("prefetch.stage", "after")
        return staged

    def _stage(self, batch):
        """Recursively device_put a batch, preserving structure and
        handle types (NDArray stays NDArray). Each staged device buffer
        is tracked in the census ``prefetch`` pool."""
        if isinstance(batch, NDArray):
            return self._track(NDArray(self._put(batch._data)))
        if isinstance(batch, (tuple, list)):
            return type(batch)(self._stage(b) for b in batch)
        if isinstance(batch, dict):
            return {k: self._stage(v) for k, v in batch.items()}
        if isinstance(batch, (onp.ndarray, jax.Array)):
            return self._track(self._put(batch))
        return batch

    # ---------------- telemetry ----------------
    def _fetch(self, it, ordinal):
        """One batch pulled from the source and staged, under the
        ``batch_fetch`` span (producer side). ``ordinal`` is this
        prefetcher's batch number: the 0-based index of the step the
        batch feeds, the closest step attribution the data layer has.
        ``StopIteration`` passes through and leaves no span."""
        with _telemetry().span("batch_fetch", step=ordinal):
            return self._stage_batch(next(it), ordinal)

    def _count_wait(self, seconds):
        """The operator's always-on view of the consumer's wait; the
        ``h2d_wait`` span around the same region is the gated one."""
        with self._stats_mu:
            self.stats["input_wait_ms"] += seconds * 1e3
        self._m_wait.inc(seconds)

    # ---------------- iteration ----------------
    def __iter__(self):
        if self._depth == 0:
            it = iter(self._source)
            n = 0
            while True:
                try:
                    staged = self._fetch(it, n)
                except StopIteration:
                    return
                with self._stats_mu:
                    self.stats["prefetch_batches"] += 1
                self._m_batches.inc()
                n += 1
                yield staged
            return

        q: "queue.Queue" = queue.Queue(maxsize=self._depth)
        register_queue("data.prefetch", q)   # visible in thread dumps
        stop = threading.Event()

        def produce():
            try:
                it = iter(self._source)
                n = 0
                while True:
                    try:
                        staged = self._fetch(it, n)
                    except StopIteration:
                        break
                    n += 1
                    while not stop.is_set():
                        try:
                            q.put(staged, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
                item = _DONE
            except BaseException as e:   # noqa: BLE001 - carried across
                item = _Raised(e)
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return
                except queue.Full:
                    continue

        worker = threading.Thread(target=produce, daemon=True,
                                  name="mx-device-prefetch")
        worker.start()
        try:
            n = 0
            while True:
                if q.empty():
                    with self._stats_mu:
                        self.stats["starvation_count"] += 1
                    self._m_starved.inc()
                t0 = time.perf_counter()
                try:
                    with _telemetry().span("h2d_wait", step=n):
                        item = q.get(timeout=self._timeout)
                except queue.Empty:
                    raise MXNetError(
                        f"DevicePrefetcher produced no batch within "
                        f"timeout={self._timeout}s") from None
                self._count_wait(time.perf_counter() - t0)
                if item is _DONE:
                    return
                if isinstance(item, _Raised):
                    # a device_put that exhausted HBM (or lost its
                    # device) is carried here from the producer thread —
                    # record the post-mortem at the seam the user
                    # actually sees (both records are chain-marked:
                    # exactly one event however many seams re-raise)
                    _telemetry().memory.maybe_record_oom(
                        item.exc, "prefetch staging", step=n)
                    from ...elastic import detect as _edet
                    _edet.maybe_record_device_lost(
                        item.exc, "prefetch staging", step=n)
                    raise item.exc
                with self._stats_mu:
                    self.stats["prefetch_batches"] += 1
                self._m_batches.inc()
                n += 1
                yield item
        finally:
            # deterministic staging release: on early break / error the
            # queue still holds up to `depth` staged device batches —
            # stop the producer, then DROP the queued references so the
            # census `prefetch` pool (and HBM) drains immediately
            # instead of at whenever this generator is collected
            stop.set()
            worker.join(timeout=5.0)
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
