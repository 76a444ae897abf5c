"""Checkpoint lifecycle: retention, async writes, auto-resume.

``TrainCheckpointManager`` drives the atomic format (atomic.py) with the
policy a long training run needs:

- ``save(step, trainer, net)`` captures device state synchronously (one
  device->host copy per buffer — the only part that must pause
  training) and hands serialization + fsync + commit to a background
  thread, overlapped with the next training steps;
- a failed background write surfaces on the NEXT ``save``/``wait`` —
  never silently;
- after each commit the newest ``keep_last`` checkpoints are kept and
  older ones pruned (prune runs strictly after publish, so a crash
  mid-prune can never reduce the directory below its newest valid
  checkpoint);
- ``restore_latest`` loads the newest checkpoint that VALIDATES
  (corrupt/truncated ones are skipped with a warning) and applies it;
- under multi-host ``parallel.dist`` each process stages into its own
  ``host-<rank>/`` subtree (one atomic commit per host, no cross-host
  write races); restore merges every host's segment files.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, Optional

from ..analysis.threads import mx_lock
from ..base import MXNetError
from . import atomic
from .state import TrainState, apply_train_state, capture_train_state

__all__ = ["TrainCheckpointManager"]

_LOG = logging.getLogger("mxnet_tpu.checkpoint")

_TELEM = None


def _telemetry():
    global _TELEM
    if _TELEM is None:
        from .. import telemetry as _t
        _TELEM = _t
    return _TELEM


def _dist_rank_size():
    try:
        from ..parallel import dist
        return dist.rank(), dist.size()
    except Exception:        # pragma: no cover - parallel not importable
        return 0, 1


class TrainCheckpointManager:
    """Step-indexed atomic train-state checkpoints with retention.

    ::

        mgr = mx.checkpoint.TrainCheckpointManager(dir, keep_last=3)
        ...
        mgr.save(step, trainer=trainer, net=net)     # async by default
        ...
        meta = mgr.restore_latest(trainer=trainer, net=net)
        start = meta["step"] if meta else 0

    ``gluon.TrainLoop(checkpoint_dir=...)`` wraps exactly this.
    """

    def __init__(self, directory: str, keep_last: int = 3,
                 async_save: bool = True):
        if keep_last < 1:
            raise MXNetError(f"keep_last must be >= 1, got {keep_last}")
        self._base = os.path.abspath(directory)
        rank, size = _dist_rank_size()
        self._rank, self._size = rank, size
        self._root = self._base if size == 1 else \
            os.path.join(self._base, f"host-{rank}")
        self._keep_last = keep_last
        self._async = async_save
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        # guards the writer handoff (_thread/_error) between save(),
        # wait() and the background writer; the join itself runs
        # outside it so waiters never block each other behind slow I/O
        self._mu = mx_lock("checkpoint.manager")
        self._last_saved: Optional[int] = None
        t = _telemetry()
        reg = t.registry()
        self._m_saves = reg.counter(t.names.CHECKPOINT_SAVES)
        self._m_errors = reg.counter(t.names.CHECKPOINT_ERRORS)
        self._m_capture = reg.histogram(t.names.CHECKPOINT_CAPTURE_SECONDS)
        self._m_write = reg.histogram(t.names.CHECKPOINT_SAVE_SECONDS)
        self._m_restores = reg.counter(t.names.CHECKPOINT_RESTORES)
        self._m_recovery = reg.histogram(
            t.names.CHECKPOINT_RECOVERY_SECONDS)
        self._last_restore: Optional[Dict[str, Any]] = None

    @property
    def directory(self) -> str:
        return self._base

    # ---------------- save ----------------
    def save(self, step: int, trainer=None, net=None,
             extra: Optional[Dict[str, Any]] = None,
             block: Optional[bool] = None) -> TrainState:
        """Capture (synchronously) and persist (async unless
        ``block=True``/``async_save=False``) the full train state."""
        self.wait()   # one write in flight; surfaces any prior failure
        t0 = time.perf_counter()
        state = capture_train_state(trainer=trainer, net=net, step=step,
                                    extra=extra)
        self._m_capture.observe(time.perf_counter() - t0)
        if self._last_restore is not None:
            # restore provenance rides every subsequent save: a
            # post-mortem on this checkpoint can tell WHERE the run it
            # belongs to came from (elastic reshard forensics)
            state.meta.setdefault("resumed_from", {
                k: self._last_restore[k]
                for k in ("step", "resumed_from", "dp_from", "dp_to")})
        try:
            # the capture copies live until the background write drops
            # them — visible in the census `checkpoint` pool meanwhile
            _telemetry().memory.census().register("checkpoint", state)
        except Exception:        # pragma: no cover - census must never
            pass                 # block a save
        sync = not self._async if block is None else block
        if sync:
            self._write(state)
        else:
            t = threading.Thread(
                target=self._write_guarded, args=(state,),
                name=f"ckpt-write-step{step}", daemon=True)
            with self._mu:
                self._thread = t
            t.start()
        return state

    def save_state(self, state: TrainState):
        """Persist an already-captured TrainState synchronously."""
        self.wait()
        self._write(state)

    def _write_guarded(self, state: TrainState):
        try:
            self._write(state)
        except BaseException as e:   # propagate via wait()/next save()
            _LOG.error("async checkpoint write for step %d failed: %s",
                       state.step, e)
            self._m_errors.inc()
            with self._mu:
                self._error = e

    def _write(self, state: TrainState):
        t0 = time.perf_counter()
        # runs on the background writer thread for async saves (the span
        # lands on that thread's profiler line); the timeline ring +
        # histogram are thread-safe
        with _telemetry().span("checkpoint", step=state.step):
            atomic.write_checkpoint(self._root, state.step, state.arrays,
                                    array_meta=state.array_meta,
                                    meta=state.meta)
            self._last_saved = state.step
            atomic.prune_checkpoints(self._root, self._keep_last)
        self._m_write.observe(time.perf_counter() - t0)
        self._m_saves.inc()

    def wait(self):
        """Block until the in-flight write finishes; re-raise its error."""
        with self._mu:
            t, self._thread = self._thread, None
        if t is not None:
            t.join()        # outside the lock: never join while holding it
        with self._mu:
            err, self._error = self._error, None
        if err is not None:
            raise MXNetError(
                f"background checkpoint write failed: {err}") from err

    # ---------------- query ----------------
    def latest_step(self) -> Optional[int]:
        found = atomic.latest_valid(self._root)
        return found[0] if found else None

    def has_checkpoint(self) -> bool:
        return self.latest_step() is not None

    def latest_path(self) -> Optional[str]:
        """Directory of the newest VALID checkpoint, or None — the
        path a serving-side weight rollout loads
        (:meth:`~mxnet_tpu.serving.FleetController.swap_weights`
        accepts it directly; corrupt candidates are already skipped
        here, and the fleet re-validates before any replica drains)."""
        found = atomic.latest_valid(self._root)
        return found[1] if found else None

    @property
    def last_saved_step(self) -> Optional[int]:
        return self._last_saved

    # ---------------- restore ----------------
    def _load_merged(self):
        """Newest step valid on every host (merging per-host segment
        files); single-host: newest valid step."""
        if self._size == 1:
            return atomic.load_latest(self._root)
        # pragma: no cover start - exercised only on multi-host rigs
        hosts = [d for d in sorted(os.listdir(self._base))
                 if d.startswith("host-") and
                 os.path.isdir(os.path.join(self._base, d))]
        valid: Dict[int, list] = {}
        for h in hosts:
            sub = os.path.join(self._base, h)
            for s in atomic.list_checkpoints(sub):
                valid.setdefault(s, []).append(sub)
        for s in sorted(valid, reverse=True):
            if len(valid[s]) != len(hosts):
                continue
            arrays: Dict[str, Any] = {}
            manifest = None
            try:
                for sub in valid[s]:
                    a, m = atomic.read_checkpoint(
                        os.path.join(sub, atomic.step_dir_name(s)))
                    arrays.update(a)
                    manifest = m
                return s, arrays, manifest
            except atomic.CheckpointCorruptError as e:
                _LOG.warning("skipping corrupt multi-host step %d: %s",
                             s, e)
        return None
        # pragma: no cover end

    def restore_latest(self, trainer=None, net=None,
                       strict: bool = True) -> Optional[Dict[str, Any]]:
        """Apply the newest valid checkpoint; returns its meta (incl.
        'step'), or None when the directory holds no valid checkpoint."""
        self.wait()
        t0 = time.perf_counter()
        found = self._load_merged()
        if found is None:
            return None
        return self._apply_found(found, trainer, net, strict, t0)

    def restore_step(self, step: int, trainer=None, net=None,
                     strict: bool = True) -> Dict[str, Any]:
        """Apply ONE SPECIFIC retained checkpoint step (raises if it is
        missing or corrupt) — the elastic reference-replay / planned
        rollback path, where "newest" is not the state you want."""
        self.wait()
        t0 = time.perf_counter()
        path = os.path.join(self._root, atomic.step_dir_name(step))
        arrays, manifest = atomic.read_checkpoint(path)
        return self._apply_found((step, arrays, manifest), trainer, net,
                                 strict, t0)

    def _apply_found(self, found, trainer, net, strict, t0):
        """Shared restore tail: apply + restore metrics + provenance."""
        step, arrays, manifest = found
        array_meta = {k: v for k, v in manifest["arrays"].items()}
        state = TrainState(arrays, manifest.get("meta", {}),
                           array_meta=array_meta)
        meta = apply_train_state(state, trainer=trainer, net=net,
                                 strict=strict)
        _LOG.info("restored checkpoint step %d from %s", step, self._root)
        meta = dict(meta)
        meta.setdefault("step", step)
        dt = time.perf_counter() - t0
        self._m_restores.inc()
        self._m_recovery.observe(dt)
        dp_from = meta.get("dp_size")
        dp_to = self._current_dp()
        self._last_restore = {
            "step": int(step),
            "resumed_from": os.path.join(self._root,
                                         atomic.step_dir_name(step)),
            "dp_from": dp_from, "dp_to": dp_to,
            "reshard": (f"dp{dp_from}->dp{dp_to}"
                        if dp_from and dp_from != dp_to else None),
            "duration_s": dt, "time_unix": time.time()}
        if self._last_restore["reshard"]:
            _LOG.info("restore reshards %s",
                      self._last_restore["reshard"])
        return meta

    @staticmethod
    def _current_dp() -> Optional[int]:
        try:
            from ..parallel.mesh import current_mesh
            m = current_mesh()
            return int(m.shape.get("dp", 1)) if m is not None else 1
        except Exception:        # pragma: no cover - defensive
            return None

    @property
    def restore_provenance(self) -> Optional[Dict[str, Any]]:
        """Where the current run's state came from: ``{step,
        resumed_from, dp_from, dp_to, reshard, duration_s, time_unix}``
        of the most recent restore through this manager (None before
        any restore). ``reshard`` names a dp=N→dp=M layout change, the
        elastic shrink/grow signature."""
        return self._last_restore
