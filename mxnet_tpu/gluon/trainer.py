"""Gluon Trainer: optimizer application + data-parallel gradient reduction.

Reference analog: python/mxnet/gluon/trainer.py (_init_kvstore :188 decision
matrix, step :334 = allreduce + update, update :411). The TPU-native
difference is in what "allreduce" means: with one logical array per Parameter
(possibly mesh-sharded), reduction over devices is either a no-op (replicated
arrays under pjit get psum'ed by XLA inside the step) or a kvstore pushpull
for reference-style per-device replica lists.
"""
from __future__ import annotations

import weakref
from typing import Dict, List, Optional

from .. import optimizer as opt_mod
from ..base import MXNetError
from ..kvstore import kvstore as kvs_mod
from .parameter import Parameter

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, dict):
            param_items = sorted(params.items())
            self._params = [p for _, p in param_items]
            self._param_names = [k for k, _ in param_items]
        elif isinstance(params, (list, tuple)):
            self._params = list(params)
            self._param_names = [p.name for p in params]
        else:
            raise MXNetError("params must be a dict or list of Parameters")
        # full set incl. grad_req='null' (running stats): the fused whole-
        # step program (compile_step) must bind these as traced state too
        self._all_params = list(self._params)
        self._params = [p for p in self._params if p.grad_req != "null"]
        self._param2idx = {id(p): i for i, p in enumerate(self._params)}

        optimizer_params = optimizer_params or {}
        self._optimizer = opt_mod.create(optimizer, **optimizer_params)
        self._optimizer.param_dict = dict(enumerate(self._params))
        self._updater = opt_mod.get_updater(self._optimizer)

        self._kvstore_kind = kvstore
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._compression_params = compression_params
        self._kv_initialized = False
        self._scale = 1.0
        self._contains_sparse = False
        # live CompiledTrainStep programs built from this trainer: the
        # checkpoint stack asks them whether a ZeRO plan owns the
        # optimizer state (weakrefs — a dropped step must not leak)
        self._compiled_refs: List[weakref.ref] = []
        # fp32 masters restored from a checkpoint, consumed when the
        # next _ZeroShardPlan materializes (checkpoint/state.py)
        self._restored_masters: Dict[int, object] = {}

    # ---------------- properties ----------------
    @property
    def learning_rate(self) -> float:
        return self._optimizer.learning_rate

    @learning_rate.setter
    def learning_rate(self, lr):
        self._optimizer.learning_rate = lr

    def set_learning_rate(self, lr):
        self._optimizer.learning_rate = lr

    @property
    def optimizer(self):
        return self._optimizer

    # ---------------- fused whole-step compilation ----------------
    def compile_step(self, loss_fn, donate: bool = True,
                     train_mode: bool = True,
                     zero_shard: Optional[bool] = None,
                     zero_axis: str = "dp", mesh=None,
                     analyze: Optional[str] = None,
                     numerics: Optional[str] = None):
        """Compile the ENTIRE training step — forward, backward, gradient
        reduction, optimizer update — into one donated-buffer XLA program
        per input-shape bucket (gluon/fused_step.py)::

            step = trainer.compile_step(lambda x, y: loss_blk(net(x), y))
            for x, y in batches:
                loss = step(x, y)          # == record/backward/step(bs)

        The returned loss is an ASYNC NDArray — the call dispatches and
        returns while the device works; reading it (``float``,
        ``asnumpy``) is the sync point. Pair with ``gluon.TrainLoop``
        for the bounded in-flight dispatch window
        (``MXNET_INFLIGHT_STEPS``) and device input prefetch
        (``loop.prefetch`` / ``DataLoader(device=...)``) that keep the
        host a fixed number of steps ahead of the chip
        (docs/PERF_NOTES.md "async engine").

        Gradient semantics match ``loss.backward()`` (seed ones) followed
        by ``trainer.step(batch_size)`` with ``batch_size`` inferred from
        the leading batch axis (override per call:
        ``step(x, y, batch_size=n)``). lr/wd/update-count/rescale are
        traced arguments — mutating ``trainer.learning_rate`` or varying
        the batch size never recompiles. Sparse-grad parameters,
        ``update_on_kvstore`` stores, and non-traceable forwards fall
        back transparently to the eager tape path.

        **ZeRO-1 sharded update** (arXiv:2004.13336): when a
        ``parallel.DeviceMesh`` with a ``zero_axis`` ('dp') axis of size
        N >= 2 is active — or passed via ``mesh=`` — the redundant
        replicated weight update is cross-replica sharded: gradients
        reduce-scatter, each replica updates its 1/N flat shard against
        permanently-NamedSharding-sharded optimizer state (momenta, Adam
        moments, fp32 masters of multi-precision params), and the new
        weights all-gather back. Per-replica optimizer-state memory
        drops ~N×. ``zero_shard``: None = auto-detect, True = require
        (raises if no mesh), False = keep the plain in-program psum.
        Parameters below ``MXNET_ZERO_SHARD_MIN_SIZE`` elements bucket
        into one fused shard per dtype (docs/PERF_NOTES.md).

        **Program analysis** (``analyze=`` — docs/ANALYSIS.md): after
        the first step, run the ``mx.analysis`` program lint over the
        compiled program (collective census, donation audit, host
        transfers, dtype drift).  ``'report'`` stores the ProgramReport
        on ``step.analysis_report``, ``'warn'`` also logs findings,
        ``'raise'`` raises on error-severity findings.  Default comes
        from ``MXNET_ANALYSIS``.

        **Numerics observability** (``numerics=`` — docs/OBSERVABILITY
        .md "numerics"): ``'global'`` threads global grad/param norms,
        the update/weight ratio, and per-dtype non-finite counts
        through the compiled program as auxiliary outputs (bit-exact on
        params/loss, psum-composed under ZeRO so shards report true
        global norms); ``'per_layer'`` adds a per-parameter norm vector
        (costlier — see the docs note). The statistics retire sync-free
        through the TrainLoop's dispatch window, feed the
        ``mx_numerics_*`` series and the divergence watchdog
        (grad_spike / nonfinite_grad / update_ratio / master_drift
        anomalies), and a non-finite gradient triggers NaN-origin
        forensics plus an atomic post-mortem dump
        (``MXNET_NUMERICS_DUMP_DIR``). Default comes from
        ``MXNET_NUMERICS``.
        """
        from .fused_step import CompiledTrainStep
        return CompiledTrainStep(self, loss_fn, donate=donate,
                                 train_mode=train_mode,
                                 zero_shard=zero_shard,
                                 zero_axis=zero_axis, mesh=mesh,
                                 analyze=analyze, numerics=numerics)

    # ---------------- compiled-step registry ----------------
    def _register_compiled(self, step):
        self._compiled_refs.append(weakref.ref(step))

    def _live_compiled_steps(self):
        alive, out = [], []
        for ref in self._compiled_refs:
            s = ref()
            if s is not None:
                alive.append(ref)
                out.append(s)
        self._compiled_refs = alive
        return out

    def _zero_state_owner(self):
        """The CompiledTrainStep whose ZeRO plan owns (or will own) the
        sharded optimizer state, if any."""
        for s in self._live_compiled_steps():
            if getattr(s, "_zero", None) is not None or \
                    getattr(s, "_zero_ok", None) is not None:
                return s
        return None

    # ---------------- kvstore setup (reference trainer.py:188) -------------
    def _init_kvstore(self):
        if self._kvstore_kind is None:
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            self._kvstore = kvs_mod.create(self._kvstore_kind) \
                if isinstance(self._kvstore_kind, str) else self._kvstore_kind
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            if self._update_on_kvstore is None:
                import os
                env = os.environ.get("MXNET_UPDATE_ON_KVSTORE")
                if env is not None:
                    # reference trainer.py honors this override in its
                    # decision matrix (env_var.md MXNET_UPDATE_ON_KVSTORE)
                    self._update_on_kvstore = \
                        env.lower() not in ("0", "false", "no", "")
                else:
                    # single-worker: updating locally is cheaper; dist sync
                    # stores traditionally update on store
                    self._update_on_kvstore = \
                        self._kvstore.num_workers > 1 and \
                        "dist" in getattr(self._kvstore, "type", "")
            if self._update_on_kvstore:
                self._kvstore.set_optimizer(self._optimizer)
            # seed store with current weights
            for i, p in enumerate(self._params):
                self._kvstore.init(i, p.data())
        self._kv_initialized = True

    # ---------------- core ----------------
    def step(self, batch_size: int, ignore_stale_grad: bool = False):
        """allreduce gradients then apply optimizer
        (reference trainer.py:334)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        # the store's one-host-sync-per-step IS the design here — bless
        # it for the transfer guard so MXNET_TRANSFER_GUARD only flags
        # UNexpected syncs (analysis/guard.py)
        from ..analysis.guard import allow_transfers
        with allow_transfers("kvstore gradient reduction"):
            if not self._update_on_kvstore:
                # one fused multi-key call: a dist store packs the
                # collectives into buckets and pays ONE host sync per
                # step instead of one per parameter (pushpull_list)
                keys = list(range(len(self._params)))
                self._kvstore.pushpull_list(
                    keys, [p.list_grad() for p in self._params])
                return
            for i, p in enumerate(self._params):
                self._kvstore.push(i, p.list_grad())

    def update(self, batch_size: int, ignore_stale_grad: bool = False):
        """Apply optimizer only (grads assumed reduced;
        reference trainer.py:411)."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        # batch all live params into ONE fused updater call (the
        # reference's multi-tensor update, optimizer_op.cc multi_sgd_*)
        idxs, grads, datas = [], [], []
        for i, p in enumerate(self._params):
            if self._update_on_kvstore:
                # store ran the optimizer during push; pull fresh weights
                self._kvstore.pull(i, p.list_data())
                continue
            data = p.data()
            if p.grad_req != "null" and data.grad is not None \
                    and not data.fresh_grad:
                if not ignore_stale_grad:
                    raise MXNetError(
                        f"gradient of parameter {p.name} has not been "
                        "updated by backward since the last step; set "
                        "ignore_stale_grad=True to suppress")
                # reference trainer.py skips stale params entirely rather
                # than re-applying the old gradient
                continue
            idxs.append(i)
            grads.append(p.grad())
            datas.append(data)
        if not idxs:
            return
        if len(idxs) == len(self._params):  # _params already excludes null
            self._updater(idxs, grads, datas)   # fused: one XLA dispatch
        else:
            # stale/partial subset: per-param path — a fused program keyed
            # on this exact subset would recompile per distinct subset
            for i, g, d in zip(idxs, grads, datas):
                self._updater(i, g, d)
        for d in datas:
            d.fresh_grad = False

    # ---------------- persistence (reference trainer.py:477,506) -----------
    def train_state(self, step: int = 0, net=None, extra=None):
        """Snapshot the COMPLETE training state — params, optimizer state
        (including fused and ZeRO-sharded buffers that live inside a
        ``compile_step`` program), update counters, lr-scheduler state,
        RNG key — as a ``mx.checkpoint.TrainState`` of host arrays. Pair
        with ``mx.checkpoint.write_checkpoint``/``TrainCheckpointManager``
        for atomic on-disk persistence."""
        from ..checkpoint.state import capture_train_state
        return capture_train_state(trainer=self, net=net, step=step,
                                   extra=extra)

    def load_train_state(self, state, net=None, strict: bool = True):
        """Restore a ``TrainState`` (inverse of :meth:`train_state`);
        returns its meta dict (incl. ``'step'``)."""
        from ..checkpoint.state import apply_train_state
        return apply_train_state(state, trainer=self, net=net,
                                 strict=strict)

    def save_states(self, fname: str):
        """Reference single-file optimizer-state dump. The write is
        crash-safe (staged + fsync + ``os.replace``) but the FORMAT only
        covers the eager updater: when a ZeRO-sharded ``compile_step``
        owns NamedSharding-sharded moments/masters this raises instead
        of silently writing stale state — use :meth:`train_state` /
        ``mx.checkpoint.TrainCheckpointManager`` there."""
        owner = self._zero_state_owner()
        if owner is not None:
            raise MXNetError(
                "Trainer.save_states cannot serialize the ZeRO-sharded "
                "optimizer state owned by a compile_step program (the "
                "eager updater it pickles no longer holds the live "
                "momenta/moments/fp32 masters). Use trainer.train_state()"
                " with mx.checkpoint.write_checkpoint, or "
                "mx.checkpoint.TrainCheckpointManager / "
                "gluon.TrainLoop(checkpoint_dir=...).")
        from ..checkpoint.atomic import atomic_write_bytes
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            atomic_write_bytes(
                fname, self._updater.get_states(dump_optimizer=True),
                fault="trainer.save_states")

    def load_states(self, fname: str):
        """Reads both the single-file updater pickle (reference format,
        still what :meth:`save_states` writes) and — shim for the new
        world — an atomic checkpoint directory produced by
        ``mx.checkpoint`` (its optimizer state + counters are applied)."""
        import os
        if not self._kv_initialized:
            self._init_kvstore()
        if os.path.isdir(fname):
            from ..checkpoint.atomic import read_checkpoint
            from ..checkpoint.state import TrainState, apply_train_state
            arrays, manifest = read_checkpoint(fname)
            state = TrainState(arrays, manifest.get("meta", {}),
                               array_meta=manifest["arrays"])
            apply_train_state(state, trainer=self, strict=False)
            return
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())
