"""Device contexts: ``mx.cpu()``, ``mx.tpu(i)`` (and ``mx.gpu`` as an alias).

TPU-native re-design of the reference's Context (reference:
python/mxnet/context.py, include/mxnet/base.h Context struct). A Context names
a logical device; it resolves lazily to a ``jax.Device``. ``mx.tpu(i)`` is the
first-class accelerator context per the north star; ``mx.gpu(i)`` is kept as a
compatibility alias so reference user code runs unchanged.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = [
    "Context", "cpu", "tpu", "gpu", "cpu_pinned", "current_context",
    "num_tpus", "num_gpus", "device", "gpu_memory_info",
]

_DEVTYPE_CPU = 1
_DEVTYPE_TPU = 2  # occupies the accelerator slot the reference gives to kGPU
_DEVTYPE_CPU_PINNED = 3

_DEVTYPE_NAMES = {_DEVTYPE_CPU: "cpu", _DEVTYPE_TPU: "tpu",
                  _DEVTYPE_CPU_PINNED: "cpu_pinned"}


def _accelerator_devices():
    """Non-CPU jax devices addressable by THIS process, else local CPU
    devices (CPU-only test rigs). Local, not global: under jax.distributed a
    Context can only place data on this worker's own chips — the reference's
    ctx is likewise per-process (each worker addresses its own GPUs)."""
    devs = [d for d in jax.local_devices() if d.platform != "cpu"]
    return devs if devs else jax.local_devices()


class Context:
    """A logical device. Compares by (device_type, device_id) like the
    reference Context; ``ctx.jax_device`` resolves to the backing jax device.
    """

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type in ("gpu",):  # compat alias
            device_type = "tpu"
        if device_type not in ("cpu", "tpu", "cpu_pinned"):
            raise MXNetError(f"unknown device type {device_type!r}")
        if device_type == "tpu" and device_id != 0:
            # eager bounds check: a dangling tpu(i) would otherwise fail
            # far from its construction site (reference Context is lazy,
            # but its CUDA calls fail fast at first use on a bad ordinal)
            n = len(_accelerator_devices())
            if device_id >= n:
                raise MXNetError(
                    f"tpu({device_id}) requested but only {n} accelerator "
                    "device(s) present")
        self.device_type = device_type
        self.device_id = device_id
        self._old_ctx: Optional["Context"] = None

    @property
    def device_typeid(self) -> int:
        return {"cpu": _DEVTYPE_CPU, "tpu": _DEVTYPE_TPU,
                "cpu_pinned": _DEVTYPE_CPU_PINNED}[self.device_type]

    @property
    def jax_device(self) -> jax.Device:
        if self.device_type in ("cpu", "cpu_pinned"):
            cpus = [d for d in jax.local_devices() if d.platform == "cpu"]
            if not cpus:
                # A TPU-only runtime lists no CPU device, and mx.cpu() is
                # the DEFAULT context: every array made without a ctx
                # goes to local chip 0, on a four-chip host too, and
                # COMMITTED there (``jax.default_device`` does not move
                # it). The dp path expects exactly that — compile_step
                # re-places params and batch on the mesh with their
                # NamedSharding, wherever they started. The fleet does
                # not: see FleetController._pinned_build.
                cpus = jax.local_devices()
            return cpus[min(self.device_id, len(cpus) - 1)]
        devs = _accelerator_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                f"tpu({self.device_id}) requested but only {len(devs)} "
                f"accelerator device(s) present")
        return devs[self.device_id]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    def __enter__(self):
        self._old_ctx = current_context()
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx

    # reference parity: Context.empty_cache frees the memory pool
    def empty_cache(self):
        """Release cached device memory (reference: context.py empty_cache).

        XLA/PjRt owns the allocator; this is a best-effort hint.
        """
        import gc
        gc.collect()


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "value") or Context._default_ctx.value is None:
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compatibility alias: reference code using mx.gpu(i) lands on tpu(i)."""
    return Context("tpu", device_id)


def device(dev_type: str, device_id: int = 0) -> Context:
    return Context(dev_type, device_id)


def num_tpus() -> int:
    devs = [d for d in jax.local_devices() if d.platform != "cpu"]
    return len(devs)


def num_gpus() -> int:  # compat alias used by reference scripts
    return num_tpus()


def gpu_memory_info(device_id=0):
    """CUDA memory query (reference context.py:249) — no analog on TPU
    builds; raises with the TPU-native alternative."""
    from .base import MXNetError
    raise MXNetError(
        "gpu_memory_info is CUDA-specific; use "
        "mx.profiler.memory_summary() for accelerator memory here")
