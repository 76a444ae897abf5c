"""Runtime feature introspection (reference: python/mxnet/runtime.py backed
by src/libinfo.cc — ``mx.runtime.feature_list()`` / ``Features``).

Features here describe the TPU build: which backends/subsystems are live in
this process (XLA platform, Pallas, the native C++ host runtime, …).
"""
from __future__ import annotations

import collections
import logging
import os
import threading
import time

__all__ = ["Feature", "Features", "feature_list", "setup_compile_cache",
           "compile_cache_stats", "compile_log"]

_LOG = logging.getLogger("mxnet_tpu.runtime")

# persistent-compilation-cache hit/miss census (setup_compile_cache)
_CACHE_STATS = {"enabled": False, "dir": None, "hits": 0, "misses": 0}

#: jax.monitoring duration event -> the compile log's ``phase``
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}


class _CompileLog:
    """Bounded ring of what JAX compiled, when, and for how long.

    Sized from the chip: a whole run of the benchmark's BERT cell (set-up,
    window and its float32 reference) is 17,403 events, nearly all of
    them sub-millisecond ``trace`` events of the ``jit``-wrapped
    ``jax.numpy`` functions inside the step's own tracing; the LSTM
    cell's is 1,966 (PERF.md section 6, PR 26). A session that outgrows
    the ring loses its OLDEST events and ``dropped`` says how many."""

    def __init__(self, capacity: int = 65536):
        self._events: "collections.deque[tuple]" = collections.deque(
            maxlen=capacity)
        self._dropped = 0
        # bare on purpose: telemetry substrate, held for one append
        self._lock = threading.Lock()  # mx-lint: allow=MXA009

    def append(self, phase: str, fun_name: str, duration: float):
        # the event fires as the timed region closes: its end is now, on
        # the clock the step timeline's spans use
        t1 = time.perf_counter()
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append((phase, fun_name, t1 - duration, t1))

    def read(self) -> dict:
        with self._lock:
            events, dropped = list(self._events), self._dropped
        return {"events": [dict(zip(("phase", "fun_name", "t0", "t1"), e))
                           for e in events], "dropped": dropped}


_COMPILE_LOG = _CompileLog()

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` does not
#: place it: one fixed path in the checkout. The directory is part of
#: the cache key, so a temp name, pid or time here would never hit.
_FIXED_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def setup_compile_cache() -> str:
    """Arm JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps its
    cache there and no code sets another; unset, the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored). Every compiled program —
    ``Trainer.compile_step`` shape buckets, serving/decode AOT warmups,
    ``hybridize()`` traces — is keyed and written there, so a RESTART
    (or a second process with the same shapes) loads the executable
    from disk instead of recompiling. Hits and misses are counted
    (jax.monitoring's ``/jax/compilation_cache/*`` events) and logged at
    compile time; read the totals with :func:`compile_cache_stats`.

    Called once from ``mxnet_tpu/__init__`` — safe to call again
    (idempotent).
    """
    if _CACHE_STATS["enabled"]:
        return _CACHE_STATS["dir"]
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _FIXED_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache EVERYTHING: the default floors (1s compile time / 4KB entry)
    # would skip exactly the many small programs eager-op dispatch and
    # tiny tests pay for repeatedly
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from .telemetry import names as _tnames
    from .telemetry.registry import default as _treg
    _hits = _treg().counter(_tnames.COMPILE_CACHE_HITS)
    _misses = _treg().counter(_tnames.COMPILE_CACHE_MISSES)

    def _on_event(event: str, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            _CACHE_STATS["hits"] += 1
            _hits.inc()
            _LOG.info("compile cache HIT (%d so far) [%s]",
                      _CACHE_STATS["hits"], cache_dir)
        elif event == "/jax/compilation_cache/cache_misses":
            _CACHE_STATS["misses"] += 1
            _misses.inc()
            _LOG.info("compile cache MISS (%d so far) — compiling, "
                      "will persist to %s",
                      _CACHE_STATS["misses"], cache_dir)

    _seconds = _treg().counter(_tnames.COMPILE_SECONDS, label_key="phase")
    _programs = _treg().counter(_tnames.COMPILE_PROGRAMS)

    def _on_duration(event: str, duration: float, **kwargs):
        phase = _COMPILE_PHASES.get(event)
        if phase is None:
            return
        _COMPILE_LOG.append(phase, str(kwargs.get("fun_name", "")),
                            duration)
        _seconds.inc(max(0.0, duration), label=phase)
        if phase == "backend_compile":
            _programs.inc()

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _CACHE_STATS["enabled"] = True
    _CACHE_STATS["dir"] = cache_dir
    _LOG.info("persistent compilation cache armed at %s", cache_dir)
    return cache_dir


def compile_cache_stats() -> dict:
    """{'enabled', 'dir', 'hits', 'misses'} for the persistent
    compilation cache (tools/diagnose.py prints this)."""
    return dict(_CACHE_STATS)


def compile_log() -> dict:
    """``{"events": [...], "dropped": n}``: one entry per phase of every
    program JAX built in this process, oldest first, each
    ``{"phase": "trace" | "lower" | "backend_compile" | "cache_read",
    "fun_name", "t0", "t1"}`` with ``time.perf_counter()`` stamps (the
    step timeline's clock). ``trace`` is Python tracing to a jaxpr (an
    inner ``jit``'s nests inside its caller's), ``lower`` the jaxpr to an
    MLIR module, ``backend_compile`` XLA's compile OR, on a persistent-
    cache hit, the read that replaced it (the ``cache_read`` entry just
    before it, inside its interval; ``fun_name`` empty). The listeners
    fire only when JAX compiles: a warm step adds nothing. Which step
    recompiled: the entry whose time falls inside a ``dispatch`` span of
    ``telemetry.timeline()``, which carries the step number
    (docs/OBSERVABILITY.md "Compile log")."""
    return _COMPILE_LOG.read()


def _cache_collector(reg):
    """Pull-model refresh for the compile-cache gauge at export time
    (telemetry registers this; hits/misses increment live)."""
    from .telemetry import names as _tnames
    reg.gauge(_tnames.COMPILE_CACHE_ENABLED).set(
        1.0 if _CACHE_STATS["enabled"] else 0.0)


try:
    from .telemetry.registry import default as _telemetry_registry
    _telemetry_registry().register_collector(_cache_collector)
except Exception:       # pragma: no cover - telemetry must not block
    pass

Feature = collections.namedtuple("Feature", ["name", "enabled"])


def _detect():
    import jax
    from . import _native
    backend = jax.default_backend()
    feats = {
        "TPU": backend == "tpu",
        "CUDA": False,            # by design: this build targets XLA/TPU
        "CUDNN": False,
        "NCCL": False,            # collectives ride XLA/ICI instead
        "XLA": True,
        "PALLAS": True,
        "BLAS_OPEN": True,        # XLA's CPU backend carries its own BLAS
        "MKLDNN": False,
        "OPENCV": False,
        "NATIVE_ENGINE": _native.available(),
        "RECORDIO": True,
        "DIST_KVSTORE": True,     # jax.distributed-backed
        "F16C": True,             # bf16/fp16 casts via XLA
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": False,
        "DEBUG": False,
        "TVM_OP": False,
    }
    return feats


class Features(collections.abc.Mapping):
    """Mapping of feature name → Feature (reference runtime.py:52)."""

    def __init__(self):
        self._feats = {k: Feature(k, v) for k, v in _detect().items()}

    def __getitem__(self, k):
        return self._feats[k]

    def __iter__(self):
        return iter(self._feats)

    def __len__(self):
        return len(self._feats)

    def is_enabled(self, name: str) -> bool:
        return self._feats[name].enabled

    def __repr__(self):
        on = [k for k, f in self._feats.items() if f.enabled]
        return f"[{', '.join('✔ ' + k for k in on)}]"


def feature_list():
    """List of Feature namedtuples (reference runtime.py:75)."""
    return list(Features().values())
