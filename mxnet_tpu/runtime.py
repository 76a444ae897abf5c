"""Runtime feature introspection (reference: python/mxnet/runtime.py backed
by src/libinfo.cc — ``mx.runtime.feature_list()`` / ``Features``).

Features here describe the TPU build: which backends/subsystems are live in
this process (XLA platform, Pallas, the native C++ host runtime, …).
"""
from __future__ import annotations

import collections
import logging
import os

__all__ = ["Feature", "Features", "feature_list", "setup_compile_cache",
           "compile_cache_stats"]

_LOG = logging.getLogger("mxnet_tpu.runtime")

# persistent-compilation-cache hit/miss census (setup_compile_cache)
_CACHE_STATS = {"enabled": False, "dir": None, "hits": 0, "misses": 0}

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

    jax.monitoring.register_event_listener(_on_event)
    _CACHE_STATS["enabled"] = True
    _CACHE_STATS["dir"] = cache_dir
    _LOG.info("persistent compilation cache armed at %s", cache_dir)
    return cache_dir


def compile_cache_stats() -> dict:
    """{'enabled', 'dir', 'hits', 'misses'} for the persistent
    compilation cache (tools/diagnose.py prints this)."""
    return dict(_CACHE_STATS)


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
