"""Device counters: what the device computed about a step's own work.

A number a compiled train step's ops compute on the way (the pairs a
router gave the held experts) dies in the program unless it is an output.
An op says ``emit(name, value)`` while the step's loss function is traced;
``CompiledTrainStep`` opens ``collect()`` around that trace and returns
what was written, ``stacked`` by name in trace order, with the program's
outputs. The arrays ride the dispatch window in the step's ``StepAux`` and
are read at the blessed retire, by ``observe``, only while telemetry is
active (docs/OBSERVABILITY.md "Device counters").

A value written under a transformation the collector cannot see out of
(an inner ``jax.checkpoint``, ``lax.scan``, ``custom_vjp`` rule or ``jit``)
is dropped and counted, never leaked and never raised. With no collector
open (eager calls, the tape, ``hybridize()`` alone) ``emit`` does nothing.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, NamedTuple, Optional

import jax.numpy as jnp
import numpy as onp
from jax.core import get_opaque_trace_state

from ..base import MXNetError
from . import names
from .registry import default as _registry

__all__ = ["StepAux", "collect", "emit", "stacked", "observe"]
_OPEN = threading.local()


class StepAux(NamedTuple):
    """What one step hands the dispatch window beside its loss."""
    numerics: Any = None              # a telemetry.StepNumerics
    counters: Optional[dict] = None   # {name: device array}


def _dropped(name: str, n: int = 1):
    _registry().counter(names.DEVICE_COUNTER_DROPPED,
                        label_key="name").inc(n, label=name)


@contextlib.contextmanager
def collect():
    """A collector on this thread for as long as the step's loss function
    is traced; yields ``{name: [traced arrays, trace order]}``."""
    prev, emitted = getattr(_OPEN, "collector", None), {}
    _OPEN.collector = (get_opaque_trace_state(convention="flax"), emitted)
    try:
        yield emitted
    finally:
        _OPEN.collector = prev


def emit(name: str, value) -> None:
    """One more traced array under ``name`` (of ``names.DEVICE_COUNTERS``)."""
    if name not in names.DEVICE_COUNTERS:
        raise MXNetError(f"device counter {name!r} is not declared in "
                         "telemetry/names.py DEVICE_COUNTERS")
    collector = getattr(_OPEN, "collector", None)
    if collector is None:
        return
    opened_in, emitted = collector
    if get_opaque_trace_state(convention="flax") != opened_in:
        _dropped(name)
        return
    emitted.setdefault(name, []).append(getattr(value, "_data", value))


def stacked(emitted: dict) -> dict:
    """``{name: array[sites, ...]}``; sites of one name whose shapes differ
    cannot stack and are dropped and counted."""
    out = {}
    for name, values in emitted.items():
        if len({(v.shape, v.dtype) for v in values}) == 1:
            out[name] = jnp.stack(values)
        else:
            _dropped(name, len(values))
    return out


def observe(aux: Optional[StepAux]) -> dict:
    """A retired step's counters as lists, for its ``window`` span (``{}``
    if it emitted none): inside the blessed retire, one small read a name."""
    if aux is None or not aux.counters:
        return {}
    host = {name: onp.asarray(v).tolist() for name, v in aux.counters.items()}
    reg = _registry()
    pairs = reg.gauge(names.MOE_HELD_PAIRS, label_key="layer")
    ratio = reg.gauge(names.MOE_EXPERT_LOAD_MAX_RATIO, label_key="layer")
    for layer, row in enumerate(host.get(names.COUNTER_MOE_HELD_PAIRS, ())):
        total = sum(row)
        pairs.set(total, label=str(layer))
        ratio.set(max(row) * len(row) / total if total else 0.0,
                  label=str(layer))
    return host
