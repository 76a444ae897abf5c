"""The one line on standard output, right by construction.

``capture()`` is the first thing ``run.py`` does: it duplicates file
descriptor 1, then points 1 (and ``sys.stdout``) at descriptor 2. From
then on nothing the profiler, libtpu, a logger, a warning, an ``atexit``
hook or a stray ``os.write(1, ...)`` prints can reach the real standard
output. ``emit()`` is the last act of a run: it validates the result
against the cell's entry in ``BENCHMARK.json`` and writes exactly one
line to the saved descriptor, or raises ``Refused`` and writes nothing.

The shape of the line is the driver's contract:

    {"correct", "attempted", "failed", "metrics": {name: {"value",
     "unit"}}, "device": {"platform", "kind", "count",
     "memory_peak_bytes"[, "busy_s", "window_s"]}[, "breakdown"],
     "compared": {name: {"value", "limit"}}}

``--trace 0`` carries the cell's ``end_to_end`` metrics, ``--trace 1`` its
``per_layer`` metrics (those whose reader found something to read), and
``compared`` comes last: every number the correctness check compared,
beside its limit.
"""
import json
import math
import os
import sys

_SAVED_FD = None


class Refused(Exception):
    """The result does not meet the contract; no line is printed."""


def capture() -> None:
    """Save descriptor 1 and point it at descriptor 2 (idempotent)."""
    global _SAVED_FD
    if _SAVED_FD is not None:
        return
    sys.stdout.flush()
    _SAVED_FD = os.dup(1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr


def cell_metrics(manifest: dict, workload: str, trace: bool) -> dict:
    """{name: unit} of the metrics the cell reports in this mode: its
    ``end_to_end`` metrics untraced, its ``per_layer`` metrics traced."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return {m["name"]: m["unit"] for m in group
            if "workloads" not in m or workload in m["workloads"]}


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def build(manifest: dict, workload: str, trace: bool, *, correct: bool,
          attempted: int, failed: int, values: dict, device: dict,
          compared: dict, breakdown: dict = None) -> dict:
    """The result object, validated. ``values`` maps metric name to a
    number; a per-layer metric whose reader found nothing is absent."""
    units = cell_metrics(manifest, workload, trace)
    if not units:
        raise Refused(f"{workload}: no metric for trace={int(trace)}")
    metrics = {}
    for name, unit in units.items():
        if name not in values:
            if trace:
                continue        # a reader that found nothing to read
            raise Refused(f"metric {name} missing")
        if not _finite(values[name]):
            raise Refused(f"metric {name} is {values[name]!r}")
        metrics[name] = {"value": values[name], "unit": unit}
    if not metrics:
        raise Refused("no metric was read")
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if device.get(key) in (None, ""):
            raise Refused(f"device.{key} missing")
    if not _finite(device["memory_peak_bytes"]) \
            or device["memory_peak_bytes"] <= 0:
        raise Refused("device.memory_peak_bytes is "
                      f"{device['memory_peak_bytes']!r}")
    if trace:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not (_finite(busy) and _finite(window)):
            raise Refused(f"busy_s={busy!r} window_s={window!r}")
        if not 0 < busy <= window:
            raise Refused(f"busy_s={busy} outside (0, window_s={window}]")
    if not (isinstance(attempted, int) and isinstance(failed, int)
            and 0 <= failed <= attempted and attempted > 0):
        raise Refused(f"attempted={attempted!r} failed={failed!r}")
    for name, pair in compared.items():
        if not (_finite(pair.get("value")) and _finite(pair.get("limit"))):
            raise Refused(f"compared {name} is {pair!r}")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace and breakdown:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def emit(result: dict) -> None:
    """Write the one line to the real standard output."""
    if _SAVED_FD is None:
        raise Refused("capture() was never called")
    for stream in (sys.stdout, sys.stderr):
        stream.flush()
    data = (json.dumps(result, allow_nan=False) + "\n").encode()
    while data:
        data = data[os.write(_SAVED_FD, data):]
