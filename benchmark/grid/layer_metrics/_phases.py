"""Device self time of the traced steps by the phase scope the program put
around it (``gluon/fused_step.py``: ``loss_and_grad``, ``optimizer_update``,
``grad_reduce``, ``numerics``), for the readers ``fwd_ms.train``,
``bwd_ms.train``, ``update_ms.train`` and ``unphased_device_share``: one
partition, so the four add up to the device's self time.

An event's scope is its instruction's ``op_name`` in the step's optimized
HLO (``trace_reduce.parse_hlo``). A fusion carries ONE, its root's: where
XLA fuses the SGD update into the head's dW matmul (the LSTM cell) that
time reads as backward, not as update. Backward is what autodiff
transposed: some part of the path starts with ``transpose(``. The names
are the yardstick's own; ``tests/benchmark_grid/test_layer_readers.py``
holds them to the program's."""

PHASES = ("loss_and_grad", "optimizer_update", "grad_reduce", "numerics")
FORWARD, BACKWARD, UNPHASED = "forward", "backward", "unphased"


def split(ctx):
    """``{phase or "forward"/"backward"/"unphased": self seconds}`` summed
    over the chips, or None where no event of the trace sits under a phase
    scope (a program without them, or an empty trace)."""
    trace = ctx["trace"]
    if not trace or not trace.get("leaf"):
        return None
    out, phased = {}, False
    for ev, seconds in trace["leaf"]:
        parts = ev.scope.split("/")
        phase = next((p for p in PHASES if p in parts), UNPHASED)
        if phase == "loss_and_grad":
            phase = BACKWARD if any(p.startswith("transpose(")
                                    for p in parts) else FORWARD
        phased = phased or phase != UNPHASED
        out[phase] = out.get(phase, 0.0) + seconds
    return out if phased else None


def ms_per_step(ctx, phase):
    """Milliseconds of ``phase`` per traced step and chip; None where the
    trace has no phase scopes or nothing ran under this one."""
    seconds = (split(ctx) or {}).get(phase)
    steps = ctx["traced"]["steps"] * ctx["chips"]
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
