"""Device self time under the ``moe_experts`` scope (the held pairs'
gather, the grouped products, the activation; forward and backward) over
the traced steps (summed over the chips), for each thousand token-expert pairs those steps' own
routers gave the held experts: the cost of a unit of TRUE work, whatever
the routing did that window. None where the trace has no such scope, the
program no record of the pairs, or the steps held none."""
from layer_metrics import _device_counters
import trace_reduce


def read(ctx):
    trace = ctx["trace"]
    found = _device_counters.pairs_a_step(ctx)
    if found is None or not trace or not trace.get("leaf"):
        return None
    seconds = trace_reduce.scope_seconds(trace, "moe_experts")
    pairs = sum(p for p, _ in found[:ctx["traced"]["steps"]])
    if seconds is None or not pairs:
        return None
    return 1e6 * seconds / (pairs / 1e3)
