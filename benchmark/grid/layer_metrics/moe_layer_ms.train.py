"""Device self time a step and chip of the expert layers' feed-forward,
forward and backward: the router (``moe_route``), the held experts'
gather and grouped products (``moe_experts``), the weighted sum back
(``moe_combine``) and the shared expert every token passes
(``shared_expert``). None where the trace has none of these scopes."""
import trace_reduce

SCOPES = ("moe_route", "moe_experts", "moe_combine", "shared_expert")


def read(ctx):
    trace = ctx["trace"]
    steps = ctx["traced"]["steps"] * ctx["chips"]
    if not trace or not trace.get("leaf") or not steps:
        return None
    seconds = [trace_reduce.scope_seconds(trace, scope) for scope in SCOPES]
    if all(s is None for s in seconds):
        return None
    return 1e3 * sum(s or 0.0 for s in seconds) / steps
