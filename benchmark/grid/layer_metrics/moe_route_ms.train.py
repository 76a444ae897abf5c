"""Device self time a step and chip around the experts: the router product,
top-k, the sort of the token-expert pairs and the group sizes
(``moe_route``), and the weighted sum of each token's expert outputs
(``moe_combine``), forward and backward. None where the trace has neither
scope."""
import trace_reduce


def read(ctx):
    trace = ctx["trace"]
    steps = ctx["traced"]["steps"] * ctx["chips"]
    if not trace or not trace.get("leaf") or not steps:
        return None
    seconds = [trace_reduce.scope_seconds(trace, scope)
               for scope in ("moe_route", "moe_combine")]
    if all(s is None for s in seconds):
        return None
    return 1e3 * sum(s or 0.0 for s in seconds) / steps
