"""Device self time a step and chip under one of the program's scopes,
for the readers that report a layer's milliseconds."""
import trace_reduce


def ms_per_step(ctx, scope):
    """None where the trace is empty or carries no event under ``scope``."""
    trace = ctx["trace"]
    steps = ctx["traced"]["steps"] * ctx["chips"]
    if not trace or not trace.get("leaf") or not steps:
        return None
    seconds = trace_reduce.scope_seconds(trace, scope)
    if seconds is None:
        return None
    return 1e3 * seconds / steps
