"""Device self time a step and chip in front of latent attention's flash
call, under the program's ``latent_proj`` scope: the down-projections to
the latents c_q and [c_kv | k_r], their two norms, the up-projections to
the heads, RoPE on q's rotary lanes and on the shared rotary key, and the
broadcast of that key into every head's key, forward and backward. The
output projection is not part of it. None where the trace has no such
scope."""
import trace_reduce


def read(ctx):
    trace = ctx["trace"]
    steps = ctx["traced"]["steps"] * ctx["chips"]
    if not trace or not trace.get("leaf") or not steps:
        return None
    seconds = trace_reduce.scope_seconds(trace, "latent_proj")
    if seconds is None:
        return None
    return 1e3 * seconds / steps
