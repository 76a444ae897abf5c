"""Device self time a step and chip of the multi-token-prediction module,
everything under the program's ``mtp`` scope, forward and backward: the
second embedding lookup, the two norms and the projection that join it to
the trunk's hidden state, the module's own expert layer and the second
pass through the head. The module's attention counts here AND under
``flash_attention`` (``mla_attention_roofline``), its experts here and
under ``moe_layer_ms.train``: the scopes nest, so these metrics overlap
and do not add up to the step. None where the trace has no such scope."""
import trace_reduce


def read(ctx):
    trace = ctx["trace"]
    steps = ctx["traced"]["steps"] * ctx["chips"]
    if not trace or not trace.get("leaf") or not steps:
        return None
    seconds = trace_reduce.scope_seconds(trace, "mtp")
    if seconds is None:
        return None
    return 1e3 * seconds / steps
