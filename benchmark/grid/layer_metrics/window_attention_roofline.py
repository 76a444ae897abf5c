"""Roofline share of the attention of a model with window layers and
grouped key/value heads, under the ``flash_attention`` scope: the
configuration's ``kernel_costs`` count the causal in-window pairs only
and q, o at the query heads' width, k, v at the key/value heads', the
same work whatever implements it, so a kernel that computes or moves
what the mask hides reads low and none reads over 100."""
import trace_reduce


def read(ctx):
    return trace_reduce.roofline_share(ctx, "flash_attention")
