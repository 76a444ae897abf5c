"""Roofline share of the attention under the ``flash_attention`` scope,
forward and both backward kernels, whatever implements them."""
import trace_reduce


def read(ctx):
    return trace_reduce.roofline_share(ctx, "flash_attention")
