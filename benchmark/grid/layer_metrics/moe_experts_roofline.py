"""Roofline share of the sparse experts under the ``moe_experts`` scope
(the gather of the routed tokens, the three grouped products, ReGLU,
forward and backward), against the products of the EXPECTED number of
token-expert pairs the held experts are given (tokens x k x held / router
width) and the held weights read twice and their gradient written once.
The true count is the routing's: with the configuration's embedding
scale two seeds on the chip gave their layers 97 % to 102 % of the
expectation at the first step and 6 % more after 93 steps (PR 28; training
pulls tokens towards the held experts), so the share reads within a few
percent of what the traced steps' own pairs would give; ``ctx`` carries
neither the seed nor the net to count them."""
import trace_reduce


def read(ctx):
    return trace_reduce.roofline_share(ctx, "moe_experts")
