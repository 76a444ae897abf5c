"""Roofline share of latent attention (MLA) under the ``flash_attention``
scope: keys 192 lanes wide beside values of 128. The configuration's
``kernel_costs`` count QK^T over the keys' 192 lanes and PV over the
values' 128, of the causal pairs only, and q, k, dq, dk at 32 x 192 beside
v, o, do, dv at 32 x 128: the work the model asks for, whatever implements
it. A kernel that pads a width, contracts a neighbour's masked lanes or
computes what the mask hides spends time, not work, so it reads low and
none reads over 100. None where the trace carries no such scope."""
import trace_reduce


def read(ctx):
    return trace_reduce.roofline_share(ctx, "flash_attention")
