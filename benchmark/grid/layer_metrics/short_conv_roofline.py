"""Roofline share of the gated short convolution under the program's
``short_conv`` scope (``ops/ssm.py`` ``gated_short_conv``: the gates and
the conv between a conv mixer's two projections), forward and backward.
The configuration's ``kernel_costs`` count no matrix product there and the
bytes of ``[B | C | x]`` read and y written forward, dy and ``[B | C | x]``
read and their cotangent written backward, in bf16: bound by bytes. A form
that writes an intermediate to HBM spends time, not work: it reads low,
and none reads over 100. None where the trace carries no such scope."""
import trace_reduce


def read(ctx):
    return trace_reduce.roofline_share(ctx, "short_conv")
