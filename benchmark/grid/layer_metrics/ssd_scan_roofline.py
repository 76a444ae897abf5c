"""Roofline share of the state-space mixers' selective scan under the
program's ``ssd_scan`` scope (``ops/ssm.py``), forward and backward. The
configuration's ``kernel_costs`` count the work of the chunked form at the
published chunk, whatever implements it: the scores C.B a group and (L *
scores) applied to x a head over the causal in-chunk pairs, the chunk
states built and read, backward twice the forward, and x, B, C, dt read
and y written once in bf16 with as much again for their cotangents.
Recomputation is not counted: a scan whose backward makes the forward
again, or that writes its decay matrices to HBM, spends time, not work,
so it reads low and none reads over 100. None where the trace carries no
such scope."""
import trace_reduce


def read(ctx):
    return trace_reduce.roofline_share(ctx, "ssd_scan")
