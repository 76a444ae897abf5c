"""Device self time a step and chip of the state-space mixers' two
projections, under the program's ``mamba_proj`` scope: ``u W_in`` (2688 ->
10304) and ``y W_out`` (4096 -> 2688), forward and backward. What is left
of ``mamba_layer_ms.train`` without it is the conv, the scan and the norm.
None where the trace has no such scope."""
from layer_metrics import _scope_ms


def read(ctx):
    return _scope_ms.ms_per_step(ctx, "mamba_proj")
