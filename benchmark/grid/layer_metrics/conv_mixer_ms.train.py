"""Device self time a step and chip of the gated short-convolution mixers
(LFM2's conv layers), under the program's ``conv_mixer`` scope: both
projections, the gates and the conv, forward and backward. The layer's
own pre-norm and residual are not part of it. None where the trace has no
such scope."""
from layer_metrics import _scope_ms


def read(ctx):
    return _scope_ms.ms_per_step(ctx, "conv_mixer")
