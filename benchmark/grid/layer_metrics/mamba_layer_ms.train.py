"""Device self time a step and chip of the state-space (Mamba-2) mixers,
under the program's ``mamba_mixer`` scope: both projections, the conv, the
step sizes, the selective scan and the gated group norm, forward, backward
and whatever the backward makes again. The layer's own pre-norm and
residual are not part of it. None where the trace has no such scope."""
from layer_metrics import _scope_ms


def read(ctx):
    return _scope_ms.ms_per_step(ctx, "mamba_mixer")
