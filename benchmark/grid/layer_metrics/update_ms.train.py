"""Device self time per step under the ``optimizer_update`` scope: the
fused update's own fusions or the Pallas multi-tensor kernel. What XLA
fuses into a backward matmul reads under ``bwd_ms.train`` (``_phases``)."""
from layer_metrics import _phases


def read(ctx):
    return _phases.ms_per_step(ctx, "optimizer_update")
