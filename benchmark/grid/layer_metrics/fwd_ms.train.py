"""Device self time per step under the ``loss_and_grad`` scope with no
``transpose(`` part: the forward pass and the loss. A fusion counts under
its root's scope (``_phases``)."""
from layer_metrics import _phases


def read(ctx):
    return _phases.ms_per_step(ctx, _phases.FORWARD)
