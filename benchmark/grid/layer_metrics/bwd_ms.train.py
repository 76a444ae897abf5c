"""Device self time per step under the ``loss_and_grad`` scope with a
``transpose(`` part: the backward pass. A fusion counts under its root's
scope, so an update that XLA fuses into a dW matmul (the LSTM cell's SGD)
reads here and not under ``update_ms.train``."""
from layer_metrics import _phases


def read(ctx):
    return _phases.ms_per_step(ctx, _phases.BACKWARD)
