"""Seconds of set-up JAX spent tracing Python to jaxprs and lowering them
to MLIR, whether or not the compile cache then hits: the union of the
compile log's ``trace`` and ``lower`` intervals before the window."""
from layer_metrics import _compile_log


def read(ctx):
    return _compile_log.union_before_window(ctx, ("trace", "lower"))
