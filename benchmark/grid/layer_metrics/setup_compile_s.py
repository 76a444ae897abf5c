"""Seconds of set-up inside the backend: XLA's compile or, on a hit of the
persistent cache, the read that replaced it (the log's ``cache_read``
entries lie inside these): the union of the compile log's
``backend_compile`` intervals before the window."""
from layer_metrics import _compile_log


def read(ctx):
    return _compile_log.union_before_window(ctx, ("backend_compile",))
