"""Programs handed to the backend during set-up: the compile log's
``backend_compile`` entries before the window."""
from layer_metrics import _compile_log


def read(ctx):
    spans = _compile_log.before_window(ctx, ("backend_compile",))
    return len(spans) if spans else None
