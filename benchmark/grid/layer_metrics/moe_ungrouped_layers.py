"""Expert layers the process traced on any path but ``grouped`` (pairs
sorted by expert, one grouped product a projection, no dropped token),
from the program's counter ``mx_moe_dispatch_total{path}``. None where the
program has no such counter or traced no expert layer; 0 is the reading
wanted."""


def read(ctx):
    try:
        from mxnet_tpu import telemetry
        from mxnet_tpu.telemetry import names
        by_path = telemetry.registry().counter(
            names.MOE_DISPATCH, label_key="path").values()
    except Exception:       # a program without the counter: silent
        return None
    if not by_path:
        return None
    return sum(n for path, n in by_path.items() if path != "grouped")
