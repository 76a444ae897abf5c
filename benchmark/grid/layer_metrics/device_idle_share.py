"""1 - busy_s / window_s of the reduction that fills ``device``."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
