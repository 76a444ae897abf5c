"""Mean ``h2d_wait`` span per step: how long ``TrainLoop``'s consumer
blocked on the prefetcher's staged batch, from telemetry's timeline, which
is on in the traced run only."""


def read(ctx):
    spans = [e["dur"] for e in ctx["spans"] if e["phase"] == "h2d_wait"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
