"""Mean host time of ``TrainLoop.step``'s ``dispatch`` span per step, from
telemetry's timeline, which is on in the traced run only."""


def read(ctx):
    spans = [e["dur"] for e in ctx["spans"] if e["phase"] == "dispatch"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
