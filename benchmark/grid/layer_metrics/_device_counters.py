"""What the program's ``window`` spans say the device computed about
each step of the window (``telemetry.device_counters``, from PR 37), for
the readers that report the held pairs of the sparse experts.

A step is a ``window`` span of ``ctx["spans"]``, in step order; the
traced steps are the first ``ctx["traced"]["steps"]`` of them. The span
is stamped on ``perf_counter``, the clock ``run.py`` cuts the spans by,
so nothing of the program is imported here."""

PAIRS = "moe_held_pairs"    # int[expert layers][held experts] a step


def pairs_a_step(ctx):
    """``[(pairs held over all expert layers, expert layers)]`` a step,
    ``(0, 0)`` for a step whose record names no expert layer. None where
    no ``window`` span carries ``counters`` (a program without the
    channel), or where the ring kept fewer of them than the window ran
    steps (no number beats a low one)."""
    events = sorted((e for e in ctx["spans"] if e["phase"] == "window"),
                    key=lambda e: e["step"])
    if not events or any("counters" not in e for e in events) \
            or len(events) < ctx["window"]["steps"]:
        return None
    layers = [e["counters"].get(PAIRS, ()) for e in events]
    return [(sum(map(sum, rows)), len(rows)) for rows in layers]
