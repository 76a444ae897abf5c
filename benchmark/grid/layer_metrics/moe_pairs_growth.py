"""How far the routers' training moved the work inside one window: 100 x
(mean held pairs a step over the window's last 16 steps / over its first
16 - 1). A cell's share of a deployment trains its routers towards the
experts it holds, so its step grows through the window and the 95th
percentile of the step time sits at the window's end. 0 where there are
no pairs; None on a program without the record."""
from layer_metrics import _device_counters

EDGE = 16


def read(ctx):
    found = _device_counters.pairs_a_step(ctx)
    if found is None:
        return None
    pairs = [p for p, _ in found]
    first, last = sum(pairs[:EDGE]), sum(pairs[-EDGE:])
    if not first:
        return 0.0
    return 100.0 * (last / first - 1.0)
