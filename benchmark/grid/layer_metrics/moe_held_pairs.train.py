"""Token-expert pairs an expert layer's held experts were given, the mean
over the traced steps: a step's ``moe_held_pairs`` (the group sizes the
router computed ON THE DEVICE in that step, read at its window retire)
summed over its layers and divided by them. The routing decides it, not
the shapes: a time under the experts' scopes is a rate only beside this.
0 where the program's record is there and names no expert layer; None on
a program without the record, or where the ring dropped steps."""
from layer_metrics import _device_counters


def read(ctx):
    found = _device_counters.pairs_a_step(ctx)
    if found is None:
        return None
    traced = found[:ctx["traced"]["steps"]]
    if not traced:
        return None
    return sum(pairs / layers for pairs, layers in traced if layers) \
        / len(traced)
