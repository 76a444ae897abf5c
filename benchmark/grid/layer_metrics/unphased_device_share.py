"""Share of the device's self time in the traced window under none of the
program's phase scopes: the step's side programs (``_threefry_split``,
``_unstack``) and whatever XLA adds without a name. None, never 100, for a
program that has no phase scopes."""
from layer_metrics import _phases


def read(ctx):
    by_phase = _phases.split(ctx)
    if by_phase is None:
        return None
    return 100.0 * by_phase.get(_phases.UNPHASED, 0.0) \
        / sum(by_phase.values())
