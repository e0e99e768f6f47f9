"""Mean targets per serving step (step_log n_targets)."""
from bench import readers


def read(ctx):
    return readers.step_mean(ctx, "n_targets", 1.0)
