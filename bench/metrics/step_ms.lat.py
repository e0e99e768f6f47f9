"""Mean host wall per serving step (the engine's step_log wall_s: sample,
upload, forward, fetch), in ms."""
from bench import readers


def read(ctx):
    return readers.step_mean(ctx, "wall_s", 1e3)
