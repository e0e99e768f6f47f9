"""Mean sampled frontier feature bytes per step (step_log frontier_bytes), in
MB."""
from bench import readers


def read(ctx):
    return readers.step_mean(ctx, "frontier_bytes", 1e-6)
