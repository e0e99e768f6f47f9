"""Device idle share of the full-graph window, in %."""
from bench import readers


def read(ctx):
    return readers.idle_pct(ctx)
