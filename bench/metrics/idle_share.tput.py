"""Device idle share of the closed-loop serving window, in %."""
from bench import readers


def read(ctx):
    return readers.idle_pct(ctx)
