"""The forward's share of its roofline: counted least time (the larger of
FLOPs over peak FLOP/s and least bytes over HBM bandwidth) over device ms
per forward, in %."""
from bench import readers


def read(ctx):
    return readers.roofline_pct(ctx)
