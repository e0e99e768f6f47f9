"""The whole forward's share of the chip's peak: counted FLOPs per forward x
forwards per second over peak FLOP/s, in %."""
from bench import readers


def read(ctx):
    return readers.mfu_pct(ctx)
