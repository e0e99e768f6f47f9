"""Mean host time of one sampler call, timed by the benchmark's proxy,
in ms."""
from bench import readers


def read(ctx):
    return readers.sample_ms(ctx)
