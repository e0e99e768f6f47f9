"""Device-busy ms per serving step: the trace's busy time in the window over
the steps run."""
from bench import readers


def read(ctx):
    return readers.device_ms_per(ctx, readers.steps(ctx))
