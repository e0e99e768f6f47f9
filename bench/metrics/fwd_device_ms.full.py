"""Device-busy ms per full-graph forward: the trace's busy time in the window
over the forwards run."""
from bench import readers


def read(ctx):
    return readers.device_ms_per(ctx, readers.forwards(ctx))
