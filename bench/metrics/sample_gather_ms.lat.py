"""Mean host time per serving step of the sampler's local tables, relabel
and feature rows (span ``hgnn.sample.gather``, step_log gather_s), in ms."""
from bench import timeline


def read(ctx):
    return timeline.step_mean(ctx, "gather_s", 1e3)
