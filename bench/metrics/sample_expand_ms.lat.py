"""Mean host time per serving step of the sampler's frontier expansion and
rung choice (span ``hgnn.sample.expand``, step_log expand_s), in ms."""
from bench import timeline


def read(ctx):
    return timeline.step_mean(ctx, "expand_s", 1e3)
