"""Mean bytes per serving step that the sampler uploads, padded to the rung
(step_log upload_bytes), in MB."""
from bench import timeline


def read(ctx):
    return timeline.step_mean(ctx, "upload_bytes", 1e-6)
