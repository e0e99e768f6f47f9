"""Mean host time per serving step of the sampled batch's host-to-device
transfer call (span ``hgnn.sample.upload``, step_log upload_s), in ms."""
from bench import timeline


def read(ctx):
    return timeline.step_mean(ctx, "upload_s", 1e3)
