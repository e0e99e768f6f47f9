"""Mean host time per serving step from the forward's dispatch through its
logits on the host (span ``hgnn.forward``, step_log forward_s), in ms."""
from bench import timeline


def read(ctx):
    return timeline.step_mean(ctx, "forward_s", 1e3)
