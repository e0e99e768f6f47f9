"""Mean wait of an answered request from its due time until the serve
engine took it (``admitted_at - due``), in ms."""
from bench import timeline


def read(ctx):
    return timeline.request_mean_ms(ctx, "due", "admitted_at")
