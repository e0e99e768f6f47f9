"""Mean wait of an answered request from admission to the start of the
first step that served it (``started_at - admitted_at``), in ms."""
from bench import timeline


def read(ctx):
    return timeline.request_mean_ms(ctx, "admitted_at", "started_at")
