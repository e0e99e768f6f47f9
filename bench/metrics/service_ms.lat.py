"""Mean time of an answered request from the start of its first step to
its terminal status (``finished_at - started_at``), in ms."""
from bench import timeline


def read(ctx):
    return timeline.request_mean_ms(ctx, "started_at", "finished_at")
