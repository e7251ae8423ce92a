"""Median over the requests due in the window of the time from a request's wall due time to its placements on the host (host clock)."""
from benchlib.readers import latency_percentile


def read(ctx):
    return latency_percentile(ctx, 50)
