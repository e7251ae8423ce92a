"""95th percentile over the requests due in the measured window of the time from a request's wall due time to its placements on the host (host clock); read in the traced run, from its measured window."""
from benchlib.readers import latency_percentile


def read(ctx):
    return latency_percentile(ctx, 95)
