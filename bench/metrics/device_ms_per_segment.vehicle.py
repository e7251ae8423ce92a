"""Device busy time per dispatched segment in the traced window (profiler trace over the dispatch counter)."""
from benchlib.readers import device_ms_per_segment


def read(ctx):
    return device_ms_per_segment(ctx)
