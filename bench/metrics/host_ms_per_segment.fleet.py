"""Host time in the engine's serving loop per dispatched segment: the benchmark's spans around and inside the loop, over the dispatch counter."""
from benchlib.readers import host_ms_per_segment


def read(ctx):
    return host_ms_per_segment(ctx)
