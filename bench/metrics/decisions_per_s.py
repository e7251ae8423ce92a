"""Real placement decisions (padding excluded) whose placements reached the host in the window, over the window's length; the window closes at the first wave boundary at or after its nominal length (host clock)."""
from benchlib.readers import per_second


def read(ctx):
    return per_second(ctx, "decisions")
