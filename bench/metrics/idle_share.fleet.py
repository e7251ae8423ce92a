"""Device idle share of the traced window (profiler trace)."""
from benchlib.readers import idle_share


def read(ctx):
    return idle_share(ctx)
