"""Share of dispatched lane slots that carried padding (program counters: dispatches, slots, chunk, real tasks placed)."""
from benchlib.readers import pad_share


def read(ctx):
    return pad_share(ctx)
