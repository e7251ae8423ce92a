"""Host ms per training episode outside the jitted call (the trainer's ``episode`` span less ``episode.call``) over the episodes of the profiled window (program spans)."""
from benchlib.train_readers import host_ms_per_episode


def read(ctx):
    return host_ms_per_episode(ctx)
