"""Training model FLOPs per second (acting Q-net forward per decision plus the TD updates' share) over the chips' bf16 peak, at the window's decisions per second (host clock)."""
from benchlib.train_readers import train_mfu


def read(ctx):
    return train_mfu(ctx)
