"""The dqn_td_update kernel's share of its roofline: least time of its operations and bytes (benchlib/flops.td_update_cost per update, times the trainer's td_updates counter) at the chip's peaks over its device time (profiler trace)."""
from benchlib.train_readers import td_update_roofline


def read(ctx):
    return td_update_roofline(ctx)
