"""Per cent of the jit_train_episode module's device time spent in the dqn_td_update kernel (profiler trace)."""
from benchlib.train_readers import td_update_share


def read(ctx):
    return td_update_share(ctx)
