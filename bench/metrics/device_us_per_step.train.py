"""Device time of the jit_train_episode module in the profiled window per task trained on, in microseconds (profiler trace over the trainer's train_steps counter)."""
from benchlib.train_readers import device_us_per_step


def read(ctx):
    return device_us_per_step(ctx)
