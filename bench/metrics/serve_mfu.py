"""Q-net forward FLOPs of real decisions per second over the chips' bf16 peak (shapes from the configuration)."""
from benchlib.readers import serve_mfu


def read(ctx):
    return serve_mfu(ctx)
