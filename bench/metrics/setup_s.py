"""Set-up: process start to window start — imports, device start, traffic, weights, warm-up and any compilation (host clock)."""


def read(ctx):
    return ctx["setup_s"]
