"""Simulated device-iterations completed in the window over the window's
host-clock time: m x iterations x cells of every call, first call's start
to last call's end."""


def read(ctx):
    return sum(c["dev_iters"] for c in ctx.calls) / ctx.window_s
