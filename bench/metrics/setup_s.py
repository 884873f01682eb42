"""Process start to the window's start: imports, data and fabric, the
program's staging and compilation, and the warm-up call."""


def read(ctx):
    return ctx.setup_s
