"""Device busy time per scan iteration executed in the traced window, in
ms.  A launch's iteration counts once for all its vmapped cells."""


def read(ctx):
    iters = sum(c["scan_iters"] for c in ctx.calls)
    if ctx.trace is None or not iters:
        return None
    return 1000.0 * ctx.trace.busy_s / iters
