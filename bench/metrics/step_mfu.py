"""The whole step's share of the chip's peak, in percent: the least time
the chip could take for one scan iteration, max(operations / peak
operations per second, bytes / peak bytes per second) with the counts of
``bench.work``, over the window's wall time per scan iteration."""


def read(ctx):
    iters = sum(c["scan_iters"] for c in ctx.calls)
    if not iters or not ctx.peaks:
        return None
    least = max(ctx.work["flops"] / ctx.peaks["flops"],
                ctx.work["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.window_s / iters)
