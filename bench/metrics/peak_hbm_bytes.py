"""The largest device-memory peak over the cell's chips after the window:
per chip ``peak_bytes_in_use`` (buffers: parameters, data, a call's inputs
and outputs) plus ``peak_bytes_reserved`` (the compiled programs'
temporaries, the scan carry among them), which the TPU runtime keeps and
counts apart (``harness.PEAK_COUNTERS``)."""


def read(ctx):
    return max(ctx.peak_bytes) if ctx.peak_bytes else None
