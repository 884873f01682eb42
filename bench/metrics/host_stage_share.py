"""Percent of the traced window in which the program staged minibatch
indices on the host: the union of its ``sim.stage`` and ``service.stage``
spans over the window, on the trace's clock."""


def read(ctx):
    att = ctx.scopes
    if att is None or not att.window_s:
        return None
    return 100.0 * att.stage_s / att.window_s
