"""Device self time of Events 1 and 2 per scan iteration, in ms: the ops
of the ``efhc.event1`` (graph) and ``efhc.event2`` (trigger) scopes,
counted as ``step_device_ms`` counts busy time."""
from bench.scopes import device_ms


def read(ctx):
    return device_ms(ctx, ("efhc.event1", "efhc.event2"))
