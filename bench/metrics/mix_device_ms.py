"""Device self time of Event 3, the mix, per scan iteration, in ms: the
ops of the ``efhc.event3`` scope, counted as ``step_device_ms`` counts
busy time."""
from bench.scopes import device_ms


def read(ctx):
    return device_ms(ctx, ("efhc.event3",))
