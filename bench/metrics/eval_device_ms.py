"""Device self time of the on-device evaluation per scan iteration, in ms:
the ops of the ``efhc.eval`` scope, counted as ``step_device_ms`` counts
busy time."""
from bench.scopes import device_ms


def read(ctx):
    return device_ms(ctx, ("efhc.eval",))
