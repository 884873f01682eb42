"""Device self time of Event 4, the local step, per scan iteration, in ms:
the ops of the ``efhc.event4`` scope (the minibatch gather, forward and
backward passes, the optimizer), counted as ``step_device_ms`` counts busy
time."""
from bench.scopes import device_ms


def read(ctx):
    return device_ms(ctx, ("efhc.event4",))
