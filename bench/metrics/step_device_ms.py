"""Device busy time per training step in the traced epochs, in ms."""

from readers import mean_device


def read(ctx):
    busy = mean_device(ctx, "busy_ns")
    steps = ctx["traced_epochs"] * ctx["steps_per_epoch"]
    if busy is None or not steps:
        return None
    return busy / 1e6 / steps
