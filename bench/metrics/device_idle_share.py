"""Share of the traced window in which no operation runs on the device,
the mean over the cell's devices."""

from readers import mean_device, share


def read(ctx):
    busy = mean_device(ctx, "busy_ns")
    if busy is None:
        return None
    return 100.0 - share(busy, ctx["trace"]["window_ns"])
