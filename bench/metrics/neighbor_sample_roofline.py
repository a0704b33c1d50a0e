"""Roofline share of the ``neighbor_sample`` kernel: its demanded work
at the chip's peak over its device time in the trace."""

from readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "neighbor_sample")
