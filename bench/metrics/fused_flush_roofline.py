"""Roofline share of the ``fused_flush`` kernel: its demanded work
at the chip's peak over its device time in the trace."""

from readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "fused_flush")
