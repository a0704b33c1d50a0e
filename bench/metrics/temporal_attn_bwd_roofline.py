"""Roofline share of the ``temporal_attn_bwd`` kernel: its demanded work
at the chip's peak over its device time in the trace."""

from readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "temporal_attn_bwd")
