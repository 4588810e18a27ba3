"""Roofline share of the flash-attention backward kernel (``flash_bwd`` in the
trace; cost from ``flops.flash_bwd_cost``; compute-bound at these shapes)."""

from benchmark import flops, readers


def read(ctx):
    return readers.flash_roofline_pct(ctx, "flash_bwd", flops.flash_bwd_cost)
