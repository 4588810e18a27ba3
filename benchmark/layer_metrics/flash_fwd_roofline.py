"""Roofline share of the flash-attention forward kernel (``flash_fwd`` in the
trace; cost from ``flops.flash_fwd_cost``; compute-bound at these shapes)."""

from benchmark import flops, readers


def read(ctx):
    return readers.flash_roofline_pct(ctx, "flash_fwd", flops.flash_fwd_cost)
