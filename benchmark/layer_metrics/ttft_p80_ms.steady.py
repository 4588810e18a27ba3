"""80th percentile of the TTFT (``readers.ttft_percentile_ms``): four fifths of a
decode step plus a prefill; some twenty requests lie beyond it in a window,
among them those that waited for a slot or for blocks."""

from benchmark import readers


def read(ctx):
    return readers.ttft_percentile_ms(ctx, 80)
