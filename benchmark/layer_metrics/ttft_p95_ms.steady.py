"""95th percentile of the TTFT (``readers.ttft_percentile_ms``).  Below the knee
with some hundred requests a window it swings with the order of the arrivals (a
few requests wait for a slot or for blocks, or none does), so it is read here;
``ttft_p50_ms.steady`` and ``ttft_p80_ms.steady`` stand beside it."""

from benchmark import readers


def read(ctx):
    return readers.ttft_percentile_ms(ctx, 95)
