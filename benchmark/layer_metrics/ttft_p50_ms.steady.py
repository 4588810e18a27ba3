"""Median TTFT (``readers.ttft_percentile_ms``).  It is half a decode step plus a
prefill, and over the hundred-odd requests of a 51 s window it spreads by more
than a bound may be wide (PERF.md, PR 30), so it is read here and no percentile
of the TTFT is end to end in the steady cell."""

from benchmark import readers


def read(ctx):
    return readers.ttft_percentile_ms(ctx, 50)
