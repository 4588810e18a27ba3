"""Median device time of one execution of the decode program in the trace."""

from benchmark import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
