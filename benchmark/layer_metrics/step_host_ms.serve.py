"""Median over engine steps of ``atpu/serve/step`` less the blocking reads inside
it: the host's own work between a token read and the next launch."""

from benchmark import span_readers


def read(ctx):
    return span_readers.step_host_ms(ctx)
