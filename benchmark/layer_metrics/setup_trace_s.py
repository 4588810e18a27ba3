"""Seconds of set-up in outermost ``atpu/trace`` + ``atpu/lower`` spans: jaxpr
tracing and MLIR lowering of every program built before the window."""

from benchmark import setup_readers


def read(ctx):
    return setup_readers.setup_value(ctx, "trace_s")
