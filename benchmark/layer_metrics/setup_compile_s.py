"""Seconds of set-up in outermost ``atpu/compile`` spans that the persistent
cache did not serve (``cache`` miss or off): XLA compiles.  About 0 on a warm
cache."""

from benchmark import setup_readers


def read(ctx):
    return setup_readers.setup_value(ctx, "compile_s")
