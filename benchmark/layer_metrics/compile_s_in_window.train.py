"""Seconds of outermost trace, lowering and compile (a cache load too) from the
window's open to the run's last captured call; each one, and each generation-2
collection there, is named on standard error with its call.  Expected 0."""

from benchmark import setup_readers


def read(ctx):
    return setup_readers.compile_s_in_window(ctx)
