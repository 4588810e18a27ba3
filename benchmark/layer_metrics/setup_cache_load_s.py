"""Seconds of set-up in outermost ``atpu/compile`` spans the persistent cache
served (``cache`` hit): executables loaded instead of compiled."""

from benchmark import setup_readers


def read(ctx):
    return setup_readers.setup_value(ctx, "cache_load_s")
