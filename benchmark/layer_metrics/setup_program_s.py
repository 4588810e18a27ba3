"""Seconds of set-up under the program's own spans (union of every ``atpu/*``
span that ended before the window opened): prepare, the service's
construction, tracing, lowering, compiles, cache loads, the warm-up's engine
steps and captured calls.  The rest of ``setup_s`` is the import and the
benchmark's own weight making."""

from benchmark import setup_readers


def read(ctx):
    return setup_readers.setup_value(ctx, "program_s")
