"""Programs compiled in set-up instead of loaded: ``atpu/compile`` spans with
``cache`` other than hit.  Each one's ``fun`` is named on standard error.  0 on
a warm cache."""

from benchmark import setup_readers


def read(ctx):
    return setup_readers.setup_value(ctx, "programs_compiled")
