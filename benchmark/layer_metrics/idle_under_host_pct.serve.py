"""Share of the traced window in which the first chip idles while the program's
innermost span is host work (admit, prefill launch, decode launch, emit)."""

from benchmark import span_readers


def read(ctx):
    return span_readers.idle_under_host_pct(ctx)
