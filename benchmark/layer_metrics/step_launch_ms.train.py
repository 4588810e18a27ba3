"""Mean per captured call of ``atpu/dispatch`` (the executable call alone)."""

from benchmark import span_readers


def read(ctx):
    return span_readers.captured_call_mean_ms(ctx, "dispatch")
