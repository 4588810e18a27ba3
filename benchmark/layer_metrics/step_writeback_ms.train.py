"""Mean per captured call of ``atpu/step/writeback`` (state writeback and the
deferred scheduler steps)."""

from benchmark import span_readers


def read(ctx):
    return span_readers.captured_call_mean_ms(ctx, "writeback")
