"""Mean per captured call of ``atpu/step/assemble`` (entry to launch)."""

from benchmark import span_readers


def read(ctx):
    return span_readers.captured_call_mean_ms(ctx, "assemble")
