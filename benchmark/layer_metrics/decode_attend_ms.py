"""Device ms per decode step under ``atpu_serve_attend``."""

from benchmark import span_readers


def read(ctx):
    return span_readers.decode_group_ms(ctx, "attend")
