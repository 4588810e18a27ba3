"""Device ms per decode step under ``atpu_serve_kv_write`` + ``atpu_serve_kv_gather``."""

from benchmark import span_readers


def read(ctx):
    return span_readers.decode_group_ms(ctx, "kv_pool")
