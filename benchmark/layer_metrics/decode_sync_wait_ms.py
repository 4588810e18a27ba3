"""Median of ``atpu/serve/decode_sync``: how long the host blocks on the decode
program's token block."""

from benchmark import span_readers


def read(ctx):
    return span_readers.median_ms(ctx, "atpu/serve/decode_sync")
