"""Median per admitted request of the host's time to launch its prefill and
to read its first token back (``atpu/serve/prefill_launch`` + ``prefill_sync``)."""

from benchmark import span_readers


def read(ctx):
    return span_readers.prefill_host_ms(ctx)
