"""Device ms of the prefill programs per thousand bucket tokens, over the
prefills launched inside the traced window."""

from benchmark import hybrid_readers


def read(ctx):
    got, buckets = hybrid_readers.scope_ms(ctx, hybrid_readers.PREFILL, ""), hybrid_readers.traced_prefill_buckets(ctx)
    if got is None or buckets is None:
        return None
    return got[1] / (sum(buckets) / len(buckets) / 1e3)
