"""95th percentile, over all requests due in the window, of admission time
minus due time; a request never admitted lies above every finite value."""

from benchmark import stats


def read(ctx):
    c = ctx["counters"]
    if "queue_wait_ms" not in c:
        return None
    return stats.percentile_with_missing(c["queue_wait_ms"], c["never_admitted"], 95)
