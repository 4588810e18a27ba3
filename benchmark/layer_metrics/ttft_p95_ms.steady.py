"""95th percentile over all requests due in the window of first token minus
time due; a request with no first token lies above every finite value.  Below
the knee with some sixty requests a window it swings with the order of the
arrivals (a few requests wait for a slot or not), so it is read here and the
median is the end-to-end metric."""

from benchmark import stats


def read(ctx):
    c = ctx["counters"]
    if "ttft_ms" not in c:
        return None
    return stats.percentile_with_missing(c["ttft_ms"], c["ttft_missing"], 95)
