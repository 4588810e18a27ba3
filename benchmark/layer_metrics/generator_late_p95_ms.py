"""95th percentile of how late the load generator handed a request to
``submit`` against its schedule.  A starved generator must not read as a fast
server; requests are timed from when they were due all the same."""

from benchmark import stats


def read(ctx):
    late = ctx["counters"].get("generator_late_ms")
    return stats.percentile_with_missing(late, 0, 95) if late else None
