"""Arithmetic of the end-to-end metrics: percentiles over all requests with
the missing ones placed above every finite value, and the spread a bound is
set from."""

from __future__ import annotations

import math
import statistics


def percentile_with_missing(values, n_missing: int, pct: float) -> float:
    """Nearest-rank percentile of ``values`` plus ``n_missing`` entries that
    lie above every finite value.  ``inf`` when the rank falls on a missing
    entry; ``nan`` when there is nothing at all."""
    ordered = sorted(values)
    total = len(ordered) + n_missing
    if total == 0:
        return math.nan
    rank = max(1, math.ceil(pct / 100.0 * total))
    return ordered[rank - 1] if rank <= len(ordered) else math.inf


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the median
    (``statistics.quantiles(values, n=4)``, as the driver takes it)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
