"""Helpers that more than one per-layer metric's reader uses.  A reader that
finds nothing to read returns ``None`` and the metric is left out of the line."""

from __future__ import annotations

import statistics

from . import flops, stats, trace_reduce

DECODE_MODULE = "_decode_jit"


def flash_roofline_pct(ctx, needle: str, cost) -> float | None:
    """Least time one call of the kernel could take (``cost`` against the
    peaks) over the mean device time of the trace's events named ``needle``."""
    if ctx.get("planes") is None:
        return None
    times = trace_reduce.kernel_durations(ctx["planes"], needle)
    if not times:
        return None
    cell = ctx["cell"]
    cfg = cell.config
    ops, nbytes = cost(
        ctx["counters"]["rows_per_chip"], cfg["n_head"], cell.mix["seq_len"],
        cfg["n_embd"] // cfg["n_head"],
    )
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return 100.0 * least / statistics.fmean(times)


def decode_step_ms(ctx) -> float | None:
    """Median device time of one execution of the decode program."""
    if ctx.get("planes") is None:
        return None
    times = trace_reduce.kernel_durations(ctx["planes"], DECODE_MODULE, trace_reduce.MODULE_LINE)
    return statistics.median(times) * 1e3 if times else None


def ttft_percentile_ms(ctx, pct: float) -> float | None:
    """Percentile over all requests due in the window (in a traced run: before
    the profiler started) of first token minus time due; a request with no
    first token lies above every finite value."""
    c = ctx["counters"]
    if "ttft_ms" not in c:
        return None
    return stats.percentile_with_missing(c["ttft_ms"], c["ttft_missing"], pct)
