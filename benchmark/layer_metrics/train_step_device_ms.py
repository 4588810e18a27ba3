"""Median device time of one execution of the captured step's program
(``jit_traced`` on the trace's module line).  Beside ``train_tokens_per_s`` it
says whether a slower window was the device's doing or the host's."""

import statistics

from benchmark import trace_reduce

MODULE_NEEDLE = "jit_traced"


def read(ctx):
    if ctx.get("planes") is None:
        return None
    times = trace_reduce.kernel_durations(ctx["planes"], MODULE_NEEDLE, trace_reduce.MODULE_LINE)
    return statistics.median(times) * 1e3 if times else None
