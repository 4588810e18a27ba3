"""``DecodeService.mean_batch_occupancy`` over the window's engine steps."""


def read(ctx):
    v = ctx["counters"].get("occupancy_mean")
    return None if v is None else 100.0 * v
