"""Least time for the one-token recurrence over the live slots (their state read
and written, x, B, C read, y written; ``flops_nemotron_h.ssm_step_cost``) over
the decode program's device time under ``atpu_serve_ssm_step``.  The program
runs the recurrence over every slot, live or not, so the share falls with the
occupancy."""

from benchmark import flops, hybrid_readers
from benchmark import flops_nemotron_h as costs


def read(ctx):
    got, means = hybrid_readers.scope_ms(ctx, hybrid_readers.DECODE, "atpu_serve_ssm_step"), hybrid_readers.decode_means(ctx)
    if got is None or means is None or not got[0]:
        return None
    least, _ = flops.roofline_seconds(*costs.ssm_step_cost(ctx["cell"].config, means["live"]), ctx["peaks"])
    return 100.0 * least / (got[0] / 1e3)
