"""Least time for the delta rule's one-token recurrence over the live slots
(their state read and written once, q, k, v, g, beta read, o written;
``flops_olmo_hybrid.gdn_step_cost``) over the decode program's device time under
``atpu_serve_gdn_step``.  The program runs the recurrence over every slot, live
or not, so the share falls with the occupancy."""

from benchmark import flops, hybrid_readers, plan_readers
from benchmark import flops_olmo_hybrid as costs

SCOPES = ("atpu_serve_gdn_step",)


def read(ctx):
    got, live = hybrid_readers.scope_ms(ctx, hybrid_readers.DECODE, SCOPES[0]), plan_readers.live_mean(ctx)
    if got is None or live is None or not got[0]:
        return None
    least, _ = flops.roofline_seconds(*costs.gdn_step_cost(ctx["cell"].config, live), ctx["peaks"])
    return 100.0 * least / (got[0] / 1e3)
