"""Least time for the routed experts' grouped products of one decode step (the
touched experts' weights once, the rows in and out, the FLOPs beside them;
``flops_nemotron_h.moe_experts_cost``) over the decode program's device time
under ``atpu_serve_moe_experts``."""

from benchmark import flops, hybrid_readers
from benchmark import flops_nemotron_h as costs


def read(ctx):
    got, means = hybrid_readers.scope_ms(ctx, hybrid_readers.DECODE, "atpu_serve_moe_experts"), hybrid_readers.decode_means(ctx)
    if got is None or means is None or not got[0]:
        return None
    cost = costs.moe_experts_cost(ctx["cell"].config, means["expert_tokens"], means["touched"])
    least, _ = flops.roofline_seconds(*cost, ctx["peaks"])
    return 100.0 * least / (got[0] / 1e3)
