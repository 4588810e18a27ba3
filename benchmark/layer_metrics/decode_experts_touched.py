"""Held experts that got at least one token, per expert layer and decode step
(mean over the part; of ``n_routed_experts`` held)."""

from benchmark import flops_nemotron_h as costs
from benchmark import hybrid_readers


def read(ctx):
    means = hybrid_readers.decode_means(ctx)
    if means is None:
        return None
    return means["touched"] / costs.counts(ctx["cell"].config)["experts"]
