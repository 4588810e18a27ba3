"""The whole serving step's share of the chip's bf16 peak on the Olmo-Hybrid
plan: forward FLOPs of every token the part processed (every layer's products,
the convolution and the one-token recurrence; the head once a sampled token)
over the part and the peak, from the launches the service put on the ring.
Small by nature (decode is bound by bandwidth)."""

from benchmark import flops_olmo_hybrid as costs
from benchmark import plan_readers


def read(ctx):
    active, prompts = plan_readers.decode_active(ctx), plan_readers.prefill_lengths(ctx)
    if active is None and prompts is None:
        return None
    active, prompts, cfg = active or [], prompts or [], ctx["cell"].config
    tokens, sampled = sum(active) + sum(prompts), sum(active) + len(prompts)
    total = costs.token_flops(cfg) * tokens + costs.head_flops(cfg) * sampled
    return 100.0 * total / ctx["counters"]["window_s"] / (
        ctx["cell"].chips * ctx["peaks"]["bf16_flops_per_s"]
    )
