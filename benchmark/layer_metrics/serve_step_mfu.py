"""The whole serving step's share of the chip's bf16 peak: 2 N FLOPs for every
prompt and output token the window processed, over the window and the peak.
Small by nature (decode is bound by bandwidth)."""

from benchmark import flops


def read(ctx):
    c = ctx["counters"]
    if not c.get("tokens_processed"):
        return None
    rate = c["tokens_processed"] / c["window_s"]
    return 100.0 * flops.forward_flops_per_token(ctx["cell"].config) * rate / (
        ctx["cell"].chips * ctx["peaks"]["bf16_flops_per_s"]
    )
