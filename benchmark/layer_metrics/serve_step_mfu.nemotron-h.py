"""The whole serving step's share of the chip's bf16 peak on a mixed layer plan:
forward FLOPs of every token the part processed (mixers, routers, shared
experts; the head once a sampled token; the routed experts by the
(token, expert) products the program's counter counted) over the part and the
peak.  Small by nature (decode is bound by bandwidth)."""

from benchmark import flops_nemotron_h as costs
from benchmark import hybrid_readers


def read(ctx):
    decode, prefill = hybrid_readers.loads(ctx, "decode"), hybrid_readers.loads(ctx, "prefill")
    if decode is None and prefill is None:
        return None
    decode, prefill, cfg = decode or [], prefill or [], ctx["cell"].config
    tokens = sum(e["active"] for e in decode) + sum(e["tokens"] for e in prefill)
    sampled = sum(e["active"] for e in decode) + len(prefill)
    products = sum(sum(e["per_expert"]) for e in decode + prefill)
    total = (costs.token_flops(cfg) * tokens + costs.head_flops(cfg) * sampled
             + costs.expert_token_flops(cfg) * products)
    return 100.0 * total / ctx["counters"]["window_s"] / (
        ctx["cell"].chips * ctx["peaks"]["bf16_flops_per_s"]
    )
