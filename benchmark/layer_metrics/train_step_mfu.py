"""The whole training step's share of the chips' bf16 peak: FLOPs the model
needs per token (``flops.train_flops_per_token``: 6 N + 6 L S d, nothing
recomputed counted) times the window's tokens per second, over chips times
peak."""

from benchmark import flops


def read(ctx):
    c, cell = ctx["counters"], ctx["cell"]
    if not c.get("tokens"):
        return None
    per_token = flops.train_flops_per_token(cell.config, cell.mix["seq_len"])
    rate = c["tokens"] / c["window_s"]
    return 100.0 * per_token * rate / (cell.chips * ctx["peaks"]["bf16_flops_per_s"])
