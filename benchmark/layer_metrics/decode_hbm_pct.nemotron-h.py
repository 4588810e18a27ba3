"""Bytes one decode step of a mixed layer plan must move (the mixers', routers',
shared experts' and head's weights once, the routed experts the counter saw
touched, the live slots' state read and written, the live keys and values) over
the decode program's median device time, as a share of the HBM peak."""

from benchmark import flops_nemotron_h as costs
from benchmark import hybrid_readers, readers


def read(ctx):
    step_ms, means, c = readers.decode_step_ms(ctx), hybrid_readers.decode_means(ctx), ctx["counters"]
    if step_ms is None or means is None or not c.get("decode_steps"):
        return None
    live_kv = c["kv_token_steps"] / c["decode_steps"]
    nbytes = costs.decode_step_bytes(ctx["cell"].config, means["live"], means["touched"], live_kv)
    return 100.0 * nbytes / (step_ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]
