"""Bytes one decode step of the Olmo-Hybrid plan must move (every weight once
except the table's unread rows, the live slots' recurrent state read and
written once, the live keys and values) over the decode program's median device
time, as a share of the HBM peak."""

from benchmark import flops_olmo_hybrid as costs
from benchmark import plan_readers, readers


def read(ctx):
    step_ms, live, c = readers.decode_step_ms(ctx), plan_readers.live_mean(ctx), ctx["counters"]
    if step_ms is None or live is None or not c.get("decode_steps"):
        return None
    live_kv = c["kv_token_steps"] / c["decode_steps"]
    nbytes = costs.decode_step_bytes(ctx["cell"].config, live, live_kv)
    return 100.0 * nbytes / (step_ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]
