"""Bytes one decode step must read (every weight once and the live keys and
values of the active slots, from counts) over the decode program's median
device time, as a share of the HBM peak."""

from benchmark import flops, readers


def read(ctx):
    step_ms = readers.decode_step_ms(ctx)
    c = ctx["counters"]
    if step_ms is None or not c.get("decode_steps"):
        return None
    live = c["kv_token_steps"] / c["decode_steps"]
    nbytes = flops.decode_step_bytes(ctx["cell"].config, live)
    return 100.0 * nbytes / (step_ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]
