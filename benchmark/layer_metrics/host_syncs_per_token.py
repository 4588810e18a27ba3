"""``DecodeService.host_syncs_per_token``: blocking device-to-host reads per
emitted decode token (1.0 at ``decode_steps`` 1)."""


def read(ctx):
    return ctx["counters"].get("host_syncs_per_token")
