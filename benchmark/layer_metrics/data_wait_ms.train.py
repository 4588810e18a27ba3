"""Mean host time of one ``next()`` on the prepared loader, over every step of
the window.  Source: the benchmark's own span."""


def read(ctx):
    c = ctx["counters"]
    return c["data_wait_s"] / c["data_calls"] * 1e3 if c.get("data_calls") else None
