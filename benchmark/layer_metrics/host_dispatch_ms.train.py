"""Mean host time of one call of the captured step (return of the call, not
the sync), over every step of the window.  Source: the benchmark's own span."""


def read(ctx):
    c = ctx["counters"]
    return c["dispatch_s"] / c["steps"] * 1e3 if c.get("steps") else None
