"""Share of the traced window in which a collective runs on a chip while no
compute operation does; the worst chip."""

from benchmark import trace_reduce


def read(ctx):
    if ctx.get("planes") is None:
        return None
    exposed = trace_reduce.exposed_collective_seconds(ctx["planes"], ctx["summary"]["window"])
    if not exposed:
        return None
    return 100.0 * max(exposed) / ctx["summary"]["window_s"]
