"""Device ms per decode step under the ``atpu_serve_moe_*`` scopes (the expert
layers: route, the held experts' products, the shared expert)."""

from benchmark import hybrid_readers


def read(ctx):
    got = hybrid_readers.scope_ms(ctx, hybrid_readers.DECODE, "atpu_serve_moe_")
    return None if got is None else got[0]
