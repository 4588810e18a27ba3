"""Device ms per decode step under ``atpu_serve_mlp`` (the dense SwiGLU MLP of
every layer of a plan whose layers carry one)."""

from benchmark import hybrid_readers

SCOPES = ("atpu_serve_mlp",)


def read(ctx):
    got = hybrid_readers.scope_ms(ctx, hybrid_readers.DECODE, SCOPES[0])
    return None if got is None or not got[0] else got[0]
