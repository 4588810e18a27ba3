"""Device ms per decode step under the ``atpu_serve_gdn_*`` scopes (the gated
delta-rule mixers: projections, convolution, one-token recurrence with the
state pool's rows read and written, norm, gate, out)."""

from benchmark import hybrid_readers

SCOPES = ("atpu_serve_gdn_",)


def read(ctx):
    got = hybrid_readers.scope_ms(ctx, hybrid_readers.DECODE, SCOPES[0])
    return None if got is None else got[0]
