"""Device ms per decode step under the ``atpu_serve_ssm_*`` scopes (the Mamba-2
layers: in-projection, convolution, one-token recurrence, gate, norm, out)."""

from benchmark import hybrid_readers


def read(ctx):
    got = hybrid_readers.scope_ms(ctx, hybrid_readers.DECODE, "atpu_serve_ssm_")
    return None if got is None else got[0]
