"""Share of the prefill programs' device time under the ``atpu_serve_gdn_*`` scopes."""

from benchmark import hybrid_readers

SCOPES = ("atpu_serve_gdn_",)


def read(ctx):
    got = hybrid_readers.scope_ms(ctx, hybrid_readers.PREFILL, SCOPES[0])
    return None if got is None or not got[1] or not got[0] else 100.0 * got[0] / got[1]
