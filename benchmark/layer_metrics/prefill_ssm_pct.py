"""Share of the prefill programs' device time under the ``atpu_serve_ssm_*`` scopes."""

from benchmark import hybrid_readers


def read(ctx):
    got = hybrid_readers.scope_ms(ctx, hybrid_readers.PREFILL, "atpu_serve_ssm_")
    return None if got is None or not got[1] else 100.0 * got[0] / got[1]
