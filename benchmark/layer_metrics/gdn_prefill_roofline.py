"""Least time for the chunked delta-rule scan over a prefill's bucket
(``flops_olmo_hybrid.gdn_prefill_cost``, mean over the prefills launched inside
the traced window) over the prefill programs' device time under
``atpu_serve_gdn_scan``.  The program runs the scan's small products in float32
at highest precision (six bfloat16 passes); the algorithm's operations count once."""

import statistics

from benchmark import flops, hybrid_readers
from benchmark import flops_olmo_hybrid as costs

SCOPES = ("atpu_serve_gdn_scan",)


def read(ctx):
    got, buckets = hybrid_readers.scope_ms(ctx, hybrid_readers.PREFILL, SCOPES[0]), hybrid_readers.traced_prefill_buckets(ctx)
    if got is None or buckets is None or not got[0]:
        return None
    cfg = ctx["cell"].config
    chunk = cfg["assumed_sizes"]["chunk_size"]
    least = statistics.fmean(
        flops.roofline_seconds(*costs.gdn_prefill_cost(cfg, b, chunk), ctx["peaks"])[0] for b in buckets
    )
    return 100.0 * least / (got[0] / 1e3)
