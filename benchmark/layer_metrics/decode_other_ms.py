"""Device ms per decode step outside the three scope groups (embed, unscoped ops,
gaps between ops): with them it sums to the decode module's mean time."""

from benchmark import span_readers


def read(ctx):
    return span_readers.decode_group_ms(ctx, "other")
