"""Tokens the busiest held expert got over the mean expert's, over the part's
decode steps and all expert layers: how uneven the router is."""

from benchmark import hybrid_readers


def read(ctx):
    got = hybrid_readers.loads(ctx, "decode")
    if got is None:
        return None
    per_expert = [sum(col) for col in zip(*(e["per_expert"] for e in got))]
    mean = sum(per_expert) / len(per_expert)
    return max(per_expert) / mean if mean else None
