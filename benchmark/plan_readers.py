"""Readers for the cells of a mixed layer plan without expert layers (PR 36):
what the service puts on the program's ring at every launch — the live slots of
a decode execution (``atpu/serve/decode_launch``, ``active``) and a prefill's
true and bucketed lengths (``atpu/serve/prefill_launch``).  A program that
records none of it gives every reader ``None``.
"""

from __future__ import annotations

import statistics

from . import span_readers

DECODE_LAUNCH, PREFILL_LAUNCH = "atpu/serve/decode_launch", "atpu/serve/prefill_launch"


def decode_active(ctx):
    """Live slots of every decode execution launched in the part, or ``None``."""
    got = span_readers.in_part(ctx, DECODE_LAUNCH)
    active = [e["active"] for e in got or () if "active" in e]
    return active or None


def live_mean(ctx):
    active = decode_active(ctx)
    return None if active is None else statistics.fmean(active)


def prefill_lengths(ctx):
    """True prompt length of every prefill launched in the part, or ``None``."""
    got = span_readers.in_part(ctx, PREFILL_LAUNCH)
    lens = [e["prompt_len"] for e in got or () if "prompt_len" in e]
    return lens or None
