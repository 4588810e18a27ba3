"""The one traffic generator.  A mix is a data file of parameters under
``benchmark/traffic/``; everything a run feeds the system is a pure function
of (mix, seed, window length).  Every seed gets the same multiset of sizes and
arrival gaps in an order of its own (a plain shuffle), so that seeds change the
order of the work and not its amount.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def train_rows(mix: dict, vocab_size: int, seed: int) -> np.ndarray:
    """``(distinct_batches * rows_per_step, seq_len)`` int32 ids, every row
    different, drawn from the published vocabulary."""
    rng = np.random.default_rng(seed)
    n = mix["distinct_batches"] * mix["rows_per_step"]
    return rng.integers(0, vocab_size, (n, mix["seq_len"]), dtype=np.int32)


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    due_s: float  # seconds after the window opens
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int


def _stratified(spec: dict, n: int) -> np.ndarray:
    """``n`` whole numbers at the mid-quantiles of the stated distribution."""
    if spec["dist"] != "loguniform":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    q = (np.arange(n) + 0.5) / n
    return np.rint(spec["low"] * (spec["high"] / spec["low"]) ** q).astype(int)


def serve_requests(mix: dict, vocab_size: int, seed: int, seconds: float) -> list:
    """Open-loop arrivals for a window of ``seconds``: ``round(rate * seconds)``
    requests.  The gaps between them are the mid-quantiles of the exponential
    distribution and the lengths the mid-quantiles of theirs; the seed shuffles
    each (gaps, prompt lengths and output budgets apart).  So a seed can put
    arrivals or long requests side by side, as a Poisson stream does, but the
    window's amount of work is the same for every seed."""
    rng = np.random.default_rng(seed)
    n = max(1, round(mix["rate_per_s"] * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    # every request is due inside the window; the scale is the same for every seed
    due = np.cumsum(gaps) * (seconds / (gaps.sum() + gaps.mean()))
    prompt_lens = rng.permutation(_stratified(mix["prompt_len"], n))
    budgets = rng.permutation(_stratified(mix["output_len"], n))
    return [
        ServeRequest(
            due_s=float(due[i]),
            prompt=rng.integers(0, vocab_size, int(prompt_lens[i]), dtype=np.int32),
            max_new_tokens=int(budgets[i]),
        )
        for i in range(n)
    ]


def sample_indices(n: int, k: int, seed: int, always: int) -> list:
    """``k`` of ``range(n)`` drawn from the seed, ``always`` among them."""
    rng = np.random.default_rng([seed, 0x5A3])
    rest = [i for i in rng.permutation(n) if i != always][: max(0, min(k, n) - 1)]
    return [always] + [int(i) for i in rest]
