#!/usr/bin/env python3
"""Which of a cell's metrics its runs can bound end to end (README, "The yardstick"):

    python3 benchmark/noise.py --set a1.err a2.err ... [--set b1.err b2.err ...]

A file holds one run: its result line, or the serve runner's log (every metric it
computes, also those the cell does not report).  Per metric and set: median,
``stats.spread``, the spread as the check takes it, and the range without the run
farthest from the median as a share of it; "end to end" where the check's spread
is at most ADMIT in every set.  Not part of a run.
"""

import argparse
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import stats  # noqa: E402

ADMIT = 0.07  # a spread read from six runs wanders by some 1.4x, and no bound may pass 0.1
LOGGED = re.compile(r"'([\w.]+)': ([0-9.e+-]+|inf|nan)")


def values_of(path: str) -> dict:  # from the last line of the file that holds any metric
    with open(path) as f:
        for line in reversed(f.read().splitlines()):
            if line.startswith("{") and '"metrics"' in line:
                return {k: v["value"] for k, v in json.loads(line)["metrics"].items()}
            if "window closed:" in line and LOGGED.search(line):
                return {k: float(v) for k, v in LOGGED.findall(line)}
    return {}


def without_farthest(values) -> list:
    middle = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - middle))[:-1]


def check_spread(values) -> float:
    """The spread as the driver's check takes it: "a spread leaves out the run
    farthest from its median where that narrows it" (ledger, PR 29)."""
    return min(stats.spread(values), stats.spread(without_farthest(values)))


def trimmed_range(values) -> float:
    kept = without_farthest(values)
    return (max(kept) - min(kept)) / statistics.median(values)


def admitted(sets) -> bool:
    return all(check_spread(values) <= ADMIT for values in sets)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--set", dest="sets", action="append", nargs="+", required=True)
    runs = [[values_of(path) for path in files] for files in parser.parse_args().sets]
    for name in sorted(set.intersection(*(set(run) for one in runs for run in one))):
        sets = [[run[name] for run in one] for one in runs]
        if all(statistics.median(v) for v in sets):  # a count that reads 0 has no share
            cols = [f"{statistics.median(v):.6g} {stats.spread(v):.4f} {check_spread(v):.4f} {trimmed_range(v):.4f}"
                    for v in sets]
            print(f"{name:24s}", *cols, "end to end" if admitted(sets) else "per-layer only", sep="  ")
