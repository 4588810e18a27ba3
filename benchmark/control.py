#!/usr/bin/env python3
"""Read what ``correct``'s limits are set from, on the chip, many seeds in one
process (set-up is long, so the benchmark's own runs never do this):

    python3 benchmark/control.py --workload <cell> --seeds 12 --others 3 \\
        --kinds control,bf16,half_batch [--seconds 10]

For each of ``--seeds`` seeds: the program's numbers against the reference (the
lower readings).  For the first ``--others`` of them also the numbers of each
of ``--kinds`` put in the program's place (the upper readings).  One JSON line
per seed on standard output and in ``chiprun_out/control-<cell>.jsonl``; under
``correct`` in it, what ``harness.decide`` makes of each against the cell's
limits: true for the program, false for every stand-in that the limits catch.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _verdict(numbers: dict, limits: dict) -> bool:
    """``harness.decide`` over the numbers read here (a window's own are not)."""
    from benchmark import harness

    held = {k: limits[k] for k in limits if k in numbers}
    return harness.decide({k: numbers[k] for k in held}, held)[0]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2_200_000_001)
    parser.add_argument("--others", type=int, default=3)
    parser.add_argument("--kinds", default="control")
    parser.add_argument("--seconds", type=float, default=10.0)
    args = parser.parse_args(argv)

    from benchmark import harness

    opened = harness.open_cell(args.workload, "control.py")
    if opened is None:
        return 3
    cell, device = opened
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    kinds = [k for k in args.kinds.split(",") if k]
    with open(os.path.join(out_dir, f"control-{cell.name}.jsonl"), "a") as f:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            got = cell.runner.readings(cell, seed, kinds if i < args.others else [], args.seconds)
            details = got.pop("leaf_gaps", None)
            if details is not None:
                with open(os.path.join(out_dir, f"leafgaps-{cell.name}.jsonl"), "a") as g:
                    g.write(json.dumps({"seed": seed, **details}) + "\n")
            verdicts = {kind: _verdict(numbers, cell.limits) for kind, numbers in got.items()}
            line = json.dumps({"cell": cell.name, "seed": seed, "device": device,
                               "seconds": round(time.perf_counter() - t0, 1),
                               "correct": verdicts, **got})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
