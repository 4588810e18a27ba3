#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell by name (``cells.py``), runs it on the chips this machine
holds, and prints one JSON object as the last line of standard output.  Without
the chips the cell asks for it exits with code 3 and prints no result; it has
no CPU mode.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def report(cell, out: dict, trace: bool, units: dict) -> tuple:
    """(result line, correct) from what a runner returned."""
    from benchmark import flops, harness

    correct, compared = harness.decide(out["numbers"], cell.limits)
    device, breakdown = out["device"], None
    if trace:
        summary = out["tracer"].reduce()
        device = dict(device, busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = summary["breakdown"]
        ctx = {
            "cell": cell, "counters": out["counters"], "planes": out["tracer"].planes,
            "summary": summary, "peaks": flops.load_peaks(device["kind"]),
        }
        metrics = {}
        for name in cell.per_layer:
            value = cell.layer_metric(name).read(ctx)
            if value is not None:
                metrics[name] = value
    else:
        metrics = {k: v for k, v in out["metrics"].items() if k in cell.end_to_end}
    harness.print_compared(compared, correct)
    line = harness.result_line(
        correct=correct, attempted=out["attempted"], failed=out["failed"], metrics=metrics,
        units=units, device=device, compared=compared, breakdown=breakdown,
    )
    return line, correct


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import harness

    opened = harness.open_cell(args.workload, "run.py")
    if opened is None:
        return 3
    cell, device = opened
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    out = cell.runner.run(cell, args.seed, args.seconds, bool(args.trace), T_START, device)
    line, _ = report(cell, out, bool(args.trace), units)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
