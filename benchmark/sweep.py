#!/usr/bin/env python3
"""Find the knee of a serving mix once, on the chip: one service, one window
per offered rate, the mix otherwise as its file says.

    python3 benchmark/sweep.py --workload <cell> --rates 4,6,8,10,12 --seconds 30

The knee is the highest rate at which the queue is not growing when the window
closes, every request due in its first three quarters had completed by then,
and the median TTFT of the window's two halves agree (PR 30 took: within 1.25x
of each other, at most 2 requests waiting at the close, in every sweep made).  The cell's fixed rate is 0.8 of it, written into the traffic file by
hand with this table in PERF.md; the benchmark never searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--seed", type=int, default=2_300_000_011)
    args = parser.parse_args(argv)

    from benchmark import harness, stats, traffic

    opened = harness.open_cell(args.workload, "sweep.py")
    if opened is None:
        return 3
    cell, device = opened
    serve = cell.runner
    prog = serve.build(cell, args.seed)
    service = prog["service"]
    serve.warm_up(cell, service)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix["rate_per_s"] = rate
        requests = traffic.serve_requests(cell.mix, cell.config["vocab_size"], args.seed, args.seconds)
        run = serve.drive(cell, service, requests, args.seconds, harness.TracedWindow(False, cell.name),
                          drain_limit_s=180.0)
        metrics, counters, _ = serve.measure(cell, service, requests, run)
        close, t0 = run["closed"]["t"], run["t0"]
        early = [rid for rid, r in zip(run["rids"], requests) if r.due_s <= 0.75 * args.seconds]
        early_done = sum(
            1 for rid in early
            if (q := service.results.get(rid)) is not None and q.done_t is not None and q.done_t <= close
        )
        waits = sorted(counters["queue_wait_ms"])
        first_half, second_half = counters["ttft_p50_halves_ms"]
        print(json.dumps({
            "rate_per_s": rate, "device": device["kind"], "requests": len(requests),
            "queue_depth_at_close": counters["queue_depth_at_close"],
            "early_requests": len(early), "early_done_by_close": early_done,
            "ttft_p50_first_half_ms": first_half, "ttft_p50_second_half_ms": second_half,
            **metrics,
            "occupancy_mean": counters["occupancy_mean"],
            "queue_wait_p95_ms": stats.percentile_with_missing(waits, 0, 95),
            "generator_late_p95_ms": stats.percentile_with_missing(counters["generator_late_ms"], 0, 95),
            "drain_s": counters["drain_s"], "steps": counters["decode_steps"],
        }), flush=True)
        for rid in list(service.results):
            service.pop_result(rid)
    return 0


if __name__ == "__main__":
    sys.exit(main())
