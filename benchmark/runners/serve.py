"""Serving cells: ``DecodeService`` under an open loop.  Requests arrive on
the mix's schedule whether or not earlier ones have finished; each is passed
to ``submit(arrival_t=due)`` so that its clock starts when it was due, and how
late the generator really was is reported beside it.

One thread drives the service, as a user of the library would: hand over what
is due, take one engine step, repeat.  The window closes ``seconds`` after it
opened; requests due inside it are then served to their end (no new arrivals)
so that the tails are the tails of all requests.  The reference runs once the
window has closed, the peak has been read and the program's state is freed.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare, harness, stats, traffic

DRAIN_LIMIT_S = 60.0


def build(cell, seed: int):
    import jax.numpy as jnp

    from accelerate_tpu import Accelerator, DecodeService, ServingConfig

    cfg, mix = cell.config, cell.mix
    accelerator = Accelerator(mixed_precision=cfg["precision"]["mixed_precision"])
    params = cell.reference.init_params(cfg, seed, jnp.bfloat16)
    model = cell.family.build_model(cfg, params)
    del params
    model = accelerator.prepare(model)
    model.eval()
    service = DecodeService(model, ServingConfig(**mix["service"]))
    return {"accelerator": accelerator, "model": model, "service": service}


def warm_up(cell, service) -> None:
    """Every prefill bucket the mix can reach, and the decode program."""
    from accelerate_tpu.serving import bucket_length

    mix = cell.mix
    bucket = mix["service"]["prompt_bucket"]
    lens = {bucket_length(n, bucket) for n in range(mix["prompt_len"]["low"], mix["prompt_len"]["high"] + 1)}
    for b in sorted(lens):
        service.submit(np.ones(b, np.int32), max_new_tokens=3)
    service.run()
    for rid in list(service.results):
        service.pop_result(rid)


def drive(cell, service, requests, seconds: float, tracer, step_wrapper=None,
          drain_limit_s: float = DRAIN_LIMIT_S) -> dict:
    """The measured window and the drain after it."""
    step = step_wrapper(service.step) if step_wrapper else service.step
    n = len(requests)
    rids, late_ms, steps = [None] * n, [], []
    before = dict(service.stats)
    t0 = time.perf_counter()
    t_end = t0 + seconds
    trace_from = t_end - cell.mix["trace_seconds"] if tracer.on else None
    i, closed, untraced = 0, None, None
    while True:
        now = time.perf_counter()
        while i < n and t0 + requests[i].due_s <= now:
            r = requests[i]
            due = t0 + r.due_s
            late_ms.append((time.perf_counter() - due) * 1e3)
            with harness.span("service.submit"):
                rids[i] = service.submit(r.prompt, r.max_new_tokens, arrival_t=due)
            i += 1
        if closed is None and now >= t_end:
            closed = {"t": now, "stats": dict(service.stats),
                      "queue_depth": service.fleet_signal()["queue_depth"]}
        if trace_from is not None and untraced is None and now >= trace_from:
            untraced = {"t": now, "stats": dict(service.stats)}
            tracer.start()
        if closed is not None and now - closed["t"] > drain_limit_s:
            break
        if service.has_work:
            t_s = time.perf_counter()
            with harness.span("service.step"):
                step()
            steps.append((t_s, time.perf_counter()))
        elif i < n:
            with harness.span("generator.sleep"):
                time.sleep(max(0.0, min(0.002, t0 + requests[i].due_s - time.perf_counter())))
        elif closed is not None:
            break
        else:
            with harness.span("generator.sleep"):
                time.sleep(max(0.0, min(0.002, t_end - time.perf_counter())))
    tracer.stop()
    return {
        "t0": t0, "closed": closed, "before": before, "untraced": untraced or closed,
        "rids": rids, "late_ms": late_ms, "steps": steps,
    }


def _queue_waits(done: list, steps: list) -> list:
    """Admission minus due, per admitted request.  A request is admitted inside
    one ``step()``: when that step began, or when the request admitted before
    it in the same step got its first token."""
    starts = np.asarray([s for s, _ in steps])
    by_step = {}
    for req in done:
        k = int(np.searchsorted(starts, req.first_token_t, side="right")) - 1
        by_step.setdefault(k, []).append(req)
    waits = []
    for k, reqs in by_step.items():
        cursor = steps[k][0]
        for req in sorted(reqs, key=lambda r: r.first_token_t):
            waits.append(max(0.0, cursor - req.submitted_t) * 1e3)
            cursor = req.first_token_t
    return waits


def measure(cell, service, requests, run: dict) -> tuple:
    """End-to-end metrics over the whole window, and the counters the per-layer
    readers take: in a traced run those of the part before the profiler started
    (it runs over the window's last ``trace_seconds`` and the drain), so that
    its cost is not in them."""
    results = [service.results.get(rid) for rid in run["rids"]]
    finished = [r for r in results if r is not None and r.state == "done" and r.done_t is not None]
    first = [r for r in results if r is not None and r.first_token_t is not None]
    n = len(requests)
    ttft = [r.ttft_ms for r in first]
    tpot = [r.tpot_ms for r in finished if r.tpot_ms is not None]
    closed, before, part = run["closed"], run["before"], run["untraced"]
    window_s = closed["t"] - run["t0"]
    keys = ("decode_tokens", "admitted", "steps", "occupancy_sum", "decode_syncs")
    tokens_out = sum(closed["stats"][k] - before[k] for k in ("decode_tokens", "admitted"))
    d = {k: part["stats"][k] - before[k] for k in keys}
    part_s = part["t"] - run["t0"]
    early = [r for r in first if r.submitted_t <= part["t"]]
    n_early = sum(1 for r in requests if run["t0"] + r.due_s <= part["t"])
    prompt_in_part = sum(r.prompt_len for r in first if r.first_token_t <= part["t"])
    by_due = [r.ttft_ms if r is not None and r.first_token_t is not None else float("inf") for r in results]
    # BENCHMARK.json says which of these a cell reports (benchmark/README.md:
    # only what its runs can bound); all go to the log, where noise.py reads them
    metrics = {
        **{f"serve_ttft_p{p}_ms": stats.percentile_with_missing(ttft, n - len(ttft), p)
           for p in (50, 80, 95)},
        "serve_tpot_p95_ms": stats.percentile_with_missing(tpot, n - len(tpot), 95),
        "serve_tokens_per_s": tokens_out / window_s,
    }
    counters = {
        "window_s": part_s,
        "tokens_processed": d["decode_tokens"] + d["admitted"] + prompt_in_part,
        "queue_wait_ms": _queue_waits(early, run["steps"]),
        "never_admitted": n_early - len(early),
        "occupancy_mean": d["occupancy_sum"] / d["steps"] if d["steps"] else None,
        "host_syncs_per_token": d["decode_syncs"] / d["decode_tokens"] if d["decode_tokens"] else None,
        "generator_late_ms": run["late_ms"][:n_early],
        "ttft_ms": [r.ttft_ms for r in early], "ttft_missing": n_early - len(early),
        # over the whole run, drain included: decode step j of a request reads
        # its prompt and the j tokens before it
        "decode_steps": len(run["steps"]),
        "kv_token_steps": float(sum(
            (len(r.tokens) - 1) * r.prompt_len + (len(r.tokens) - 1) * len(r.tokens) / 2
            for r in finished
        )),
        "recompile_events": service.recompile_events,
        "requests": n, "finished": len(finished),
        "drain_s": max(0.0, run["steps"][-1][1] - closed["t"]) if run["steps"] else 0.0,
        # under the knee the queue is empty at the close and the halves agree (sweep.py)
        "queue_depth_at_close": closed["queue_depth"],
        "ttft_p50_halves_ms": [stats.percentile_with_missing(h, 0, 50) for h in (by_due[: n // 2], by_due[n // 2:])],
    }
    return metrics, counters, finished


def _stalls(steps: list) -> str:
    """For the log: where a run that reads far off lost its time (a step that
    took seconds, or a pause between steps while requests were in flight)."""
    took = np.asarray([e - s for s, e in steps]) * 1e3
    pauses = np.asarray([b[0] - a[1] for a, b in zip(steps, steps[1:])]) * 1e3
    if not len(pauses):
        return "under two steps"
    slow = took[took > 2 * np.median(took)]
    return (f"{len(took)} steps, median {np.median(took):.1f} ms, longest {took.max():.1f} ms, "
            f"{len(slow)} over twice the median taking {slow.sum():.0f} ms in all; "
            f"pauses between steps over 50 ms: {np.sort(pauses[pauses > 50]).round().tolist()[-8:]}")


def reference_gaps(cell, seed: int, sample: list, precision="float32") -> list:
    """For each sampled request ``(prompt_len, ids)``: the gaps of its served
    tokens below the reference's best, by one full forward, no cache."""
    import jax.numpy as jnp

    cfg = cell.config
    params = cell.reference.init_params(cfg, seed, jnp.bfloat16)
    return [
        cell.reference.served_token_gaps(
            params, ids, prompt_len, cfg["n_head"], cell.mix["service"]["max_request_len"],
            precision=precision,
        )
        for prompt_len, ids in sample
    ]


def readings(cell, seed: int, kinds, seconds: float) -> dict:
    """What the limit is set from, for one seed: a short window at the cell's
    own load, then the served tokens' gap (``program``) and, at the same
    prompts and tokens, the gap of the token that a lower precision puts first
    (``control``: the one the configuration names for serving; ``bf16``: its own)."""
    got = timed(cell, seed, seconds, harness.TracedWindow(False, cell.name), time.perf_counter())
    metrics, counters, sample = got["metrics"], got["counters"], got["sample"]
    unfinished = counters["requests"] - counters["finished"]
    out = {"program": compare.serve_numbers(reference_gaps(cell, seed, sample), unfinished)}
    out["program"]["tokens_compared"] = int(sum(len(ids) - n for n, ids in sample))
    out["program"].update({k: v for k, v in metrics.items()})
    precisions = {"control": cell.config["precision"]["control"]["serve"], "bf16": "bfloat16"}
    for kind in kinds:
        gaps = reference_gaps(cell, seed, sample, precision=precisions.get(kind, kind))
        out[kind] = compare.serve_numbers(gaps, 0)
    return out


def take_sample(cell, finished: list, seed: int) -> list:
    """``check_requests`` finished requests drawn from the seed, the longest
    among them, as ``(prompt_len, prompt then served tokens)``."""
    longest = max(range(len(finished)), key=lambda k: len(finished[k].output_ids))
    picked = traffic.sample_indices(len(finished), cell.mix["check_requests"], seed, longest)
    return [(finished[k].prompt_len, np.asarray(finished[k].output_ids)) for k in picked]


def timed(cell, seed: int, seconds: float, tracer, t_start: float, step_wrapper=None) -> dict:
    """Set-up, the window and its drain.  Returns plain host data only, so that
    every reference to the program's device state dies with this frame."""
    prog = build(cell, seed)
    service = prog["service"]
    harness.log("built; warming up")
    warm_up(cell, service)
    warm_recompiles = service.recompile_events
    requests = traffic.serve_requests(cell.mix, cell.config["vocab_size"], seed, seconds)
    setup_s = time.perf_counter() - t_start
    run_ = drive(cell, service, requests, seconds, tracer, step_wrapper)
    metrics, counters, finished = measure(cell, service, requests, run_)
    counters["recompile_events"] -= warm_recompiles
    metrics["setup_s"] = setup_s
    harness.log(
        f"window closed: {counters['finished']}/{counters['requests']} finished, "
        f"drain {counters['drain_s']:.2f}s, {metrics}; queue at close {counters['queue_depth_at_close']}, "
        f"TTFT p50 of the halves {counters['ttft_p50_halves_ms']}; "
        f"{counters['recompile_events']} recompiles; {_stalls(run_['steps'])}"
    )
    out = {
        "metrics": metrics, "counters": counters,
        "memory_peak_bytes": harness.memory_peak_bytes(),
        "sample": take_sample(cell, finished, seed) if finished else [],
    }
    del service, finished
    harness.free_program(prog)
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_start: float, device: dict,
        step_wrapper=None) -> dict:
    """One run.  ``step_wrapper`` lets a test break the timed path underneath
    (it gets ``service.step`` and returns what is called in its place)."""
    import gc

    tracer = harness.TracedWindow(trace, cell.name)
    got = timed(cell, seed, seconds, tracer, t_start, step_wrapper)
    gc.collect()
    harness.log(f"program freed: {harness.memory_in_use_bytes()} bytes still in use")
    counters = got["counters"]
    t_ref = time.perf_counter()
    gaps = reference_gaps(cell, seed, got["sample"])
    numbers = compare.serve_numbers(gaps, counters["requests"] - counters["finished"])
    notes = {
        "reference_s": time.perf_counter() - t_ref,
        "tokens_compared": int(sum(len(g) for g in gaps)), "requests_compared": len(gaps),
        "tokens_off_the_reference_best": int(sum((g > 0).sum() for g in gaps)),
    }
    harness.log(f"reference done: {notes}")
    return {
        "attempted": counters["requests"], "failed": counters["requests"] - counters["finished"],
        "metrics": got["metrics"], "numbers": numbers, "counters": counters,
        "device": dict(device, memory_peak_bytes=got["memory_peak_bytes"]),
        "tracer": tracer, "notes": notes,
    }
