"""Readers of what the program itself records (PR 27): the spans and instants
on its flight recorder's ring (``accelerate_tpu/telemetry/flightrec.py``) and
the named scopes of its compiled programs (``telemetry/profiler.py``'s
registry).  The metrics under ``layer_metrics/`` that use them are one-liners.

The ring's clock is Unix-epoch ns; a profiler session rebases every plane to
its own start, so the trace's clock is the ring's less one constant a
session.  ``clock_offset`` finds it from one pair of events that must nest (a
benchmark span around a program span) and then proves it on all of them.

A program without the ring's span API or the registry (the parent of PR 27)
gives every reader ``None``; so does anything a reader cannot vouch for, with
the reason on standard error.
"""

from __future__ import annotations

import bisect
import statistics
import sys

from . import stats, trace_reduce

SERVE_STEP = "atpu/serve/step"
# benchmark span that encloses one engine step / one captured call
BENCH_ANCHOR = {"serve": "service.step", "train": "step.dispatch"}
SERVE_SPANS = (
    "atpu/serve/step", "atpu/serve/admit", "atpu/serve/prefill_launch",
    "atpu/serve/prefill_sync", "atpu/serve/decode_launch", "atpu/serve/decode_sync",
    "atpu/serve/emit",
)
HOST_WORK = (  # not a blocking read: the chip waits for these
    "atpu/serve/admit", "atpu/serve/prefill_launch", "atpu/serve/decode_launch",
    "atpu/serve/emit",
)
NEST_SLACK_NS = 200_000
NEST_SHARE = 0.99
COVERAGE = 0.95


def say(what: str) -> None:
    print(f"benchmark/span_readers: {what}", file=sys.stderr, flush=True)


def _memo(ctx, key, make):
    memo = ctx.setdefault("_span_readers", {})
    if key not in memo:
        memo[key] = make()
    return memo[key]


# -- the ring ------------------------------------------------------------------

def ring_events(ctx):
    """Everything the ring retains, oldest first, or ``None``: no span API
    (an older program), recorder off, or the ring has dropped events."""
    def make():
        try:
            from accelerate_tpu.telemetry import flightrec
        except ImportError:
            return None
        rec = flightrec.recorder()
        if not hasattr(rec, "spans"):
            return None
        if not rec.enabled:
            say("the flight recorder is off (ACCELERATE_FLIGHTREC=0)")
            return None
        events, lost = rec.spans(0, rec.now_ns())
        if lost:
            say(f"the ring dropped {lost} events: raise ACCELERATE_FLIGHTREC_CAPACITY")
            return None
        return events

    return _memo(ctx, "events", make)


def named(events, name: str) -> list:
    return [e for e in events if e["name"] == name]


def dur_ms(e) -> float:
    return (e["end_ns"] - e["start_ns"]) / 1e6


def captured_calls(events) -> list:
    """One ``{"assemble", "dispatch", "writeback"}`` of spans per captured call
    that has all three, in order."""
    calls, cur = [], {}
    for e in events:
        short = {"atpu/step/assemble": "assemble", "atpu/dispatch": "dispatch",
                 "atpu/step/writeback": "writeback"}.get(e["name"])
        if short is None:
            continue
        if short == "assemble":
            cur = {}
        cur[short] = e
        if short == "writeback" and len(cur) == 3:
            calls.append(cur)
            cur = {}
    return calls


def program_anchors(ctx, events) -> list:
    """``[start_ns, end_ns]`` of every engine step or captured call."""
    if ctx["cell"].mix["kind"] == "serve":
        return [[e["start_ns"], e["end_ns"]] for e in named(events, SERVE_STEP)]
    return [[c["assemble"]["start_ns"], c["writeback"]["end_ns"]] for c in captured_calls(events)]


def bench_anchors(ctx) -> list:
    name = BENCH_ANCHOR[ctx["cell"].mix["kind"]]
    return sorted(
        [s, s + d]
        for p in ctx["planes"] if p["name"].startswith(trace_reduce.HOST_PREFIX)
        for ln in p["lines"] for n, s, d in ln["events"] if n == name
    )


def nesting(program: list, bench: list, offset: int) -> tuple:
    """``(nested, in_window)``: of the program anchors that start inside the
    benchmark's first-to-last anchor once moved by ``offset``, how many lie
    inside one of the benchmark's with no more than the slack."""
    if not bench:
        return 0, 0
    starts = [b[0] for b in bench]
    nested = inside = 0
    for s, e in program:
        s, e = s - offset, e - offset
        if s < bench[0][0] - NEST_SLACK_NS or s > bench[-1][1]:
            continue
        inside += 1
        k = bisect.bisect_right(starts, s + NEST_SLACK_NS) - 1
        if k >= 0 and bench[k][0] - NEST_SLACK_NS <= s and e <= bench[k][1] + NEST_SLACK_NS:
            nested += 1
    return nested, inside


def clock_offset(ctx):
    """Ring clock minus trace clock, in ns, or ``None``.  Taken from the last
    benchmark anchor and the last program anchor (nothing runs after them),
    then held to the proof: at least 99% of the program's anchors in the
    trace nest in the benchmark's within 0.2 ms."""
    def make():
        events = ring_events(ctx)
        if events is None or ctx.get("planes") is None:
            return None
        program, bench = program_anchors(ctx, events), bench_anchors(ctx)
        if not program or not bench:
            say(f"no anchors to set the clocks by ({len(program)} program, {len(bench)} benchmark)")
            return None
        (ps, pe), (bs, be) = program[-1], bench[-1]
        offset = ((ps - bs) + (pe - be)) // 2
        nested, inside = nesting(program, bench, offset)
        if not inside or nested < NEST_SHARE * inside:
            say(f"clocks do not agree: {nested} of {inside} program anchors nest in the benchmark's")
            return None
        say(f"clock offset {offset} ns; {nested} of {inside} program anchors nest within 0.2 ms")
        return offset

    return _memo(ctx, "offset", make)


def part(ctx):
    """``(lo, hi)`` on the ring clock: the ``counters["window_s"]`` seconds
    before the traced window opened, which is what the counters cover.  With
    no trace (a rehearsal off the chip) the same length up to the program's
    last span."""
    events = ring_events(ctx)
    if not events:
        return None
    length = int(ctx["counters"]["window_s"] * 1e9)
    if ctx.get("planes") is not None:
        offset = clock_offset(ctx)
        if offset is None:
            return None
        hi = ctx["summary"]["window"][0] + offset
    else:
        hi = max(e["end_ns"] for e in events if e["name"].startswith("atpu/"))
    return hi - length, hi


def in_part(ctx, name: str):
    """The ring's events of that name that end inside the part, or ``None``."""
    span = part(ctx)
    if span is None:
        return None
    lo, hi = span
    return [e for e in named(ring_events(ctx), name) if lo <= e["end_ns"] < hi]


# -- host-span metrics -----------------------------------------------------------

def median_ms(ctx, name: str):
    got = in_part(ctx, name)
    return statistics.median(dur_ms(e) for e in got) if got else None


def admission_wait_p95_ms(ctx):
    """p95 of admitted − submitted over the requests submitted in the part; a
    request the ring never saw finish counts above every finite value."""
    span = part(ctx)
    if span is None:
        return None
    lo, hi = span
    events = ring_events(ctx)
    admitted = {e["rid"]: e.get("admitted") for e in named(events, "serve/request")}
    submitted = [e for e in named(events, "serve/submit") if lo <= e["submitted"] < hi]
    if not submitted:
        return None
    waits = [
        (admitted[e["rid"]] - e["submitted"]) / 1e6
        for e in submitted if admitted.get(e["rid"]) is not None
    ]
    return stats.percentile_with_missing(waits, len(submitted) - len(waits), 95)


def prefill_host_ms(ctx):
    """Median over the part's admitted requests of launch + first-token read."""
    launches, syncs = in_part(ctx, "atpu/serve/prefill_launch"), in_part(ctx, "atpu/serve/prefill_sync")
    if not launches or syncs is None:
        return None
    sync_of = {e["rid"]: e for e in syncs}
    both = [dur_ms(e) + dur_ms(sync_of[e["rid"]]) for e in launches if e["rid"] in sync_of]
    return statistics.median(both) if both else None


def step_host_ms(ctx):
    """Median over the part's engine steps of the step less the blocking reads
    inside it: what the host does while the chip could be waiting."""
    steps = in_part(ctx, SERVE_STEP)
    if not steps:
        return None
    events = ring_events(ctx)
    syncs = sorted(
        (e for e in events if e["name"] in ("atpu/serve/decode_sync", "atpu/serve/prefill_sync")),
        key=lambda e: e["start_ns"],
    )
    starts = [e["start_ns"] for e in syncs]
    host = []
    for step in steps:
        k = bisect.bisect_left(starts, step["start_ns"])
        blocked = 0.0
        while k < len(syncs) and syncs[k]["end_ns"] <= step["end_ns"]:
            blocked += dur_ms(syncs[k])
            k += 1
        host.append(dur_ms(step) - blocked)
    return statistics.median(host)


def captured_call_mean_ms(ctx, which: str):
    """Mean per captured call of one of its three spans, over the part."""
    span = part(ctx)
    if span is None:
        return None
    lo, hi = span
    calls = [c for c in captured_calls(ring_events(ctx)) if lo <= c["writeback"]["end_ns"] < hi]
    return statistics.fmean(dur_ms(c[which]) for c in calls) if calls else None


# -- device time, laid against the program's spans and scopes ---------------------

def idle_under_host_pct(ctx):
    """Share of the traced window in which the first chip is idle and the
    innermost program span over the gap is host work, not a blocking read."""
    offset = clock_offset(ctx) if ctx.get("planes") is not None else None
    if offset is None:
        return None
    window = ctx["summary"]["window"]
    ring = [
        [e["name"], e["start_ns"] - offset, e["end_ns"] - e["start_ns"]]
        for e in ring_events(ctx) if e["name"] in SERVE_SPANS
    ]
    planes = trace_reduce.device_planes(ctx["planes"]) + [
        {"name": trace_reduce.HOST_PREFIX + " (the program's ring)",
         "lines": [{"name": "ring", "events": ring}]}
    ]
    split = trace_reduce.idle_gaps_by_span(planes, SERVE_SPANS, window, k=len(SERVE_SPANS) + 1)
    say("idle seconds by innermost program span: " + ", ".join(f"{n} {s:.4f}" for n, s in split))
    under = sum(s for n, s in split if n in HOST_WORK)
    return 100.0 * under / ((window[1] - window[0]) / 1e9)


def device_ms_by_scope(ctx, module_needle: str):
    """``({scope: device ms per execution}, module ms per execution)`` of the
    program whose module-line events hold ``module_needle``: each op-line
    event inside one of them goes to its instruction's ``atpu`` scope
    (``unscoped`` where it has none; a fused instruction carries its root's).
    ``None`` off the chip, without the registry, or when under 95% of the
    module's device time falls on instruction names its text has."""
    def make():
        if ctx.get("planes") is None:
            return None
        try:
            from accelerate_tpu.telemetry import profiler
            names = profiler.instruction_names(module_needle)
            scopes = profiler.scope_map(module_needle)
        except (ImportError, AttributeError):
            return None
        totals, module_ns, runs, known, seen = {}, 0, 0, 0, 0
        for plane in trace_reduce.device_planes(ctx["planes"]):
            modules = sorted(
                [s, s + d] for n, s, d in trace_reduce.line_events(plane, trace_reduce.MODULE_LINE)
                if module_needle in n
            )
            if not modules:
                continue
            runs += len(modules)
            module_ns += sum(e - s for s, e in modules)
            starts = [m[0] for m in modules]
            for n, s, d in trace_reduce.line_events(plane, trace_reduce.OP_LINE):
                if n.startswith(trace_reduce.CONTAINERS):
                    continue
                k = bisect.bisect_right(starts, s) - 1
                if k < 0 or s >= modules[k][1]:
                    continue
                seen += d
                known += d if n in names else 0
                scope = scopes.get(n, "unscoped")
                totals[scope] = totals.get(scope, 0) + d
        if not runs:
            return None
        if not names:
            say(f"{module_needle}: the program's scope registry has no such program")
            return None
        if known < COVERAGE * seen:
            say(f"{module_needle}: only {known / max(1, seen):.1%} of the module's device time "
                "falls on instruction names in the registered program's text")
            return None
        by_scope = {k: v / 1e6 / runs for k, v in totals.items()}
        say(f"{module_needle}: {runs} executions, coverage {known / seen:.1%}, ms by scope "
            + ", ".join(f"{k} {v:.3f}" for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])))
        return by_scope, module_ns / 1e6 / runs

    return _memo(ctx, ("scopes", module_needle), make)


DECODE_GROUPS = {
    "kv_pool": ("atpu_serve_kv_write", "atpu_serve_kv_gather"),
    "attend": ("atpu_serve_attend",),
    "weights": ("atpu_serve_qkv", "atpu_serve_out_mlp", "atpu_serve_head"),
}


def decode_group_ms(ctx, group: str):
    """Device ms per execution of the decode program under one group of its
    scopes; ``other`` is the module's time less the three groups (unscoped
    ops, the embed, and the gaps between ops), so the four sum to the
    module's mean time."""
    got = device_ms_by_scope(ctx, "_decode_jit")
    if got is None:
        return None
    by_scope, module_ms = got
    grouped = {g: sum(by_scope.get(s, 0.0) for s in scopes) for g, scopes in DECODE_GROUPS.items()}
    return module_ms - sum(grouped.values()) if group == "other" else grouped[group]


def train_scope_ms(ctx, scope: str):
    got = device_ms_by_scope(ctx, "jit_traced")
    return got[0].get(scope, 0.0) if got is not None else None
