"""Readers of set-up and compilation (PR 42): the spans the program's flight
recorder writes for every jaxpr trace, MLIR lowering and XLA compile or
persistent-cache load that JAX reports (``atpu/trace``, ``atpu/lower``,
``atpu/compile`` with ``fun`` and ``cache``), beside its own set-up phases
(``atpu/setup/prepare``, ``atpu/serve/init``) and the warm-up's engine steps
and captured calls.

The set-up part is every ring event that ends before the window opened:
``span_readers.part(ctx)[0]`` on the ring clock, so these readers share its
clock proof and give ``None`` where it does (a ring that dropped events, the
recorder off).  The window runs from there to the run's last engine step or
captured call.  A compile-phase span nested in another (an inner jit traced
inside an outer one, an eager op run while tracing) belongs to the outer one:
each reader counts the outermost alone.

A program whose recorder has no compile listener (the parent of PR 42) gives
every reader ``None``.
"""

from __future__ import annotations

import bisect
import sys

from . import span_readers

TRACE, LOWER, COMPILE = "atpu/trace", "atpu/lower", "atpu/compile"
PHASES = (TRACE, LOWER, COMPILE)
GC = "atpu/gc"
# stamps are taken when JAX reports a phase, a few microseconds after it ended
NEST_SLACK_NS = 20_000


def say(what: str) -> None:
    print(f"benchmark/setup_readers: {what}", file=sys.stderr, flush=True)


def outermost(events) -> list:
    """The compile-phase spans that lie inside no other, oldest first."""
    out = []
    for e in sorted(events, key=lambda e: (e["start_ns"], -e["end_ns"])):
        if out and e["end_ns"] <= out[-1]["end_ns"] + NEST_SLACK_NS:
            continue
        while out and e["start_ns"] <= out[-1]["start_ns"] + NEST_SLACK_NS:
            out.pop()  # it starts with e and ends before it: e holds it
        out.append(e)
    return out


def union_s(events) -> float:
    """Seconds covered by the union of the events' intervals."""
    total, reach = 0, None
    for e in sorted(events, key=lambda e: e["start_ns"]):
        s, t = e["start_ns"], e["end_ns"]
        if reach is None or s > reach:
            total, reach = total + t - s, t
        elif t > reach:
            total, reach = total + t - reach, t
    return total / 1e9


def _s(events) -> float:
    return sum(e["end_ns"] - e["start_ns"] for e in events) / 1e9


def _loaded(e) -> bool:
    return e.get("cache") == "hit"


def _ring(ctx):
    """``(events, window open)`` on the ring clock, or ``None``."""
    try:
        from accelerate_tpu.telemetry import flightrec
    except ImportError:
        return None
    if not hasattr(flightrec, "CompilePhases"):
        return None  # no compile listener: an older program
    events = span_readers.ring_events(ctx)
    span = span_readers.part(ctx) if events else None
    if span is None:
        return None
    return events, span[0]


def setup(ctx):
    """The set-up part's numbers, or ``None``; said once on standard error."""
    def make():
        got = _ring(ctx)
        if got is None:
            return None
        events, opened = got
        before = [e for e in events if e["name"].startswith("atpu/") and e["end_ns"] < opened]
        outer = outermost([e for e in before if e["name"] in PHASES])
        compiles = [e for e in outer if e["name"] == COMPILE]
        missed = [e for e in before if e["name"] == COMPILE and not _loaded(e)]
        out = {
            "program_s": union_s(before),
            "trace_s": _s(e for e in outer if e["name"] != COMPILE),
            "compile_s": _s(e for e in compiles if not _loaded(e)),
            "cache_load_s": _s(e for e in compiles if _loaded(e)),
            "programs_compiled": len(missed),
        }
        by_name = {}
        for e in before:
            if e["name"] not in PHASES:
                by_name.setdefault(e["name"], []).append(e)
        rec = _recorder_health()
        say(f"set-up: {out}; compiles {sum(1 for e in before if e['name'] == COMPILE)} "
            f"({sum(1 for e in before if e['name'] == COMPILE and _loaded(e))} loaded from the cache); "
            + ", ".join(f"{n} {union_s(v):.3f}s x{len(v)}" for n, v in sorted(by_name.items())
                        if n.startswith(("atpu/setup", "atpu/serve/init", "atpu/serve/step",
                                         "atpu/step", "atpu/dispatch", GC)))
            + f"; ring {rec}")
        by_fun = {}
        for e in missed:
            by_fun.setdefault((e.get("fun"), e.get("cache")), []).append(e)
        for (fun, cache), got in sorted(by_fun.items(), key=lambda kv: -_s(kv[1])):
            say(f"compiled in set-up: {fun} cache={cache} x{len(got)}, {_s(got) * 1e3:.1f} ms")
        return out

    memo = ctx.setdefault("_setup_readers", {})
    if "setup" not in memo:
        memo["setup"] = make()
    return memo["setup"]


def _recorder_health() -> str:
    from accelerate_tpu.telemetry import flightrec

    h = flightrec.recorder().health()
    return f"{h['events_total']} events of {h['capacity']}, {h['dropped_total']} dropped"


def setup_value(ctx, key: str):
    got = setup(ctx)
    return None if got is None else got[key]


def _anchors(ctx, events) -> list:
    """``(start_ns, end_ns, step)`` of every engine step or captured call."""
    if ctx["cell"].mix["kind"] == "serve":
        return [(e["start_ns"], e["end_ns"], e.get("step"))
                for e in span_readers.named(events, span_readers.SERVE_STEP)]
    return [(c["assemble"]["start_ns"], c["writeback"]["end_ns"], c["assemble"].get("step"))
            for c in span_readers.captured_calls(events)]


def _where(anchors, starts, e) -> str:
    k = bisect.bisect_right(starts, e["start_ns"]) - 1
    if k >= 0 and e["end_ns"] <= anchors[k][1]:
        return f"inside step {anchors[k][2]}"
    return "between steps"


def compile_s_in_window(ctx):
    """Seconds of outermost trace, lowering and compile (a cache load too)
    from the window's open to the run's last engine step or captured call;
    each is named on standard error with the step it fell in, as is every
    generation-2 collection of that stretch."""
    got = _ring(ctx)
    if got is None:
        return None
    events, opened = got
    anchors = sorted(_anchors(ctx, events))
    if not anchors:
        return None
    closed = anchors[-1][1]
    starts = [a[0] for a in anchors]
    inside = [e for e in events if opened <= e["end_ns"] <= closed]
    phases = outermost([e for e in inside if e["name"] in PHASES])
    for e in phases:
        say(f"in the window: {e['name']} {e.get('fun')} cache={e.get('cache', '-')} "
            f"{(e['end_ns'] - e['start_ns']) / 1e6:.1f} ms, {_where(anchors, starts, e)}")
    pauses = span_readers.named(inside, GC)
    if pauses:
        say(f"in the window: {len(pauses)} generation-2 collections, {_s(pauses):.3f} s; longest "
            + ", ".join(f"{(e['end_ns'] - e['start_ns']) / 1e6:.1f} ms {_where(anchors, starts, e)}"
                        for e in sorted(pauses, key=lambda e: e["start_ns"] - e["end_ns"])[:4]))
    return _s(phases)
