"""Readers for the cells of a mixed layer plan (PR 31): the ``atpu/serve/moe_load``
records the service puts on the program's ring beside every program execution
(the tokens each held expert got, summed over the expert layers, and how many
(layer, expert) pairs got any), and the ``atpu_serve_ssm_*`` / ``atpu_serve_moe_*``
scopes of its programs.  A program that records none of it gives every reader
``None``.
"""

from __future__ import annotations

import bisect
import statistics

from . import span_readers, trace_reduce

MOE_LOAD = "atpu/serve/moe_load"
DECODE, PREFILL = "_decode_jit", "_prefill_jit"


def loads(ctx, phase: str):
    """The part's ``moe_load`` records of one phase (``decode``, ``prefill``),
    or ``None``."""
    got = span_readers.in_part(ctx, MOE_LOAD)
    if not got:
        return None
    return [e for e in got if e.get("phase") == phase] or None


def decode_means(ctx):
    """Per decode execution over the part: live slots, (layer, expert) pairs
    touched, tokens through routed experts."""
    got = loads(ctx, "decode")
    if got is None:
        return None
    return {
        "live": statistics.fmean(e["active"] for e in got),
        "touched": statistics.fmean(e["touched"] for e in got),
        "expert_tokens": statistics.fmean(sum(e["per_expert"]) for e in got),
    }


def _texts(module: str):
    """``({instruction: scope}, every instruction name)`` over EVERY registered
    program whose module name holds ``module``: the trace's prefill events come
    from one program a prompt bucket, all under one module name, and the
    registry's own lookups answer with the newest alone, so names that only
    another bucket's text has would count as unknown.  The newest wins a
    clash.  Nothing is registered: the maps live in the reader's context."""
    try:
        from accelerate_tpu.telemetry import profiler

        programs = [p for p in profiler.registered_programs() if module in p.name]
    except (ImportError, AttributeError):
        return {}, frozenset()
    scopes, names = {}, set()
    for program in programs:  # oldest first
        scopes.update(program.scope_map())
        names.update(program.instruction_names())
    return scopes, frozenset(names)


def _by_scope(ctx, module: str):
    """``({scope: device ms per execution}, module ms per execution)`` of the
    trace's ``module`` events, as ``span_readers.device_ms_by_scope`` takes
    them but over every bucket's text; ``None`` off the chip, without the
    registry, or under its coverage."""
    memo = ctx.setdefault("_hybrid_by_scope", {})
    if module in memo:
        return memo[module]
    memo[module] = None
    if ctx.get("planes") is None:
        return None
    scopes, names = _texts(module)
    totals, unscoped, module_ns, runs, known, seen = {}, {}, 0, 0, 0, 0
    for plane in trace_reduce.device_planes(ctx["planes"]):
        modules = sorted([s, s + d] for n, s, d in trace_reduce.line_events(plane, trace_reduce.MODULE_LINE)
                         if module in n)
        runs += len(modules)
        module_ns += sum(e - s for s, e in modules)
        starts = [m[0] for m in modules]
        for n, s, d in trace_reduce.line_events(plane, trace_reduce.OP_LINE):
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= modules[k][1] or n.startswith(trace_reduce.CONTAINERS):
                continue
            seen += d
            known += d if n in names else 0
            scope = scopes.get(n, "unscoped")
            totals[scope] = totals.get(scope, 0) + d
            if scope == "unscoped":
                unscoped[n] = unscoped.get(n, 0) + d
    if not runs or not names or known < span_readers.COVERAGE * seen:
        if runs:
            span_readers.say(f"{module}: {known / max(1, seen):.1%} of the module's device time falls on "
                             f"instruction names in the {len(names)} its registered programs' texts have")
        return None
    by_scope = {k: v / 1e6 / runs for k, v in totals.items()}
    span_readers.say(f"{module}: {runs} executions, coverage {known / seen:.1%}, ms by scope "
                     + ", ".join(f"{k} {v:.3f}" for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])))
    # the instructions that carry no scope and take the most device time (the
    # compiler's own copies and prefetches)
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:8]
    span_readers.say(f"{module}: unscoped, by total ms in the trace: " + ", ".join(f"{n} {d / 1e6:.2f}" for n, d in top))
    memo[module] = by_scope, module_ns / 1e6 / runs
    return memo[module]


def scope_ms(ctx, module: str, prefix: str):
    """Device ms per execution of ``module`` under the scopes that start with
    ``prefix``, and the module's own ms per execution; or ``None``."""
    got = _by_scope(ctx, module)
    if got is None:
        return None
    by_scope, module_ms = got
    return sum(v for k, v in by_scope.items() if k.startswith(prefix)), module_ms


def traced_prefill_buckets(ctx):
    """``bucket_len`` of every prefill launched inside the traced window."""
    offset = span_readers.clock_offset(ctx) if ctx.get("planes") is not None else None
    events = span_readers.ring_events(ctx)
    if offset is None or not events:
        return None
    lo, hi = (t + offset for t in ctx["summary"]["window"])
    got = [e["bucket_len"] for e in span_readers.named(events, "atpu/serve/prefill_launch")
           if lo <= e["start_ns"] < hi]
    return got or None
