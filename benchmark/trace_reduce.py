"""From a profiler trace to numbers.  The benchmark's own reduction, written
against the planes a TPU v5e trace really has (looked at by hand, PR 26):

* one plane per chip, ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds one
  event per executed HLO instruction, named by the instruction's whole text;
  ``XLA Modules`` one per executed program (``jit_traced(<hash>)`` for a
  captured train step); ``Steps`` and the module line span the gaps between
  ops, and ``Async XLA Ops`` holds copies that run beside the ops, so busy time
  is the union of the op line alone.
* ``/host:CPU`` holds one line per host thread; ``jax.profiler.TraceAnnotation``
  spans sit there under their own names.

``load`` turns an ``.xplane.pb`` into plain lists (also the shape of the test
fixture); every reduction below works on those lists.
"""

from __future__ import annotations

import glob
import os
import statistics

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:CPU"
COLLECTIVE_PREFIXES = (
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all",
    "async_collective", "send", "recv",
)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, keep_host_names=None) -> list:
    """``[{"name": plane, "lines": [{"name": line, "events": [[name, start_ns,
    duration_ns], ...]}]}]`` for the device planes and the host plane.  Of the
    host plane only events named in ``keep_host_names`` are kept."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(DEVICE_PREFIX)
        if not (is_device or plane.name.startswith(HOST_PREFIX)):
            continue
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            events = [
                [short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if is_device or keep_host_names is None or ev.name in keep_host_names
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def short_name(name: str) -> str:
    """An op-line event is named by its whole HLO text (``%fusion.16 = bf16[...]
    fusion(...)``); its operands can name other instructions, so everything
    from " = " on is dropped, and the leading ``%``."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_planes(planes: list) -> list:
    return [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]


def line_events(plane: dict, line_name: str) -> list:
    return [ev for ln in plane["lines"] if ln["name"] == line_name for ev in ln["events"]]


def union(intervals: list) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def _total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def op_intervals(plane: dict, window=None) -> list:
    iv = [[s, s + d] for _, s, d in line_events(plane, OP_LINE)]
    return _clip(iv, *window) if window else iv


def traced_window(planes: list, window_span: str = None) -> tuple:
    """The host span named ``window_span`` where the trace has it (the traced
    part of the measured window, idle ends included); else first op start to
    last op end over all chips.  In ns."""
    if window_span:
        for p in planes:
            if p["name"].startswith(HOST_PREFIX):
                for ln in p["lines"]:
                    for n, s, d in ln["events"]:
                        if n == window_span:
                            return s, s + d
    starts, ends = [], []
    for plane in device_planes(planes):
        for _, s, d in line_events(plane, OP_LINE):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("no operation ran on a device in the traced window")
    return min(starts), max(ends)


def busy_seconds(planes: list, window=None) -> list:
    """Per chip: seconds in which at least one operation ran."""
    window = window or traced_window(planes)
    return [_total(union(op_intervals(p, window))) / 1e9 for p in device_planes(planes)]


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVE_PREFIXES)


def exposed_collective_seconds(planes: list, window=None) -> list:
    """Per chip: seconds in which a collective runs and no compute op does."""
    window = window or traced_window(planes)
    out = []
    for plane in device_planes(planes):
        events = line_events(plane, OP_LINE)
        coll = _clip(union([[s, s + d] for n, s, d in events if is_collective(n)]), *window)
        comp = _clip(union([[s, s + d] for n, s, d in events if not is_collective(n)]), *window)
        hidden = 0
        j = 0
        for s, e in coll:
            while j < len(comp) and comp[j][1] <= s:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < e:
                hidden += min(e, comp[k][1]) - max(s, comp[k][0])
                k += 1
        out.append((_total(coll) - hidden) / 1e9)
    return out


def kernel_durations(planes: list, needle: str, line_name: str = OP_LINE) -> list:
    """Device seconds of every event whose name holds ``needle``, chip by chip
    then in order."""
    return [
        d / 1e9
        for plane in device_planes(planes)
        for n, _, d in line_events(plane, line_name)
        if needle in n
    ]


CONTAINERS = ("while", "conditional", "call")  # their events span their bodies' ops


def top_ops(planes: list, k: int = 10) -> list:
    """``[[instruction, seconds], ...]``: device operations by total time, every
    run of one instruction added up, the mean over the chips.  Loops and
    branches are left out: the ops of their bodies are on the line themselves."""
    chips = device_planes(planes)
    totals = {}
    for plane in chips:
        for n, _, d in line_events(plane, OP_LINE):
            if not n.startswith(CONTAINERS):
                totals[n] = totals.get(n, 0) + d
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9 / max(1, len(chips))] for n, d in ranked]


def idle_gaps_by_span(planes: list, span_names, window=None, k: int = 10) -> list:
    """``[[what the host was doing, seconds], ...]``: the first chip's idle gaps
    inside the window, each second of a gap given to the benchmark's host span
    that covers it (the innermost where they nest; ``(no span)`` otherwise)."""
    window = window or traced_window(planes)
    chips = device_planes(planes)
    if not chips:
        return []
    busy = union(op_intervals(chips[0], window))
    gaps, cursor = [], window[0]
    for s, e in busy:
        if s > cursor:
            gaps.append([cursor, s])
        cursor = max(cursor, e)
    if cursor < window[1]:
        gaps.append([cursor, window[1]])
    spans = sorted(
        (
            [s, s + d, n]
            for p in planes if p["name"].startswith(HOST_PREFIX)
            for ln in p["lines"] for n, s, d in ln["events"] if n in span_names
        ),
        key=lambda x: (x[0], -x[1]),
    )
    totals = {}
    for g0, g1 in gaps:
        covered = []
        # later-starting spans are the inner ones: let them claim first
        for s, e, n in reversed(spans):
            lo, hi = max(s, g0), min(e, g1)
            if hi <= lo:
                continue
            free = [[lo, hi]]
            for c0, c1 in covered:
                free = [
                    part for f0, f1 in free
                    for part in ([f0, min(f1, c0)], [max(f0, c1), f1]) if part[1] > part[0]
                ]
            got = _total(free)
            if got:
                totals[n] = totals.get(n, 0) + got
                covered.extend(free)
        rest = (g1 - g0) - _total(union(covered))
        if rest > 0:
            totals["(no span)"] = totals.get("(no span)", 0) + rest
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in ranked]


def summarize(planes: list, span_names, window_span: str = None) -> dict:
    """What every traced run reports under ``device`` and ``breakdown``."""
    window = traced_window(planes, window_span)
    busy = busy_seconds(planes, window)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": statistics.fmean(busy),
        "busy_s_by_chip": busy,
        "window": window,
        "breakdown": {
            "device_ops": top_ops(planes),
            "idle_gaps": idle_gaps_by_span(planes, span_names, window),
        },
    }
