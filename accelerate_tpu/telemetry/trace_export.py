"""Chrome/Perfetto trace export (docs/telemetry.md §trace export) — default
OFF, zero cost unless called.

Joins three record streams onto one navigable timeline (chrome://tracing /
https://ui.perfetto.dev, "Trace Event Format" JSON):

* **flight events** (``telemetry/flightrec.py``) — instant events on the
  ``flight events`` track, monotonic-stamped at the source; the
  ``step_begin``/``step_end`` pair per captured call is also the *anchor*
  that places the device stream on the absolute axis;
* **host phases** — the ring's spans (``atpu/step/assemble``,
  ``atpu/dispatch``, ``atpu/step/writeback``, the compile phases
  ``atpu/trace`` / ``atpu/lower`` / ``atpu/compile``, ``atpu/gc``, and the
  engine step's ``atpu/serve/*``) as complete
  ("X") events on the ``host phases`` track, at the stamps they were taken
  at.  A step whose spans the ring no longer holds (it wrapped, or the
  recorder is off) falls back to its ``StepRecord``'s durations laid out in
  phase order from the step's begin stamp; ``dataloader_wait_ms`` always
  comes from the record and sits *before* the begin stamp (it was paid
  between calls);
* **device op timelines** (``DeviceStepRecord.top_ops`` from the sampled
  profiler) — complete events on the ``device ops`` track, laid
  sequentially from the step's begin stamp.  Placement within the step is
  synthetic (the parsed trace keeps durations, not cross-stream clocks);
  durations are real.

Everything is fail-soft: steps with no flight anchor are skipped, an
export error returns ``None`` — and nothing here ever issues a collective
(the module is rank-local-by-design; one trace file per process).
"""

from __future__ import annotations

import json
import os
from typing import Optional

from ..logging import get_logger
from . import flightrec

logger = get_logger(__name__)

_HOST_TID = 1
_DEVICE_TID = 2
_FLIGHT_TID = 3

# in-call StepRecord phases in execution order; dataloader_wait_ms is laid
# before the begin anchor (it precedes the captured call)
_PHASE_ORDER = ("assembly_ms", "trace_ms", "compile_ms", "dispatch_ms")


def _metadata(pid: int, rank: int) -> list[dict]:
    return [
        {"ph": "M", "pid": pid, "name": "process_name",
         "args": {"name": f"rank {rank}"}},
        {"ph": "M", "pid": pid, "tid": _HOST_TID, "name": "thread_name",
         "args": {"name": "host phases"}},
        {"ph": "M", "pid": pid, "tid": _DEVICE_TID, "name": "thread_name",
         "args": {"name": "device ops"}},
        {"ph": "M", "pid": pid, "tid": _FLIGHT_TID, "name": "thread_name",
         "args": {"name": "flight events"}},
    ]


def build_trace(telemetry=None, recorder: Optional[flightrec.FlightRecorder] = None) -> dict:
    """Assemble the Trace Event Format document (µs timestamps) from the
    flight ring plus — when a telemetry hub is given — its host
    ``StepRecord`` timeline and sampled ``DeviceStepRecord`` stream."""
    rec = recorder if recorder is not None else flightrec.recorder()
    rank = flightrec.resolve_rank()
    pid = rank
    events: list[dict] = _metadata(pid, rank)

    flight = rec.snapshot()
    step_begin: dict[int, float] = {}
    step_end: dict[int, float] = {}
    spanned_steps = set()  # captured calls whose host phases the ring holds
    for ev in flight:
        t_us = ev["t"] * 1e6
        if "dur_ms" in ev:
            args = {k: v for k, v in ev.items() if k not in ("kind", "t", "dur_ms")}
            events.append(
                {"ph": "X", "pid": pid, "tid": _HOST_TID, "ts": t_us,
                 "dur": ev["dur_ms"] * 1e3, "name": ev["kind"], "cat": "host",
                 "args": args}
            )
            if ev["kind"] == "atpu/step/assemble" and "step" in ev:
                spanned_steps.add(ev["step"])
            continue
        if ev["kind"] == "step_begin" and "step" in ev:
            step_begin.setdefault(ev["step"], t_us)
        elif ev["kind"] == "step_end" and "step" in ev:
            step_end[ev["step"]] = t_us
        name = ev["kind"]
        if ev["kind"] == "collective":
            name = f"collective:{ev.get('op', '?')} #{ev.get('cseq', '?')}"
        args = {k: v for k, v in ev.items() if k not in ("kind", "t")}
        events.append(
            {"ph": "i", "s": "t", "pid": pid, "tid": _FLIGHT_TID,
             "ts": t_us, "name": name, "args": args}
        )

    host_records = []
    device_records = []
    if telemetry is not None:
        try:
            host_records = [r.to_dict() for r in telemetry.timeline.records()]
            device_records = [d.to_dict() for d in telemetry.device_records]
        except Exception:
            host_records, device_records = [], []

    for record in host_records:
        step = record.get("step")
        begin = step_begin.get(step)
        if begin is None:
            continue  # no flight anchor (recorder disabled mid-run): skip
        wait_ms = record.get("dataloader_wait_ms") or 0.0
        if wait_ms > 0:
            events.append(
                {"ph": "X", "pid": pid, "tid": _HOST_TID,
                 "ts": begin - wait_ms * 1e3, "dur": wait_ms * 1e3,
                 "name": f"step {step}: dataloader_wait", "cat": "host",
                 "args": {"step": step}}
            )
        if step in spanned_steps:
            continue  # its phases are on the track already, at real stamps
        cursor = begin
        for phase in _PHASE_ORDER:
            ms = record.get(phase) or 0.0
            if ms <= 0:
                continue
            events.append(
                {"ph": "X", "pid": pid, "tid": _HOST_TID, "ts": cursor,
                 "dur": ms * 1e3,
                 "name": f"step {step}: {phase[:-3]}", "cat": "host",
                 "args": {"step": step, "key": record.get("key"),
                          "built": record.get("built")}}
            )
            cursor += ms * 1e3

    for record in device_records:
        step = record.get("step")
        begin = step_begin.get(step)
        if begin is None:
            continue
        cursor = begin
        for name, ms in record.get("top_ops") or []:
            if not isinstance(ms, (int, float)) or ms <= 0:
                continue
            events.append(
                {"ph": "X", "pid": pid, "tid": _DEVICE_TID, "ts": cursor,
                 "dur": ms * 1e3, "name": str(name), "cat": "device",
                 "args": {"step": step, "ms": ms}}
            )
            cursor += ms * 1e3

    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "source": "accelerate_tpu.telemetry.trace_export",
            "rank": rank,
            "collective_seq": rec.collective_seq,
        },
    }


def export_chrome_trace(path: str, telemetry=None,
                        recorder: Optional[flightrec.FlightRecorder] = None
                        ) -> Optional[str]:
    """Write the joined trace JSON; returns the path, or ``None`` on any
    failure (export is observability — it must never crash the run)."""
    try:
        doc = build_trace(telemetry=telemetry, recorder=recorder)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        return path
    except Exception as exc:
        logger.warning("chrome trace export to %r failed: %s", path, exc)
        return None


def validate_trace(doc) -> list[str]:
    """Structural well-formedness of a Trace Event Format document; ``[]``
    when valid.  ``tests/test_telemetry.py`` additionally
    asserts the three tracks carry events for the same steps."""
    errors: list[str] = []
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"), list):
        return ["document is not an object with a traceEvents list"]
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict):
            errors.append(f"event {i} is not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "i", "I", "M", "B", "E", "C"):
            errors.append(f"event {i}: unknown ph {ph!r}")
            continue
        if "name" not in ev:
            errors.append(f"event {i}: no name")
        if ph in ("X", "i", "I") and not isinstance(ev.get("ts"), (int, float)):
            errors.append(f"event {i}: ph={ph} without numeric ts")
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            errors.append(f"event {i}: complete event without numeric dur")
    return errors
