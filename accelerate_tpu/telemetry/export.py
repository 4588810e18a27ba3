"""Pillar 4 — export: telemetry events → tracker fleet / JSONL.

Two sinks:

* :class:`TelemetryTracker` — a ``GeneralTracker`` that *bridges*: it holds
  the run's :class:`~.Telemetry` plus the already-resolved concrete trackers
  (JSONL/TensorBoard/WandB/...) as delegates, and on every ``log()`` call
  (i.e. every ``accelerator.log``) drains the not-yet-exported telemetry
  records into them as flat ``telemetry/...`` metrics.  ``Accelerator.
  init_trackers`` appends one automatically when telemetry is enabled, so
  training loops that already log metrics get step-phase timing and
  recompile causes in the same backends for free.
* :func:`write_jsonl` — the full retained history as one JSON object per
  line, ``kind``-tagged (``meta``/``step``/``recompile``/``program``/
  ``resources``/``summary``); the schema ``tools/telemetry_report.py``
  renders and ``tests/test_telemetry.py`` validates.  Schema reference:
  docs/telemetry.md.
"""

from __future__ import annotations

import json
from typing import Optional

from ..tracking import GeneralTracker


def flatten_record(record: dict) -> dict:
    """One telemetry record → flat ``telemetry/<kind>/<field>`` metrics.

    Numbers stay numbers (scalar backends plot them); strings ride along for
    backends with text support (TensorBoard add_text, JSONL); nested dicts
    (per-device byte maps) flatten one level."""
    kind = record.get("kind", "event")
    out: dict = {}
    for field, value in record.items():
        if field == "kind":
            continue
        name = f"telemetry/{kind}/{field}"
        if isinstance(value, dict):
            for sub, subvalue in value.items():
                if isinstance(subvalue, (int, float)):
                    out[f"{name}/{sub}"] = subvalue
        elif isinstance(value, (list, tuple)):
            if value and all(isinstance(v, str) for v in value):
                out[name] = "; ".join(value)
        elif isinstance(value, (int, float, str, bool)):
            out[name] = value
    return out


class TelemetryTracker(GeneralTracker):
    """Bridge tracker: drains telemetry records into delegate trackers."""

    requires_logging_directory = False

    def __init__(self, telemetry, delegates=(), **kwargs):
        super().__init__()
        self.telemetry = telemetry
        self.delegates = [t for t in delegates if not isinstance(t, TelemetryTracker)]
        # the bridge is the only export-queue consumer; enqueueing starts
        # (and the pre-bridge history backfills) the moment one attaches
        telemetry.attach_export_sink()

    @property
    def name(self) -> str:
        return "telemetry"

    @property
    def tracker(self):
        return self.telemetry

    def store_init_configuration(self, values: dict) -> None:
        pass  # config belongs to the delegates, which already received it

    def log(self, values: dict, step: Optional[int] = None, **kwargs) -> None:
        # `values` were already logged to the delegates by Accelerator.log;
        # this call is purely the piggyback trigger for a drain
        self.flush(step=step)

    def flush(self, step: Optional[int] = None) -> int:
        """Export every pending telemetry record; returns how many.

        Records land on the *piggyback* step (the user's ``accelerator.log``
        step) — never telemetry's internal captured-call index, which lives
        on a different axis (backends like WandB enforce a monotonic run
        step, and jumping to the internal index would make them drop the
        user's own metrics).  Each record's index still rides along as the
        ``telemetry/<kind>/step`` field."""
        records = self.telemetry.drain()
        for record in records:
            flat = flatten_record(record)
            if not flat:
                continue
            for tracker in self.delegates:
                tracker.log(flat, step=step)
        return len(records)

    def finish(self) -> None:
        self.flush()
        # an ACCELERATE_TELEMETRY_JSONL / TelemetryKwargs(jsonl_path=...) run
        # also lands the full dump at end_training
        self.telemetry.write_jsonl()


def write_jsonl(telemetry, path: str) -> str:
    # export_records(): the fleet-merged view when aggregate_fleet() ran
    # (rank-tagged records + the kind="fleet" skew record), rank-local
    # history otherwise
    with open(path, "w", encoding="utf-8") as f:
        for record in telemetry.export_records():
            f.write(json.dumps(record, default=float) + "\n")
    return path
