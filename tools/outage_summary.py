#!/usr/bin/env python
"""outage_summary — aggregate backend-availability probe logs.

    python tools/outage_summary.py probe.log [more.log ...]
    python tools/outage_summary.py --json probe.log
    python tools/outage_summary.py probe.log --bench-json bench_row.json

A watcher writes one line per probe: ``<epoch-seconds> <STATE> <detail>``
where STATE is ``TPU_UP`` (probe saw a healthy accelerator) or ``DOWN``
(probe failed; detail is the last stderr line).  The raw logs were
write-only; this renders what the round verdicts actually need: total
up/down time, availability, and the longest DOWN window per log.

Interval attribution: the span between consecutive probes belongs to the
*earlier* probe's state (the probe cadence is ~4-6 min, so this is the
finest resolution the data supports).  The span after the final probe is
unknown and excluded.  Exit 0 on success, 2 when no parseable probe lines
were found in any input.

``--bench-json`` joins the logs' DOWN windows against a benchmark
artifact's init diagnostics (init_attempts/init_detail/fallback — emitted
by bench.py via resilience.backend.InitReport): was the recorded init
failure inside a DOWN window the watcher independently observed?  Accepts
both raw bench output and the driver-wrapped ``{"parsed": {...}}`` form;
the time join needs the ``init_ts`` key (emitted since the library init
path landed) — older artifacts without it report the overlap as unknown.

``--telemetry-jsonl`` joins the logs' DOWN windows against a telemetry
JSONL dump's ``kind="autopilot"`` decision records (docs/elastic.md
§autopilot): a post-mortem then shows what the autopilot DID during each
outage — which signal fired, whether it resized or suppressed, and the
dp move — instead of reconstructing it from scattered logs.  Decisions
carry a wall-clock ``ts``; records without one are counted but cannot be
joined.

``--blackbox`` joins the logs' DOWN windows against per-rank flight-
recorder dumps (``blackbox_rank*.json`` files or directories holding
them — docs/telemetry.md §flight recorder): each dump's wall-clock
``time_unix`` stamp places the watchdog stall / fatal signal on the same
absolute timeline as the probe log, answering whether a recorded hang
happened while the watcher independently saw the accelerator DOWN.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def parse_log(path: str) -> list[tuple[int, bool]]:
    """[(epoch_seconds, is_up), ...] in file order; unparseable lines skipped."""
    probes: list[tuple[int, bool]] = []
    with open(path, encoding="utf-8", errors="replace") as f:
        for line in f:
            parts = line.split(None, 2)
            if len(parts) < 2 or not parts[0].isdigit():
                continue
            state = parts[1].upper()
            if state not in ("TPU_UP", "UP", "DOWN"):
                continue
            probes.append((int(parts[0]), state != "DOWN"))
    return probes


def summarize(probes: list[tuple[int, bool]]) -> dict:
    up_s = down_s = 0
    transitions = 0
    longest_down = {"seconds": 0, "start": None, "end": None}
    run_start: int | None = None  # start epoch of the current DOWN run
    for (t0, state0), (t1, state1) in zip(probes, probes[1:]):
        span = max(0, t1 - t0)
        if state0:
            up_s += span
        else:
            down_s += span
            if run_start is None:
                run_start = t0
        if state0 != state1:
            transitions += 1
        # a DOWN run ends when the *next* probe is up (or at the last probe)
        if run_start is not None and (state1 or (t1, state1) == probes[-1]):
            if t1 - run_start > longest_down["seconds"]:
                longest_down = {"seconds": t1 - run_start, "start": run_start, "end": t1}
            if state1:
                run_start = None
    observed = up_s + down_s
    return {
        "probes": len(probes),
        "probes_up": sum(1 for _, up in probes if up),
        "probes_down": sum(1 for _, up in probes if not up),
        "first_probe": probes[0][0] if probes else None,
        "last_probe": probes[-1][0] if probes else None,
        "observed_s": observed,
        "up_s": up_s,
        "down_s": down_s,
        "availability_pct": round(100.0 * up_s / observed, 1) if observed else None,
        "transitions": transitions,
        "longest_down_s": longest_down["seconds"],
        "longest_down_start": longest_down["start"],
        "longest_down_end": longest_down["end"],
    }


def down_windows(probes: list[tuple[int, bool]]) -> list[dict]:
    """Every DOWN window as {start, end, seconds}: from its first DOWN probe
    to the next UP probe (or the last probe for a trailing run) — the same
    attribution summarize() uses for longest_down."""
    windows: list[dict] = []
    run_start: int | None = None
    last = probes[-1] if probes else None
    for (t0, state0), (t1, state1) in zip(probes, probes[1:]):
        if not state0 and run_start is None:
            run_start = t0
        if run_start is not None and (state1 or (t1, state1) == last):
            windows.append({"start": run_start, "end": t1, "seconds": t1 - run_start})
            run_start = None
    return windows


def load_bench_diag(path: str) -> dict:
    """Init diagnostics out of a bench JSON artifact (raw bench.py output or
    the driver's {"parsed": {...}} wrapper)."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    parsed = data
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        parsed = data["parsed"]
    if not isinstance(parsed, dict):
        return {}
    keys = (
        "init_attempts", "init_detail", "platform_requested", "fallback",
        "init_ts", "platform",
    )
    return {k: parsed[k] for k in keys if parsed.get(k) is not None}


def join_bench(path: str, diag: dict, windows: list[dict]) -> dict:
    """Did this bench's init failure land inside an observed DOWN window?"""
    out = {"bench": path, **diag}
    out["init_failed"] = bool(diag.get("fallback")) or (
        (diag.get("init_attempts") or 0) > 1
    )
    ts = diag.get("init_ts")
    if ts is None:
        out["in_down_window"] = None  # pre-init_ts artifact: overlap unknown
        return out
    for window in windows:
        if window["start"] <= ts <= window["end"]:
            out["in_down_window"] = True
            out["down_window"] = window
            return out
    out["in_down_window"] = False
    return out


def load_autopilot_records(path: str) -> list[dict]:
    """``kind="autopilot"`` decision records out of a telemetry JSONL dump;
    unparseable lines are skipped (the dump interleaves every record
    kind)."""
    records: list[dict] = []
    with open(path, encoding="utf-8", errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except ValueError:
                continue
            if isinstance(record, dict) and record.get("kind") == "autopilot":
                records.append(record)
    return records


def _decision_summary(record: dict) -> dict:
    out = {
        "ts": record.get("ts"),
        "signal": record.get("signal"),
        "action": record.get("action"),
        "fired": bool(record.get("fired")),
        "suppressed": bool(record.get("suppressed")),
    }
    if record.get("reason"):
        out["reason"] = record["reason"]
    resize = record.get("resize")
    if isinstance(resize, dict):
        out["resize"] = {
            k: resize.get(k) for k in ("old_dp", "dp", "direction")
        }
    return out


def join_autopilot(path: str, records: list[dict], windows: list[dict]) -> dict:
    """What the autopilot did during each observed DOWN window — decisions
    whose ``ts`` falls inside the window, plus totals for decisions outside
    every window and records carrying no timestamp."""
    timed = [r for r in records if isinstance(r.get("ts"), (int, float))]
    per_window = []
    joined_ids = set()
    for window in windows:
        inside = [
            r for r in timed if window["start"] <= r["ts"] <= window["end"]
        ]
        joined_ids.update(id(r) for r in inside)
        per_window.append(
            {
                "window": window,
                "decisions": [_decision_summary(r) for r in inside],
                "fired": sum(1 for r in inside if r.get("fired")),
                "suppressed": sum(1 for r in inside if r.get("suppressed")),
            }
        )
    return {
        "telemetry": path,
        "decisions_total": len(records),
        "decisions_no_ts": len(records) - len(timed),
        "decisions_outside_windows": sum(
            1 for r in timed if id(r) not in joined_ids
        ),
        "windows": per_window,
    }


def render_autopilot_join(joined: dict) -> str:
    lines = [
        f"{joined['telemetry']}: {joined['decisions_total']} autopilot "
        f"decision(s) ({joined['decisions_outside_windows']} outside DOWN "
        "windows"
        + (
            f", {joined['decisions_no_ts']} without ts"
            if joined["decisions_no_ts"]
            else ""
        )
        + ")"
    ]
    for entry in joined["windows"]:
        w = entry["window"]
        lines.append(
            f"  DOWN {_utc(w['start'])} → {_utc(w['end'])} "
            f"({_hms(w['seconds'])}): {len(entry['decisions'])} decision(s), "
            f"{entry['fired']} fired, {entry['suppressed']} suppressed"
        )
        for d in entry["decisions"]:
            offset = (
                f"+{int(d['ts'] - w['start'])}s" if d.get("ts") is not None else "?"
            )
            verdict = "fired" if d["fired"] else (
                "suppressed" if d["suppressed"] else "quiet"
            )
            detail = f"    {offset} {d.get('action')}({d.get('signal')}) {verdict}"
            resize = d.get("resize")
            if resize and resize.get("old_dp") is not None:
                detail += f" dp {resize['old_dp']}->{resize['dp']}"
            if d.get("reason"):
                detail += f" ({d['reason']})"
            lines.append(detail)
    return "\n".join(lines)


def load_blackbox_dumps(path: str) -> list[dict]:
    """Per-rank blackbox payloads from a dump file or a directory of them
    (tools/blackbox_report.py owns the parsing rules)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import blackbox_report

    dumps = []
    for p in blackbox_report.find_dumps([path]):
        dump = blackbox_report.load_dump(p)
        if dump is not None:
            dumps.append(dump)
    return dumps


def join_blackbox(path: str, dumps: list[dict], windows: list[dict]) -> dict:
    """Did each rank's dump land inside an observed DOWN window?"""
    per_dump = []
    for dump in dumps:
        entry = {
            "rank": dump.get("rank"),
            "reason": dump.get("reason"),
            "collective_seq": dump.get("collective_seq"),
            "time_unix": dump.get("time_unix"),
        }
        ts = dump.get("time_unix")
        if ts is None:
            entry["in_down_window"] = None
        else:
            entry["in_down_window"] = False
            for window in windows:
                if window["start"] <= ts <= window["end"]:
                    entry["in_down_window"] = True
                    entry["down_window"] = window
                    break
        per_dump.append(entry)
    return {
        "blackbox": path,
        "dumps": per_dump,
        "in_down_windows": sum(1 for d in per_dump if d["in_down_window"]),
    }


def render_blackbox_join(joined: dict) -> str:
    lines = [
        f"{joined['blackbox']}: {len(joined['dumps'])} blackbox dump(s), "
        f"{joined['in_down_windows']} inside observed DOWN windows"
    ]
    for d in joined["dumps"]:
        if d["in_down_window"] is None:
            verdict = "no timestamp"
        elif d["in_down_window"]:
            w = d["down_window"]
            verdict = f"inside DOWN {_utc(w['start'])} → {_utc(w['end'])}"
        else:
            verdict = "NOT inside any observed DOWN window"
        lines.append(
            f"  rank {d['rank']} ({d['reason']}, seq={d['collective_seq']}) "
            f"at {_utc(d['time_unix'])}: {verdict}"
        )
    return "\n".join(lines)


def render_bench_join(joined: dict) -> str:
    label = "init failed" if joined["init_failed"] else "init ok"
    detail = (
        f"{joined['bench']}: {label} "
        f"(attempts={joined.get('init_attempts', '?')}"
        + (f", fallback={joined['fallback']}" if joined.get("fallback") else "")
        + ")"
    )
    if joined["in_down_window"] is None:
        verdict = "overlap unknown (no init_ts in bench JSON)"
    elif joined["in_down_window"]:
        w = joined["down_window"]
        verdict = (
            f"inside DOWN window {_utc(w['start'])} → {_utc(w['end'])} "
            f"({_hms(w['seconds'])})"
        )
    else:
        verdict = "NOT inside any observed DOWN window"
    return f"{detail}\n  {verdict}"


def _hms(seconds) -> str:
    if not seconds:
        return "0m"
    h, rem = divmod(int(seconds), 3600)
    m = rem // 60
    return f"{h}h{m:02d}m" if h else f"{m}m"


def _utc(epoch) -> str:
    if epoch is None:
        return "-"
    return time.strftime("%Y-%m-%d %H:%MZ", time.gmtime(epoch))


def render(path: str, s: dict) -> str:
    avail = f"{s['availability_pct']}%" if s["availability_pct"] is not None else "n/a"
    lines = [
        f"{path}: {s['probes']} probes "
        f"({_utc(s['first_probe'])} → {_utc(s['last_probe'])})",
        f"  up   {_hms(s['up_s']):>7}   down {_hms(s['down_s']):>7}   "
        f"availability {avail}   transitions {s['transitions']}",
        f"  longest DOWN window: {_hms(s['longest_down_s'])}"
        + (
            f" ({_utc(s['longest_down_start'])} → {_utc(s['longest_down_end'])})"
            if s["longest_down_start"] is not None
            else ""
        ),
    ]
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="outage_summary", description=__doc__)
    parser.add_argument("logs", nargs="+", help="probe log files")
    parser.add_argument("--json", action="store_true", help="machine output")
    parser.add_argument(
        "--bench-json",
        nargs="+",
        default=[],
        metavar="BENCH",
        help="BENCH_r*.json artifacts to join against the logs' DOWN windows",
    )
    parser.add_argument(
        "--telemetry-jsonl",
        nargs="+",
        default=[],
        metavar="JSONL",
        help="telemetry JSONL dumps whose kind=\"autopilot\" decision "
        "records are joined against the logs' DOWN windows",
    )
    parser.add_argument(
        "--blackbox",
        nargs="+",
        default=[],
        metavar="DUMP",
        help="blackbox_rank*.json flight-recorder dumps (or directories of "
        "them) whose wall-clock stamps are joined against the logs' DOWN "
        "windows",
    )
    args = parser.parse_args(argv)

    summaries = {}
    all_windows: list[dict] = []
    for path in args.logs:
        try:
            probes = parse_log(path)
        except OSError as e:
            print(f"outage_summary: cannot read {path}: {e}", file=sys.stderr)
            continue
        if not probes:
            print(f"outage_summary: no probe lines in {path}", file=sys.stderr)
            continue
        summaries[path] = summarize(probes)
        all_windows.extend(down_windows(probes))

    if not summaries:
        return 2

    bench_joins: list[dict] = []
    for path in args.bench_json:
        try:
            diag = load_bench_diag(path)
        except (OSError, ValueError) as e:
            print(f"outage_summary: cannot read bench {path}: {e}", file=sys.stderr)
            continue
        bench_joins.append(join_bench(path, diag, all_windows))

    autopilot_joins: list[dict] = []
    for path in args.telemetry_jsonl:
        try:
            records = load_autopilot_records(path)
        except OSError as e:
            print(
                f"outage_summary: cannot read telemetry {path}: {e}",
                file=sys.stderr,
            )
            continue
        autopilot_joins.append(join_autopilot(path, records, all_windows))

    blackbox_joins: list[dict] = []
    for path in args.blackbox:
        try:
            dumps = load_blackbox_dumps(path)
        except OSError as e:
            print(
                f"outage_summary: cannot read blackbox {path}: {e}",
                file=sys.stderr,
            )
            continue
        if not dumps:
            print(
                f"outage_summary: no blackbox dumps in {path}", file=sys.stderr
            )
            continue
        blackbox_joins.append(join_blackbox(path, dumps, all_windows))

    if args.json:
        payload: dict = dict(summaries)
        if bench_joins:
            payload["bench_join"] = bench_joins
        if autopilot_joins:
            payload["autopilot_join"] = autopilot_joins
        if blackbox_joins:
            payload["blackbox_join"] = blackbox_joins
        print(json.dumps(payload, indent=2))
    else:
        for path, s in summaries.items():
            print(render(path, s))
        for joined in bench_joins:
            print(render_bench_join(joined))
        for joined in autopilot_joins:
            print(render_autopilot_join(joined))
        for joined in blackbox_joins:
            print(render_blackbox_join(joined))
    return 0


if __name__ == "__main__":
    sys.exit(main())
