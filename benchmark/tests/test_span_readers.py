"""The readers of the program's own spans and scopes (PR 27): on the tiny
cells' in-process rehearsal, on 60 ms of a real v5e trace with a hand-written
scope map, and on hand-made rings and planes where those have nothing to show."""

import json
import math
import os
import time

import pytest

import helpers
from accelerate_tpu.telemetry import flightrec, profiler
from accelerate_tpu.telemetry.flightrec import FlightRecorder
from benchmark import cells, harness, span_readers

SEED = 2**31 + 4242
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "v5e_train_trace_60ms.json")
HOST = {"serve": ["admission_wait_p95_ms", "prefill_host_ms", "step_host_ms.serve", "decode_sync_wait_ms"],
        "train": ["step_assemble_ms.train", "step_launch_ms.train", "step_writeback_ms.train"]}
DEVICE_READ = {"serve": ["idle_under_host_pct.serve", "decode_kv_pool_ms", "decode_attend_ms",
                         "decode_weights_ms", "decode_other_ms"],
               "train": ["train_update_device_ms", "train_head_loss_device_ms"]}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.fixture_repo(str(tmp_path_factory.mktemp("spans")))


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    monkeypatch.setattr(flightrec, "_RECORDER", FlightRecorder())
    monkeypatch.setattr(profiler, "_programs", {})
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 0)
    monkeypatch.setattr(harness, "memory_in_use_bytes", lambda: 0)


class FakeCell:
    def __init__(self, kind):
        self.mix = {"kind": kind}


@pytest.mark.parametrize("name, kind", [("tiny.serve", "serve"), ("tiny.train", "train")])
def test_readers_on_the_rehearsal_of_a_tiny_cell(root, name, kind):
    cell = cells.resolve(name, root)
    assert set(HOST[kind] + DEVICE_READ[kind]) <= set(cell.per_layer)
    out = cell.runner.run(cell, SEED, 0.5, False, time.perf_counter(), DEVICE)
    ctx = {"cell": cell, "counters": out["counters"], "planes": None, "summary": None, "peaks": None}
    host = {m: cell.layer_metric(m).read(ctx) for m in HOST[kind]}
    assert all(v is not None and math.isfinite(v) and v >= 0 for v in host.values()), host
    if kind == "train":  # the three spans are what the benchmark's own span times from outside
        outside = 1e3 * out["counters"]["dispatch_s"] / out["counters"]["steps"]
        assert sum(host.values()) == pytest.approx(outside, rel=0.25)
    else:
        assert host["decode_sync_wait_ms"] < out["counters"]["window_s"] * 1e3
    # no trace off the chip: the device readers have nothing to read
    assert {m: cell.layer_metric(m).read(ctx) for m in DEVICE_READ[kind]} == dict.fromkeys(DEVICE_READ[kind])
    # the program was freed, yet its decode / step program is still known by name
    needle = "_decode_jit" if kind == "serve" else "jit_traced"
    assert profiler.scope_map(needle)


def test_an_older_program_gives_every_reader_none(monkeypatch):
    class Old:  # a recorder without the span API, as the parent of PR 27 has
        enabled = True

    monkeypatch.setattr(flightrec, "_RECORDER", Old())
    ctx = {"cell": FakeCell("serve"), "counters": {"window_s": 1.0}, "planes": None, "summary": None}
    assert span_readers.ring_events(ctx) is None
    assert span_readers.admission_wait_p95_ms(ctx) is None and span_readers.step_host_ms(ctx) is None
    assert span_readers.median_ms(ctx, "atpu/serve/decode_sync") is None
    assert span_readers.captured_call_mean_ms(dict(ctx, cell=FakeCell("train")), "dispatch") is None


def test_a_ring_that_dropped_events_is_not_read(capsys):
    rec = FlightRecorder(capacity=16)
    flightrec._RECORDER = rec  # (the autouse fixture restores the module's own)
    for k in range(40):
        with rec.span("atpu/serve/step", step=k):
            pass
    ctx = {"cell": FakeCell("serve"), "counters": {"window_s": 1.0}, "planes": None, "summary": None}
    assert span_readers.step_host_ms(ctx) is None
    assert "dropped 24" in capsys.readouterr().err


# -- clocks ---------------------------------------------------------------------

def serve_ring_and_planes(steps=50, session_start=None, drift_ns=0):
    """A ring with ``steps`` engine steps of 10 ms, each 1 ms of admit, 1 ms of
    launch, 7 ms of sync and 1 ms of emit, and planes in which the benchmark's
    ``service.step`` wraps each by 20 us; the chip runs during syncs only."""
    rec = flightrec.recorder()
    t = rec.now_ns() - 2_000_000_000  # all of it in the ring's past
    session_start = t - 5_000_000 if session_start is None else session_start
    bench, ops = [], []
    for k in range(steps):
        s = t + k * 12_000_000
        rec.record_span("atpu/serve/admit", s, s + 1_000_000, admitted=0)
        rec.record_span("atpu/serve/decode_launch", s + 1_000_000, s + 2_000_000)
        rec.record_span("atpu/serve/decode_sync", s + 2_000_000, s + 9_000_000)
        rec.record_span("atpu/serve/emit", s + 9_000_000, s + 10_000_000)
        rec.record_span("atpu/serve/step", s, s + 10_000_000, step=k)
        shift = session_start + drift_ns * k
        bench.append(["service.step", s - 20_000 - shift, 10_040_000])
        ops.append(["fusion.1", s + 2_000_000 - shift, 7_000_000])
    window = [bench[0][1], bench[-1][1] + bench[-1][2]]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit__decode_jit(1)", s, d] for _, s, d in ops]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": bench}]},
    ]
    ctx = {"cell": FakeCell("serve"), "counters": {"window_s": 0.1}, "planes": planes,
           "summary": {"window": window}}
    return ctx, session_start


def test_clock_offset_is_found_and_proven():
    ctx, session_start = serve_ring_and_planes()
    assert span_readers.clock_offset(ctx) == session_start
    # the part: the 100 ms before the traced window opened, on the ring's clock
    lo, hi = span_readers.part(ctx)
    assert hi == ctx["summary"]["window"][0] + session_start and hi - lo == 100_000_000


def test_clocks_that_drift_apart_are_refused(capsys):
    ctx, _ = serve_ring_and_planes(drift_ns=300_000)  # 0.3 ms a step: no constant offset fits
    assert span_readers.clock_offset(ctx) is None
    assert span_readers.idle_under_host_pct(ctx) is None and span_readers.part(ctx) is None
    assert "clocks do not agree" in capsys.readouterr().err


def test_idle_is_given_to_the_innermost_program_span(capsys):
    ctx, _ = serve_ring_and_planes()
    # of each 12 ms: 7 busy; idle 1 ms under admit, 1 under launch, 1 under emit
    # (host work), 2 ms between steps (outside the program), none under a sync
    got = span_readers.idle_under_host_pct(ctx)
    window_ms = (ctx["summary"]["window"][1] - ctx["summary"]["window"][0]) / 1e6
    assert got == pytest.approx(100.0 * 50 * 3.0 / window_ms, rel=1e-3)
    assert "atpu/serve/decode_sync" not in capsys.readouterr().err.split("idle seconds")[1]


def test_host_span_metrics_on_a_hand_made_ring():
    ctx, session_start = serve_ring_and_planes()
    ctx["counters"]["window_s"] = 10.0  # reach back over nothing but what is there
    # none of the hand-made steps ends before the window opens but in a longer trace
    ctx["summary"]["window"][0] += 30 * 12_000_000
    assert span_readers.median_ms(ctx, "atpu/serve/decode_sync") == pytest.approx(7.0)
    assert span_readers.step_host_ms(ctx) == pytest.approx(3.0)
    rec = flightrec.recorder()
    base = ctx["summary"]["window"][0] + session_start - 50_000_000
    for rid, wait_ms in enumerate([1.0, 2.0, 40.0]):
        rec.record("serve/submit", rid=rid, submitted=base)
        if rid < 2:
            rec.record("serve/request", rid=rid, submitted=base, admitted=base + int(wait_ms * 1e6))
    ctx.pop("_span_readers")
    # three submitted, one never admitted: p95 lies on the missing one
    assert span_readers.admission_wait_p95_ms(ctx) == math.inf
    rec.record("serve/request", rid=2, submitted=base, admitted=base + 40_000_000)
    ctx.pop("_span_readers")
    assert span_readers.admission_wait_p95_ms(ctx) == pytest.approx(40.0)


# -- device time by scope -------------------------------------------------------

@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE) as f:
        return json.load(f)


def hand_written_text(planes, scoped: dict, leave_out=()) -> str:
    """HLO text naming every op of the fixture's op line, some under a scope."""
    names = {n for n, _, _ in planes[0]["lines"][1]["events"]} | {
        n for ln in planes[0]["lines"] for n, _, _ in ln["events"] if ln["name"] == "XLA Ops"}
    lines = []
    for n in sorted(names - set(leave_out)):
        meta = f', metadata={{op_name="jit(traced)/atpu_captured_body/{scoped[n]}/mul"}}' if n in scoped else ""
        lines.append(f"  %{n} = f32[8]{{0}} multiply(f32[8]{{0}} %p, f32[8]{{0}} %p){meta}")
    return "HloModule jit_traced\n\nENTRY %main {\n" + "\n".join(lines) + "\n}\n"


def op_time_ms(planes, names) -> float:
    ops = [e for ln in planes[0]["lines"] if ln["name"] == "XLA Ops" for e in ln["events"]]
    return sum(d for n, _, d in ops if n in names) / 1e6


def test_device_time_by_scope_on_a_real_trace(planes, capsys):
    ops = [e for ln in planes[0]["lines"] if ln["name"] == "XLA Ops" for e in ln["events"]]
    by_time = sorted({n for n, _, _ in ops if not n.startswith(("while", "conditional", "call"))},
                     key=lambda n: -op_time_ms(planes, {n}))
    scoped = {by_time[0]: "atpu_update", by_time[1]: "atpu_update", by_time[2]: "atpu_head_loss"}
    profiler.register_program("jit_traced", lambda: hand_written_text(planes, scoped))
    ctx = {"cell": FakeCell("train"), "counters": {}, "planes": planes, "summary": None}
    assert span_readers.train_scope_ms(ctx, "atpu_update") == pytest.approx(op_time_ms(planes, set(by_time[:2])))
    assert span_readers.train_scope_ms(ctx, "atpu_head_loss") == pytest.approx(op_time_ms(planes, {by_time[2]}))
    by_scope, module_ms = span_readers.device_ms_by_scope(ctx, "jit_traced")
    assert module_ms == pytest.approx(60.0) and sum(by_scope.values()) <= module_ms
    assert "coverage 100.0%" in capsys.readouterr().err
    # through the metric's own file
    cell = cells.resolve("gpt2-medium.train-1k")
    assert cell.layer_metric("train_update_device_ms").read(ctx) == span_readers.train_scope_ms(ctx, "atpu_update")
    # no decode program ran in this trace
    assert span_readers.decode_group_ms(ctx, "kv_pool") is None


def test_a_map_that_misses_the_traces_names_is_refused(planes, capsys):
    ops = [e for ln in planes[0]["lines"] if ln["name"] == "XLA Ops" for e in ln["events"]]
    heavy = max({n for n, _, _ in ops if not n.startswith("while")}, key=lambda n: op_time_ms(planes, {n}))
    share = op_time_ms(planes, {heavy}) / op_time_ms(planes, {n for n, _, _ in ops})
    assert share > 0.05
    profiler.register_program("jit_traced", lambda: hand_written_text(planes, {}, leave_out=[heavy]))
    ctx = {"cell": FakeCell("train"), "counters": {}, "planes": planes, "summary": None}
    assert span_readers.train_scope_ms(ctx, "atpu_update") is None
    assert "of the module's device time" in capsys.readouterr().err
    # and with no program registered at all
    profiler._programs.clear()
    assert span_readers.train_scope_ms({**ctx, "_span_readers": {}}, "atpu_update") is None


def test_decode_groups_sum_to_the_module(capsys):
    ctx, _ = serve_ring_and_planes(steps=4)
    ops = ctx["planes"][0]["lines"][0]["events"]
    for k, (_, s, _) in enumerate(list(ops)):  # three more ops inside each decode execution
        ops[k] = ["fusion.1", s, 3_000_000]
        ops += [["copy.2", s + 3_000_000, 2_000_000], ["fusion.3", s + 5_000_000, 1_000_000],
                ["convert.4", s + 6_000_000, 500_000]]
    text = "\n".join(
        f'  %{n} = f32[] add(), metadata={{op_name="jit(_decode_jit)/while/body/{scope}/add"}}'
        for n, scope in [("fusion.1", "atpu_serve_qkv"), ("copy.2", "atpu_serve_kv_gather"),
                         ("fusion.3", "atpu_serve_attend")]) + "\n  %convert.4 = f32[] convert()\n"
    profiler.register_program("jit__decode_jit", lambda: text)
    got = {g: span_readers.decode_group_ms(ctx, g) for g in ("kv_pool", "attend", "weights", "other")}
    assert got == pytest.approx({"kv_pool": 2.0, "attend": 1.0, "weights": 3.0, "other": 1.0})
    assert sum(got.values()) == pytest.approx(7.0)  # the module's own time


def test_every_new_metric_has_a_reader_and_an_entry():
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for kind, cell_name in (("serve", "gpt2-xl.serve-steady"), ("train", "gpt2-medium.train-1k")):
        cell = cells.resolve(cell_name)
        for name in HOST[kind] + DEVICE_READ[kind]:
            assert entries[name]["workloads"] == [cell_name] and name in cell.per_layer
            assert entries[name]["source"] == ("program_span" if name in HOST[kind] else "device_trace")
            assert callable(cell.layer_metric(name).read)
    assert len(manifest["per_layer"]) >= 15 + 14  # later PRs append
    assert [m["name"] for m in manifest["per_layer"]][:15] == [
        "host_dispatch_ms.train", "recompiles_in_window.train", "data_wait_ms.train", "train_step_mfu",
        "train_step_device_ms", "flash_fwd_roofline", "flash_bwd_roofline", "decode_step_ms",
        "serve_step_mfu", "decode_hbm_pct", "queue_wait_p95_ms", "batch_occupancy_pct",
        "host_syncs_per_token", "generator_late_p95_ms", "ttft_p95_ms.steady"]
