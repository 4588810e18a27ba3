"""Telemetry subsystem (docs/telemetry.md): phases recorded per step on CPU,
recompile forensics attribute the right cause, the disabled path touches
nothing, the tracker bridge writes valid JSONL, and the telemetry AOT
capture path is loss-bitwise-identical to the plain jit path."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu.nn as nn
import accelerate_tpu.optim as optim
from accelerate_tpu import Accelerator, TelemetryKwargs
from accelerate_tpu.data_loader import batch_to_global_array
from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
from accelerate_tpu.telemetry import (
    StepRecord,
    StepTimeline,
    Telemetry,
    _set_active,
    current_telemetry,
    diff_keys,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_active_telemetry():
    yield
    _set_active(None)


def _tiny_cfg():
    return GPTConfig(vocab_size=256, n_positions=64, n_embd=32, n_layer=1, n_head=2)


def _make_step(enabled=True, acc_kwargs=None, **tel_kwargs):
    nn.manual_seed(0)
    acc = Accelerator(
        kwargs_handlers=[TelemetryKwargs(enabled=enabled, **tel_kwargs)],
        **(acc_kwargs or {}),
    )
    model = GPTLMHeadModel(_tiny_cfg())
    opt = optim.AdamW(model.parameters(), lr=1e-3)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    return acc, model, acc.compile_step(step_fn)


def _batch(acc, seq=32, seed=0):
    ids = np.random.default_rng(seed).integers(0, 256, (8, seq), dtype=np.int32)
    return batch_to_global_array(jnp.asarray(ids), mesh=acc.mesh)


# ---------------------------------------------------------------------------
# pillar 1: step-phase timing
# ---------------------------------------------------------------------------

def test_phases_recorded_per_step_and_cover_wall_clock():
    acc, _, step = _make_step()
    batch = _batch(acc)
    for _ in range(3):
        loss = step(batch)
    assert np.isfinite(float(loss))
    records = acc.telemetry.timeline.records()
    assert len(records) == 3
    build, *replays = records
    assert build.built and not any(r.built for r in replays)
    assert build.trace_ms > 0 and build.compile_ms > 0
    for rec in records:
        assert rec.total_ms > 0
        for phase in ("assembly_ms", "trace_ms", "compile_ms", "dispatch_ms",
                      "dataloader_wait_ms"):
            assert getattr(rec, phase) >= 0.0
        # the phases partition __call__: their sum accounts for the wall
        # clock (acceptance: within 20%)
        assert rec.phase_sum_ms <= rec.total_ms * 1.001
        assert rec.phase_sum_ms >= rec.total_ms * 0.8, (
            rec.phase_sum_ms,
            rec.total_ms,
        )
    # replays share the build's variant key and do not re-trace
    assert {r.key for r in records} == {build.key}
    assert len(step._cache) == 1


def test_dataloader_wait_phase_flows_from_prepared_loader():
    acc, _, step = _make_step()

    data = np.random.default_rng(0).integers(0, 256, (128, 32)).astype(np.int32)

    class Dataset:
        def __len__(self):
            return len(data)

        def __getitem__(self, i):
            return data[i]

    from accelerate_tpu.data_loader import prepare_data_loader

    loader = prepare_data_loader(Dataset(), batch_size=8, mesh=acc.mesh)
    waits = []
    for batch in loader:
        step(batch)
        waits.append(acc.telemetry.timeline.last().dataloader_wait_ms)
    assert len(waits) == 2
    assert all(w > 0 for w in waits), waits


def test_prepared_loader_keeps_pinned_hub_after_later_accelerator():
    acc, _, step = _make_step()

    data = np.random.default_rng(0).integers(0, 256, (128, 32)).astype(np.int32)

    class Dataset:
        def __len__(self):
            return len(data)

        def __getitem__(self, i):
            return data[i]

    from accelerate_tpu.data_loader import prepare_data_loader

    loader = acc.prepare_data_loader(
        prepare_data_loader(Dataset(), batch_size=8, mesh=acc.mesh)
    )
    assert loader._telemetry is acc.telemetry
    # a later telemetry-off Accelerator clears the module-global slot …
    acc2 = Accelerator()
    assert current_telemetry() is None
    # … but the prepared loader's wait accounting survives via its pin
    for batch in loader:
        step(batch)
    assert acc.telemetry.timeline.last().dataloader_wait_ms > 0


def test_eager_eval_epoch_wait_is_not_dumped_on_next_step():
    """Batch-scoped wait attribution (ISSUE 8 satellite): an eager eval
    epoch consumes its batches with no captured step, so its accumulated
    loader wait must be settled at epoch end into the hub's eager counter —
    pre-fix it stayed pending and the NEXT captured step's record absorbed
    the whole eval epoch's wait as its own."""
    acc, _, step = _make_step()

    data = np.random.default_rng(0).integers(0, 256, (128, 32)).astype(np.int32)

    class Dataset:
        def __len__(self):
            return len(data)

        def __getitem__(self, i):
            return data[i]

    from accelerate_tpu.data_loader import prepare_data_loader

    loader = prepare_data_loader(Dataset(), batch_size=8, mesh=acc.mesh)
    for _ in loader:  # eager eval epoch: no captured step pops any wait
        pass
    # the regression pin: nothing pending for the next step, the eval
    # epoch's wait is accounted where it belongs
    assert acc.telemetry._dataloader_wait_ms == 0.0
    assert acc.telemetry.eager_dataloader_wait_ms > 0
    assert acc.telemetry.summary()["eager_dataloader_wait_ms"] > 0
    # a captured step after the eval phase still gets its own batch's wait
    for batch in loader:
        step(batch)
        break
    assert acc.telemetry.timeline.last().dataloader_wait_ms > 0


def test_program_labels_stay_unique_across_rebuilds():
    acc, _, step = _make_step()
    step(_batch(acc, seq=32))
    step(_batch(acc, seq=48))
    # evict a variant and replay it: the rebuild (the layout-drift retry
    # shape — pop + rebuild) must get a fresh label, not reuse an old one
    step._cache.clear()
    step(_batch(acc, seq=32))
    labels = [p.label for p in acc.telemetry.program_records]
    assert labels == ["capture:0", "capture:1", "capture:2"]


def test_telemetry_losses_bitwise_equal_to_disabled_path():
    def run(enabled):
        Accelerator._reset_state()
        _set_active(None)
        acc, _, step = _make_step(enabled=enabled)
        batch = _batch(acc)
        return [float(step(batch)) for _ in range(3)]

    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# pillar 2: recompile forensics
# ---------------------------------------------------------------------------

def test_shape_change_emits_recompile_event_naming_the_argument():
    acc, _, step = _make_step()
    step(_batch(acc, seq=32))
    assert len(acc.telemetry.recompile_events) == 0  # first build: expected
    step(_batch(acc, seq=48))
    events = list(acc.telemetry.recompile_events)
    assert len(events) == 1
    assert "arg[0] shape changed" in events[0].cause
    assert "(8, 32)" in events[0].cause and "(8, 48)" in events[0].cause
    assert events[0].kind == "key"
    assert acc.telemetry.recompiles_total == 1


def test_train_eval_flip_emits_recompile_event():
    acc, model, step = _make_step()
    batch = _batch(acc)
    step(batch)
    model.eval()
    step(batch)
    events = list(acc.telemetry.recompile_events)
    assert len(events) == 1
    assert "training changed" in events[0].cause


def test_accumulate_refile_keeps_forensics_baseline():
    """First-call accumulate re-files the cache entry under the traced
    sync_gradients flag; forensics must diff later misses against the
    re-filed key, or the flagship accumulation-boundary recompile loses
    its cause attribution."""
    from accelerate_tpu.nn import F, Tensor

    nn.manual_seed(0)
    acc = Accelerator(
        gradient_accumulation_steps=2,
        kwargs_handlers=[TelemetryKwargs(enabled=True)],
    )
    model = nn.Linear(4, 1)
    opt = optim.SGD(model.parameters(), lr=0.1)
    model, opt = acc.prepare(model, opt)

    def step_fn(xb, yb):
        with acc.accumulate(model):
            pred = model(Tensor(xb)).squeeze(-1)
            loss = F.mse_loss(pred, Tensor(yb))
            acc.backward(loss)
            opt.step()
            opt.zero_grad()
        return loss

    step = acc.compile_step(step_fn)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 4)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(2,)).astype(np.float32))
    step(x, y)  # builds + re-files under the traced sync flag
    step(x, y)  # sync flips at the accumulation boundary → second variant
    events = list(acc.telemetry.recompile_events)
    assert len(events) == 1
    assert "sync_gradients flipped" in events[0].cause, events[0].cause
    # the build's record key matches its variant's replays, not the
    # popped pre-advance key
    records = acc.telemetry.timeline.records()
    step(x, y)  # replay of variant 1
    assert acc.telemetry.timeline.last().key == records[0].key
    # program records follow the re-file too: each variant's HBM/FLOP
    # stats join to its own key, with no cross-variant collision
    prog_keys = [p.key for p in acc.telemetry.program_records]
    assert prog_keys == [records[0].key, records[1].key]
    assert len(set(prog_keys)) == 2


def test_repeated_layout_drift_falls_back_to_plain_jit():
    """One layout drift rebuilds AOT (loud event, fresh executable); a
    second drift on the same variant means layouts alternate — the AOT
    path must yield to plain jit or it would trace+compile every step."""
    acc, _, step = _make_step()
    batch = _batch(acc)
    loss0 = float(step(batch))
    key = next(iter(step._cache))

    class _Rejecting:
        def __call__(self, *a, **k):
            raise ValueError("simulated sharding/layout mismatch")

    def _inject():
        entry = step._cache[key]
        step._cache[key] = (_Rejecting(), *entry[1:])

    _inject()  # drift 1 → loud event, rebuilt still AOT (no .lower on Compiled)
    step(batch)
    assert acc.telemetry.recompile_events[-1].kind == "layout"
    assert not hasattr(step._cache[key][0], "lower")

    _inject()  # drift 2 on the same key → plain-jit fallback (jitted has .lower)
    loss2 = float(step(batch))
    assert "falling back to plain jit" in acc.telemetry.recompile_events[-1].cause
    assert hasattr(step._cache[key][0], "lower")
    assert np.isfinite(loss2) and loss2 != loss0  # training kept moving

    events_before = len(acc.telemetry.recompile_events)
    step(batch)  # jit dispatch absorbs further calls: no new events, no rebuild
    assert len(acc.telemetry.recompile_events) == events_before
    rec = acc.telemetry.timeline.last()
    assert not rec.built and rec.trace_ms == 0.0 and rec.compile_ms == 0.0


def test_diff_keys_names_every_moved_component():
    prev = ("treeA", (((4, 32), "int32"),), True, (True,))
    new = ("treeA", (((4, 48), "int32"),), False, (False,))
    causes = diff_keys(prev, new)
    text = "\n".join(causes)
    assert "arg[0] shape changed" in text
    assert "sync_gradients flipped" in text
    assert "model[0].training changed" in text


# ---------------------------------------------------------------------------
# pillar 3: resource accounting
# ---------------------------------------------------------------------------

def test_capture_records_program_stats_and_resource_sample():
    acc, _, step = _make_step()
    step(_batch(acc))
    programs = list(acc.telemetry.program_records)
    assert len(programs) == 1
    # CPU backend exposes both analyses; at minimum the FLOP count must land
    assert programs[0].stats.get("flops", 0) > 0
    samples = list(acc.telemetry.resource_samples)
    assert len(samples) == 1
    assert samples[0].total_bytes > 0
    # on-demand sampling works outside capture too
    sample = acc.telemetry.sample_resources("manual")
    assert sample.total_bytes > 0 and sample.tag == "manual"


# ---------------------------------------------------------------------------
# telemetry off: identical path, no allocations
# ---------------------------------------------------------------------------

def test_disabled_leaves_ring_buffer_and_counters_untouched(monkeypatch):
    monkeypatch.delenv("ACCELERATE_TELEMETRY", raising=False)
    nn.manual_seed(0)
    acc = Accelerator()  # no handler, env unset → default off
    model = GPTLMHeadModel(_tiny_cfg())
    opt = optim.AdamW(model.parameters(), lr=1e-3)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    assert step._telemetry is None
    assert current_telemetry() is None
    slots_before = list(acc.telemetry.timeline._slots)
    batch = _batch(acc)
    for _ in range(3):
        step(batch)
    assert len(acc.telemetry.timeline) == 0
    assert acc.telemetry.timeline._slots == slots_before  # ring untouched
    assert acc.telemetry.steps_total == 0
    assert acc.telemetry.recompiles_total == 0
    assert len(acc.telemetry._export_queue) == 0
    # the pre-telemetry host-assembly counters still tick (replays only)
    assert step.host_assembly_calls == 2


def test_ring_buffer_capacity_bounds_retention():
    timeline = StepTimeline(capacity=4)
    for i in range(10):
        timeline.append(
            StepRecord(
                step=i, key="k", built=False, total_ms=1.0, assembly_ms=0.2,
                trace_ms=0.0, compile_ms=0.0, dispatch_ms=0.8,
                dataloader_wait_ms=0.0,
            )
        )
    assert len(timeline) == 4
    assert timeline.total_appended == 10
    assert [r.step for r in timeline.records()] == [6, 7, 8, 9]
    assert timeline.last().step == 9


# ---------------------------------------------------------------------------
# pillar 4: export
# ---------------------------------------------------------------------------

def test_tracker_bridge_writes_valid_jsonl(tmp_path):
    acc, _, step = _make_step(
        acc_kwargs={"log_with": "jsonl", "project_dir": str(tmp_path)}
    )
    acc.init_trackers("run", config={"lr": 1e-3}, init_kwargs={})
    # the bridge was auto-inserted FIRST so end_training's in-order finish()
    # flushes it into delegates that are still open
    names = [t.name for t in acc.trackers]
    assert names == ["telemetry", "jsonl"]
    assert acc.get_tracker("telemetry").tracker is acc.telemetry

    step(_batch(acc, seq=32))
    step(_batch(acc, seq=48))  # recompile event
    acc.log({"loss": 1.0}, step=0)  # piggyback drain
    acc.end_training()

    path = os.path.join(str(tmp_path), "run", "metrics.jsonl")
    records = [json.loads(line) for line in open(path)]
    assert all(isinstance(r, dict) for r in records)
    keys = {k for r in records for k in r}
    assert "telemetry/step/total_ms" in keys
    assert "telemetry/recompile/cause" in keys
    assert any(k.startswith("telemetry/program/") for k in keys)
    # the drain is one-shot: nothing pending after flush
    assert len(acc.telemetry._export_queue) == 0


def test_write_jsonl_roundtrips_through_report_tool(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from telemetry_report import load_records, render, validate
    finally:
        sys.path.pop(0)

    acc, _, step = _make_step()
    for _ in range(3):
        step(_batch(acc))
    path = str(tmp_path / "run.jsonl")
    acc.telemetry.write_jsonl(path)
    records = load_records(path)
    assert validate(records, min_steps=3) == []
    kinds = {r["kind"] for r in records}
    assert {"meta", "step", "program", "resources", "summary"} <= kinds
    report = render(records)
    assert "step-time breakdown" in report
    assert "steady state" in report  # no recompiles in this run


def test_export_queue_skipped_without_sink():
    """ROADMAP item: with no tracker bridge attached, per-step records skip
    the export queue (and its to_dict()) entirely — sink-less runs like
    bench's primary loop pay zero per-step export work.  The retained
    history (timeline, JSONL dump) is unaffected."""
    acc, _, step = _make_step()
    for _ in range(3):
        step(_batch(acc))
    assert len(acc.telemetry.timeline) == 3  # retained history intact
    assert len(acc.telemetry.program_records) == 1
    assert len(acc.telemetry._export_queue) == 0  # nothing enqueued
    # the JSONL dump feed reads the retained history, not the queue
    kinds = {r["kind"] for r in acc.telemetry.all_records()}
    assert {"step", "program"} <= kinds


def test_bridge_attach_backfills_pre_attach_records(tmp_path):
    """Records produced BEFORE init_trackers (no sink yet → not enqueued)
    still reach the delegates: the bridge backfills from retained history
    when it attaches."""
    acc, _, step = _make_step(
        acc_kwargs={"log_with": "jsonl", "project_dir": str(tmp_path)}
    )
    step(_batch(acc, seq=32))  # pre-attach: queue stays empty
    assert len(acc.telemetry._export_queue) == 0
    acc.init_trackers("run", config=None, init_kwargs={})
    assert len(acc.telemetry._export_queue) > 0  # backfilled on attach
    step(_batch(acc, seq=48))  # post-attach: normal enqueue (recompile too)
    acc.log({"loss": 1.0}, step=0)
    acc.end_training()
    path = os.path.join(str(tmp_path), "run", "metrics.jsonl")
    keys = {k for line in open(path) for k in json.loads(line)}
    # both the pre-attach step and the post-attach recompile were exported
    assert "telemetry/step/total_ms" in keys
    assert "telemetry/recompile/cause" in keys


# ---------------------------------------------------------------------------
# pillar 5: black-box flight recorder (always-on) + hang watchdog
# ---------------------------------------------------------------------------

import signal
import time

from accelerate_tpu.telemetry import flightrec
from accelerate_tpu.telemetry.flightrec import FlightRecorder
from accelerate_tpu.telemetry.watchdog import HangWatchdog, current_watchdog


def test_flightrec_ring_wraps_and_counts_drops():
    rec = FlightRecorder(capacity=16)
    for i in range(40):
        rec.record("tick", i=i)
    assert rec.events_total == 40
    assert rec.depth == 16
    assert rec.dropped == 24
    events = rec.snapshot()
    # oldest retained first; exactly the last `capacity` survive the wrap
    assert [e["seq"] for e in events] == list(range(24, 40))
    assert [e["i"] for e in events] == list(range(24, 40))
    health = rec.health()
    assert health["events_total"] == 40
    assert health["dropped_total"] == 24
    assert health["depth"] == 16
    assert health["last_event_age_seconds"] >= 0.0


def test_flightrec_collective_seq_and_dump_roundtrip(tmp_path):
    rec = FlightRecorder(capacity=64)
    assert rec.health()["last_event_age_seconds"] is None  # nothing yet
    assert [rec.note_collective("gather_object", world=2) for _ in range(3)] \
        == [1, 2, 3]
    rec.record("step_begin", step=0)
    path = rec.dump(str(tmp_path), reason="manual", extra={"note": "hi"})
    assert path is not None and os.path.basename(path).startswith("blackbox_rank")
    dump = json.load(open(path, encoding="utf-8"))
    assert dump["kind"] == "blackbox"
    assert dump["reason"] == "manual"
    assert dump["collective_seq"] == 3
    assert dump["note"] == "hi"
    collectives = [e for e in dump["events"] if e["kind"] == "collective"]
    assert [e["cseq"] for e in collectives] == [1, 2, 3]
    assert all(e["op"] == "gather_object" for e in collectives)
    # the wall anchor lets tools place monotonic stamps on absolute time
    assert dump["anchor_wall"] > 0 and dump["time_unix"] > 0
    # an explicit .json path is honored verbatim (no rank suffix appended)
    explicit = rec.dump(str(tmp_path / "sub" / "my.json"), reason="manual")
    assert explicit is not None and explicit.endswith("my.json")
    assert json.load(open(explicit))["events_total"] == rec.events_total


def test_blackbox_report_names_the_stalled_rank_and_first_divergent_collective(tmp_path):
    """``tools/blackbox_report.py`` on two ranks' dumps: rank 0's watchdog
    fired while it was blocked inside its third gather, rank 1 went silent
    (an injected hang) after its second and dumped on the signal that ended
    it.  The lowest counter names the stalled rank, the next collective the
    one that diverged.  (The same story in a real two-process world, where
    the collective really blocks, is ``make telemetry-smoke``.)"""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from blackbox_report import find_dumps, load_dump, merge, render
    finally:
        sys.path.pop(0)

    blocked, silent = FlightRecorder(capacity=64), FlightRecorder(capacity=64)
    for _ in range(3):
        blocked.note_collective("gather_object", world=2)
    for _ in range(2):
        silent.note_collective("gather_object", world=2)
    silent.record("hang_injected", step=2, seconds=600)
    blocked.dump(str(tmp_path), reason="watchdog_stall",
                 extra={"rank": 0, "stalled_label": "collective:gather_object #3"})
    silent.dump(str(tmp_path), reason="signal", extra={"rank": 1})
    (tmp_path / "blackbox_rank2.json").write_text("{not a dump")

    paths = find_dumps([str(tmp_path)])
    assert [os.path.basename(p) for p in paths] == [f"blackbox_rank{i}.json" for i in range(3)]
    dumps = [d for d in map(load_dump, paths) if d is not None]
    assert len(dumps) == 2  # the torn file is left out, not fatal
    report = merge(dumps)
    assert report["stalled_ranks"] == [1] and not report["aligned"]
    assert report["first_divergent_seq"] == 3 and report["first_divergent_op"] == "gather_object"
    ranks = {r["rank"]: r for r in report["ranks"]}
    assert ranks[0]["reason"] == "watchdog_stall" and ranks[0]["blocked_in"] == {"op": "gather_object", "seq": 3}
    assert ranks[1]["reason"] == "signal" and ranks[1]["hang_injected"]["step"] == 2
    assert "STALLED rank(s): 1" in render(report)
    # ranks at one counter: nothing diverged
    assert merge([dumps[0], dict(dumps[0], rank=1)])["stalled_ranks"] == []


def test_flightrec_disabled_is_noop():
    rec = FlightRecorder(capacity=32, enabled=False)
    rec.record("tick")
    assert rec.note_collective("gather") == 0  # seq untouched
    assert rec.events_total == 0 and rec.depth == 0
    assert rec.snapshot() == []


def test_flightrec_shields_slot_schema_keys_from_payload_passthrough():
    # producers mirror whole payload dicts (``**payload``) into the ring;
    # payload keys named like the slot schema (fleet autopilot decisions
    # carry their own "kind") must neither raise nor clobber the schema
    rec = FlightRecorder(capacity=32)
    rec.record("fleet", **{"kind": "skew", "t": 9.9, "seq": 7, "event": "x"})
    got = rec.note_collective("gather", **{"op": "inner", "cseq": 99, "kind": "y"})
    assert got == 1
    ev, coll = rec.snapshot()
    assert ev["kind"] == "fleet" and ev["seq"] == 0
    assert (ev["field_kind"], ev["field_t"], ev["field_seq"]) == ("skew", 9.9, 7)
    assert coll["kind"] == "collective" and coll["op"] == "gather"
    assert coll["cseq"] == 1
    assert (coll["field_op"], coll["field_cseq"]) == ("inner", 99)


def test_captured_step_records_flight_events_without_telemetry(monkeypatch):
    """The recorder is the default-off convention's one exception: with
    telemetry fully off, captured-step begin/end still lands in the ring
    (with a locally-maintained step index)."""
    # room for the build's compile spans (the recorder's listener) beside
    # the step events
    fresh = FlightRecorder(capacity=4096)
    monkeypatch.setattr(flightrec, "_RECORDER", fresh)
    nn.manual_seed(0)
    acc = Accelerator()  # telemetry off
    model = GPTLMHeadModel(_tiny_cfg())
    opt = optim.AdamW(model.parameters(), lr=1e-3)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    assert step._telemetry is None
    batch = _batch(acc)
    for _ in range(3):
        step(batch)
    kinds = [(e["kind"], e.get("step")) for e in fresh.snapshot()
             if e["kind"] in ("step_begin", "step_end")]
    assert kinds == [
        ("step_begin", 0), ("step_end", 0),
        ("step_begin", 1), ("step_end", 1),
        ("step_begin", 2), ("step_end", 2),
    ]


def test_captured_step_skips_ring_when_recorder_disabled(monkeypatch):
    """The bench A/B "off" arm: a recorder disabled BEFORE compile_step is
    never consulted again on the hot path (pinned None at construction)."""
    fresh = FlightRecorder(capacity=64, enabled=False)
    monkeypatch.setattr(flightrec, "_RECORDER", fresh)
    nn.manual_seed(0)
    acc = Accelerator()
    model = GPTLMHeadModel(_tiny_cfg())
    opt = optim.AdamW(model.parameters(), lr=1e-3)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    assert step._flightrec is None
    step(_batch(acc))
    fresh.enabled = True  # re-enabling later does not reach the pinned step
    step(_batch(acc))
    assert all(e["kind"] != "step_begin" for e in fresh.snapshot())


def _test_watchdog(tmp_path, **kwargs):
    rec = FlightRecorder(capacity=128)
    wd = HangWatchdog(
        timeout_s=kwargs.pop("timeout_s", 0.3),
        dump_dir=str(tmp_path),
        recorder=rec,
        poll_s=0.05,
        install_signal_handlers=kwargs.pop("install_signal_handlers", False),
        dump_at_exit=kwargs.pop("dump_at_exit", False),
        **kwargs,
    )
    return rec, wd


def test_watchdog_fires_on_stall_and_dump_is_valid(tmp_path):
    rec, wd = _test_watchdog(tmp_path)
    wd.start()
    try:
        assert current_watchdog() is wd
        rec.note_collective("gather_object")
        with wd.guard("collective:gather_object #1"):
            # the "hung" section: wait on the dump path (set AFTER the poll
            # thread finishes writing), not the fired counter (set before)
            deadline = time.monotonic() + 10.0
            while wd.last_dump_path is None and time.monotonic() < deadline:
                time.sleep(0.05)
        assert wd.fired >= 1
        assert wd.last_dump_path is not None
        dump = json.load(open(wd.last_dump_path, encoding="utf-8"))
        assert dump["reason"] == "watchdog_stall"
        assert dump["stalled_label"] == "collective:gather_object #1"
        assert dump["stalled_s"] >= 0.3
        assert dump["collective_seq"] == 1
        assert dump["threads"]  # python stacks for every live thread
        assert any(e["kind"] == "watchdog_stall" for e in dump["events"])
        assert os.path.exists(f"{wd.last_dump_path}.stacks.txt")  # sidecar
    finally:
        wd.stop()
    assert current_watchdog() is None


def test_watchdog_fires_once_per_armed_section(tmp_path):
    rec, wd = _test_watchdog(tmp_path)
    wd.start()
    try:
        with wd.guard("slow"):
            deadline = time.monotonic() + 10.0
            while wd.fired == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            time.sleep(0.5)  # well past a second deadline: must NOT re-fire
        assert wd.fired == 1
        # a fresh armed section can fire again
        with wd.guard("slow again"):
            deadline = time.monotonic() + 10.0
            while wd.fired == 1 and time.monotonic() < deadline:
                time.sleep(0.05)
        assert wd.fired == 2
    finally:
        wd.stop()


def test_watchdog_nested_guard_keeps_outermost_deadline(tmp_path):
    _, wd = _test_watchdog(tmp_path, timeout_s=30.0)
    with wd.guard("outer"):
        with wd.guard("inner", timeout_s=0.01):
            label, deadline, _ = wd._armed
            assert label == "outer"  # inner arm did not displace the outer
            assert deadline > time.monotonic() + 10
        assert wd._armed is not None  # still armed until the outer exits
    assert wd._armed is None


def test_watchdog_stop_restores_signal_handlers_and_slot(tmp_path):
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_abrt = signal.getsignal(signal.SIGABRT)
    rec, wd = _test_watchdog(tmp_path, install_signal_handlers=True)
    wd.start()
    assert signal.getsignal(signal.SIGTERM) == wd._handle_signal
    assert signal.getsignal(signal.SIGABRT) == wd._handle_signal
    wd.stop()
    assert signal.getsignal(signal.SIGTERM) is prev_term
    assert signal.getsignal(signal.SIGABRT) is prev_abrt
    assert current_watchdog() is None
    # manual dumps work without the thread (the preemption-guard hook path)
    path = wd.dump_now(reason="preemption_signal")
    assert json.load(open(path))["reason"] == "preemption_signal"


def test_watchdog_atexit_dump_yields_to_earlier_stall_dump(tmp_path):
    # the stalled rank usually EXITS after the stall (its collective raises
    # once a peer dies): the atexit dump must not overwrite the stall dump
    rec, wd = _test_watchdog(tmp_path, dump_at_exit=True)
    wd.start()
    try:
        assert wd._exit_hook is not None
        rec.note_collective("gather_object")
        with wd.guard("collective:gather_object #1"):
            deadline = time.monotonic() + 10.0
            while wd.last_dump_path is None and time.monotonic() < deadline:
                time.sleep(0.05)
        assert wd.last_dump_path is not None
        wd._exit_hook()  # what atexit would run at interpreter shutdown
        dump = json.load(open(wd.last_dump_path, encoding="utf-8"))
        assert dump["reason"] == "watchdog_stall"
    finally:
        wd.stop()

    # a rank that dies without ever stalling still leaves its half
    rec2, wd2 = _test_watchdog(tmp_path / "clean", dump_at_exit=True)
    wd2.start()
    try:
        rec2.note_collective("broadcast")
        wd2._exit_hook()
        assert wd2.last_dump_path is not None
        dump = json.load(open(wd2.last_dump_path, encoding="utf-8"))
        assert dump["reason"] == "atexit"
    finally:
        wd2.stop()


def test_watchdog_start_displaces_prior_instance(tmp_path):
    _, first = _test_watchdog(tmp_path)
    _, second = _test_watchdog(tmp_path)
    first.start()
    try:
        second.start()
        assert current_watchdog() is second
        assert first._thread is None  # stopped, not leaked
    finally:
        second.stop()
        first.stop()


def test_trace_export_writes_joinable_tracks(tmp_path, monkeypatch):
    from accelerate_tpu.telemetry.trace_export import validate_trace

    # fresh ring: the process-global recorder carries earlier tests' steps;
    # room for the build's compile spans beside them
    monkeypatch.setattr(flightrec, "_RECORDER", FlightRecorder(capacity=4096))
    trace_path = str(tmp_path / "trace.json")
    acc, _, step = _make_step(profile_every_n=1, trace_export_path=trace_path)
    for _ in range(2):
        step(_batch(acc))
    acc.end_training()
    doc = json.load(open(trace_path, encoding="utf-8"))
    assert validate_trace(doc) == []
    by_tid = {}
    for ev in doc["traceEvents"]:
        step_arg = (ev.get("args") or {}).get("step")
        if step_arg is not None:
            by_tid.setdefault(ev["tid"], set()).add(step_arg)
    # host phases (1), device ops (2) and flight events (3) share the steps
    assert by_tid.get(1) == by_tid.get(2) == by_tid.get(3) == {0, 1}


# ---------------------------------------------------------------------------
# pillar 6 edge cases: fleet aggregation on degenerate per-rank shapes
# ---------------------------------------------------------------------------

from accelerate_tpu.telemetry.aggregate import fleet_skew, merge_rank_records


def _replay(total_ms, dispatch_ms=0.0, **extra):
    return {"kind": "step", "built": False, "total_ms": total_ms,
            "dispatch_ms": dispatch_ms, **extra}


def test_fleet_skew_single_rank_reports_without_comparing():
    out = fleet_skew([[_replay(10.0), _replay(12.0)]])
    assert out["kind"] == "fleet" and out["ranks"] == 1
    assert out["per_rank"][0]["replay_steps"] == 2
    assert out["per_rank"][0]["replay_total_ms_mean"] == 11.0
    # a one-rank fleet has no skew pair to compare
    assert "slowest_rank" not in out and "skew_ms" not in out


def test_fleet_skew_empty_and_ragged_inputs():
    assert fleet_skew([]) == {"kind": "fleet", "ranks": 0, "per_rank": []}
    # ragged: one rank with replays, one empty, one with only builds /
    # malformed records — none of it may crash or fabricate a comparison
    ragged = [
        [_replay(10.0)],
        [],
        [{"kind": "step", "built": True, "total_ms": 9.0},
         {"kind": "step", "built": False, "total_ms": None},
         {"kind": "recompile"}],
    ]
    out = fleet_skew(ragged)
    assert [s["replay_steps"] for s in out["per_rank"]] == [1, 0, 0]
    assert "slowest_rank" not in out  # only one usable rank


def test_fleet_skew_names_straggler_and_phase():
    per_rank = [
        [_replay(10.0, dispatch_ms=8.0)],
        [_replay(30.0, dispatch_ms=27.0)],
    ]
    out = fleet_skew(per_rank)
    assert out["slowest_rank"] == 1 and out["fastest_rank"] == 0
    assert out["skew_ms"] == 20.0 and out["skew_pct"] == 200.0
    assert out["straggler_phase"] == "dispatch_ms"
    assert out["straggler_phase_delta_ms"] == 19.0


def test_merge_rank_records_tags_without_mutating_and_dedups_periodic():
    rank0 = [_replay(10.0), {"kind": "fleet", "periodic": True, "ranks": 2}]
    rank1 = [_replay(11.0), {"kind": "fleet", "periodic": True, "ranks": 2}]
    originals = [dict(r) for r in rank0]
    merged = merge_rank_records([rank0, rank1])
    assert rank0 == originals  # inputs untouched
    # rank-tagged copies; rank 1's periodic fleet duplicate dropped
    fleet_periodic = [r for r in merged if r.get("periodic")]
    assert len(fleet_periodic) == 1 and fleet_periodic[0]["rank"] == 0
    steps = [(r["rank"], r["total_ms"]) for r in merged if r["kind"] == "step"]
    assert steps == [(0, 10.0), (1, 11.0)]
    # the appended summary record is the fleet_skew of the same inputs
    assert merged[-1]["kind"] == "fleet" and merged[-1]["ranks"] == 2


def test_merge_rank_records_empty_world():
    merged = merge_rank_records([])
    assert merged == [{"kind": "fleet", "ranks": 0, "per_rank": []}]
