"""Device-time attribution + fleet observability (docs/telemetry.md).

The acceptance contract (ISSUE 8): sampled steps produce DeviceStepRecords
whose busy+idle split accounts for >=80% of the step's measured wall clock,
joined 1:1 to host StepRecords by step index; profiling off leaves the
capture hot path untouched (and bitwise-identical losses); the multi-host
merge produces per-rank skew stats; the metrics endpoint serves valid
Prometheus text with live serving gauges — all on the CPU mesh.
"""

import urllib.request

import numpy as np
import pytest

import accelerate_tpu.nn as nn
import accelerate_tpu.optim as optim
from accelerate_tpu import Accelerator, TelemetryKwargs
from accelerate_tpu.data_loader import batch_to_global_array
from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
from accelerate_tpu.telemetry import (
    DeviceStepRecord,
    StepRecord,
    Telemetry,
    _set_active,
)
from accelerate_tpu.telemetry.aggregate import fleet_skew, merge_rank_records
from accelerate_tpu.telemetry.profiler import (
    classify_op,
    derive_mfu,
    parse_trace_events,
)


@pytest.fixture(autouse=True)
def _reset_active_telemetry():
    yield
    _set_active(None)


def _tiny_cfg():
    return GPTConfig(vocab_size=256, n_positions=64, n_embd=32, n_layer=1, n_head=2)


def _make_step(**tel_kwargs):
    nn.manual_seed(0)
    acc = Accelerator(
        kwargs_handlers=[TelemetryKwargs(enabled=True, **tel_kwargs)]
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

    return acc, acc.compile_step(step_fn)


def _batch(acc, seq=32, seed=0):
    import jax.numpy as jnp

    ids = np.random.default_rng(seed).integers(0, 256, (8, seq), dtype=np.int32)
    return batch_to_global_array(jnp.asarray(ids), mesh=acc.mesh)


# ---------------------------------------------------------------------------
# trace parsing (pure host code, synthetic events)
# ---------------------------------------------------------------------------

def test_parse_trace_events_classifies_and_unions():
    events = [
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/host:CPU"}},
        # two overlapping compute ops on different worker threads: busy is
        # the interval UNION (10µs), not the duration sum (15µs)
        {"ph": "X", "pid": 1, "tid": 10, "ts": 100.0, "dur": 10.0,
         "name": "dot.1", "args": {"hlo_op": "dot.1"}},
        {"ph": "X", "pid": 1, "tid": 11, "ts": 105.0, "dur": 5.0,
         "name": "fusion.2", "args": {"hlo_op": "fusion.2"}},
        {"ph": "X", "pid": 1, "tid": 10, "ts": 130.0, "dur": 4.0,
         "name": "all-reduce.3", "args": {"hlo_op": "all-reduce.3"}},
        {"ph": "X", "pid": 1, "tid": 10, "ts": 140.0, "dur": 2.0,
         "name": "copy.4", "args": {"hlo_op": "copy.4"}},
        # host noise: python frame without hlo_op on a host process
        {"ph": "X", "pid": 1, "tid": 12, "ts": 100.0, "dur": 50.0,
         "name": "PjitFunction(step)"},
    ]
    parsed = parse_trace_events(events)
    assert parsed["op_events"] == 4
    dev = parsed["devices"]["/host:CPU"]
    assert dev["busy_ms"] == pytest.approx((10.0 + 4.0 + 2.0) / 1e3)
    assert dev["compute_ms"] == pytest.approx(15.0 / 1e3)
    assert dev["collective_ms"] == pytest.approx(4.0 / 1e3)
    assert dev["transfer_ms"] == pytest.approx(2.0 / 1e3)
    assert parsed["top_ops"][0][0] == "dot.1"


def test_parse_trace_events_tpu_device_pids():
    """Carried ROADMAP item: a synthetic chrome-trace in the TPU layout —
    ops live under ``/device:TPU:N`` processes and carry NO ``hlo_op`` arg
    — exercises the same classification path CI otherwise only hits with
    CPU traces.  The device-pid route alone must classify, split per
    device, and ignore host processes."""
    events = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 8, "name": "process_name",
         "args": {"name": "/device:TPU:1"}},
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "python"}},
        # TPU op events: bare names, no args.hlo_op — the /device: process
        # name is the only marker.  Two overlap on TPU:0 (union = 1500µs).
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 1000.0,
         "name": "fusion.123"},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 500.0, "dur": 1000.0,
         "name": "all-reduce.7"},
        {"ph": "X", "pid": 8, "tid": 1, "ts": 0.0, "dur": 400.0,
         "name": "copy-done.2"},
        # host-side python frame on a non-device pid without hlo_op: noise
        {"ph": "X", "pid": 1, "tid": 3, "ts": 0.0, "dur": 5000.0,
         "name": "ExecuteOnDevice"},
    ]
    parsed = parse_trace_events(events)
    assert set(parsed["devices"]) == {"/device:TPU:0", "/device:TPU:1"}
    assert parsed["op_events"] == 3
    tpu0 = parsed["devices"]["/device:TPU:0"]
    assert tpu0["busy_ms"] == pytest.approx(1.5)  # union, not 2.0 sum
    assert tpu0["compute_ms"] == pytest.approx(1.0)
    assert tpu0["collective_ms"] == pytest.approx(1.0)
    tpu1 = parsed["devices"]["/device:TPU:1"]
    assert tpu1["transfer_ms"] == pytest.approx(0.4)
    assert tpu1["busy_ms"] == pytest.approx(0.4)
    # the host frame must not appear as a device nor in the top ops
    assert all(name != "ExecuteOnDevice" for name, _ in parsed["top_ops"])


@pytest.mark.parametrize("line, counts", [
    ("XLA Ops", True), ("XLA Modules", False), ("Steps", False), ("Async XLA Ops", False),
])
def test_parse_trace_events_takes_the_op_line_alone(line, counts):
    """A real v5e trace names its device threads: the module and step lines
    span the gaps between ops and the async line runs beside them, so only
    ``XLA Ops`` is device work (PERF.md, PR 26; benchmark/trace_reduce.py)."""
    events = [
        {"ph": "M", "pid": 7, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name", "args": {"name": line}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0, "dur": 100.0, "name": "fusion.1"},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 900.0, "dur": 100.0, "name": "fusion.2"},
    ]
    if line != "XLA Ops":
        # one event over the whole second op-gap and both ops
        events.append({"ph": "X", "pid": 7, "tid": 2, "ts": 0.0, "dur": 1000.0,
                       "name": "jit_traced(123)"})
    parsed = parse_trace_events(events)
    tpu = parsed["devices"]["/device:TPU:0"]
    assert counts or line != "XLA Ops"
    assert parsed["op_events"] == 2
    assert tpu["busy_ms"] == pytest.approx(0.2)
    assert all(name != "jit_traced(123)" for name, _ in parsed["top_ops"])


def test_split_phases_joins_scope_map_and_buckets_unscoped():
    """Per-phase device attribution (docs/telemetry.md): sampled op
    durations joined to the program's HLO op->scope map, with ops outside
    every atpu scope in 'unscoped' — regression pin for the ROADMAP
    carried item."""
    from accelerate_tpu.telemetry.profiler import split_phases

    op_detail = {
        "dot.1": ["compute", 2.0],
        "all-reduce.3": ["collective", 1.5],
        "fusion.9": ["compute", 0.5],
        "copy.4": ["transfer", 0.25],
    }
    scope_map = {
        "dot.1": "atpu_captured_body",
        "all-reduce.3": "atpu_update",
        "fusion.9": "atpu_update",
    }
    phases = split_phases(op_detail, scope_map)
    assert phases["atpu_captured_body"] == {
        "total_ms": 2.0, "compute_ms": 2.0, "collective_ms": 0.0,
        "transfer_ms": 0.0, "ops": 1,
    }
    assert phases["atpu_update"]["collective_ms"] == 1.5
    assert phases["atpu_update"]["compute_ms"] == 0.5
    assert phases["atpu_update"]["ops"] == 2
    assert phases["unscoped"]["transfer_ms"] == 0.25


def test_sampled_run_splits_device_time_per_named_scope():
    """Integration: a sampled captured run splits its device timeline by
    the atpu named scopes (forward body / backward / optimizer update),
    each phase carrying its own compute/collective split — what makes the
    kernel A/B legible per phase (docs/kernels.md).

    Uses the standard tiny GPT rather than the 1-layer micro model: with a
    handful of ops XLA fuses whole phases into one fusion whose metadata
    names a single representative scope — the split is honest but
    single-phase, and the pin would be vacuous.

    The suite's persistent XLA compilation cache is disabled for this test:
    a cache-DESERIALIZED executable drops its HLO op_name metadata, so the
    scope map is empty and the split (correctly, documented) fail-softs to
    none — the pin needs a fresh compile."""
    import jax

    prev_cache = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        _run_phase_split_assertions()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_cache)


def _run_phase_split_assertions():
    nn.manual_seed(0)
    acc = Accelerator(
        mixed_precision="bf16",
        kwargs_handlers=[TelemetryKwargs(enabled=True, profile_every_n=1)],
    )
    model = GPTLMHeadModel(GPTConfig.tiny())
    opt = optim.AdamW(model.parameters(), lr=1e-3)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    batch = _batch(acc)
    for _ in range(3):
        step(batch)
    replay = list(acc.telemetry.device_records)[-1]
    assert replay.phases, "sampled replay carried no per-phase split"
    names = set(replay.phases)
    assert {"atpu_captured_body", "atpu_backward", "atpu_update"} <= names, names
    for name in ("atpu_captured_body", "atpu_backward", "atpu_update"):
        split = replay.phases[name]
        assert split["total_ms"] > 0 and split["ops"] > 0
    # the export dict carries the (rounded) split
    exported = replay.to_dict()["phases"]
    assert set(exported) == names
    # the phase sum accounts for the classified op time (same op universe)
    phase_total = sum(s["total_ms"] for s in replay.phases.values())
    op_total = sum(ms for _, ms in replay.op_detail.values())
    assert phase_total == pytest.approx(op_total, rel=1e-6)


def test_classify_op_names():
    assert classify_op("fused_all-gather.7") == "collective"
    assert classify_op("reduce-scatter.1") == "collective"
    assert classify_op("copy-start.2") == "transfer"
    assert classify_op("dot_general.9") == "compute"


def test_derive_mfu_uses_peak_override(monkeypatch):
    monkeypatch.setenv("ACCELERATE_PEAK_FLOPS", "1e12")
    # 1e9 FLOPs in 1 ms against a 1 TFLOP/s chip = 100% MFU
    assert derive_mfu(1e9, 1.0) == pytest.approx(1.0)
    assert derive_mfu(1e9, 1.0, n_devices=2) == pytest.approx(0.5)
    monkeypatch.delenv("ACCELERATE_PEAK_FLOPS")
    # CPU has no table entry: MFU is honestly underivable
    assert derive_mfu(1e9, 1.0) is None


# ---------------------------------------------------------------------------
# sampled capture: DeviceStepRecord <-> StepRecord join + coverage
# ---------------------------------------------------------------------------

def test_sampled_steps_join_host_records_and_cover_wall_clock(tmp_path):
    acc, step = _make_step(profile_every_n=2)
    assert step._telemetry.profiler is not None
    batch = _batch(acc)
    for _ in range(4):
        loss = step(batch)
    assert np.isfinite(float(loss))
    device_records = list(acc.telemetry.device_records)
    # cadence 2 over steps 0..3 samples steps 0 and 2
    assert [r.step for r in device_records] == [0, 2]
    # sampling must not perturb the capture cache (forensics-asserted)
    assert acc.telemetry.recompiles_total == 0
    host = {r.step: r for r in acc.telemetry.timeline.records()}
    for rec in device_records:
        joined = host[rec.step]  # 1:1 by step index
        assert rec.key == joined.key
        assert rec.window_ms > 0 and rec.op_events > 0
        assert rec.compute_ms > 0  # nonempty device split
        assert rec.top_ops and rec.top_ops[0][1] > 0
        assert rec.flops and rec.flops > 0  # joined from cost_analysis
    # ISSUE 8 acceptance on the replay sample: busy+idle accounts for >=80%
    # of the measured step wall clock (profiler stop/parse overhead is
    # recorded separately and excluded — it is not device time)
    replay = device_records[1]
    joined = host[replay.step]
    assert not joined.built
    covered = (replay.busy_ms + replay.idle_ms) / (
        joined.total_ms - replay.overhead_ms
    )
    assert covered >= 0.8, (replay, joined)
    # the JSONL roundtrip renders the new section and stays schema-valid
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        from telemetry_report import load_records, render, validate
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "run.jsonl")
    acc.telemetry.write_jsonl(path)
    records = load_records(path)
    assert validate(records, min_steps=4) == []
    report = render(records)
    assert "device-time attribution" in report
    assert "top ops" in report


def test_profiling_off_is_inert_and_bitwise_identical():
    def run(profile_every_n):
        Accelerator._reset_state()
        _set_active(None)
        acc, step = _make_step(profile_every_n=profile_every_n)
        batch = _batch(acc)
        losses = [float(step(batch)) for _ in range(2)]
        return acc, step, losses

    acc_off, step_off, losses_off = run(0)
    # off = the pre-profiler hot path: no profiler object, no records, no
    # stray trace state — the same pin discipline as telemetry/resilience
    assert acc_off.telemetry.profiler is None
    assert step_off._telemetry.profiler is None
    assert len(acc_off.telemetry.device_records) == 0
    _, _, losses_on = run(1)
    assert losses_on == losses_off  # sampling must not change the math


# ---------------------------------------------------------------------------
# multi-host aggregation (merge math is host-only; gather degenerates at 1)
# ---------------------------------------------------------------------------

def _rank_records(dispatch_ms, n=4, rank_tag=None):
    return [
        {"kind": "step", "step": i, "built": i == 0, "total_ms": 2.0 + dispatch_ms,
         "assembly_ms": 1.0, "trace_ms": 0.0, "compile_ms": 0.0,
         "dispatch_ms": dispatch_ms, "dataloader_wait_ms": 1.0,
         "retry_wait_ms": 0.0}
        for i in range(n)
    ]


def test_merge_rank_records_tags_and_attributes_straggler():
    fast, slow = _rank_records(5.0), _rank_records(9.0)
    merged = merge_rank_records([fast, slow])
    # every record is rank-tagged, inputs are not mutated
    assert {r.get("rank") for r in merged if r.get("kind") == "step"} == {0, 1}
    assert "rank" not in fast[0]
    fleet = [r for r in merged if r.get("kind") == "fleet"]
    assert len(fleet) == 1
    skew = fleet[0]
    assert skew["ranks"] == 2
    assert skew["slowest_rank"] == 1 and skew["fastest_rank"] == 0
    assert skew["skew_ms"] == pytest.approx(4.0)
    # the straggler's extra time sits in dispatch — named, not guessed
    assert skew["straggler_phase"] == "dispatch_ms"
    assert skew["straggler_phase_delta_ms"] == pytest.approx(4.0)


def test_fleet_skew_handles_replay_free_ranks():
    skew = fleet_skew([[{"kind": "meta"}], _rank_records(3.0)])
    assert skew["ranks"] == 2
    assert skew["per_rank"][0]["replay_steps"] == 0
    assert "slowest_rank" not in skew  # <2 usable ranks: no comparison


def test_aggregate_fleet_single_process_tags_rank_zero():
    hub = Telemetry(_EnabledKwargs())
    from accelerate_tpu.telemetry import StepRecord

    for i in range(3):
        hub.record_step(
            StepRecord(step=i, key="k", built=i == 0, total_ms=2.0,
                       assembly_ms=1.0, trace_ms=0.0, compile_ms=0.0,
                       dispatch_ms=1.0, dataloader_wait_ms=0.0)
        )
    merged = hub.aggregate_fleet()
    assert merged is not None
    steps = [r for r in merged if r.get("kind") == "step"]
    assert len(steps) == 3 and all(r["rank"] == 0 for r in steps)
    assert any(r.get("kind") == "fleet" for r in merged)
    # the JSONL dump now describes the fleet view
    assert hub.export_records() is merged


def _EnabledKwargs():
    return TelemetryKwargs(enabled=True)


# ---------------------------------------------------------------------------
# metrics endpoint: valid Prometheus text, live serving gauges
# ---------------------------------------------------------------------------

# the renderer's own sample-line grammar (incl. histogram `le` labels): one
# source of truth, so no validator tracks another format than the endpoint's
from accelerate_tpu.telemetry.metrics import SAMPLE_LINE_RE as _SAMPLE_RE


def _scrape(url):
    body = urllib.request.urlopen(url, timeout=10).read().decode("utf-8")
    for line in body.splitlines():
        if line.startswith("#") or not line:
            continue
        assert _SAMPLE_RE.match(line), f"invalid exposition line: {line!r}"
    return body


@pytest.mark.parametrize("profile_every_n", [0, 1], ids=["unprofiled", "every_step_profiled"])
def test_metrics_endpoint_scrapes_training_hub(profile_every_n):
    acc, step = _make_step(profile_every_n=profile_every_n)
    batch = _batch(acc)
    for _ in range(2):
        step(batch)
    server = acc.telemetry.serve_metrics(port=0)
    try:
        assert server is acc.telemetry.serve_metrics()  # idempotent
        body = _scrape(server.url)
        assert "# TYPE atpu_telemetry_steps_total counter" in body
        assert "atpu_telemetry_steps_total 2" in body
        assert "atpu_telemetry_recompiles_total 0" in body
        assert "atpu_telemetry_replay_dispatch_ms_mean" in body
        # native step-latency histogram: _bucket series, not percentiles
        assert "# TYPE atpu_telemetry_step_latency_ms histogram" in body
        assert 'atpu_telemetry_step_latency_ms_bucket{le="+Inf"} 1' in body
        assert "atpu_telemetry_step_latency_ms_count 1" in body  # replay only
        # the device split is scraped live once a step has been sampled
        assert ("atpu_telemetry_device_busy_ms" in body) == bool(profile_every_n)
    finally:
        acc.telemetry.close_metrics()
    assert acc.telemetry.metrics_server is None


def test_latency_histogram_cumulative_and_replay_scoped():
    """ROADMAP carried item: native Prometheus `_bucket` series replace the
    point-in-time percentile gauges — bucket counts are CUMULATIVE (le is
    inclusive), sum/count track every observation, and the hub's step
    histogram observes replays only (a build's compile time would park the
    whole mass in the top bucket)."""
    from accelerate_tpu.telemetry.metrics import (
        LatencyHistogram,
        render_prometheus,
    )

    hist = LatencyHistogram(buckets=(1.0, 10.0, 100.0))
    for value in (0.5, 1.0, 5.0, 50.0, 5000.0):
        hist.observe(value)
    assert hist.cumulative_counts() == [2, 3, 4, 5]  # le="1" includes 1.0
    assert hist.count == 5 and hist.sum == 5056.5
    body = render_prometheus([("t", {"lat_ms": hist})])
    assert '# TYPE atpu_t_lat_ms histogram' in body
    assert 'atpu_t_lat_ms_bucket{le="1"} 2' in body
    assert 'atpu_t_lat_ms_bucket{le="+Inf"} 5' in body
    assert "atpu_t_lat_ms_count 5" in body
    # hub scoping: builds excluded from the step histogram
    def _record(step, built, total_ms):
        return StepRecord(
            step=step, key="k", built=built, total_ms=total_ms,
            assembly_ms=0.0, trace_ms=0.0, compile_ms=0.0,
            dispatch_ms=total_ms, dataloader_wait_ms=0.0,
        )

    hub = Telemetry(_EnabledKwargs())
    hub.record_step(_record(0, built=True, total_ms=5000.0))
    hub.record_step(_record(1, built=False, total_ms=3.0))
    assert hub.step_hist.count == 1 and hub.step_hist.sum == 3.0


def test_decode_service_metrics_snapshot_and_scrape():
    from accelerate_tpu.serving import DecodeService, ServingConfig
    from accelerate_tpu.telemetry.metrics import MetricsServer

    nn.manual_seed(0)
    model = GPTLMHeadModel(GPTConfig.tiny())
    model.eval()
    service = DecodeService(
        model, ServingConfig(max_slots=2, block_size=16, prompt_bucket=16)
    )
    rng = np.random.default_rng(0)
    for n in (5, 12, 9):
        service.submit(rng.integers(0, 1024, (n,), dtype=np.int32), 6)
    server = MetricsServer()
    server.add_service(service)
    server.start()
    try:
        mid_metrics = None
        while service.has_work:
            service.step()
            if mid_metrics is None:
                mid_metrics = service.metrics()  # live mid-flight snapshot
        assert mid_metrics["occupancy"] > 0
        done = service.metrics()
        assert done["completed_total"] == 3
        assert done["queue_depth"] == 0
        assert done["block_pool_free_frac"] == 1.0  # all blocks back
        assert done["recompile_events_total"] == 0
        assert done["ttft_ms_p50"] > 0 and done["ttft_ms_p99"] >= done["ttft_ms_p50"]
        assert done["tpot_ms_p50"] > 0
        body = _scrape(server.url)
        assert "atpu_serving_completed_total 3" in body
        assert "atpu_serving_occupancy" in body
        assert "atpu_serving_queue_depth" in body
        assert "atpu_serving_block_pool_free_frac" in body
        assert "atpu_serving_ttft_ms_p50" in body
        assert "atpu_serving_ttft_ms_p99" in body
        # native TTFT/TPOT histograms alongside the window percentiles:
        # one observation per completed request, cumulative over lifetime
        assert "# TYPE atpu_serving_ttft_ms histogram" in body
        assert 'atpu_serving_ttft_ms_bucket{le="+Inf"} 3' in body
        assert "atpu_serving_ttft_ms_count 3" in body
        assert 'atpu_serving_tpot_ms_bucket{le="+Inf"} 3' in body
    finally:
        server.close()


def test_service_with_hub_registers_metrics_provider():
    """A DecodeService built on a telemetry hub self-registers: the hub's
    endpoint scrapes its gauges without extra wiring."""
    hub = Telemetry(_EnabledKwargs())

    class _FakeService:
        def metrics(self):
            return {"occupancy": 0.5, "queue_depth": 2}

    hub.register_metrics_provider("serving", _FakeService().metrics)
    server = hub.serve_metrics(port=0)
    try:
        body = _scrape(server.url)
        assert "atpu_serving_occupancy 0.5" in body
        assert "atpu_serving_queue_depth 2" in body
    finally:
        hub.close_metrics()


def test_render_prometheus_drops_duplicates_and_non_numbers():
    from accelerate_tpu.telemetry.metrics import render_prometheus

    body = render_prometheus([
        ("a", {"x": 1, "nested": {"y": 2.5}, "skip": None, "name": "str",
               "flag": True}),
        ("a", {"x": 99}),  # duplicate name: first sample wins
    ])
    lines = [l for l in body.splitlines() if not l.startswith("#")]
    assert "atpu_a_x 1" in lines
    assert "atpu_a_nested_y 2.5" in lines
    assert "atpu_a_flag 1" in lines
    assert not any(l.startswith("atpu_a_x 99") for l in lines)
    assert not any("skip" in l or "name" in l for l in lines)


def test_device_step_record_to_dict_schema():
    rec = DeviceStepRecord(
        step=3, key="kabc", window_ms=10.0, busy_ms=6.0, idle_ms=4.0,
        compute_ms=5.0, collective_ms=1.5, transfer_ms=0.5,
        devices={"/host:CPU": {"busy_ms": 6.0}}, top_ops=[["dot.1", 4.2]],
        op_events=7,
    )
    d = rec.to_dict()
    assert d["kind"] == "device_step"
    assert d["collective_share"] == pytest.approx(1.5 / 7.0, abs=1e-4)
    assert d["devices"]["/host:CPU"]["busy_ms"] == 6.0
    assert d["top_ops"] == [["dot.1", 4.2]]
