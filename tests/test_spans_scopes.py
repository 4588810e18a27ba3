"""Spans and scopes inside the program (docs/telemetry.md §spans and scopes).

The flight recorder's ring carries spans beside its instants, stamped on the
profiler's clock; the engine step and the captured call write their host
phases there; the decode and train programs carry ``atpu_*`` named scopes
that a process-wide, lazy registry maps back to instruction names.
"""

import re
import signal
import time

import numpy as np
import pytest

import accelerate_tpu.nn as nn
import accelerate_tpu.optim as optim
from accelerate_tpu import Accelerator, TelemetryKwargs
from accelerate_tpu.data_loader import batch_to_global_array
from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
from accelerate_tpu.serving import DecodeService, ServingConfig
from accelerate_tpu.telemetry import _set_active, flightrec, profiler
from accelerate_tpu.telemetry.flightrec import FlightRecorder


@pytest.fixture(autouse=True)
def _fresh_ring_and_registry(monkeypatch):
    """Every test reads its own ring and its own registry."""
    monkeypatch.setattr(flightrec, "_RECORDER", FlightRecorder(capacity=4096))
    monkeypatch.setattr(profiler, "_programs", {})
    saved = signal.getsignal(signal.SIGTERM)
    yield
    signal.signal(signal.SIGTERM, saved)
    _set_active(None)


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def test_ring_stores_and_returns_spans_beside_instants():
    rec = FlightRecorder(capacity=64)
    t0 = rec.now_ns()
    rec.record("tick", i=0)
    with rec.span("atpu/work", step=3) as sp:
        time.sleep(0.002)
        sp.fields["done"] = True
    rec.record_span("atpu/given", sp.start_ns, sp.end_ns, who="caller")
    events, lost = rec.spans(t0, rec.now_ns())
    assert lost == 0
    assert [e["name"] for e in events] == ["tick", "atpu/work", "atpu/given"]
    tick, work, given = events
    assert tick["end_ns"] == tick["start_ns"] and tick["i"] == 0
    assert work["step"] == 3 and work["done"] is True
    assert work["end_ns"] - work["start_ns"] >= 2_000_000
    assert (work["start_ns"], work["end_ns"]) == (sp.start_ns, sp.end_ns)
    assert (given["start_ns"], given["end_ns"], given["who"]) == (sp.start_ns, sp.end_ns, "caller")
    assert sp.ms == pytest.approx((sp.end_ns - sp.start_ns) / 1e6)
    # the dump's view: monotonic seconds and a duration, one slot per span
    snap = {e["kind"]: e for e in rec.snapshot()}
    assert snap["atpu/work"]["dur_ms"] >= 2.0 and "dur_ms" not in snap["tick"]
    assert rec.events_total == 3


def test_ring_interval_selects_what_touches_it():
    rec = FlightRecorder(capacity=64)
    base = rec.now_ns()
    for k in range(5):
        rec.record_span("s", base + 10 * k, base + 10 * k + 5, k=k)
    events, _ = rec.spans(base + 12, base + 31)
    # span 1 ends at 15 (inside), span 3 starts at 30 (inside); 0 and 4 lie outside
    assert [e["k"] for e in events] == [1, 2, 3]


def test_ring_wraps_and_counts_drops_per_interval():
    rec = FlightRecorder(capacity=16)
    base = rec.now_ns()
    for k in range(40):
        rec.record_span("s", base + 100 * k, base + 100 * k + 50, k=k)
    assert rec.dropped == 24 and rec.depth == 16
    kept, lost = rec.spans(base + 100 * 30, base + 100 * 40)
    assert [e["k"] for e in kept] == list(range(30, 40)) and lost == 0
    # an interval that reaches back into what was overwritten says so
    kept, lost = rec.spans(base, base + 100 * 40)
    assert [e["k"] for e in kept] == list(range(24, 40)) and lost == 24


def test_disabled_ring_stamps_and_stores_nothing():
    rec = FlightRecorder(capacity=32, enabled=False)
    with rec.span("atpu/work") as sp:
        pass
    rec.record_span("atpu/given", 1, 2)
    assert sp.end_ns >= sp.start_ns > 0  # a caller can still read a duration
    assert rec.events_total == 0 and rec.spans(0, rec.now_ns()) == ([], 0)


def test_span_start_is_the_ring_clock_at_entry_and_the_clock_is_wall_time():
    rec = FlightRecorder(capacity=32)
    before = rec.now_ns()
    with rec.span("atpu/work") as sp:
        inside = rec.now_ns()
    assert before <= sp.start_ns <= inside
    assert sp.start_ns - before < 1_000_000  # within 1 ms of the read at entry
    # Unix-epoch ns (what the profiler stamps host events with, before a
    # session rebases them), ticking with the monotonic clock
    assert abs(rec.now_ns() - time.time_ns()) < 50_000_000
    t = time.perf_counter()
    assert abs(rec.from_perf_counter(t) - rec.now_ns()) < 5_000_000
    assert rec.from_perf_counter(None) is None


def test_default_capacity_holds_a_minute_of_fast_serving():
    assert flightrec._DEFAULT_CAPACITY == 65536


# ---------------------------------------------------------------------------
# the engine step
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    nn.manual_seed(0)
    model = GPTLMHeadModel(GPTConfig.tiny())
    model.eval()
    return model


def _cfg(**kw):
    base = dict(max_slots=4, block_size=16, prompt_bucket=16, max_request_len=64)
    base.update(kw)
    return ServingConfig(**base)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1024, (n,), dtype=np.int32) for n in lengths]


def _by_step(events):
    """``[(step span, [spans inside it])]`` in order."""
    steps = [e for e in events if e["name"] == "atpu/serve/step"]
    inner = [e for e in events if e["name"].startswith("atpu/serve/") and e not in steps]
    return [
        (s, sorted((e for e in inner if s["start_ns"] <= e["start_ns"] and e["end_ns"] <= s["end_ns"]),
                   key=lambda e: e["start_ns"]))
        for s in steps
    ]


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_engine_step_spans_nest_in_order_without_overlap(tiny_model, decode_steps):
    service = DecodeService(tiny_model, _cfg(decode_steps=decode_steps))
    rec = flightrec.recorder()
    t0 = rec.now_ns()
    for p, b in zip(_prompts([5, 11, 17, 9, 13, 21]), [6, 4, 9, 3, 7, 5]):
        service.submit(p, max_new_tokens=b)
    done = service.run()
    events, lost = rec.spans(t0, rec.now_ns())
    assert lost == 0
    steps = _by_step(events)
    assert len(steps) == service.stats["steps"]
    claimed = sum(len(inside) for _, inside in steps)
    assert claimed == sum(e["name"].startswith("atpu/serve/") for e in events) - len(steps)
    admitted = 0
    for k, (step, inside) in enumerate(steps):
        assert step["step"] == k and {"active", "queue_depth"} <= set(step)
        top = [e for e in inside if not e["name"].startswith("atpu/serve/prefill_")]
        names = [e["name"].rsplit("/", 1)[1] for e in top]
        assert names in (
            ["admit", "decode_launch", "decode_sync", "emit"],
            ["decode_launch", "decode_sync", "emit"],
        ), names
        for a, b in zip(top, top[1:]):
            assert a["end_ns"] <= b["start_ns"]
        assert top[-1]["name"] == "atpu/serve/emit" and {"emitted", "completed"} <= set(top[-1])
        prefills = [e for e in inside if e["name"].startswith("atpu/serve/prefill_")]
        if prefills:
            admit = top[0]
            assert admit["name"] == "atpu/serve/admit"
            assert admit["admitted"] == len(prefills) // 2
            admitted += admit["admitted"]
            for launch, sync in zip(prefills[0::2], prefills[1::2]):
                assert launch["name"].endswith("prefill_launch") and sync["name"].endswith("prefill_sync")
                assert launch["rid"] == sync["rid"] and launch["end_ns"] <= sync["start_ns"]
                assert admit["start_ns"] <= launch["start_ns"] and sync["end_ns"] <= admit["end_ns"]
        # the budget: at most 8 spans an engine step, prefills apart
        assert len(top) + 1 <= 8
    assert admitted == len(done) == 6
    # every finished request: ordered stamps, on the Request and on the ring
    finished = {e["rid"]: e for e in events if e["name"] == "serve/request"}
    submits = {e["rid"]: e for e in events if e["name"] == "serve/submit"}
    assert set(finished) == set(submits) == set(done)
    for rid, req in done.items():
        assert req.submitted_t <= req.admitted_t <= req.first_token_t <= req.done_t
        e = finished[rid]
        assert e["submitted"] <= e["admitted"] <= e["first_token"] <= e["done"]
        assert e["submitted"] == submits[rid]["submitted"] == rec.from_perf_counter(req.submitted_t)
        assert e["tokens"] == len(req.tokens) and e["prompt_len"] == req.prompt_len


def test_submit_stamp_is_the_arrival_time_it_was_given(tiny_model):
    service = DecodeService(tiny_model, _cfg())
    rec = flightrec.recorder()
    due = time.perf_counter() - 0.25
    rid = service.submit(_prompts([7])[0], max_new_tokens=2, arrival_t=due)
    service.run()
    events, _ = rec.spans(0, rec.now_ns())
    submit = next(e for e in events if e["name"] == "serve/submit")
    assert submit["rid"] == rid and submit["submitted"] == rec.from_perf_counter(due)
    assert submit["start_ns"] - submit["submitted"] >= 250_000_000
    assert service.results[rid].admitted_t - due >= 0.25


def test_recovered_requests_carry_admission_stamps_too(tiny_model, tmp_path):
    jdir = str(tmp_path / "j")
    first = DecodeService(tiny_model, _cfg(journal_dir=jdir))
    for p, b in zip(_prompts([5, 11, 17]), [8, 6, 10]):
        first.submit(p, max_new_tokens=b)
    for _ in range(2):
        first.step()
    del first  # crash
    second = DecodeService(tiny_model, _cfg(journal_dir=jdir))
    resumed = second.resume_from_journal()
    assert resumed
    done = second.run()
    assert second.stats["recovered"] == len(resumed)
    for rid in resumed:
        req = done[rid]
        assert req.submitted_t <= req.admitted_t <= req.first_token_t <= req.done_t
    events, _ = flightrec.recorder().spans(0, flightrec.recorder().now_ns())
    relaunched = {e["rid"] for e in events if e["name"] == "atpu/serve/prefill_launch"}
    assert set(resumed) <= relaunched


# ---------------------------------------------------------------------------
# the captured call
# ---------------------------------------------------------------------------

def _train_step(telemetry: bool):
    nn.manual_seed(0)
    acc = Accelerator(
        kwargs_handlers=[TelemetryKwargs(enabled=True)] if telemetry else None
    )
    model = GPTLMHeadModel(GPTConfig(vocab_size=256, n_positions=64, n_embd=32, n_layer=1, n_head=2))
    opt = optim.AdamW(model.parameters(), lr=1e-3)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    import jax.numpy as jnp

    ids = np.random.default_rng(0).integers(0, 256, (8, 32), dtype=np.int32)
    return acc, acc.compile_step(step_fn), batch_to_global_array(jnp.asarray(ids), mesh=acc.mesh)


def _call_spans(events):
    """Per captured call ``{short name: span}``, cut at ``step_begin``."""
    calls = []
    for e in events:
        if e["name"] == "step_begin":
            calls.append({})
        elif calls and e["name"] in ("atpu/step/assemble", "atpu/dispatch", "atpu/step/writeback",
                                     "atpu/trace", "atpu/lower", "atpu/compile", "step_end"):
            # the last of a name: an inner jit's trace closes inside the step's
            calls[-1][e["name"].rsplit("/", 1)[-1]] = e
    return calls


def _ms(span):
    return (span["end_ns"] - span["start_ns"]) / 1e6


def test_captured_call_spans_cover_the_call_and_equal_the_step_record():
    acc, step, batch = _train_step(telemetry=True)
    rec = flightrec.recorder()
    for _ in range(4):
        step(batch)
    calls = _call_spans(rec.spans(0, rec.now_ns())[0])
    records = acc.telemetry.timeline.records()
    assert len(calls) == len(records) == 4
    assert {"trace", "lower", "compile"} <= set(calls[0]) and "trace" not in calls[1]
    for k, (spans, record) in enumerate(zip(calls, records)):
        a, d, w, end = spans["assemble"], spans["dispatch"], spans["writeback"], spans["step_end"]
        assert a["step"] == record.step == k and a["built"] == record.built == (k == 0)
        # each span starts at the stamp that ended the one before
        assert a["end_ns"] == d["start_ns"] and d["end_ns"] == w["start_ns"] <= w["end_ns"] <= end["start_ns"]
        covered = sum(s["end_ns"] - s["start_ns"] for s in (a, d, w)) / 1e6
        whole = (w["end_ns"] - a["start_ns"]) / 1e6
        assert record.total_ms == pytest.approx(whole, abs=1e-6)
        assert covered == pytest.approx(whole, abs=1e-6)
        # StepRecord's phases are these stamps (a build's trace + compile
        # taken out of its assembly, as documented)
        built_ms = record.trace_ms + record.compile_ms
        assert record.assembly_ms + built_ms == pytest.approx((d["start_ns"] - a["start_ns"]) / 1e6, abs=1e-6)
        assert record.dispatch_ms == pytest.approx((w["end_ns"] - d["start_ns"]) / 1e6, abs=1e-6)
        if k == 0:
            # the build's times are the recorder's listener's spans of the
            # step's program: trace + lower, then compile
            trace, lower, compiling = spans["trace"], spans["lower"], spans["compile"]
            assert (trace["fun"], lower["fun"], compiling["fun"]) == ("traced", "jit(traced)", "jit(traced)")
            assert compiling["cache"] in ("hit", "miss")
            assert record.trace_ms == pytest.approx(_ms(trace) + _ms(lower))
            assert record.compile_ms == pytest.approx(_ms(compiling))


def test_captured_call_spans_exist_with_telemetry_off():
    _, step, batch = _train_step(telemetry=False)
    assert step._telemetry is None
    for _ in range(2):
        step(batch)
    calls = _call_spans(flightrec.recorder().spans(0, flightrec.recorder().now_ns())[0])
    # the plain-jit build inside the first dispatch: its compile phases are
    # the recorder's listener's, telemetry on or off
    assert [sorted(c) for c in calls] == [
        ["assemble", "compile", "dispatch", "lower", "step_end", "trace", "writeback"],
        ["assemble", "dispatch", "step_end", "writeback"],
    ]
    assert calls[0]["dispatch"]["start_ns"] <= calls[0]["trace"]["start_ns"]
    assert calls[0]["compile"]["end_ns"] <= calls[0]["dispatch"]["end_ns"]
    assert [c["assemble"]["step"] for c in calls] == [0, 1]


def test_one_span_mechanism_remains():
    import pathlib

    import accelerate_tpu
    from accelerate_tpu.telemetry import Telemetry

    assert not hasattr(Telemetry, "span")
    assert "annotate_spans" not in TelemetryKwargs.__dataclass_fields__
    root = pathlib.Path(accelerate_tpu.__file__).parent
    users = sorted(
        str(p.relative_to(root)) for p in root.rglob("*.py")
        if re.search(r"^[^#\n]*\bTraceAnnotation\b", p.read_text(), re.M)
        and "import TraceAnnotation" in p.read_text()
    )
    assert users == ["telemetry/flightrec.py"]


# ---------------------------------------------------------------------------
# scopes and the program registry
# ---------------------------------------------------------------------------

SERVE_SCOPES = ("embed", "qkv", "kv_write", "attend", "out_mlp", "head")


def _paths(text):
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_decode_program_text_carries_every_serve_scope(tiny_model, decode_steps):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.serving import engine

    service = DecodeService(tiny_model, _cfg(decode_steps=decode_steps))
    statics = dict(family=service.spec.family, cfg=service.spec.cfg, qbits=service._qbits, temperature=0.0)
    args = (service._k_pool, service._v_pool, service._g, service._layers,
            jnp.asarray(service._tables), jnp.asarray(service._positions),
            jnp.asarray(service._tokens), service._rngs)
    specs = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
    if decode_steps == 1:
        lowered = engine._decode_jit.lower(*specs, **statics)
    else:
        lowered = engine._decode_n_jit.lower(*specs, decode_steps=decode_steps, **statics)
    # before the compiler fuses anything every scope stands in some op's path,
    # as a plain segment: that is what the deepest-atpu-segment rule needs
    segments = {seg for m in re.finditer(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
                for seg in m.group(1).split("/")}
    assert {f"atpu_serve_{s}" for s in SERVE_SCOPES} <= segments
    assert "atpu_serve_kv_gather" not in segments  # it went with the gathered span it named
    # and the compiled program keeps them as instruction metadata (the CPU
    # compiler fuses a tiny model's embed into its neighbour)
    scoped = set(profiler.scope_map_from_text(lowered.compile().as_text()).values())
    assert {"atpu_serve_qkv", "atpu_serve_kv_write", "atpu_serve_attend",
            "atpu_serve_out_mlp", "atpu_serve_head"} <= scoped


def test_prefill_program_carries_the_scopes_its_phases_have(tiny_model):
    service = DecodeService(tiny_model, _cfg())
    service.submit(_prompts([9])[0], max_new_tokens=2)
    service.run()
    scoped = set(profiler.scope_map("_prefill_jit").values())
    # (the CPU compiler fuses a tiny prefill's pool write into a neighbour)
    assert {"atpu_serve_qkv", "atpu_serve_out_mlp", "atpu_serve_head"} <= scoped
    assert "atpu_serve_attend" in scoped  # cached_attention on the bucket's own k/v


def test_train_step_text_carries_head_loss_scope_forward_and_backward():
    _, step, batch = _train_step(telemetry=True)
    step(batch)
    paths = _paths(next(iter(step._cache.values()))[0].as_text())
    forward = [p for p in paths if "/atpu_head_loss/" in p and "atpu_backward" not in p]
    backward = [p for p in paths if "atpu_backward" in p and "/atpu_head_loss/" in p]
    assert forward and backward
    # the tape transposes an op under its forward scope path, so the head's
    # backward keeps atpu_head_loss as its deepest segment (docs/telemetry.md)
    scopes = profiler.scope_map("jit_traced")
    assert {"atpu_head_loss", "atpu_update", "atpu_backward"} <= set(scopes.values())
    assert profiler.instruction_names("jit_traced") >= set(scopes)


def test_registry_is_lazy_and_outlives_service_and_accelerator(tiny_model, monkeypatch):
    import gc

    parsed = []
    real = profiler.scope_map_from_text
    monkeypatch.setattr(profiler, "scope_map_from_text", lambda text: parsed.append(len(text)) or real(text))
    acc = Accelerator()
    model = acc.prepare(tiny_model)
    service = DecodeService(model, _cfg())
    for p in _prompts([5, 20]):
        service.submit(p, max_new_tokens=3)
    service.run()
    programs = profiler.registered_programs()
    assert sorted(p.name for p in programs) == ["jit__decode_jit", "jit__prefill_jit", "jit__prefill_jit"]
    # nothing was lowered, compiled or parsed for the maps while the service ran
    assert not parsed and not any(p.evaluated for p in programs)
    del service, model
    acc.free_memory()  # settles what would perish; the serving entries need no owner
    Accelerator._reset_state()
    gc.collect()
    assert not parsed and not any(p.evaluated for p in programs)
    scopes = profiler.scope_map("_decode_jit")
    assert "atpu_serve_qkv" in scopes.values() and len(parsed) == 1
    assert profiler.instruction_names("_decode_jit") >= set(scopes)
    profiler.scope_map("_decode_jit")
    assert len(parsed) == 1  # memoised
    assert profiler.scope_map("no_such_module") == {} == dict.fromkeys(profiler.instruction_names("no_such_module"))


def test_captured_step_is_held_weakly_and_settled_when_memory_is_freed():
    import gc

    acc, step, batch = _train_step(telemetry=True)
    step(batch)
    (program,) = profiler.registered_programs()
    assert program.name == "jit_traced" and program.perishable and not program.evaluated
    acc.free_memory()
    assert program.evaluated
    del step
    gc.collect()
    assert "atpu_update" in profiler.scope_map("jit_traced").values()

    # without the settling, the text goes with its owner; the map is then empty
    acc, step, batch = _train_step(telemetry=True)
    step(batch)
    program = profiler.registered_programs()[-1]
    acc._capture_cache.clear()
    del step
    gc.collect()
    assert not program.evaluated and program.scope_map() == {}


def test_registry_keeps_the_newest_eight():
    for k in range(11):
        profiler.register_program(f"jit_p{k}", lambda k=k: f"%x.{k} = f32[] add()")
    assert [p.name for p in profiler.registered_programs()] == [f"jit_p{k}" for k in range(3, 11)]
    assert profiler.instruction_names("jit_p10") == {"x.10"} and profiler.scope_map("jit_p1") == {}


def test_scopes_in_cache_key_restores_what_it_found():
    import jax

    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    with profiler.scopes_in_cache_key():
        assert getattr(jax.config, name) is True
    assert getattr(jax.config, name) == before
