"""Fault-tolerant serving (docs/serving.md §fault tolerance).

The acceptance contract (ISSUE 20): with a journal armed, a replica that
dies mid-decode — transient dispatch fault, SIGTERM preemption, or plain
crash — is replaced by a fresh replica whose recovered continuations are
BITWISE identical to the uninterrupted run, greedy and sampled alike,
under quantized weights, with zero requests lost.  With the journal off
(the default) the hot path is byte-identical to the pre-recovery service
and none of the new config reaches the AOT service fingerprint.
"""

import json
import os
import signal

import jax
import numpy as np
import pytest

import accelerate_tpu.nn as nn
from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
from accelerate_tpu.serving import (
    DecodeService,
    QueueFullError,
    RequestJournal,
    ServingConfig,
    replay_journal,
)
from accelerate_tpu.serving.recovery import advance_rng  # noqa: F401 (API pin)


@pytest.fixture(scope="module")
def tiny_model():
    nn.manual_seed(0)
    model = GPTLMHeadModel(GPTConfig.tiny())
    model.eval()
    return model


@pytest.fixture(autouse=True)
def _restore_sigterm():
    """Journal-armed services install a PreemptionGuard SIGTERM handler;
    give every test a clean slate and never leak one into the runner."""
    saved = signal.getsignal(signal.SIGTERM)
    yield
    signal.signal(signal.SIGTERM, saved)


def _prompts(lengths, vocab=1024, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,), dtype=np.int32) for n in lengths]


_LENGTHS = [5, 11, 17]
_BUDGETS = [8, 6, 10]


def _cfg(**kw):
    base = dict(max_slots=4, block_size=16, prompt_bucket=16)
    base.update(kw)
    return ServingConfig(**base)


def _run_all(service, prompts=None, budgets=None):
    """Submit (optional) + drive to completion; returns {rid: tokens}."""
    rids = []
    if prompts is not None:
        for p, b in zip(prompts, budgets):
            rids.append(service.submit(p, max_new_tokens=b))
    while service.has_work and not service.draining:
        service.step()
    return rids


def _outputs(service):
    return {rid: list(req.output_ids) for rid, req in service.results.items()
            if req.state == "done"}


# ---------------------------------------------------------------------------
# the request journal: WAL roundtrip, idempotent replay, bounded compaction
# ---------------------------------------------------------------------------

def test_journal_roundtrip(tmp_path):
    meta = {"temperature": 0.0, "rng_seed": 7}
    j = RequestJournal(str(tmp_path), meta=meta)
    j.log_submit(0, np.array([1, 2, 3], np.int32), 4, None)
    j.log_submit(1, np.array([9], np.int32), 2, 50)
    j.log_tokens(0, [10, 11])
    j.log_tokens(0, [12])
    j.log_tokens(1, [20])
    j.log_complete(1)
    j.close()

    state = replay_journal(str(tmp_path))
    assert state.meta["temperature"] == 0.0 and state.meta["rng_seed"] == 7
    assert not state.drained
    assert sorted(state.entries) == [0, 1]
    assert state.entries[0].tokens == [10, 11, 12]
    assert state.entries[0].open
    assert state.entries[1].done and not state.entries[1].open
    assert state.entries[1].eos_token_id == 50
    np.testing.assert_array_equal(state.entries[0].prompt, [1, 2, 3])
    # only the incomplete request is resumable, FIFO by rid
    assert [e.rid for e in state.open_requests] == [0]


def test_journal_replay_is_idempotent_and_tolerates_torn_tail(tmp_path):
    j = RequestJournal(str(tmp_path))
    j.log_submit(0, np.array([1, 2], np.int32), 6, None)
    j.log_tokens(0, [5, 6, 7])
    j.close()
    path = j.path
    # duplicate append at an already-applied offset (a crashed writer's
    # re-log): absolute `at` offsets make replay idempotent
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(
            {"ev": "tok", "rid": 0, "at": 1, "toks": [6, 7]}) + "\n")
        # an out-of-range offset (lost intermediate record) is skipped,
        # never applied with a gap
        f.write(json.dumps(
            {"ev": "tok", "rid": 0, "at": 9, "toks": [99]}) + "\n")
        # torn trailing line from a crash mid-write: dropped, not fatal
        f.write('{"ev": "tok", "rid": 0, "at"')
    state = replay_journal(path)
    assert state.entries[0].tokens == [5, 6, 7]


def test_journal_compaction_bounds_the_file(tmp_path):
    j = RequestJournal(str(tmp_path), compact_every=8)
    done_prompt = np.array([1], np.int32)
    j.log_submit(0, done_prompt, 64, None)
    j.log_submit(1, np.array([2, 3], np.int32), 4, None)
    for i in range(40):  # way past compact_every: forces rewrites
        j.log_tokens(0, [i])
    j.log_complete(0)
    j.log_tokens(1, [7])
    j.close()
    with open(j.path, encoding="utf-8") as f:
        lines = [json.loads(l) for l in f if l.strip()]
    # compaction rewrote the log down to meta + live state: far fewer
    # records than the 44+ appends, and the finished request is gone
    assert len(lines) < 20
    assert not any(r.get("rid") == 0 and r["ev"] == "submit" for r in lines)
    state = replay_journal(j.path)
    assert [e.rid for e in state.open_requests] == [1]
    assert state.entries[1].tokens == [7]


def test_journal_dir_env_arms_config(tmp_path, monkeypatch):
    monkeypatch.setenv("ACCELERATE_SERVING_JOURNAL", str(tmp_path))
    assert _cfg().journal_dir == str(tmp_path)
    monkeypatch.delenv("ACCELERATE_SERVING_JOURNAL")
    assert _cfg().journal_dir is None


# ---------------------------------------------------------------------------
# deterministic recovery: re-prefill == uninterrupted, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("interrupt_after", [1, 2, 4])
def test_recovery_bitwise_parity(tiny_model, tmp_path, temperature,
                                 interrupt_after):
    """Kill a journaled replica after N engine steps; a fresh replica
    resumed from the journal finishes every request with tokens bitwise
    equal to an uninterrupted run — greedy AND sampled (the per-slot RNG
    stream is re-advanced through the emitted prefix)."""
    prompts = _prompts(_LENGTHS)

    ref = DecodeService(tiny_model, _cfg(temperature=temperature))
    _run_all(ref, prompts, _BUDGETS)
    want = _outputs(ref)

    jdir = str(tmp_path / "j")
    a = DecodeService(
        tiny_model, _cfg(temperature=temperature, journal_dir=jdir)
    )
    for p, b in zip(prompts, _BUDGETS):
        a.submit(p, max_new_tokens=b)
    for _ in range(interrupt_after):
        a.step()
    del a  # crash: no drain, no close — replay must cope with the raw WAL

    b_svc = DecodeService(
        tiny_model, _cfg(temperature=temperature, journal_dir=jdir)
    )
    resumed = b_svc.resume_from_journal()
    _run_all(b_svc)
    got = _outputs(b_svc)
    assert set(resumed) <= set(want)
    # zero lost: every journaled-open request completed on the new replica
    assert sorted(got) == sorted(resumed)
    for rid in got:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"rid {rid} diverged after recovery "
                    f"(T={temperature}, interrupted@{interrupt_after})",
        )
    assert b_svc.stats["recovered"] == len(resumed)


def test_recovery_parity_quantized(tiny_model, tmp_path):
    """Recovery composes with int8 weight quantization: the recovered
    continuation re-prefills through the SAME quantized program family."""
    prompts = _prompts(_LENGTHS)
    cfg = dict(temperature=0.0, quantize_weights=8)
    ref = DecodeService(tiny_model, _cfg(**cfg))
    _run_all(ref, prompts, _BUDGETS)
    want = _outputs(ref)

    jdir = str(tmp_path / "j")
    a = DecodeService(tiny_model, _cfg(journal_dir=jdir, **cfg))
    for p, b in zip(prompts, _BUDGETS):
        a.submit(p, max_new_tokens=b)
    a.step()
    a.step()
    del a

    b_svc = DecodeService(tiny_model, _cfg(journal_dir=jdir, **cfg))
    resumed = b_svc.resume_from_journal()
    assert resumed
    _run_all(b_svc)
    got = _outputs(b_svc)
    for rid in got:
        np.testing.assert_array_equal(got[rid], want[rid])


def test_resume_rejects_mismatched_sampling_config(tiny_model, tmp_path):
    jdir = str(tmp_path / "j")
    a = DecodeService(tiny_model, _cfg(temperature=0.8, journal_dir=jdir))
    a.submit(_prompts([5])[0], max_new_tokens=4)
    a.step()
    del a
    b_svc = DecodeService(tiny_model, _cfg(temperature=0.0, journal_dir=jdir))
    with pytest.raises(ValueError, match="temperature"):
        b_svc.resume_from_journal()


# ---------------------------------------------------------------------------
# decode-step retry: transient faults never recompile; exhaustion requeues
# ---------------------------------------------------------------------------

def test_decode_retry_reuses_compiled_program(tiny_model, monkeypatch):
    """One injected transient decode fault: retried against the same
    compiled program (zero extra compiles), tokens unchanged."""
    prompts = _prompts(_LENGTHS)
    ref = DecodeService(tiny_model, _cfg())
    _run_all(ref, prompts, _BUDGETS)
    want = _outputs(ref)

    monkeypatch.setenv("ACCELERATE_FAULT_PLAN", "decode_fault:step=1,times=1")
    svc = DecodeService(tiny_model, _cfg(retry_backoff_s=0.001))
    _run_all(svc, prompts, _BUDGETS)
    got = _outputs(svc)
    assert svc.stats["decode_retries"] == 1
    assert svc.stats["requeued"] == 0
    assert svc.recompile_events == 0
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    m = svc.metrics()
    assert m["decode_retries_total"] == 1 and m["requeued_total"] == 0


def test_retry_exhaustion_evicts_and_requeues(tiny_model, monkeypatch):
    """A fault that outlives the retry budget evicts the batch and requeues
    every in-flight request; re-prefill recovery still lands bitwise parity."""
    prompts = _prompts(_LENGTHS)
    ref = DecodeService(tiny_model, _cfg())
    _run_all(ref, prompts, _BUDGETS)
    want = _outputs(ref)

    monkeypatch.setenv("ACCELERATE_FAULT_PLAN", "decode_fault:step=1,times=5")
    svc = DecodeService(
        tiny_model, _cfg(max_decode_retries=2, retry_backoff_s=0.001)
    )
    _run_all(svc, prompts, _BUDGETS)
    got = _outputs(svc)
    assert svc.stats["decode_retries"] == 2  # budget spent...
    assert svc.stats["requeued"] > 0  # ...then the batch was requeued
    assert svc.stats["recovered"] > 0  # ...and re-admitted via re-prefill
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])


def test_non_transient_fault_raises(tiny_model, monkeypatch):
    svc = DecodeService(tiny_model, _cfg())
    svc.submit(_prompts([5])[0], max_new_tokens=4)

    def _boom(*a, **k):
        raise ValueError("shape mismatch: not retryable")

    monkeypatch.setattr("accelerate_tpu.serving.engine.run_decode", _boom)
    monkeypatch.setattr("accelerate_tpu.serving.engine.run_decode_n", _boom)
    with pytest.raises(ValueError, match="not retryable"):
        while svc.has_work:
            svc.step()


# ---------------------------------------------------------------------------
# preemption drain + resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("aot_store", [False, True], ids=["compiled", "aot_store"])
def test_sigterm_drains_and_fresh_replica_resumes(tiny_model, tmp_path, monkeypatch, aot_store,
                                                  compiled_in_this_process):
    """Injected SIGTERM mid-decode: the guard's sticky flag drains the
    service (journal finalized, open rids reported); a fresh replica on the
    same journal completes every request, bitwise equal, zero lost.  Against
    one AOT store the scenario runs twice, and the second pass — the
    recovery's re-prefills among it — compiles nothing the first one stored:
    a replica's restart is disk reads."""
    prompts = _prompts(_LENGTHS)
    ref = DecodeService(tiny_model, _cfg())
    _run_all(ref, prompts, _BUDGETS)
    want = _outputs(ref)

    def replica(jdir):
        if not aot_store:
            return DecodeService(tiny_model, _cfg(journal_dir=jdir))
        from accelerate_tpu import CompilationCacheKwargs
        from accelerate_tpu.native.aot_cache import AOTCompilationCache

        cache = AOTCompilationCache(CompilationCacheKwargs(cache_dir=str(tmp_path / "aot")))
        # the unprepared model lives on one device; left unpinned, a stored
        # program loads onto every device of the backend (8 here)
        cache.set_context(mesh=jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",)))
        return DecodeService(tiny_model, _cfg(journal_dir=jdir), aot_cache=cache)

    def preempt_and_resume(jdir):
        monkeypatch.setenv("ACCELERATE_FAULT_PLAN", "serving_sigterm:step=2")
        a = replica(jdir)
        for p, b in zip(prompts, _BUDGETS):
            a.submit(p, max_new_tokens=b)
        a.run(max_steps=50)
        assert a.draining
        finished_on_a = _outputs(a)
        open_rids = a.drain()  # idempotent; returns the still-open rids
        assert open_rids and set(open_rids).isdisjoint(finished_on_a)
        state = replay_journal(jdir)
        assert state.drained
        assert [e.rid for e in state.open_requests] == open_rids

        monkeypatch.delenv("ACCELERATE_FAULT_PLAN")
        b_svc = replica(jdir)
        resumed = b_svc.resume_from_journal()
        assert resumed == open_rids
        _run_all(b_svc)
        got = _outputs(b_svc)
        # zero lost: A's completions + B's recoveries cover every submission
        assert sorted(list(finished_on_a) + list(got)) == sorted(want)
        for rid in got:
            np.testing.assert_array_equal(got[rid], want[rid])
        return a, b_svc

    a, b_svc = preempt_and_resume(str(tmp_path / "j"))
    if aot_store:
        built = a.watcher.compiles_total + b_svc.watcher.compiles_total
        stored = len(list((tmp_path / "aot").glob("*-*.pkl")))
        assert 1 <= stored <= built  # under `built` only where XLA:CPU refused to serialize one
        a2, b2 = preempt_and_resume(str(tmp_path / "j2"))
        assert a2._aot.warmed == stored
        assert a2.watcher.compiles_total + b2.watcher.compiles_total <= built - stored
        assert a2.recompile_events == 0 and b2.recompile_events == 0


def test_drain_stops_admission(tiny_model):
    svc = DecodeService(tiny_model, _cfg())
    svc.drain(reason="test")
    assert svc.draining
    with pytest.raises(QueueFullError, match="draining"):
        svc.submit(_prompts([5])[0], max_new_tokens=4)
    assert svc.step() == []  # draining step is a no-op, never dispatches


# ---------------------------------------------------------------------------
# deadline shedding + bounded queueing
# ---------------------------------------------------------------------------

def test_deadline_shed_at_admission(tiny_model):
    import time

    svc = DecodeService(tiny_model, _cfg())
    # backdate arrival a full second; a 100ms deadline is long dead
    rid = svc.submit(
        _prompts([5])[0], max_new_tokens=4,
        arrival_t=time.perf_counter() - 1.0, deadline_ms=100.0,
    )
    svc.step()
    req = svc.results[rid]
    assert req.state == "shed"
    assert len(req.tokens) == 0  # shed requests are never prefilled
    assert svc.stats["shed"] == 1
    assert svc.metrics()["shed_total"] == 1


def test_queue_depth_bound_rejects_with_retry_after(tiny_model):
    svc = DecodeService(tiny_model, _cfg(max_queue_depth=1))
    svc.submit(_prompts([5])[0], max_new_tokens=4)
    with pytest.raises(QueueFullError) as exc_info:
        svc.submit(_prompts([5])[0], max_new_tokens=4)
    assert exc_info.value.retry_after_ms > 0
    assert svc.stats["shed"] == 1
    _run_all(svc)  # the admitted request still completes normally
    assert svc.metrics()["completed_total"] == 1


# ---------------------------------------------------------------------------
# default-off byte-identity + fingerprint invariance
# ---------------------------------------------------------------------------

def test_journal_off_is_byte_identical_and_on_changes_tokens_nothing(
        tiny_model, tmp_path):
    """The recovery machinery is default-off dead code: journal-off output
    equals the pre-recovery service, and journal-ON output equals
    journal-off output (the WAL observes the hot path, never perturbs it)."""
    prompts = _prompts(_LENGTHS)
    off = DecodeService(tiny_model, _cfg(temperature=0.8))
    _run_all(off, prompts, _BUDGETS)
    on = DecodeService(
        tiny_model, _cfg(temperature=0.8, journal_dir=str(tmp_path / "j"))
    )
    _run_all(on, prompts, _BUDGETS)
    want, got = _outputs(off), _outputs(on)
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    assert off._journal is None and off._guard is None
    assert on.recompile_events == 0


def test_recovery_config_stays_off_aot_fingerprint(tiny_model, tmp_path):
    """None of journal_dir/max_queue_depth/max_decode_retries reach the AOT
    service fingerprint: a warm store serves journaled and journal-less
    replicas alike (no cold compiles on the recovered replica)."""
    from accelerate_tpu import CompilationCacheKwargs
    from accelerate_tpu.native.aot_cache import AOTCompilationCache

    cache = AOTCompilationCache(
        CompilationCacheKwargs(cache_dir=str(tmp_path / "aot"))
    )
    plain = DecodeService(tiny_model, _cfg(), aot_cache=cache)
    journaled = DecodeService(
        tiny_model,
        _cfg(journal_dir=str(tmp_path / "j"), max_queue_depth=8,
             max_decode_retries=5),
        aot_cache=cache,
    )
    assert plain._aot is not None and journaled._aot is not None
    assert plain._aot.service_digest == journaled._aot.service_digest


# ---------------------------------------------------------------------------
# observability: /healthz, serving_recovery telemetry, bounded metrics retry
# ---------------------------------------------------------------------------

def _get(url):
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_healthz_readiness_tracks_service_lifecycle(tiny_model):
    """/healthz: 503 before programs warm, 200 while serving, 503 once
    draining — ready = programs warmed ∧ pool allocated ∧ not draining."""
    from accelerate_tpu import TelemetryKwargs
    from accelerate_tpu.telemetry import Telemetry

    hub = Telemetry(TelemetryKwargs(enabled=True))
    svc = DecodeService(tiny_model, _cfg(), telemetry=hub)
    server = hub.serve_metrics(port=0)
    try:
        url = f"http://127.0.0.1:{server.port}/healthz"
        code, body = _get(url)
        assert code == 503 and body["live"] and not body["ready"]
        assert not body["services"]["serving"]["programs_warmed"]

        _run_all(svc, _prompts([5]), [4])
        code, body = _get(url)
        assert code == 200 and body["ready"]
        assert body["services"]["serving"]["programs_warmed"]

        svc.drain(reason="test")
        code, body = _get(url)
        assert code == 503 and not body["ready"]
        assert body["services"]["serving"]["draining"]
        events = [r for r in hub.all_records()
                  if r.get("kind") == "serving_recovery"]
        assert any(e.get("event") == "drain" for e in events)
    finally:
        hub.close_metrics()


def test_metrics_snapshot_retry_is_bounded(tiny_model):
    """A completion stream hot enough to defeat every snapshot attempt must
    not spin the scrape: the cap trips, the counter + flight event land, and
    the scrape returns percentile-less but complete."""

    class _AlwaysMutating:
        def __iter__(self):
            raise RuntimeError("deque mutated during iteration")

    svc = DecodeService(tiny_model, _cfg())
    svc._latency_window = _AlwaysMutating()
    m = svc.metrics()
    assert m["latency_window"] == 0
    assert "ttft_ms_p50" not in m
    assert m["metrics_snapshot_retry_exhausted_total"] == 1
    svc.metrics()
    assert svc.stats["metrics_snapshot_retry_exhausted"] == 2
