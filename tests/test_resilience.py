"""Resilience subsystem (docs/resilience.md): injector-driven init
retry/backoff sequencing and fallback, SIGTERM → complete checkpoint →
bitwise-equal resume, transient dispatch faults retried then rolled back,
and the default-off path touching nothing."""

import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu.nn as nn
import accelerate_tpu.optim as optim
from accelerate_tpu import Accelerator, ResilienceKwargs, TelemetryKwargs
from accelerate_tpu.checkpointing import is_complete_checkpoint, latest_checkpoint
from accelerate_tpu.nn import Tensor
from accelerate_tpu.resilience import (
    FaultInjector,
    FaultPlan,
    InjectedTransientError,
    PreemptionGuard,
    classify_failure,
    init_backend,
    probe_backend_once,
)
from accelerate_tpu.resilience import backend as res_backend
from accelerate_tpu.resilience import preemption as res_preemption


@pytest.fixture(autouse=True)
def _resilience_hygiene():
    """Tests install real signal handlers and publish a process-global init
    report; both must not leak across tests."""
    yield
    if res_preemption._INSTALLED is not None:
        res_preemption._INSTALLED.uninstall()
    res_backend.LAST_INIT_REPORT = None


def _make_step(res_kwargs=None, tel=False):
    nn.manual_seed(0)
    handlers = []
    if res_kwargs is not None:
        handlers.append(res_kwargs)
    if tel:
        handlers.append(TelemetryKwargs(enabled=True))
    acc = Accelerator(kwargs_handlers=handlers or None)
    model = nn.Linear(8, 4)
    opt = optim.AdamW(model.parameters(), lr=1e-2)
    model, opt = acc.prepare(model, opt)

    def step_fn(x):
        opt.zero_grad()
        loss = model(Tensor(x)).sum()
        acc.backward(loss)
        opt.step()
        return loss

    return acc, model, acc.compile_step(step_fn)


def _batches(n):
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.normal(size=(4, 8)), jnp.float32) for _ in range(n)]


# ---------------------------------------------------------------------------
# fault plan / injector
# ---------------------------------------------------------------------------

def test_fault_plan_parses_grammar():
    plan = FaultPlan.parse("init_hang:times=2; dispatch:step=3,times=1; sigterm:step=2")
    kinds = [(d.kind, d.step, d.times) for d in plan.directives]
    assert kinds == [("init_hang", None, 2), ("dispatch", 3, 1), ("sigterm", 2, 1)]


@pytest.mark.parametrize(
    "bad", ["explode", "dispatch:times=1", "dispatch:step=x", "sigterm", "dispatch:step=1,frob=2"]
)
def test_fault_plan_rejects_garbage(bad):
    with pytest.raises(ValueError):
        FaultPlan.parse(bad)


def test_injector_dispatch_fault_fires_exactly_times():
    inj = FaultInjector(FaultPlan.parse("dispatch:step=1,times=2"))
    inj.maybe_dispatch_fault(0)  # wrong step: no fault
    with pytest.raises(InjectedTransientError):
        inj.maybe_dispatch_fault(1)
    with pytest.raises(InjectedTransientError):
        inj.maybe_dispatch_fault(1)  # a retry of the same call keeps faulting
    inj.maybe_dispatch_fault(1)  # times exhausted: clean


def test_fault_plan_parses_hang_directive():
    plan = FaultPlan.parse("hang:step=2,seconds=7")
    (d,) = plan.directives
    assert (d.kind, d.step, d.seconds) == ("hang", 2, 7)
    # seconds defaults to effectively-forever (the watchdog is the way out)
    assert FaultPlan.parse("hang:step=1").directives[0].seconds == 3600
    with pytest.raises(ValueError):
        FaultPlan.parse("hang:times=2")  # hang requires an anchor step
    with pytest.raises(ValueError):
        FaultPlan.parse("dispatch:step=1,seconds=5")  # seconds is hang-only


def test_injector_hang_sleeps_and_records_flight_event(monkeypatch):
    from accelerate_tpu.telemetry import flightrec
    from accelerate_tpu.telemetry.flightrec import FlightRecorder

    fresh = FlightRecorder(capacity=32)
    monkeypatch.setattr(flightrec, "_RECORDER", fresh)
    naps = []
    monkeypatch.setattr("time.sleep", lambda s: naps.append(s))
    inj = FaultInjector(FaultPlan.parse("hang:step=2,seconds=5"))
    assert inj.maybe_hang(0) is False and naps == []
    assert inj.maybe_hang(2) is True
    assert naps == [5]
    assert inj.maybe_hang(2) is False  # times exhausted: one hang only
    events = [e for e in fresh.snapshot() if e["kind"] == "hang_injected"]
    assert len(events) == 1
    assert events[0]["step"] == 2 and events[0]["seconds"] == 5


def test_fault_plan_parses_serving_verbs():
    plan = FaultPlan.parse("decode_fault:step=2,times=3; serving_sigterm:step=1")
    kinds = [(d.kind, d.step, d.times) for d in plan.directives]
    assert kinds == [("decode_fault", 2, 3), ("serving_sigterm", 1, 1)]
    # both verbs pin an engine step — a plan without one is ambiguous
    with pytest.raises(ValueError, match="needs step"):
        FaultPlan.parse("decode_fault:times=2")
    with pytest.raises(ValueError, match="needs step"):
        FaultPlan.parse("serving_sigterm")
    # the unknown-verb message teaches the full vocabulary
    with pytest.raises(ValueError, match="serving_sigterm"):
        FaultPlan.parse("decode_fualt:step=1")


def test_injector_decode_fault_fires_exactly_times():
    inj = FaultInjector(FaultPlan.parse("decode_fault:step=1,times=2"))
    inj.maybe_decode_fault(0)  # wrong engine step: no fault
    with pytest.raises(InjectedTransientError, match="engine step 1"):
        inj.maybe_decode_fault(1)
    with pytest.raises(InjectedTransientError):
        inj.maybe_decode_fault(1)  # a retry of the same step keeps faulting
    inj.maybe_decode_fault(1)  # times exhausted: clean
    # the injected error is classified transient — the serving retry loop
    # and the training rollback share one classifier
    try:
        FaultInjector(
            FaultPlan.parse("decode_fault:step=0")
        ).maybe_decode_fault(0)
    except InjectedTransientError as exc:
        assert classify_failure(exc) == "transient"


def test_injector_serving_sigterm_delivers_real_signal():
    seen = []
    saved = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        inj = FaultInjector(FaultPlan.parse("serving_sigterm:step=2"))
        inj.maybe_serving_sigterm(0)
        assert seen == []  # wrong step: nothing delivered
        inj.maybe_serving_sigterm(2)
        assert seen == [signal.SIGTERM]
        inj.maybe_serving_sigterm(2)  # times exhausted: one delivery only
        assert seen == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, saved)


# ---------------------------------------------------------------------------
# pillar 1: hardened backend init
# ---------------------------------------------------------------------------

def test_init_retry_backoff_sequencing_with_injector():
    """Two injected hangs, success on probe 3; the sleeps between attempts
    follow the exponential schedule and every attempt is recorded."""
    inj = FaultInjector(FaultPlan.parse("init_hang:times=2"))
    sleeps = []
    report = init_backend(
        platforms=["cpu"],
        attempts=4,
        timeout_s=7,
        backoff_s=2.0,
        jitter=0.0,
        injector=inj,
        sleep=sleeps.append,
    )
    assert report.ok and report.platform == "cpu" and report.fallback is None
    assert [a.ok for a in report.attempts] == [False, False, True]
    assert "exceeded 7s" in report.attempts[0].detail
    assert sleeps == [2.0, 4.0]  # base * 2**attempt, no jitter
    assert len(report.requested_attempts) == 3
    event = report.to_event()
    assert event["ok"] and event["fallback"] is None and event["attempts"] == 3
    assert report.ts > 0


def test_init_backoff_jitter_bounded():
    from accelerate_tpu.resilience.backend import backoff_delays
    import random

    delays = backoff_delays(5, 5.0, cap_s=30.0, jitter=0.25, rng=random.Random(7))
    assert len(delays) == 4
    for i, delay in enumerate(delays):
        nominal = min(30.0, 5.0 * 2 ** i)
        assert nominal * 0.75 <= delay <= nominal * 1.25


def test_init_falls_down_platform_chain(monkeypatch):
    """Every probe hangs — the requested platform's and the listed
    fallback's: the request is reported failed, and a platform whose own
    probe failed is never applied (nothing pinned, no platform named)."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # restore after
    inj = FaultInjector(FaultPlan.parse("init_hang:times=10"))
    report = init_backend(
        platforms=["tpu", "cpu"],
        attempts=3,
        timeout_s=120,
        backoff_s=0.0,
        injector=inj,
        sleep=lambda s: None,
    )
    assert not report.ok and report.platform is None and report.fallback is None
    assert os.environ["JAX_PLATFORMS"] == "tpu"  # untouched
    assert [a.platform for a in report.attempts] == ["tpu", "tpu", "tpu", "cpu"]
    assert len(report.requested_attempts) == 3
    assert report.attempts[-1].detail.startswith("backend init exceeded 120s")


def test_init_fallback_that_comes_up_is_a_failed_request(monkeypatch):
    """The requested platform hangs, the listed fallback's probe succeeds:
    the fallback is applied, and reported as a failure of the request."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    inj = FaultInjector(FaultPlan.parse("init_hang:times=2"))
    report = init_backend(
        platforms=["tpu", "cpu"], attempts=2, backoff_s=0.0, injector=inj,
        sleep=lambda s: None,
    )
    assert not report.ok  # the request failed ...
    assert report.platform == "cpu" and report.fallback == "cpu"  # ... and says so
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert report.to_event()["fallback"] == "cpu"


def test_init_default_chain_has_no_fallback(monkeypatch):
    """No chain given, none in the environment: only the requested platform
    is probed — a run that asked for a TPU never comes up on the CPU."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("ACCELERATE_RESILIENCE_INIT_FALLBACK", raising=False)
    inj = FaultInjector(FaultPlan.parse("init_hang:times=10"))
    report = init_backend(
        attempts=2, backoff_s=0.0, injector=inj, sleep=lambda s: None
    )
    assert [a.platform for a in report.attempts] == ["tpu", "tpu"]
    assert not report.ok and report.platform is None
    assert os.environ["JAX_PLATFORMS"] == "tpu"


def test_probe_child_has_exited_before_parent_touches_jax(monkeypatch):
    """A chip belongs to one process at a time: the probe child must be
    gone before the parent pins (or opens) a backend."""
    order = []

    class _Done:
        returncode, stdout, stderr = 0, "cpu 8", ""

    def fake_run(cmd, **kwargs):
        order.append("child started")
        order.append("child exited")  # subprocess.run returns only then
        return _Done()

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(res_backend.subprocess, "run", fake_run)
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: order.append("parent touched jax")
    )
    inj = FaultInjector(FaultPlan.parse("init_hang:times=1"))
    init_backend(
        platforms=["tpu", "cpu"], attempts=1, injector=inj, sleep=lambda s: None
    )
    assert order == ["child started", "child exited", "parent touched jax"]
    # and the real probe is a blocking subprocess.run, not a Popen left behind
    import inspect

    assert "subprocess.run(" in inspect.getsource(probe_backend_once)


def test_real_probe_subprocess_succeeds_on_cpu():
    ok, detail = probe_backend_once(platform="cpu", timeout_s=120)
    assert ok, detail
    assert detail.startswith("cpu")


def test_init_report_reaches_telemetry_via_hub():
    """An init that ran before the Accelerator existed (state hardening,
    bench) still lands in the resilience event stream."""
    inj = FaultInjector(FaultPlan.parse("init_hang:times=1"))
    init_backend(
        platforms=["cpu"], attempts=2, backoff_s=0.0, injector=inj,
        sleep=lambda s: None,
    )
    nn.manual_seed(0)
    acc = Accelerator(
        kwargs_handlers=[
            ResilienceKwargs(enabled=True, preemption=False, retry=False),
            TelemetryKwargs(enabled=True),
        ]
    )
    inits = [e for e in acc.resilience.events if e["event"] == "init"]
    assert len(inits) == 1 and inits[0]["attempts"] == 2 and inits[0]["ok"]
    tele = [r for r in acc.telemetry.all_records() if r.get("kind") == "resilience"]
    assert any(r["event"] == "init" for r in tele)


# ---------------------------------------------------------------------------
# pillar 2: preemption-safe checkpointing
# ---------------------------------------------------------------------------

def test_sigterm_sets_sticky_flags_and_drain_writes_complete_checkpoint(tmp_path):
    acc, model, step = _make_step(ResilienceKwargs(enabled=True, retry=False))
    x = _batches(1)[0]
    step(x)
    assert not acc.resilience.should_save
    os.kill(os.getpid(), signal.SIGTERM)
    assert acc.resilience.should_save and acc.resilience.should_exit
    assert acc.resilience.guard.signal_name == "SIGTERM"
    out = acc.resilience.drain(acc, str(tmp_path / "preempt"))
    assert is_complete_checkpoint(out)
    assert acc.resilience.last_checkpoint == out
    assert any(e["event"] == "preemption" for e in acc.resilience.events)
    assert any(e["event"] == "drain" for e in acc.resilience.events)


def test_wallclock_deadline_trips_flags():
    clock = [100.0]
    guard = PreemptionGuard(deadline_s=50.0, time_fn=lambda: clock[0])
    assert not guard.deadline_reached()
    assert guard.seconds_to_deadline() == 50.0
    clock[0] = 149.9
    assert not guard.deadline_reached()
    clock[0] = 150.0
    assert guard.deadline_reached()


def test_sigterm_mid_run_resumes_bitwise_equal(tmp_path):
    """The acceptance matrix row: an injected SIGTERM mid-step makes the loop
    drain and exit with a complete checkpoint whose resume reproduces the
    uninterrupted run's losses bitwise."""
    batches = _batches(5)

    # uninterrupted reference run
    Accelerator._reset_state()
    _, _, step = _make_step()
    reference = [float(step(b)) for b in batches]

    # interrupted run: SIGTERM delivered right before dispatch 2 (mid-step);
    # the loop finishes that step, sees the sticky flag, drains and "exits"
    Accelerator._reset_state()
    acc, _, step = _make_step(
        ResilienceKwargs(enabled=True, fault_plan="sigterm:step=2", retry=False)
    )
    seen = []
    for batch in batches:
        seen.append(float(step(batch)))
        if acc.resilience.should_exit:
            ckpt = acc.resilience.drain(acc, str(tmp_path / "preempted"))
            break
    assert seen == reference[:3]  # step 2 completed despite the signal
    acc.resilience.close()

    # resumed run: fresh process-equivalent state, restore, finish the epoch
    Accelerator._reset_state()
    acc2, _, step2 = _make_step()
    acc2.load_state(ckpt)
    resumed = [float(step2(b)) for b in batches[3:]]
    assert resumed == reference[3:]  # bitwise equality, not allclose


# ---------------------------------------------------------------------------
# pillar 3: step retry with rollback
# ---------------------------------------------------------------------------

def test_transient_dispatch_fault_retried_with_zero_extra_recompiles():
    acc, _, step = _make_step(
        ResilienceKwargs(
            enabled=True, preemption=False,
            fault_plan="dispatch:step=2,times=1", retry_backoff_s=0.0,
        ),
        tel=True,
    )
    x = _batches(1)[0]
    losses = [float(step(x)) for _ in range(4)]
    assert all(np.isfinite(losses))
    retries = [e for e in acc.resilience.events if e["event"] == "dispatch_retry"]
    assert len(retries) == 1 and retries[0]["step"] == 2
    assert acc.telemetry.recompiles_total == 0  # retry reused the program
    tele = [r for r in acc.telemetry.all_records() if r.get("kind") == "resilience"]
    assert any(r["event"] == "dispatch_retry" for r in tele)


def test_retry_wait_split_out_of_dispatch_timing():
    """ROADMAP carried item: resilience backoff sleeps must land in the step
    record's ``retry_wait_ms``, NOT in ``dispatch_ms`` — before the split a
    retried run's dispatch timing was inflated by the whole backoff, making
    A/B bench comparisons lie about the hot path."""
    backoff_s = 0.05
    acc, _, step = _make_step(
        ResilienceKwargs(
            enabled=True, preemption=False,
            fault_plan="dispatch:step=2,times=1", retry_backoff_s=backoff_s,
        ),
        tel=True,
    )
    x = _batches(1)[0]
    for _ in range(4):
        float(step(x))
    records = acc.telemetry.timeline.records()
    waits = [r.retry_wait_ms for r in records]
    # exactly the faulted call (index 2) slept; backoff_delay jitters
    # SYMMETRICALLY (±25%), so the measured sleep lives in
    # [0.75·backoff, 1.25·backoff] plus scheduler slack
    assert waits[0] == waits[1] == waits[3] == 0.0, waits
    assert backoff_s * 1e3 * 0.7 <= waits[2] <= backoff_s * 1e3 * 1.3 + 50, waits
    faulted = records[2]
    # dispatch no longer swallows the sleep: the clean replay's dispatch is
    # the honest scale, and the faulted call's dispatch must be within an
    # order of it rather than backoff-sized
    assert faulted.dispatch_ms < waits[2], (faulted.dispatch_ms, waits[2])
    # the split still partitions the call's wall clock
    assert faulted.phase_sum_ms <= faulted.total_ms * 1.5
    # schema: the field exports with the record
    assert faulted.to_dict()["retry_wait_ms"] == waits[2]


def test_exhausted_retries_roll_back_to_last_checkpoint_and_replay(tmp_path):
    acc, _, step = _make_step(
        ResilienceKwargs(
            enabled=True, preemption=False, max_retries=1,
            fault_plan="dispatch:step=3,times=3", retry_backoff_s=0.0,
        )
    )
    x = _batches(1)[0]
    losses = [float(step(x)) for _ in range(2)]
    acc.save_state(str(tmp_path / "good"))
    assert acc.resilience.last_checkpoint == str(tmp_path / "good")
    l2 = float(step(x))
    # dispatch 3 faults through 2 attempts, rolls back to the post-step-1
    # checkpoint, and the replay (fault 3 then a clean retry) re-runs step
    # 2's math from the restored state — bitwise the same loss
    l3 = float(step(x))
    assert l3 == l2
    events = [e["event"] for e in acc.resilience.events]
    assert events.count("rollback") == 1
    assert acc.resilience.retrier.rollbacks_total == 1


def test_exhaustion_without_checkpoint_raises():
    acc, _, step = _make_step(
        ResilienceKwargs(
            enabled=True, preemption=False, max_retries=1,
            fault_plan="dispatch:step=1,times=5", retry_backoff_s=0.0,
        )
    )
    x = _batches(1)[0]
    step(x)
    with pytest.raises(InjectedTransientError):
        step(x)
    assert any(e["event"] == "dispatch_exhausted" for e in acc.resilience.events)


def test_failure_classification():
    assert classify_failure(InjectedTransientError("boom")) == "transient"
    assert classify_failure(RuntimeError("UNAVAILABLE: socket closed")) == "transient"
    assert classify_failure(RuntimeError("DEADLINE_EXCEEDED: dcn timeout")) == "transient"
    # OOM retries the same program into the same HBM: not transient
    assert classify_failure(RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == "user"
    assert classify_failure(ValueError("shapes do not match")) == "user"
    assert classify_failure(TypeError("bad arg")) == "user"


# ---------------------------------------------------------------------------
# default-off / checkpoint helpers
# ---------------------------------------------------------------------------

def test_default_off_touches_nothing(tmp_path):
    prev_term = signal.getsignal(signal.SIGTERM)
    acc, _, step = _make_step()
    assert not acc.resilience.enabled
    assert acc.resilience.retrier is None and acc.resilience.guard is None
    assert step._resilience is None  # capture path: one None-check, no hooks
    assert signal.getsignal(signal.SIGTERM) is prev_term
    step(_batches(1)[0])
    acc.save_state(str(tmp_path / "ckpt"))
    assert acc.resilience.last_checkpoint is None
    assert acc.resilience.events == []


def test_latest_checkpoint_skips_incomplete(tmp_path):
    base = tmp_path / "checkpoints"
    for i, complete in ((0, True), (1, True), (2, False)):
        folder = base / f"checkpoint_{i}"
        folder.mkdir(parents=True)
        (folder / "pytree_model.safetensors").write_bytes(b"")
        if complete:
            (folder / "accelerator_meta.json").write_text("{}")
    # checkpoint_2 has no completion sentinel (killed mid-write): skipped
    assert latest_checkpoint(str(base)) == str(base / "checkpoint_1")
    assert not is_complete_checkpoint(str(base / "checkpoint_2"))
    assert latest_checkpoint(str(tmp_path / "missing")) is None


def test_meta_sentinel_written_last(tmp_path):
    """A complete save has the sentinel; its presence is what load_state's
    automatic path and the rollback machinery trust."""
    acc, _, step = _make_step()
    step(_batches(1)[0])
    out = acc.save_state(str(tmp_path / "ckpt"))
    assert is_complete_checkpoint(out)


# ----------------------------------------------------- review-pinned edges

def test_second_sigint_raises_keyboard_interrupt():
    """The sticky flag must not make Ctrl-C a no-op: the first SIGINT
    records, the second means NOW."""
    guard = PreemptionGuard()
    assert guard.install()
    try:
        os.kill(os.getpid(), signal.SIGINT)
        assert guard.triggered and guard.signal_name == "SIGINT"
        with pytest.raises(KeyboardInterrupt):
            os.kill(os.getpid(), signal.SIGINT)
    finally:
        guard.uninstall()


def test_consumed_donated_leaves_skip_retry_budget():
    """A mid-execution fault that consumed donated inputs must not burn
    retries it cannot win — it escalates straight to the rollback decision
    (here: no checkpoint → immediate exhaustion, zero retries slept)."""
    from accelerate_tpu.resilience.retry import StepRetrier

    class _Hub:
        dispatch_calls = 1
        injector = None
        last_checkpoint = None

        def __init__(self):
            self.events = []

        def record_event(self, event, **fields):
            self.events.append({"event": event, **fields})

    class _DeletedLeaf:
        def is_deleted(self):
            return True

    hub = _Hub()
    retrier = StepRetrier(hub, max_retries=3, backoff_s=0.0)

    def dispatch(dev, host, entry):
        raise RuntimeError("UNAVAILABLE: device halted mid-program")

    with pytest.raises(RuntimeError):
        retrier.run_dispatch(
            None, dispatch, entry=None,
            dev_leaves=(_DeletedLeaf(),), host_leaves=(), host_mask=(False,),
        )
    assert retrier.retries_total == 0  # no doomed re-invocations
    (event,) = hub.events
    assert event["event"] == "dispatch_exhausted"
    assert event["donated_consumed"] is True


def test_init_report_consumed_by_first_hub():
    """A stale LAST_INIT_REPORT must not be re-emitted by every later hub
    in the same process."""
    inj = FaultInjector(FaultPlan.parse("init_hang:times=1"))
    init_backend(
        platforms=["cpu"], attempts=2, backoff_s=0.0, injector=inj,
        sleep=lambda s: None,
    )
    from accelerate_tpu.resilience import Resilience
    from accelerate_tpu.utils.dataclasses import ResilienceKwargs as RK

    first = Resilience(RK(enabled=True, preemption=False, retry=False))
    second = Resilience(RK(enabled=True, preemption=False, retry=False))
    assert [e["event"] for e in first.events] == ["init"]
    assert second.events == []  # consumed on first pickup
    assert res_backend.LAST_INIT_REPORT is None
