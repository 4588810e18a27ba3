"""Elastic fleet runtime (docs/elastic.md): restore-point vote agreement,
coordinated multi-process rollback replacing the resilience refusal,
host-lost-driven dp resize with bitwise state after reshard and
zero-recompile resume off the AOT-cache prewarm, periodic mid-run fleet
aggregation, and the default-off path touching nothing."""

import json
import os

import jax
import numpy as np
import pytest

import accelerate_tpu.nn as nn
import accelerate_tpu.optim as optim
from accelerate_tpu import (
    Accelerator,
    CompilationCacheKwargs,
    FleetKwargs,
    ResilienceKwargs,
    TelemetryKwargs,
)
from accelerate_tpu.checkpointing import is_complete_checkpoint
from accelerate_tpu.data_loader import batch_to_global_array
from accelerate_tpu.fleet import (
    agree_restore_point,
    local_restore_candidates,
    surviving_mesh,
)
from accelerate_tpu.fleet import coordinate as fleet_coordinate
from accelerate_tpu.nn import Tensor
from accelerate_tpu.resilience import FaultPlan
from accelerate_tpu.resilience import retry as res_retry


def _num_devices():
    return len(jax.devices())


def _make_step(handlers=None, seed=0):
    nn.manual_seed(seed)
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

    return acc, model, opt, acc.compile_step(step_fn)


def _batches(acc, n, batch=8):
    rng = np.random.default_rng(0)
    return [
        batch_to_global_array(
            np.asarray(rng.normal(size=(batch, 8)), np.float32), mesh=acc.mesh
        )
        for _ in range(n)
    ]


def _write_complete_checkpoint(path, step):
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "accelerator_meta.json"), "w") as f:
        json.dump({"step": step}, f)
    return str(path)


# ---------------------------------------------------------------------------
# fault-plan verb
# ---------------------------------------------------------------------------

def test_host_lost_verb_parses_and_fires_once():
    plan = FaultPlan.parse("host_lost:step=2")
    assert [(d.kind, d.step, d.times) for d in plan.directives] == [
        ("host_lost", 2, 1)
    ]
    from accelerate_tpu.resilience import FaultInjector

    inj = FaultInjector(plan)
    assert not inj.maybe_host_lost(1)  # wrong step
    assert inj.maybe_host_lost(2)
    assert not inj.maybe_host_lost(2)  # times exhausted


def test_host_lost_verb_needs_step():
    with pytest.raises(ValueError):
        FaultPlan.parse("host_lost")


# ---------------------------------------------------------------------------
# pillar 1: restore-point vote
# ---------------------------------------------------------------------------

def test_agree_restore_point_newest_common(tmp_path):
    """The agreement is the HIGHEST-step offer visible to every rank — a
    newer checkpoint only some ranks drained must lose, or the losers'
    collective load_state would hang on its missing shards."""
    a = {"path": "/ckpt/a", "step": 1}
    b = {"path": "/ckpt/b", "step": 2}
    c = {"path": "/ckpt/c", "step": 3}  # rank 0 only: never eligible
    assert agree_restore_point([[c, b, a], [b, a]]) == b
    assert agree_restore_point([[a], [a]]) == a
    assert agree_restore_point([[a, b], [c]]) is None  # disjoint: no vote
    assert agree_restore_point([]) is None
    # world=1 degenerates to the rank's own newest
    assert agree_restore_point([[a, b]]) == b


def test_agree_restore_point_tie_breaks_deterministically():
    """Equal steps must break ties identically on every rank (path order),
    or ranks would load different folders and deadlock."""
    x = {"path": "/ckpt/x", "step": 2}
    y = {"path": "/ckpt/y", "step": 2}
    assert agree_restore_point([[x, y], [y, x]]) == y
    assert agree_restore_point([[y, x], [x, y]]) == y


def test_local_restore_candidates_orders_and_filters(tmp_path):
    acc, _, _, step = _make_step()
    complete_new = _write_complete_checkpoint(tmp_path / "new", step=5)
    incomplete = str(tmp_path / "torn")
    os.makedirs(incomplete)  # no sentinel: killed mid-write
    acc.resilience.enabled = True
    acc.resilience.last_checkpoint = complete_new
    offers = local_restore_candidates(acc)
    assert [o["path"] for o in offers] == [os.path.abspath(complete_new)]
    assert offers[0]["step"] == 5


def test_vote_restore_point_simulated_two_ranks(tmp_path, monkeypatch):
    """The all-ranks agreement pin: simulate the gather of two ranks'
    offers — the newest all-ranks-visible checkpoint wins and the ballot
    lands as a restore_vote fleet event."""
    acc, _, _, _ = _make_step(
        [FleetKwargs(enabled=True), ResilienceKwargs(enabled=True, preemption=False)]
    )
    shared_old = _write_complete_checkpoint(tmp_path / "shared", step=1)
    local_new = _write_complete_checkpoint(tmp_path / "local", step=7)
    acc.resilience.last_checkpoint = local_new
    peer_offers = [{"path": os.path.abspath(shared_old), "step": 1}]
    real_gather = fleet_coordinate.gather_object

    def fake_gather(payload):
        # rank 0 = this process's real offers; rank 1 = a peer that only
        # ever saw the shared checkpoint (its host missed the local drain)
        local = real_gather(payload)
        local.append(peer_offers)
        return local

    monkeypatch.setattr(fleet_coordinate, "gather_object", fake_gather)
    # make this rank ALSO offer the shared checkpoint (both visible here)
    acc.project_configuration.automatic_checkpoint_naming = False
    offers = local_restore_candidates(acc)
    assert len(offers) == 1  # only local_new — shared isn't in this rank's view
    acc.resilience.last_checkpoint = None

    def fake_candidates(accelerator):
        return [
            {"path": os.path.abspath(local_new), "step": 7},
            {"path": os.path.abspath(shared_old), "step": 1},
        ]

    monkeypatch.setattr(fleet_coordinate, "local_restore_candidates", fake_candidates)
    agreed = fleet_coordinate.vote_restore_point(acc, fleet=acc.fleet)
    # local_new (step 7) is NOT in the peer's offers → the shared step-1
    # checkpoint is the only safe restore point
    assert agreed == {"path": os.path.abspath(shared_old), "step": 1}
    votes = [e for e in acc.fleet.events if e["event"] == "restore_vote"]
    assert len(votes) == 1 and votes[0]["ranks"] == 2
    assert votes[0]["agreed"] == os.path.abspath(shared_old)


def test_multiprocess_rollback_refused_without_fleet(monkeypatch):
    """The historical refusal stands when the fleet is off: a lone rank's
    collective load_state would deadlock the mesh."""
    acc, _, _, step = _make_step(
        [ResilienceKwargs(enabled=True, preemption=False)]
    )
    monkeypatch.setattr(res_retry, "_multi_process", lambda: True)
    retrier = acc.resilience.retrier
    assert retrier._rollback_allowed() is False
    assert retrier._coordinator() is None


def test_multiprocess_rollback_coordinated_with_fleet(monkeypatch):
    """ISSUE acceptance: coordinated multi-process rollback replaces the
    single-process refusal — with the fleet armed, a multi-process retrier
    routes exhaustion through the vote protocol instead of refusing."""
    acc, _, _, step = _make_step(
        [
            FleetKwargs(enabled=True),
            ResilienceKwargs(enabled=True, preemption=False),
        ]
    )
    monkeypatch.setattr(res_retry, "_multi_process", lambda: True)
    retrier = acc.resilience.retrier
    assert retrier._coordinator() is acc.fleet
    assert retrier._rollback_allowed() is True
    # opting out of coordination restores the refusal
    acc.fleet.handler.coordinate_rollback = False
    assert retrier._coordinator() is None
    assert retrier._rollback_allowed() is False


def test_coordinated_rollback_end_to_end(tmp_path, monkeypatch):
    """Exhausted retries on a 'multi-process' run vote, agree, restore and
    replay — bitwise — where the pre-fleet retrier raised."""
    acc, _, _, step = _make_step(
        [
            FleetKwargs(enabled=True),
            ResilienceKwargs(
                enabled=True, preemption=False, max_retries=1,
                fault_plan="dispatch:step=3,times=3", retry_backoff_s=0.0,
            ),
        ]
    )
    x = _batches(acc, 1)[0]
    for _ in range(2):
        float(step(x))
    acc.save_state(str(tmp_path / "good"))
    monkeypatch.setattr(res_retry, "_multi_process", lambda: True)
    l2 = float(step(x))
    l3 = float(step(x))  # exhausts → vote → coordinated restore → replay
    assert l3 == l2
    rollbacks = [e for e in acc.resilience.events if e["event"] == "rollback"]
    assert len(rollbacks) == 1 and rollbacks[0]["coordinated"] is True
    assert any(e["event"] == "restore_vote" for e in acc.fleet.events)


# ---------------------------------------------------------------------------
# pillar 2: elastic dp resize
# ---------------------------------------------------------------------------

def test_surviving_mesh_shrinks_dp_only():
    acc, _, _, _ = _make_step()
    mesh = acc.mesh
    dp = dict(mesh.shape)["dp"]
    if dp < 2:
        pytest.skip("needs dp >= 2")
    new = surviving_mesh(mesh, dp // 2)
    assert dict(new.shape)["dp"] == dp // 2
    assert [dict(new.shape)[a] for a in new.axis_names if a != "dp"] == [
        dict(mesh.shape)[a] for a in mesh.axis_names if a != "dp"
    ]
    # survivors are the leading dp blocks: inner-axis neighborhoods intact
    assert new.devices.tolist() == np.take(
        mesh.devices, range(dp // 2), axis=mesh.axis_names.index("dp")
    ).tolist()
    with pytest.raises(ValueError):
        surviving_mesh(mesh, dp * 2)  # growing is a relaunch, not a resize
    with pytest.raises(ValueError):
        surviving_mesh(mesh, 0)


def test_surviving_mesh_honors_lost_blocks():
    """Review-pinned: when the reclamation notice names WHICH dp block
    died, the survivors — not the dead host's devices — make the mesh."""
    acc, _, _, _ = _make_step()
    mesh = acc.mesh
    dp = dict(mesh.shape)["dp"]
    if dp < 2:
        pytest.skip("needs dp >= 2")
    dp_index = mesh.axis_names.index("dp")
    new = surviving_mesh(mesh, dp // 2, lost_blocks=[0])
    # block 0 is gone: the kept blocks start at 1
    expect = np.take(
        mesh.devices, range(1, dp // 2 + 1), axis=dp_index
    ).tolist()
    assert new.devices.tolist() == expect
    with pytest.raises(ValueError):
        surviving_mesh(mesh, dp // 2, lost_blocks=[dp + 3])  # outside axis
    with pytest.raises(ValueError):
        # too many dead blocks for the requested extent
        surviving_mesh(mesh, dp, lost_blocks=[0])


def test_checkpoint_step_fail_soft_on_foreign_meta(tmp_path):
    """Review-pinned: a corrupt/foreign sentinel (non-object JSON) must be
    a skipped candidate, never a crash inside the restore vote."""
    from accelerate_tpu.checkpointing import checkpoint_step

    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "accelerator_meta.json").write_text("[]")
    assert checkpoint_step(str(bad)) is None
    good = tmp_path / "good"
    good.mkdir()
    (good / "accelerator_meta.json").write_text('{"step": 4}')
    assert checkpoint_step(str(good)) == 4


def test_host_lost_injection_trips_should_resize(tmp_path):
    acc, _, _, step = _make_step(
        [FleetKwargs(enabled=True, fault_plan="host_lost:step=1")]
    )
    x = _batches(acc, 1)[0]
    float(step(x))
    assert not acc.fleet.should_resize
    float(step(x))
    assert acc.fleet.should_resize
    assert acc.fleet.should_resize  # sticky
    assert any(e["event"] == "host_lost" for e in acc.fleet.events)


def test_resize_consumes_should_resize_flag(tmp_path):
    """Review-pinned: the documented `if should_resize: resize()` loop must
    not re-drain/re-mesh every later step — resize() consumes the flag it
    handled (a LATER host loss re-trips it)."""
    if _num_devices() < 2:
        pytest.skip("needs >= 2 devices")
    acc, _, _, step = _make_step(
        [FleetKwargs(enabled=True, fault_plan="host_lost:step=0")]
    )
    dp = dict(acc.mesh.shape)["dp"]
    float(step(_batches(acc, 1)[0]))
    assert acc.fleet.should_resize
    acc.fleet.resize(acc, target_dp=dp // 2, output_dir=str(tmp_path / "d"))
    assert not acc.fleet.should_resize
    assert acc.fleet.resizes_total == 1


def test_resize_reshards_bitwise_and_resumes(tmp_path):
    """The acceptance row: a dp=N run with an injected host loss drains a
    complete checkpoint, re-meshes at dp=N/2, reshards ZeRO-1 masters and
    moments BITWISE from the spec-carrying checkpoint, and resumes within
    loss parity of the uninterrupted run."""
    if _num_devices() < 2:
        pytest.skip("needs >= 2 devices")
    steps_total = 5
    lost_at = 2

    # uninterrupted reference at full dp
    Accelerator._reset_state()
    acc_ref, _, _, step_ref = _make_step()
    ref = [float(step_ref(b)) for b in _batches(acc_ref, steps_total)]

    Accelerator._reset_state()
    acc, model, opt, step = _make_step(
        [FleetKwargs(enabled=True, fault_plan=f"host_lost:step={lost_at}")]
    )
    dp = dict(acc.mesh.shape)["dp"]
    assert acc.state.zero1_enabled  # dp > 1, no fsdp owner
    batches = _batches(acc, steps_total)
    losses = []
    resized = None
    i = 0
    while i < len(batches):
        losses.append(float(step(batches[i])))
        i += 1
        if resized is None and acc.fleet.should_resize:
            masters = [
                np.asarray(m) for m in opt.optimizer.master_params if m is not None
            ]
            moments = [
                np.asarray(leaf)
                for leaf in jax.tree_util.tree_leaves(opt.optimizer.capture_state())
            ]
            resized = acc.fleet.resize(
                acc, target_dp=dp // 2, output_dir=str(tmp_path / "drain")
            )
            # drain → COMPLETE checkpoint
            assert is_complete_checkpoint(resized["checkpoint"])
            # re-mesh at the surviving topology
            assert dict(acc.mesh.shape)["dp"] == dp // 2
            assert resized["old_dp"] == dp and resized["dp"] == dp // 2
            # ZeRO-1 masters + moments resharded BITWISE, and actually
            # laid out on the new mesh
            masters_after = [
                np.asarray(m) for m in opt.optimizer.master_params if m is not None
            ]
            for before, after in zip(masters, masters_after):
                assert (before == after).all()
            moments_after = [
                np.asarray(leaf)
                for leaf in jax.tree_util.tree_leaves(opt.optimizer.capture_state())
            ]
            for before, after in zip(moments, moments_after):
                if before.dtype == np.float32 and before.shape:
                    assert (before == after).all()
            for m in opt.optimizer.master_params:
                if m is not None and hasattr(m, "sharding"):
                    assert m.sharding.mesh.shape == acc.mesh.shape
            # surviving batches re-laid on the new mesh
            batches = batches[:i] + [
                batch_to_global_array(np.asarray(b), mesh=acc.mesh)
                for b in batches[i:]
            ]
    assert resized is not None, "host loss never tripped"
    assert len(losses) == steps_total
    # exact through the loss step, loss-parity after the dp change (the
    # reduce order moves with dp; docs/elastic.md documents the tolerance)
    assert losses[: lost_at + 1] == ref[: lost_at + 1]
    np.testing.assert_allclose(losses, ref, rtol=1e-3)
    events = [e["event"] for e in acc.fleet.events]
    assert events.count("host_lost") == 1
    assert events.count("drain") == 1
    assert events.count("resize") == 1


def test_resize_prewarm_zero_recompiles(tmp_path, compiled_in_this_process):
    """Acceptance: zero recompiles for programs served by the AOT-cache
    prewarm — a run whose resized topology was already compiled (a prior
    fleet at that dp, same store) resumes with the post-resize first step
    deserialized, not traced."""
    if _num_devices() < 2:
        pytest.skip("needs >= 2 devices")
    cache_dir = str(tmp_path / "aot")
    steps = 3

    def handlers(plan=None):
        out = [
            CompilationCacheKwargs(cache_dir=cache_dir),
            TelemetryKwargs(enabled=True),
            FleetKwargs(enabled=True, fault_plan=plan),
        ]
        return out

    # phase 1 (the "prior fleet"): resize immediately, train at the small
    # topology so its program lands in the store
    Accelerator._reset_state()
    acc, _, _, step = _make_step(handlers())
    dp = dict(acc.mesh.shape)["dp"]
    target = dp // 2
    acc.fleet.resize(acc, target_dp=target, output_dir=str(tmp_path / "seed"))
    for b in _batches(acc, 2):
        float(step(b))
    assert acc.aot_cache.stores >= 1

    # phase 2: fresh run at full dp, host lost at step 1, resize → the
    # post-resize build must be a cache hit (zero trace, zero compile)
    Accelerator._reset_state()
    acc, _, _, step = _make_step(handlers("host_lost:step=1"))
    batches = _batches(acc, steps)
    i = 0
    resized = None
    while i < len(batches):
        float(step(batches[i]))
        i += 1
        if resized is None and acc.fleet.should_resize:
            resized = acc.fleet.resize(
                acc, target_dp=target, output_dir=str(tmp_path / "drain")
            )
            assert resized["aot_prewarmed"] >= 1
            batches = batches[:i] + [
                batch_to_global_array(np.asarray(b), mesh=acc.mesh)
                for b in batches[i:]
            ]
    assert resized is not None
    # the post-resize first call rebuilt (new topology) but deserialized
    # the stored executable: its build phases read zero
    records = acc.telemetry.timeline.records()
    post = [r for r in records if r.built][-1]
    assert post.trace_ms == 0.0 and post.compile_ms == 0.0, (
        post.trace_ms, post.compile_ms,
    )
    hits = [e for e in acc.telemetry.aot_cache_events if e["event"] == "hit"]
    assert len(hits) >= 1


# ---------------------------------------------------------------------------
# pillar 3: periodic fleet aggregation (the resize signal)
# ---------------------------------------------------------------------------

def test_periodic_aggregation_records_fleet_signal():
    acc, _, _, step = _make_step(
        [FleetKwargs(enabled=True, aggregate_every_n=2), TelemetryKwargs(enabled=True)]
    )
    assert acc.fleet.fleet_signal() is None
    for b in _batches(acc, 4):
        float(step(b))
    signals = [
        r for r in acc.telemetry.fleet_events if r.get("kind") == "fleet"
    ]
    assert len(signals) == 2  # cadence 2 over 4 dispatches
    latest = acc.fleet.fleet_signal()
    assert latest is signals[-1]
    assert latest["periodic"] is True and latest["ranks"] == 1
    assert latest["per_rank"][0]["replay_steps"] >= 1
    # the signal rides the retained history → JSONL dump schema
    kinds = {r.get("kind") for r in acc.telemetry.all_records()}
    assert "fleet" in kinds


def test_fleet_events_reach_telemetry_export():
    acc, _, _, step = _make_step(
        [
            FleetKwargs(enabled=True, fault_plan="host_lost:step=0"),
            TelemetryKwargs(enabled=True),
        ]
    )
    float(step(_batches(acc, 1)[0]))
    assert acc.fleet.should_resize
    records = [
        r for r in acc.telemetry.all_records() if r.get("kind") == "fleet_event"
    ]
    assert any(r["event"] == "host_lost" for r in records)


# ---------------------------------------------------------------------------
# default-off
# ---------------------------------------------------------------------------

def test_fleet_default_off_touches_nothing(tmp_path):
    acc, _, _, step = _make_step()
    assert not acc.fleet.enabled
    assert acc.resilience.fleet is None
    assert step._fleet is None  # capture path: one None-check, no hooks
    float(step(_batches(acc, 1)[0]))
    assert acc.fleet.dispatch_calls == 0
    assert acc.fleet.events == []
    with pytest.raises(RuntimeError):
        acc.fleet.resize(acc)


def test_resize_respects_min_dp_floor():
    acc, _, _, _ = _make_step([FleetKwargs(enabled=True, min_dp=4)])
    with pytest.raises(ValueError):
        acc.fleet.resize(acc, target_dp=1)


# ---------------------------------------------------------------------------
# grow-side resize (fleet/grow.py)
# ---------------------------------------------------------------------------

def test_host_gained_and_signal_storm_verbs_parse():
    plan = FaultPlan.parse("host_gained:step=4;signal_storm:step=1,times=6")
    assert [(d.kind, d.step, d.times) for d in plan.directives] == [
        ("host_gained", 4, 1), ("signal_storm", 1, 6),
    ]
    from accelerate_tpu.resilience import FaultInjector

    inj = FaultInjector(plan)
    assert not inj.maybe_host_gained(1)
    assert inj.maybe_host_gained(4)
    assert not inj.maybe_host_gained(4)  # exhausted
    # a storm runs from its start dispatch, alternating spike/drop
    assert inj.maybe_signal_storm(0) is None  # before start
    flaps = [inj.maybe_signal_storm(i) for i in range(1, 8)]
    assert flaps == [True, False, True, False, True, False, None]
    with pytest.raises(ValueError):
        FaultPlan.parse("host_gained")  # needs step=N
    with pytest.raises(ValueError):
        FaultPlan.parse("signal_storm")


def test_grown_mesh_appends_rejoined_blocks():
    from accelerate_tpu.fleet import grown_mesh, max_growable_dp
    from accelerate_tpu.fleet.grow import grown_axis_sizes

    acc, _, _, _ = _make_step()
    mesh = acc.mesh
    dp = dict(mesh.shape)["dp"]
    if dp < 2:
        pytest.skip("needs dp >= 2")
    small = surviving_mesh(mesh, dp // 2)
    assert max_growable_dp(small) == dp
    wide = grown_mesh(small, dp)
    assert dict(wide.shape)["dp"] == dp
    # the survivors' blocks stay in place, the rejoined blocks append —
    # live state never moves under a grow
    assert wide.devices.tolist() == mesh.devices.tolist()
    with pytest.raises(ValueError):
        grown_axis_sizes(small, dp // 2)  # not a widening
    with pytest.raises(ValueError):
        grown_mesh(small, dp * 16)  # more devices than exist


def test_agree_grow_requires_identical_proposals():
    from accelerate_tpu.fleet import agree_grow

    a = {"target_dp": 4, "device_ids": [0, 1, 2, 3]}
    assert agree_grow([a, dict(a)]) == a
    assert agree_grow([a]) == a  # world=1 degenerates
    assert agree_grow([]) is None
    assert agree_grow([a, {"target_dp": 4, "device_ids": [0, 1, 2, 9]}]) is None
    assert agree_grow([a, {"target_dp": 2, "device_ids": [0, 1]}]) is None
    # an error ballot (rank cannot see the rejoined host) aborts — even a
    # unanimous one carries no executable plan
    err = {"target_dp": 4, "error": "only 0 visible"}
    assert agree_grow([a, err]) is None
    assert agree_grow([err, err]) is None


def test_grow_reshards_bitwise_back_to_full_dp(tmp_path):
    """The grow acceptance row: after a shrink, ``fleet.grow()`` re-meshes
    dp back up through the rendezvous, reshards ZeRO-1 masters/moments
    BITWISE onto the wider mesh (vs the values before the grow — a
    from-checkpoint reshard, not a reinit), and the host_gained flag is
    consumed."""
    if _num_devices() < 2:
        pytest.skip("needs >= 2 devices")
    acc, model, opt, step = _make_step(
        [FleetKwargs(enabled=True, fault_plan="host_gained:step=1")]
    )
    dp = dict(acc.mesh.shape)["dp"]
    batches = _batches(acc, 4)
    float(step(batches[0]))
    # shrink first (the host came back AFTER a loss)
    acc.fleet.resize(acc, target_dp=dp // 2, output_dir=str(tmp_path / "d1"))
    assert dict(acc.mesh.shape)["dp"] == dp // 2
    float(step(batch_to_global_array(np.asarray(batches[1]), mesh=acc.mesh)))
    assert acc.fleet.should_grow  # injected at dispatch 1
    masters = [
        np.asarray(m) for m in opt.optimizer.master_params if m is not None
    ]
    moments = [
        np.asarray(leaf)
        for leaf in jax.tree_util.tree_leaves(opt.optimizer.capture_state())
    ]
    info = acc.fleet.grow(acc, target_dp=dp, output_dir=str(tmp_path / "d2"))
    assert info["direction"] == "grow" and info["dp"] == dp
    assert dict(acc.mesh.shape)["dp"] == dp
    assert not acc.fleet.should_grow  # consumed
    assert acc.fleet.grows_total == 1
    masters_after = [
        np.asarray(m) for m in opt.optimizer.master_params if m is not None
    ]
    for before, after in zip(masters, masters_after):
        assert (before == after).all()
    moments_after = [
        np.asarray(leaf)
        for leaf in jax.tree_util.tree_leaves(opt.optimizer.capture_state())
    ]
    for before, after in zip(moments, moments_after):
        if before.dtype == np.float32 and before.shape:
            assert (before == after).all()
    for m in opt.optimizer.master_params:
        if m is not None and hasattr(m, "sharding"):
            assert m.sharding.mesh.shape == acc.mesh.shape
    events = [e["event"] for e in acc.fleet.events]
    assert "grow_rendezvous" in events
    # one resize verb either direction: a wider target routes resize->grow
    acc.fleet.resize(acc, target_dp=dp // 2, output_dir=str(tmp_path / "d3"))
    info2 = acc.fleet.resize(acc, target_dp=dp, output_dir=str(tmp_path / "d4"))
    assert info2["direction"] == "grow"


# ---------------------------------------------------------------------------
# autopilot: FleetKwargs grammar growth
# ---------------------------------------------------------------------------

def test_autopilot_policy_parse_and_resolve():
    from accelerate_tpu.fleet import AutopilotPolicy

    p = AutopilotPolicy.parse("skew_pct=150,window=4,hysteresis=0.2,cooldown=2")
    assert (p.skew_pct, p.window, p.hysteresis, p.cooldown) == (150.0, 4, 0.2, 2)
    assert AutopilotPolicy.resolve(None) is None
    assert AutopilotPolicy.resolve(False) is None
    assert AutopilotPolicy.resolve("off") is None
    assert AutopilotPolicy.resolve("0") is None
    assert AutopilotPolicy.resolve(True) == AutopilotPolicy()
    assert AutopilotPolicy.resolve("on") == AutopilotPolicy()
    assert AutopilotPolicy.resolve({"queue_high": 3.0}).queue_high == 3.0
    assert AutopilotPolicy.resolve(p) is p
    with pytest.raises(ValueError):
        AutopilotPolicy.parse("skew_pct=abc")
    with pytest.raises(ValueError):
        AutopilotPolicy.parse("not_a_knob=1")
    with pytest.raises(ValueError):
        AutopilotPolicy.resolve({"bogus": 1})


def test_autopilot_env_kwargs_precedence(monkeypatch):
    from accelerate_tpu.fleet import AutopilotPolicy

    monkeypatch.setenv("ACCELERATE_FLEET_AUTOPILOT", "skew_pct=50")
    handler = FleetKwargs(enabled=True)
    assert handler.autopilot_policy == AutopilotPolicy(skew_pct=50.0)
    # explicit kwargs beat the env — including an explicit OFF
    handler = FleetKwargs(enabled=True, autopilot="skew_pct=70")
    assert handler.autopilot_policy.skew_pct == 70.0
    handler = FleetKwargs(enabled=True, autopilot="off")
    assert handler.autopilot_policy is None
    monkeypatch.delenv("ACCELERATE_FLEET_AUTOPILOT")
    assert FleetKwargs(enabled=True).autopilot_policy is None  # default off


def test_autopilot_bad_thresholds_raise_at_construction():
    """ISSUE satellite: bad values must raise when the kwargs handler is
    BUILT — never at the autopilot's first fire, mid-training."""
    for bad in (
        "skew_pct=-1", "skew_pct=0", "queue_high=0", "occupancy_low=1.5",
        "window=0", "hysteresis=1.0", "hysteresis=-0.1", "cooldown=-1",
    ):
        with pytest.raises(ValueError):
            FleetKwargs(enabled=True, autopilot=bad)


def test_autopilot_default_off_capture_pytree_byte_identical():
    """ISSUE satellite: with the autopilot left off (and even with the env
    spelling an armed policy while the FLEET itself is off), the captured
    state pytree and the losses are byte-identical to the no-handler
    baseline."""
    x = np.asarray(np.random.default_rng(0).normal(size=(8, 8)), np.float32)

    def leaf_bytes(leaf):
        try:
            return np.asarray(leaf).tobytes()
        except TypeError:  # typed PRNG keys refuse __array__
            return np.asarray(jax.random.key_data(leaf)).tobytes()

    def run(handlers):
        Accelerator._reset_state()
        acc, _, _, step = _make_step(handlers, seed=0)
        loss = float(step(batch_to_global_array(x, mesh=acc.mesh)))
        state = step._collect_state()
        leaves, treedef = jax.tree_util.tree_flatten(state)
        return loss, treedef, [leaf_bytes(l) for l in leaves], acc, step

    base_loss, base_tree, base_leaves, _, base_step = run(None)
    assert base_step._fleet is None
    # fleet OFF + an armed autopilot env: everything still byte-identical
    os.environ["ACCELERATE_FLEET_AUTOPILOT"] = "skew_pct=10,window=1"
    try:
        loss, tree, leaves, acc, step = run([FleetKwargs(enabled=False)])
    finally:
        del os.environ["ACCELERATE_FLEET_AUTOPILOT"]
    assert step._fleet is None and acc.fleet.autopilot is None
    assert loss == base_loss
    assert tree == base_tree
    assert leaves == base_leaves
    # fleet ON without autopilot: no autopilot constructed, no decisions
    loss, tree, leaves, acc, step = run([FleetKwargs(enabled=True)])
    assert acc.fleet.autopilot is None
    assert loss == base_loss and tree == base_tree and leaves == base_leaves


# ---------------------------------------------------------------------------
# autopilot: pure policy evaluation over synthetic signal windows
# ---------------------------------------------------------------------------

def test_evaluate_window_debounce_fires_after_window():
    from accelerate_tpu.fleet import AutopilotPolicy, evaluate_window

    policy = AutopilotPolicy(skew_pct=100.0, window=3, hysteresis=0.25)
    s = lambda v: {"skew_pct": v}  # noqa: E731
    # too young: armed now but held < window -> suppressed
    d = evaluate_window(policy, [s(150.0)])
    assert d["suppressed"] and not d["fired"] and d["signal"] == "skew_pct"
    assert "debounce" in d["reason"]
    # sustained above threshold for the full window -> fires
    d = evaluate_window(policy, [s(150.0), s(150.0), s(150.0)])
    assert d["fired"] and d["action"] == "shrink"
    assert d["window_values"] == [150.0, 150.0, 150.0]
    assert d["held"] == 3 and d["threshold"] == 100.0


def test_evaluate_window_hysteresis_dead_band_and_flap():
    from accelerate_tpu.fleet import AutopilotPolicy, evaluate_window

    policy = AutopilotPolicy(skew_pct=100.0, window=3, hysteresis=0.25)
    s = lambda v: {"skew_pct": v}  # noqa: E731
    # dip into the dead band (>= 75, < 100) does NOT reset the streak
    d = evaluate_window(policy, [s(150.0), s(80.0), s(120.0)])
    assert d["fired"], d
    # flap BELOW the sustain floor resets: armed again but held 1/3
    d = evaluate_window(policy, [s(150.0), s(0.0), s(150.0)])
    assert d["suppressed"] and not d["fired"]
    assert d["held"] == 1 and "flap" in d["reason"]
    # fully in the dead band with no arming crossing: quiet, not fired
    d = evaluate_window(policy, [s(80.0), s(80.0), s(80.0)])
    assert not d["fired"] and not d["suppressed"]


def test_evaluate_window_serving_signals():
    from accelerate_tpu.fleet import AutopilotPolicy, evaluate_window

    policy = AutopilotPolicy(queue_high=4.0, occupancy_low=0.25, window=2)
    deep = {"queue_depth": 6.0, "occupancy": 1.0}
    d = evaluate_window(policy, [deep, deep])
    assert d["fired"] and d["action"] == "grow" and d["signal"] == "queue_depth"
    # idle occupancy shrinks ONLY with an empty queue
    idle = {"queue_depth": 0.0, "occupancy": 0.1}
    d = evaluate_window(policy, [idle, idle])
    assert d["fired"] and d["action"] == "shrink" and d["signal"] == "occupancy"
    idle_but_queued = {"queue_depth": 2.0, "occupancy": 0.1}
    d = evaluate_window(policy, [idle_but_queued, idle_but_queued])
    assert not d["fired"]
    # queue pressure outranks the shrink signals when both hold
    both = {"queue_depth": 6.0, "occupancy": 0.1, "skew_pct": 500.0}
    d = evaluate_window(
        AutopilotPolicy(queue_high=4.0, window=2), [both, both]
    )
    assert d["fired"] and d["action"] == "grow"


# ---------------------------------------------------------------------------
# autopilot: the driver (closed loop, storm, skew)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("aot_store", [False, True], ids=["compiled", "aot_store"])
def test_autopilot_closed_loop_no_caller_polling(tmp_path, aot_store, compiled_in_this_process):
    """ISSUE acceptance: under an injected host_lost then host_gained plan
    the autopilot ALONE drives dp down and back up — the loop below never
    reads should_resize or calls resize — with final losses within 1e-3 of
    the uninterrupted run.  Against one AOT store the scenario runs twice,
    and the second pass builds every program (the start, after the shrink,
    after the grow) from the store: zero trace/compile time on each."""
    if _num_devices() < 2:
        pytest.skip("needs >= 2 devices")
    steps = 6

    Accelerator._reset_state()
    acc_ref, _, _, step_ref = _make_step()
    raw = [np.asarray(b) for b in _batches(acc_ref, steps)]
    ref = [float(step_ref(b)) for b in _batches(acc_ref, steps)]

    def closed_loop(tag):
        Accelerator._reset_state()
        handlers = [
            FleetKwargs(
                enabled=True, autopilot=True,
                fault_plan="host_lost:step=1;host_gained:step=3",
                checkpoint_dir=str(tmp_path / f"drain-{tag}"),
            )
        ]
        if aot_store:
            handlers += [
                CompilationCacheKwargs(cache_dir=str(tmp_path / "aot")),
                TelemetryKwargs(enabled=True),
            ]
        acc, _, _, step = _make_step(handlers)
        dp = dict(acc.mesh.shape)["dp"]
        losses = [
            float(step(batch_to_global_array(b, mesh=acc.mesh))) for b in raw
        ]
        assert acc.fleet.resizes_total == 1 and acc.fleet.grows_total == 1
        assert dict(acc.mesh.shape)["dp"] == dp
        np.testing.assert_allclose(losses, ref, rtol=1e-3)
        return acc

    acc = closed_loop("cold")
    decisions = [e for e in acc.fleet.events if e.get("kind") == "autopilot"]
    fired = [(d["signal"], d["action"]) for d in decisions if d["fired"]]
    assert fired == [("host_lost", "shrink"), ("host_gained", "grow")]
    # every decision reproducible from its record: policy + ts + resize info
    for d in decisions:
        assert "policy" in d and "ts" in d
    for d in decisions:
        if d["fired"]:
            assert d["resize"]["direction"] in ("shrink", "grow")
    if aot_store:
        assert acc.aot_cache.stores >= 1
        warm = closed_loop("warm")
        built = [r for r in warm.telemetry.timeline.records() if r.built]
        assert len(built) >= 3
        assert [(r.trace_ms, r.compile_ms) for r in built] == [(0.0, 0.0)] * len(built)
        hits = [e for e in warm.telemetry.aot_cache_events if e["event"] == "hit"]
        assert len(hits) >= 3


def test_autopilot_signal_storm_suppressed_zero_resizes():
    """ISSUE acceptance: a signal_storm flapping skew above/below the
    threshold within the debounce window produces suppressed-decision
    records and EXACTLY ZERO resizes."""
    acc, _, _, step = _make_step(
        [
            FleetKwargs(
                enabled=True, autopilot="window=3,cooldown=2",
                fault_plan="signal_storm:step=1,times=8",
            )
        ]
    )
    for b in _batches(acc, 10):
        float(step(b))
    assert acc.fleet.resizes_total == 0 and acc.fleet.grows_total == 0
    decisions = [e for e in acc.fleet.events if e.get("kind") == "autopilot"]
    suppressed = [d for d in decisions if d["suppressed"]]
    assert len(suppressed) >= 2
    assert not any(d["fired"] for d in decisions)
    assert any(d.get("reason", "").startswith("debounce") for d in suppressed)
    # the storm is visible in the recorded window values: the flap itself
    # is part of the forensic record
    assert any(0.0 in d.get("window_values", []) for d in suppressed)


def test_autopilot_sustained_skew_fires_shrink(tmp_path):
    """The soft-signal path end-to-end: a sustained straggler skew above
    the threshold (no host event) makes the autopilot shrink after the
    debounce window, respecting the cooldown afterwards."""
    if _num_devices() < 2:
        pytest.skip("needs >= 2 devices")
    acc, _, _, step = _make_step(
        [
            FleetKwargs(
                enabled=True, autopilot="skew_pct=100,window=2,cooldown=50",
                checkpoint_dir=str(tmp_path / "drain"),
            )
        ]
    )
    dp = dict(acc.mesh.shape)["dp"]
    acc.fleet.fleet_signal = lambda: {"kind": "fleet", "skew_pct": 400.0}
    batches = _batches(acc, 4)
    i = 0
    for b in batches:
        losses = float(step(batch_to_global_array(np.asarray(b), mesh=acc.mesh)))
        i += 1
    assert acc.fleet.resizes_total == 1  # fired once, then cooldown held
    assert dict(acc.mesh.shape)["dp"] == dp // 2
    decisions = [e for e in acc.fleet.events if e.get("kind") == "autopilot"]
    fired = [d for d in decisions if d["fired"]]
    assert len(fired) == 1 and fired[0]["signal"] == "skew_pct"
    assert fired[0]["value"] == 400.0 and fired[0]["threshold"] == 100.0
    # post-fire decisions (if any) were suppressed — the window refilling
    # after the fire cleared it, or the cooldown — never a second resize
    assert all(
        ("cooldown" in d.get("reason", "") or "debounce" in d.get("reason", ""))
        for d in decisions
        if d["suppressed"]
    )


def test_autopilot_shrink_at_floor_suppressed(tmp_path):
    """A hard host loss at the dp floor cannot shrink: the decision is
    recorded as suppressed (naming the floor) and the flag consumed —
    never a raise, never a record-spam loop."""
    acc, _, _, step = _make_step(
        [
            FleetKwargs(
                enabled=True, autopilot=True, min_dp=64,
                fault_plan="host_lost:step=0",
            )
        ]
    )
    for b in _batches(acc, 2):
        float(step(b))
    assert acc.fleet.resizes_total == 0
    decisions = [e for e in acc.fleet.events if e.get("kind") == "autopilot"]
    floor = [d for d in decisions if "floor" in d.get("reason", "")]
    assert len(floor) == 1  # consumed: no identical record on the next step
    assert not acc.fleet.should_resize


def test_autopilot_stale_record_counts_once(tmp_path):
    """Review-pinned: the latest retained skew record is re-READABLE every
    dispatch, but one measurement must count ONCE toward the debounce
    window — a single noisy record re-sampled until it 'held' would fire
    on exactly the transient the debounce exists to suppress."""
    if _num_devices() < 2:
        pytest.skip("needs >= 2 devices")
    acc, _, _, step = _make_step(
        [
            FleetKwargs(
                enabled=True, autopilot="skew_pct=100,window=2,cooldown=50",
                checkpoint_dir=str(tmp_path / "drain"),
            )
        ]
    )
    dp = dict(acc.mesh.shape)["dp"]
    # ONE stale measurement: at_step never advances
    acc.fleet.fleet_signal = lambda: {
        "kind": "fleet", "skew_pct": 400.0, "at_step": 7,
    }
    for b in _batches(acc, 4):
        float(step(batch_to_global_array(np.asarray(b), mesh=acc.mesh)))
    assert acc.fleet.resizes_total == 0, "a single stale measurement resized"
    assert dict(acc.mesh.shape)["dp"] == dp
    # fresh measurements (advancing marks) DO satisfy the window
    marks = iter(range(100, 200))
    acc.fleet.fleet_signal = lambda: {
        "kind": "fleet", "skew_pct": 400.0, "at_step": next(marks),
    }
    for b in _batches(acc, 3):
        float(step(batch_to_global_array(np.asarray(b), mesh=acc.mesh)))
    assert acc.fleet.resizes_total == 1


def test_autopilot_grow_rendezvous_abort_suppressed(monkeypatch):
    """Review-pinned: an aborted grow rendezvous (some rank cannot see the
    rejoined host yet) must NOT raise out of the dispatch hook — the loop
    keeps training, the decision lands suppressed, the sticky flag stays
    set, and the retry backs off instead of re-draining every dispatch."""
    import accelerate_tpu.fleet as fleet_mod
    from accelerate_tpu.fleet import grow as grow_mod

    acc, _, _, step = _make_step(
        [FleetKwargs(enabled=True, autopilot=True, fault_plan="host_gained:step=0")]
    )
    monkeypatch.setattr(
        fleet_mod, "grow_rendezvous", lambda *a, **k: None
    )
    # pretend a rejoined host doubled the pool, so the ceiling check lets
    # the grow reach the (failing) rendezvous
    dp_now = dict(acc.mesh.shape)["dp"]
    monkeypatch.setattr(grow_mod, "max_growable_dp", lambda *a, **k: dp_now * 2)
    drains = []
    monkeypatch.setattr(
        acc.fleet, "drain", lambda accelerator, output_dir=None: (
            drains.append(1), "/tmp/fake-ckpt")[-1],
    )
    for b in _batches(acc, 4):
        float(step(b))  # must not raise
    assert acc.fleet.grows_total == 0
    aborted = [
        e for e in acc.fleet.events
        if e.get("kind") == "autopilot" and "grow aborted" in e.get("reason", "")
    ]
    assert len(aborted) == 1  # backed off, not one abort per dispatch
    assert acc.fleet.should_grow  # flag survives for the retry
    assert len(drains) == 1


def test_autopilot_serving_signal_gated_on_multi_process(monkeypatch):
    """Review-pinned: serving records live on ONE rank's hub — sampling
    them on a multi-process run would fire a collective resize only that
    rank enters (deadlock).  The sampler must drop the serving half when
    the world is > 1."""
    from accelerate_tpu.fleet import autopilot as ap

    acc, _, _, _ = _make_step([FleetKwargs(enabled=True, autopilot=True)])
    acc.fleet.serving_signal = lambda: {
        "event": "step", "step": 3, "queue_depth": 50.0, "occupancy": 1.0,
    }
    sample = acc.fleet.autopilot._sample()
    assert sample["queue_depth"] == 50.0  # single-process: consumed
    monkeypatch.setattr(ap, "_multi_process", lambda: True)
    acc.fleet.autopilot._serving_mark = None
    sample = acc.fleet.autopilot._sample()
    assert "queue_depth" not in sample and "occupancy" not in sample


def test_evaluate_window_armed_grow_defers_shrink_fire():
    """Review-pinned: a fully-held lower-priority shrink must NOT fire
    while the higher-priority queue signal is armed but still debouncing —
    shrinking capacity exactly as serving demand arrives (and cooldown
    then blocking the grow) would invert the documented priority."""
    from accelerate_tpu.fleet import AutopilotPolicy, evaluate_window

    policy = AutopilotPolicy(queue_high=4.0, skew_pct=100.0, window=3)
    held_shrink = {"skew_pct": 150.0}
    both = {"skew_pct": 150.0, "queue_depth": 6.0}
    d = evaluate_window(policy, [held_shrink, held_shrink, both])
    assert not d["fired"] and d["suppressed"]
    assert d["signal"] == "queue_depth" and d["action"] == "grow"
    assert "deferring a held skew_pct shrink" in d["reason"]
    # once the queue clears (drops below its sustain floor, no longer
    # armed), the held shrink fires normally
    cleared = {"skew_pct": 150.0, "queue_depth": 0.0}
    d = evaluate_window(policy, [held_shrink, held_shrink, cleared])
    assert d["fired"] and d["action"] == "shrink" and d["signal"] == "skew_pct"


def test_autopilot_resolve_accepts_plain_ints():
    """Review-pinned: 0/1 must mean off/on like everywhere else in the
    knob surface — not a construction-time TypeError."""
    from accelerate_tpu.fleet import AutopilotPolicy

    assert AutopilotPolicy.resolve(1) == AutopilotPolicy()
    assert AutopilotPolicy.resolve(0) is None
    assert FleetKwargs(enabled=True, autopilot=1).autopilot_policy is not None
    assert FleetKwargs(enabled=True, autopilot=0).autopilot_policy is None


def test_merged_fleet_dump_dedups_periodic_skew_records():
    """Review-pinned: the periodic cadence retains the IDENTICAL skew
    record on every rank (the autopilot needs symmetric inputs) — the
    end-of-training merged dump must keep it once, not world-size times."""
    from accelerate_tpu.telemetry.aggregate import merge_rank_records

    periodic = {"kind": "fleet", "periodic": True, "at_step": 4, "skew_ms": 2.0}
    step = {"kind": "step", "step": 0, "total_ms": 5.0, "built": False}
    per_rank = [[dict(periodic), dict(step)], [dict(periodic), dict(step)]]
    merged = merge_rank_records(per_rank)
    periodics = [r for r in merged if r.get("kind") == "fleet" and r.get("periodic")]
    assert len(periodics) == 1 and periodics[0]["rank"] == 0
    # per-rank step records still merge from every rank, and the final
    # (non-periodic) skew record is appended as before
    assert sum(1 for r in merged if r.get("kind") == "step") == 2
    finals = [r for r in merged if r.get("kind") == "fleet" and not r.get("periodic")]
    assert len(finals) == 1
