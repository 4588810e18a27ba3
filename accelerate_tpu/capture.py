"""Step capture: trace the imperative loop body into one jitted XLA program.

This is the resolution of SURVEY.md §7 hard-part #2 ("eager-shaped API over
lazy compiled execution"): the user's Python step — forward through tape
Modules, ``accelerator.backward``, ``optimizer.step()`` — executes inside a
``jax.jit`` trace exactly once per (shapes, sync_gradients, training-mode)
variant.  The tape's per-op ``jax.vjp`` closures compose into the backward
graph; optimizer math and GSPMD collectives land in the same program; state
(params, grads, optax state, fp32 masters, RNG key) is threaded through as
donated arguments so replays are a single device launch with zero host work
beyond argument assembly.

Scheduler steps are recorded at trace time and replayed python-side after
every call: their LR lands in ``opt_state.hyperparams`` which is *data* to the
compiled program, so LR schedules work across replays without recompiles.
"""

from __future__ import annotations

import contextlib
import threading
import time as _time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .nn import random as nn_random
from .nn.tape import Tensor
from .telemetry import flightrec as _flightrec
from .telemetry import profiler as _profiler
from .telemetry import watchdog as _watchdog
from .telemetry.recompile import RecompileEvent, diff_keys, key_id
from .telemetry.timeline import StepRecord


class _CaptureState(threading.local):
    def __init__(self):
        self.active: Optional["CaptureContext"] = None


_capture_state = _CaptureState()


def current_capture() -> Optional["CaptureContext"]:
    return _capture_state.active


class CaptureContext:
    """Book-keeping for one trace: deferred scheduler steps, accumulate use."""

    def __init__(self, owner_advances_accumulate: bool = False):
        self.deferred_scheduler_steps: list[tuple[Any, tuple, dict]] = []
        # True when this context's entry was deserialized from the AOT
        # executable cache (docs/aot_cache.md): no trace ran, the side
        # metadata below was restored from disk, and the entry must not be
        # re-serialized (a loaded executable may not round-trip)
        self.aot_loaded = False
        # `with accelerator.accumulate(model):` inside the captured body —
        # legal: the owning CapturedStep advances the schedule host-side once
        # per replay, so the trace-time flag is already the replay-time flag
        self.used_accumulate = False
        self.owner_advances_accumulate = owner_advances_accumulate
        self._schedule_advanced = False  # sticky: a re-trace must not re-advance
        self._accumulate_calls_in_trace = 0

    def defer_scheduler(self, scheduler, args, kwargs) -> None:
        self.deferred_scheduler_steps.append((scheduler, args, kwargs))

    def begin_trace(self) -> None:
        """Reset per-trace bookkeeping (a re-trace must not double-count)."""
        self.deferred_scheduler_steps.clear()
        self._accumulate_calls_in_trace = 0

    def on_accumulate(self, accelerator) -> None:
        """Called by ``accelerator.accumulate()`` at trace time.

        Only the very first trace of a CapturedStep advances the schedule
        here (the step's variant wasn't known yet when ``__call__`` computed
        its cache key); afterwards the CapturedStep owns the advance and
        trace-time accumulate() is purely a marker."""
        self._accumulate_calls_in_trace += 1
        if self._accumulate_calls_in_trace > 1:
            # eager would advance the schedule once per block; a compiled
            # program advances once per CALL and bakes a single
            # sync_gradients value into the trace — silently different math
            raise RuntimeError(
                "compile_step body enters accelerator.accumulate() more than "
                "once; the captured program can only advance the "
                "accumulation schedule once per call. Process one "
                "micro-batch per captured call (loop outside), or capture a "
                "step without accumulate() and drive no_sync() manually."
            )
        self.used_accumulate = True
        if not self.owner_advances_accumulate and not self._schedule_advanced:
            accelerator._do_sync()
            self._schedule_advanced = True


def _unwrap_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: x.data if isinstance(x, Tensor) else x,
        tree,
        is_leaf=lambda x: isinstance(x, Tensor),
    )


def _is_offloaded(x) -> bool:
    """True when the array lives outside TPU device memory (host-offloaded
    optimizer state / params) — the predicate behind the donation split
    below.  On the CPU backend every array reports ``unpinned_host``, so CPU
    runs donate nothing; that matches historical behavior and keeps eager
    references valid in the virtual-mesh test suite."""
    s = getattr(x, "sharding", None)
    return getattr(s, "memory_kind", None) not in (None, "device")


_DEFAULT_MEMORY_KIND: Optional[str] = None


def _default_memory_kind() -> str:
    global _DEFAULT_MEMORY_KIND
    if _DEFAULT_MEMORY_KIND is None:
        try:
            _DEFAULT_MEMORY_KIND = jax.devices()[0].default_memory().kind
        except Exception:
            _DEFAULT_MEMORY_KIND = "device"
    return _DEFAULT_MEMORY_KIND


def _nondefault_memory(x) -> bool:
    """True only for genuinely offloaded leaves (pinned_host on TPU *or*
    CPU).  Unlike ``_is_offloaded`` this compares against the backend's
    default memory kind — the CPU backend's default is ``unpinned_host``,
    and treating that as "offloaded" would disable the layout pin exactly
    where the virtual-mesh tests need it (a ZeRO-1 state-sharded program
    would then drift its unpinned grad outputs to the dp layout and
    silently re-trace on call 2)."""
    s = getattr(x, "sharding", None)
    kind = getattr(s, "memory_kind", None)
    return kind is not None and kind not in ("device", _default_memory_kind())


def _zeros_like_on_device(x):
    """zeros_like, but always in device memory: a placeholder grad for a
    host-OFFLOADED param must not inherit pinned_host (the backward
    accumulates real device grads into it — XLA refuses mixed spaces)."""
    if isinstance(x, jax.Array) and _is_offloaded(x):
        s = x.sharding
        return jax.device_put(
            jnp.zeros(x.shape, x.dtype), jax.sharding.NamedSharding(s.mesh, s.spec)
        )
    return jnp.zeros_like(x)


def _grad_placeholder(p):
    """Zero grad for a param that has none this call.  When ZeRO-2 armed an
    accumulation layout on the param (``optim.relayout`` sets
    ``_grad_sharding``), the placeholder is built dp-sharded so the carried
    grad leaf is ~1/dp resident from the first micro-step AND the grad
    layout is a fixed point across captured variants (the layout pin would
    otherwise re-replicate what the body reduce-scattered)."""
    s = getattr(p, "_grad_sharding", None)
    if s is not None:
        return jax.device_put(jnp.zeros(tuple(p.shape), p.data.dtype), s)
    return _zeros_like_on_device(p.data)


# span stamps without a ring: what a step pinned to a killed recorder uses
_STAMPS_ONLY = _flightrec.FlightRecorder(capacity=16, enabled=False)


class CapturedStep:
    """Callable produced by ``accelerator.compile_step``."""

    def __init__(self, accelerator, fn: Callable):
        self.accelerator = accelerator
        self.fn = fn
        self._cache: dict = {}
        # host-side argument-assembly accounting (collect/flatten/key/split
        # before each dispatch): replay calls only — trace/compile calls are
        # excluded, so that the figure is steady-state host overhead per step
        self.host_assembly_ms_total = 0.0
        self.host_assembly_calls = 0
        # None until the first trace reveals whether the body contains
        # `with accelerator.accumulate(...):`; True → __call__ advances the
        # accumulation schedule host-side before each replay
        self._uses_accumulate: Optional[bool] = None
        # telemetry (docs/telemetry.md): pinned at construction so the
        # off-path stays a single None-check per call.  When ON, builds go
        # through jit.lower()/.compile() so trace and compile time are
        # separately measured and the executable's memory/cost analyses are
        # recorded; when OFF every line below runs exactly as before.
        tel = getattr(accelerator, "telemetry", None)
        self._telemetry = tel if (tel is not None and tel.enabled) else None
        # flight recorder (docs/telemetry.md §flight recorder): the one
        # always-ON telemetry stream — pinned here so the kill switch
        # ($ACCELERATE_FLIGHTREC=0) costs the hot path a single None-check
        rec = _flightrec.recorder()
        self._flightrec = rec if rec.enabled else None
        # who stamps this step's spans: the recorder, or (killed) a disabled
        # stand-in whose spans only read the clock — StepRecord's phases are
        # computed from the spans' stamps either way
        self._spans = rec if rec.enabled else _STAMPS_ONLY
        self._flight_steps = 0  # step-index fallback when telemetry is OFF
        # resilience (docs/resilience.md): same pinning discipline — when
        # OFF the dispatch below is byte-identical to the pre-resilience
        # path; when ON, dispatch faults are classified/retried and the
        # fault injector's hooks fire
        res = getattr(accelerator, "resilience", None)
        self._resilience = res if (res is not None and res.enabled) else None
        # persistent AOT executable cache (docs/aot_cache.md): same pinning
        # discipline — when OFF every build/dispatch line runs exactly as
        # before this subsystem existed; when ON, builds consult the on-disk
        # store before tracing and store after compiling
        cache = getattr(accelerator, "aot_cache", None)
        self._aot_cache = cache if (cache is not None and cache.enabled) else None
        # elastic fleet runtime (docs/elastic.md): same pinning discipline —
        # when OFF every line below runs exactly as before this subsystem
        # existed; when ON, each call counts on the host-lost fault axis and
        # a resize-bumped mesh generation drops the stale compiled variants
        fleet = getattr(accelerator, "fleet", None)
        self._fleet = fleet if (fleet is not None and fleet.enabled) else None
        self._mesh_generation = getattr(accelerator, "_mesh_generation", 0)
        self._last_key = None  # previous variant key, for recompile forensics
        self._last_build_ms = (0.0, 0.0)  # (trace_ms, compile_ms) of last build
        # monotonic build counter for program-record labels: cache size would
        # repeat a label after a layout-drift retry (pop + rebuild)
        self._builds_total = 0
        # per-key layout-drift rebuild count: a second drift on the same key
        # means layouts alternate, and the AOT path must yield to plain jit
        # (whose internal cache absorbs the alternation) or thrash a full
        # trace+compile every step
        self._layout_rebuilds: dict = {}
        # key -> key_id memo: the short id is per-variant constant, and
        # recomputing repr+sha1 every replay would tax the hot path
        self._key_ids: dict = {}

    def compiled_hlo(self) -> list[str]:
        """Post-optimisation HLO text of every variant this step holds as an
        explicitly compiled executable — the program the device runs, where
        a Pallas kernel shows as ``tpu_custom_call`` and GSPMD's collectives
        by name.  Builds go through ``lower().compile()`` only with
        telemetry or the AOT cache on; plain-jit variants have no
        inspectable executable and are left out."""
        return [
            entry[0].as_text()
            for entry in self._cache.values()
            if hasattr(entry[0], "as_text")
        ]

    # -- state threading -----------------------------------------------------
    def _collect_state(self) -> dict:
        acc = self.accelerator
        models = acc._models
        optimizers = acc._optimizers
        state = {
            "params": [m.param_pytree() for m in models],
            "buffers": [m.buffer_pytree() for m in models],
            "grads": [
                {
                    name: (p.grad if p.grad is not None else _grad_placeholder(p))
                    for name, p in m.named_parameters()
                }
                for m in models
            ],
            "opt": [o.optimizer.capture_state() for o in optimizers],
            "rng": nn_random.next_key(),
            "scaler": acc.scaler.capture_state() if acc.scaler is not None else None,
            # PowerSGD comm-hook (Q, error) buffers — persistent across steps
            "comm": acc._comm_hook_capture_state(),
        }
        return state

    def _bind_state(self, state: dict) -> None:
        acc = self.accelerator
        for m, params, buffers, grads in zip(
            acc._models, state["params"], state["buffers"], state["grads"]
        ):
            m.bind_params(params)
            m.bind_buffers(buffers)
            named = dict(m.named_parameters())
            for name, g in grads.items():
                named[name].grad = g
        for o, s in zip(acc._optimizers, state["opt"]):
            o.optimizer.bind_capture_state(s)
        if state.get("scaler") is not None and acc.scaler is not None:
            acc.scaler.bind_capture_state(state["scaler"])
        acc._bind_comm_hook_state(state.get("comm"))

    def _snapshot_state(self) -> dict:
        acc = self.accelerator
        return {
            "params": [m.param_pytree() for m in acc._models],
            "buffers": [m.buffer_pytree() for m in acc._models],
            "grads": [
                {
                    name: (p.grad if p.grad is not None else _grad_placeholder(p))
                    for name, p in m.named_parameters()
                }
                for m in acc._models
            ],
            "opt": [o.optimizer.capture_state() for o in acc._optimizers],
            "scaler": acc.scaler.capture_state() if acc.scaler is not None else None,
            "comm": acc._comm_hook_capture_state(),
        }

    # -- call ----------------------------------------------------------------
    def __call__(self, *args):
        # host phases (docs/telemetry.md §spans and scopes): assemble →
        # atpu/dispatch → writeback, three spans on the flight recorder's
        # ring, from whose stamps StepRecord's phases are computed too.  Each
        # starts at the stamp that ended the one before, so together they
        # cover the call
        spans = self._spans
        assemble = spans.span("atpu/step/assemble").__enter__()
        tel = self._telemetry
        # flight event: dispatch begin, stamped with the step index this call
        # will carry (telemetry's global counter when ON, a local one when
        # OFF).  The begin/end pair is the trace-export anchor and — in a
        # postmortem — the proof of which step the process died inside.
        flight = self._flightrec
        flight_step = -1
        if flight is not None:
            flight_step = tel.steps_total if tel is not None else self._flight_steps
            flight.record("step_begin", step=flight_step)
        dl_wait_ms = tel.pop_dataloader_wait_ms() if tel is not None else 0.0
        # sampled device-time attribution (docs/telemetry.md): every Nth
        # step the dispatch below runs inside a jax.profiler trace session
        # and blocks afterwards so this step's device ops land in the
        # window.  prof_step < 0 on every unsampled call — the hot path
        # pays one None-check + one modulus; with the knob off (the
        # default) the profiler is None and nothing below changes.
        prof = tel.profiler if tel is not None else None
        prof_step = -1
        acc = self.accelerator
        fleet = self._fleet
        if fleet is not None:
            # counts this call on the fault plan's host_lost axis and runs
            # the periodic fleet-aggregation cadence (docs/elastic.md)
            fleet.on_dispatch(self)
            generation = getattr(acc, "_mesh_generation", 0)
            if generation != self._mesh_generation:
                # a resize re-meshed the run AND re-resolved the plan: every
                # compiled variant binds the lost topology — drop them so
                # the lookup below builds (or AOT-warm-loads) the surviving-
                # topology program instead of dispatching against a mesh
                # that no longer exists (the new builds fingerprint under
                # the re-resolved plan via the cache's re-pinned context)
                self._cache.clear()
                self._layout_rebuilds.clear()
                self._key_ids.clear()
                self._last_key = None
                self._mesh_generation = generation
        if self._uses_accumulate is None and self._aot_cache is not None:
            # warm-start profile sidecar (docs/aot_cache.md): on a genuinely
            # first call the trace would reveal whether the body accumulates
            # — but a cache hit skips the trace, and an accumulate-using
            # body must advance its schedule host-side BEFORE the key below
            # is computed, or the key misses the entry the cold process
            # stored under.  None (no profile on disk) keeps the legacy
            # first-trace discovery path.
            self._uses_accumulate = self._aot_cache.step_profile_uses_accumulate(self)
        if self._uses_accumulate:
            # body contains `with accelerator.accumulate(...)`: advance the
            # micro-step schedule here, host-side, so the sync_gradients flag
            # in the cache key below already selects the right compiled
            # variant (trace-time accumulate() is then a no-op marker)
            acc._do_sync()
        args = _unwrap_tree(args)
        flat_args, args_treedef = jax.tree_util.tree_flatten(args)
        import numpy as _np

        key = (
            args_treedef,
            tuple(
                (tuple(_np.shape(a)), str(getattr(a, "dtype", _np.result_type(a))))
                for a in flat_args
            ),
            acc.gradient_state.sync_gradients,
            tuple(m.training for m in acc._models),
        )
        entry = self._cache.get(key)
        state = self._collect_state()
        flat_state, cur_treedef = jax.tree_util.tree_flatten(state)
        state_cause = None
        if entry is not None and cur_treedef != entry[2]:
            # state structure changed since this entry was built (e.g. more
            # objects prepared): rebuild, exactly where plain jit would
            # silently re-trace
            if tel is not None:
                state_cause = (
                    "state pytree structure changed: "
                    f"{entry[2].num_leaves} -> {cur_treedef.num_leaves} leaves"
                )
                old_host = sum(entry[3])
                new_host = sum(1 for x in flat_state if _is_offloaded(x))
                if old_host != new_host:
                    state_cause += (
                        f"; donation split moved ({old_host} -> {new_host} "
                        "host-offloaded leaves)"
                    )
            entry = None
        built = entry is None
        if built:
            if tel is not None:
                self._note_recompile(tel, key, state_cause)
            entry = self._build(key, state, args)
        jitted, ctx, _, host_mask = entry
        dev_leaves = tuple(x for x, h in zip(flat_state, host_mask) if not h)
        host_leaves = tuple(x for x, h in zip(flat_state, host_mask) if h)
        if not built:
            self.host_assembly_ms_total += (spans.now_ns() - assemble.start_ns) / 1e6
            self.host_assembly_calls += 1
        self._last_key = key
        retry_rebuild = False
        res = self._resilience
        retrier = res.retrier if res is not None else None
        if res is not None:
            # counts this dispatch on the fault plan's step axis and delivers
            # any scheduled (injected) SIGTERM — "mid-step" preemption
            res.begin_dispatch()
        if prof is not None and prof.should_sample(tel.steps_total):
            # the session brackets the dispatch (launch + device execution):
            # builds already happened above, so a trace/compile failure can
            # never orphan a session.  The measured window is backdated to
            # call entry — device idle while the host assembled/built is
            # real idle, and busy+idle must account for the step wall clock
            entered_s = _time.perf_counter() - (spans.now_ns() - assemble.start_ns) / 1e9
            if prof.start(tel.steps_total, t0=entered_s):
                prof_step = tel.steps_total
        assemble.fields.update(step=flight_step, built=built)
        assemble.__exit__(None, None, None)
        try:
            # the executable call alone (a drift rebuild or a resilience
            # retry, where one happens, is inside it and is split out of
            # StepRecord.dispatch_ms below)
            with assemble.then("atpu/dispatch") as launch:
                if tel is not None or self._aot_cache is not None:
                    # AOT-compiled entries (telemetry's split builds AND cache-
                    # armed builds) reject drifted input layouts instead of
                    # silently re-tracing — route through the drift-tolerant
                    # dispatch either way; _dispatch_aot is telemetry-optional
                    if retrier is None:
                        new_state, out, entry, retry_rebuild = self._dispatch_aot(
                            tel, key, entry, state, args, dev_leaves, host_leaves, flat_args
                        )
                    else:
                        new_state, out, entry, retry_rebuild = retrier.run_dispatch(
                            self,
                            lambda dev, host, e: self._dispatch_aot(
                                tel, key, e, state, args, dev, host, flat_args
                            ),
                            entry, dev_leaves, host_leaves, host_mask,
                        )
                    if retry_rebuild:
                        built = True
                        jitted, ctx, _, host_mask = entry
                elif retrier is not None:
                    new_state, out, _, _ = retrier.run_dispatch(
                        self,
                        lambda dev, host, e: (*e[0](dev, host, *flat_args), e, False),
                        entry, dev_leaves, host_leaves, host_mask,
                    )
                else:
                    new_state, out = jitted(dev_leaves, host_leaves, *flat_args)
            if prof_step >= 0:
                # close the sampled window before writeback: blocks on this
                # call's outputs (the documented sampling overhead), parses
                # the trace into a DeviceStepRecord joined to this
                # StepRecord by step index; fail-soft — an empty/
                # unparseable trace records nothing
                kid = self._key_ids.get(key)
                if kid is None:
                    kid = self._key_ids[key] = key_id(key)
                # prof.stop blocks on this call's outputs — the one
                # unconditional device sync in the step — so it is deadline-
                # guarded when a hang watchdog is armed (docs/telemetry.md)
                wd = _watchdog.current_watchdog()
                with (
                    wd.guard(f"profiler_stop step {prof_step}")
                    if wd is not None
                    else contextlib.nullcontext()
                ):
                    device_record = prof.stop(prof_step, kid, (new_state, out))
                if device_record is not None:
                    tel.record_device_step(device_record)
        except BaseException:
            if prof_step >= 0:
                # a dispatch failure (retry exhaustion, preemption,
                # rollback) must not leave the global trace session open —
                # it would silently trace every step until the next sample
                prof.abort()
            raise
        # from the dispatch's end stamp: on a sampled step the profiler
        # window's close above falls in this span
        with launch.then("atpu/step/writeback") as writeback:
            self._writeback(new_state)
            if self._uses_accumulate is None:
                # first ever call: the trace just revealed whether the body
                # accumulates.  If it advanced the schedule mid-trace, the key
                # computed above used the stale flag — re-file the entry under
                # the flag the program was actually traced with.
                self._uses_accumulate = ctx.used_accumulate
                if ctx.used_accumulate:
                    ctx.owner_advances_accumulate = True
                    new_key = (key[0], key[1], acc.gradient_state.sync_gradients, key[3])
                    if new_key != key:
                        self._cache[new_key] = entry
                        self._cache.pop(key, None)
                        # forensics/timeline must follow the re-file: diffing the
                        # next miss against the popped key would blame the wrong
                        # baseline, and the build record's key id would never
                        # match its replays'
                        key = self._last_key = new_key
                        if tel is not None:
                            # the ProgramRecord written in _build carries the
                            # pre-refile key — which the SECOND variant will
                            # reuse (the sync flag flips back), cross-wiring the
                            # per-program HBM/FLOP stats
                            tel.rekey_last_program(key_id(new_key))
                            if prof_step >= 0 and device_record is not None:
                                # a sampled first call recorded its device
                                # record under the same pre-refile key — follow
                                # the re-file or the device_step↔program join
                                # dangles for that sample.  Only when the sample
                                # actually produced a record: an empty-trace
                                # sample must not re-key an UNRELATED earlier
                                # record at device_records[-1]
                                tel.rekey_last_device_step(key_id(new_key))
            elif ctx.used_accumulate != self._uses_accumulate:
                # a later variant disagrees with the first trace (e.g. the body
                # enters `accumulate()` only when model.training) — the schedule
                # advance would silently skip or double-count; fail loudly
                raise RuntimeError(
                    "compile_step body uses accelerator.accumulate() in some "
                    "trace variants but not others (e.g. behind a training-mode "
                    "or warmup branch); the accumulation schedule cannot track "
                    "such a step. Call accumulate() unconditionally inside the "
                    "body, or move it outside the captured call."
                )
            if (
                built
                and self._aot_cache is not None
                and not ctx.aot_loaded
                and not hasattr(entry[0], "lower")
            ):
                # persist the freshly compiled executable under the FINAL key
                # (the accumulate re-file above already settled it) so the next
                # process starts zero-cold.  Plain-jit fallback entries (.lower
                # present: repeated layout drift) hold no serializable
                # executable; cache-loaded entries must not round-trip.
                # Fail-soft by construction — store_captured records its own
                # store_failed cause and never raises into the step.
                build_trace_ms, build_compile_ms = self._last_build_ms
                self._aot_cache.store_captured(
                    self, key, entry[0], ctx, state, entry[3],
                    build_trace_ms, build_compile_ms,
                )
            # deferred scheduler steps run for real, python-side, every replay
            for scheduler, s_args, s_kwargs in ctx.deferred_scheduler_steps:
                scheduler.step(*s_args, _from_capture_replay=True, **s_kwargs)
            # the handles of the state that went in (donated) are dropped
            # here, inside the span, not at the frame's end: letting go of
            # several hundred arrays is host time too (2.5 ms a call at
            # GPT-2-medium, PERF.md PR 27)
            del state, flat_state, dev_leaves, host_leaves, new_state
        if tel is not None:
            # the spans' own stamps: no second pair of clock reads.
            # dispatch_ms keeps its meaning: launch through writeback
            trace_ms, compile_ms = self._last_build_ms if built else (0.0, 0.0)
            assembly_ms = (launch.start_ns - assemble.start_ns) / 1e6
            dispatch_ms = (writeback.end_ns - launch.start_ns) / 1e6
            if built and not retry_rebuild:
                assembly_ms -= trace_ms + compile_ms  # build ran pre-dispatch
            elif retry_rebuild:
                dispatch_ms -= trace_ms + compile_ms  # rebuild ran mid-dispatch
            # resilience backoff sleeps happened inside the dispatch window —
            # split them out so retries don't inflate dispatch timing in A/B
            # comparisons (docs/resilience.md, StepRecord.retry_wait_ms)
            retry_wait_ms = retrier.last_wait_ms if retrier is not None else 0.0
            dispatch_ms -= retry_wait_ms
            kid = self._key_ids.get(key)
            if kid is None:
                kid = self._key_ids[key] = key_id(key)
            tel.record_step(
                StepRecord(
                    step=tel.next_step_index(),
                    key=kid,
                    built=built,
                    total_ms=(writeback.end_ns - assemble.start_ns) / 1e6,
                    assembly_ms=max(0.0, assembly_ms),
                    trace_ms=trace_ms,
                    compile_ms=compile_ms,
                    dispatch_ms=max(0.0, dispatch_ms),
                    dataloader_wait_ms=dl_wait_ms,
                    retry_wait_ms=retry_wait_ms,
                )
            )
        if fleet is not None and fleet.autopilot is not None:
            # autopilot hook (docs/elastic.md): the closed signal→decision→
            # action loop evaluates at the step boundary — after writeback
            # and the step record, so a fired resize/grow never lands
            # mid-step and never pollutes this step's timing.  Guarded on
            # the autopilot handle: plain fleet-armed runs (the manual
            # should_resize loop) pay one extra None-check, fleet-off runs
            # none at all.
            fleet.on_dispatch_end(self)
        if flight is not None:
            flight.record("step_end", step=flight_step, built=built)
            if tel is None:
                self._flight_steps += 1
        return out

    def _dispatch_aot(self, tel, key, entry, state, args, dev_leaves, host_leaves, flat_args):
        """Telemetry-path dispatch of the AOT-compiled executable.

        Plain jit re-traces *silently* when an input sharding/layout drifts;
        the AOT executable raises instead.  Keep jit's forgiving behavior —
        rebuild against the live inputs — but make the event loud: this
        rebuild is exactly the hidden multi-minute recompile the forensics
        pillar exists to expose.  Returns (new_state, out, entry,
        retry_rebuild).  ``tel`` may be None (cache-armed, telemetry-off
        runs ride this path too): events are then skipped, the drift
        handling is identical.  The caller's ``atpu/dispatch`` span covers
        all of it."""
        executable = entry[0]
        try:
            return (*executable(dev_leaves, host_leaves, *flat_args), entry, False)
        except (TypeError, ValueError) as exc:
            # TypeError/ValueError is how the executable's *argument
            # validation* rejects drifted avals/shardings (jaxlib maps
            # INVALID_ARGUMENT to ValueError) — always before any buffer is
            # donated.  Runtime failures (OOM et al. are RuntimeError
            # subclasses) propagate untouched: they are not layout drift and
            # the inputs may already be consumed.
            if hasattr(executable, "lower"):
                # plain-jit fallback entry: jit absorbs layout changes
                # silently, so a TypeError/ValueError here is a genuine
                # user/trace error — no spurious layout event, no rebuild
                raise
            # ALTERNATING layouts would make this rebuild fire every step
            # (the AOT path keeps one executable per key where plain jit
            # memoizes each layout variant): after a repeat event on the
            # same key, fall back to the jitted callable for that key —
            # jit's internal cache then absorbs the alternation, at the
            # cost of the trace/compile split for that variant
            drifts = self._layout_rebuilds.get(key, 0) + 1
            self._layout_rebuilds[key] = drifts
            cause = (
                "input layout/sharding drift: compiled executable "
                f"rejected replay inputs ({type(exc).__name__}: "
                f"{str(exc)[:200]})"
            )
            if drifts >= 2:
                cause += (
                    "; repeated drift on this variant — falling back to "
                    "plain jit dispatch (per-step trace/compile split "
                    "no longer attributed)"
                )
            if tel is not None:
                tel.record_recompile(
                    RecompileEvent(
                        step=tel.steps_total,
                        key=key_id(key),
                        prev_key=key_id(key),
                        causes=[cause],
                        kind="layout",
                    )
                )
            # skip_cache_load: the stored entry matches the layouts this
            # very rejection just proved stale — loading it back would fail
            # the retry dispatch identically; the fresh compile below gets
            # re-stored under the live layouts by __call__
            self._cache.pop(key, None)
            entry = self._build(
                key, state, args, force_plain=drifts >= 2, skip_cache_load=True
            )
            # the rebuild recomputed host_mask from the live state — if the
            # drift moved a leaf between memory spaces, the caller's dev/host
            # split is stale, so re-split against the new mask
            flat_state, _ = jax.tree_util.tree_flatten(state)
            new_mask = entry[3]
            dev_leaves = tuple(x for x, h in zip(flat_state, new_mask) if not h)
            host_leaves = tuple(x for x, h in zip(flat_state, new_mask) if h)
            # argument validation fails BEFORE any buffer is donated, so the
            # leaves the failed call touched are intact for the retry; an
            # error from the rebuilt program is real and propagates
            new_state, out = entry[0](dev_leaves, host_leaves, *flat_args)  # graftlint: disable=donation-reuse
            return new_state, out, entry, True

    def _note_recompile(self, tel, key, state_cause: Optional[str]) -> None:
        """Emit a forensics event for a rebuild (never for the first build:
        the first compile of a step is expected, not a hazard)."""
        prev = self._last_key
        if prev is None:
            return
        if state_cause is not None:
            causes, kind = [state_cause], "state"
        else:
            causes, kind = diff_keys(prev, key), "key"
            if not causes:
                # key changed in no recognized component (or an evicted
                # variant was rebuilt): still an event, cause unknown
                causes = ["cache key changed (no recognized component diff)"]
        tel.record_recompile(
            RecompileEvent(
                step=tel.steps_total,
                key=key_id(key),
                prev_key=key_id(prev),
                causes=causes,
                kind=kind,
            )
        )

    def _build(self, key, state_template, args_template, force_plain: bool = False,
               skip_cache_load: bool = False):
        acc = self.accelerator
        _, args_treedef = jax.tree_util.tree_flatten(args_template)
        captured_ctx = CaptureContext(
            owner_advances_accumulate=bool(self._uses_accumulate)
        )

        # Pin the carried state's layout to the layout it arrives with.
        # jax.jit caches on input *shardings* as well as shapes: left alone,
        # GSPMD picks arbitrary output layouts for the first step's new state
        # (e.g. a transposed spec for a weight grad), those feed back in as
        # call 2's inputs, and the whole program re-traces and re-compiles —
        # a second multi-minute XLA compile for byte-identical computation.
        # Constraining every output leaf to its input sharding makes the state
        # layout a fixed point from the first call.
        _NOPIN = object()

        def _leaf_sharding(x):
            s = getattr(x, "sharding", None)
            if not isinstance(s, jax.sharding.NamedSharding):
                return _NOPIN
            if _nondefault_memory(x):
                # host-offloaded leaves: with_sharding_constraint cannot pin
                # a non-default memory space on every backend — their
                # placement is re-established eagerly after each replay
                # (optim.reoffload_state_to_host), so leave them unpinned
                return _NOPIN
            return s

        ref_shardings = {
            k: jax.tree_util.tree_map(_leaf_sharding, state_template[k])
            for k in ("params", "buffers", "grads", "opt", "scaler", "comm")
            if state_template.get(k) is not None
        }

        def _pin_layout(new_state):
            pinned = dict(new_state)
            for k, shardings in ref_shardings.items():
                pinned[k] = jax.tree_util.tree_map(
                    lambda x, s: x if s is _NOPIN else jax.lax.with_sharding_constraint(x, s),
                    new_state[k],
                    shardings,
                )
            return pinned

        # Split the carried state by memory space: donation aliases input
        # buffers to outputs, which is illegal across memory spaces (a
        # pinned_host moment donated to — or passed through a micro-step
        # variant into — a device-resident output trips XLA's memory-kind
        # check at dispatch).  Donation is per-argument, so device leaves
        # (params/grads/masters — the big HBM win) keep aliasing and only
        # host-offloaded leaves ride a second, non-donated argument.
        flat_template, state_treedef = jax.tree_util.tree_flatten(state_template)
        host_mask = tuple(_is_offloaded(x) for x in flat_template)

        def traced(dev_leaves, host_leaves, *flat_args):
            dev_iter, host_iter = iter(dev_leaves), iter(host_leaves)
            flat = [next(host_iter) if h else next(dev_iter) for h in host_mask]
            state = jax.tree_util.tree_unflatten(state_treedef, flat)
            call_args = jax.tree_util.tree_unflatten(args_treedef, flat_args)
            prev_rng_state = nn_random.default_rng.get_state()
            prev_capture = _capture_state.active
            prev_acc_ctx = acc._capture_ctx
            _capture_state.active = captured_ctx
            acc._capture_ctx = captured_ctx
            # re-traces (e.g. after an input-layout change) must not double-
            # count python side effects recorded during a previous trace
            captured_ctx.begin_trace()
            try:
                self._bind_state(state)
                nn_random.default_rng.set_key(state["rng"])
                if self._telemetry is not None:
                    # HLO op metadata carries the scope name, so xprof's op
                    # profile groups the user's step body under one span
                    with jax.named_scope("atpu_captured_body"):
                        out = self.fn(*call_args)
                else:
                    out = self.fn(*call_args)
                out = _unwrap_tree(out)
                new_state = _pin_layout(self._snapshot_state())
                return new_state, out
            finally:
                _capture_state.active = prev_capture
                acc._capture_ctx = prev_acc_ctx
                nn_random.default_rng.set_state(prev_rng_state)

        jitted = jax.jit(traced, donate_argnums=(0,))
        tel = self._telemetry
        cache = self._aot_cache
        if (tel is not None or cache is not None) and not force_plain:
            # AOT capture: lower and compile explicitly so (a) trace vs
            # compile time are separately attributable, (b) the executable's
            # memory_analysis/cost_analysis are recordable at capture time,
            # (c) the compiled object is serializable into the persistent
            # executable cache (docs/aot_cache.md).  The compiled object is
            # call-compatible with the jitted one and honors the same
            # donation; the one behavioral difference (it *rejects* drifted
            # input layouts instead of silently re-tracing) is handled — and
            # surfaced as a telemetry event — in __call__.
            compiled = side = None
            aot_scope_map = None
            if cache is not None and not skip_cache_load:
                compiled, side = cache.load_captured(
                    self, key, state_template, host_mask
                )
            if compiled is not None:
                # zero-cold-start hit: the deserialized executable IS the
                # program the storing process compiled — no trace, no XLA
                # compile, telemetry's trace/compile phases read 0.  The
                # trace-time side metadata a skipped trace cannot rediscover
                # (accumulate use, deferred scheduler replays) is restored
                # from the entry.
                self._last_build_ms = (0.0, 0.0)
                captured_ctx.aot_loaded = True
                captured_ctx.used_accumulate = bool(side.get("uses_accumulate"))
                schedulers = acc._schedulers
                for replay in side.get("scheduler_replays", []):
                    captured_ctx.deferred_scheduler_steps.append(
                        (
                            schedulers[replay["index"]],
                            tuple(replay.get("args", ())),
                            dict(replay.get("kwargs", {})),
                        )
                    )
                label = f"capture:{self._builds_total}:aot"
                # deserialized executables carry no HLO metadata — adopt the
                # op→scope map the STORING process parsed, so warm samples
                # keep their per-phase device split (docs/aot_cache.md)
                aot_scope_map = side.get("scope_map")
            else:
                flat_state, _ = jax.tree_util.tree_flatten(state_template)
                dev_leaves = tuple(x for x, h in zip(flat_state, host_mask) if not h)
                host_leaves = tuple(x for x, h in zip(flat_state, host_mask) if h)
                flat_args, _ = jax.tree_util.tree_flatten(args_template)

                # the scopes are read back from this executable's text: its
                # cache key has to see them (profiler.scopes_in_cache_key).
                # The build's spans are the recorder's listener's (JAX's own
                # trace, lowering and compile events); its times are theirs
                with _profiler.scopes_in_cache_key():
                    with _flightrec.CompilePhases() as tracing:
                        lowered = jitted.lower(dev_leaves, host_leaves, *flat_args)
                    with _flightrec.CompilePhases() as compiling:
                        compiled = lowered.compile()
                self._last_build_ms = (tracing.ms, compiling.ms)
                label = f"capture:{self._builds_total}"
            self._builds_total += 1
            # scopes of this program for whoever reads a device trace later
            # (docs/telemetry.md §spans and scopes): only HOW to get the HLO
            # text is kept, the handle weakly; nothing is fetched or parsed
            # here.  A deserialized AOT executable has no metadata: it
            # brings the map its storing process parsed
            scopes = _profiler.register_program(
                "jit_traced", _profiler.compiled_text_fn(compiled),
                key=("captured", id(self), key_id(key)), scope_map=aot_scope_map,
                perishable=True,
            )
            if tel is not None:
                tel.record_program(key, label, compiled, scopes=scopes)
                if tel.resource_sampling:
                    tel.sample_resources(label)
            entry = (compiled, captured_ctx, state_treedef, host_mask)
        else:
            if tel is not None:
                # plain-jit fallback after repeated layout drift: the build
                # cost lands inside the first dispatch, so do not carry a
                # stale trace/compile split into this build's step record
                self._last_build_ms = (0.0, 0.0)
            entry = (jitted, captured_ctx, state_treedef, host_mask)
        self._cache[key] = entry
        return entry

    def _writeback(self, new_state: dict) -> None:
        acc = self.accelerator
        for m, params, buffers, grads in zip(
            acc._models, new_state["params"], new_state["buffers"], new_state["grads"]
        ):
            m.bind_params(params)
            m.bind_buffers(buffers)
            named = dict(m.named_parameters())
            for name, g in grads.items():
                named[name].grad = g
        for o, s in zip(acc._optimizers, new_state["opt"]):
            o.optimizer.bind_capture_state(s)
            # host-offloaded optimizer state (and, with param offload, the
            # params): the compiled program's outputs land in HBM; re-pin to
            # pinned_host so the saving is real and the next call's input
            # placement (and thus the jit cache key) stays fixed.  No-ops
            # unless offload was requested.
            o.optimizer.reoffload_state_to_host()
            o.optimizer.reoffload_params_to_host()
        if new_state.get("scaler") is not None and acc.scaler is not None:
            acc.scaler.bind_capture_state(new_state["scaler"])
        acc._bind_comm_hook_state(new_state.get("comm"))
