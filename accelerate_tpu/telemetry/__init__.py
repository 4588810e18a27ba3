"""Runtime telemetry for the capture path (``accelerator.telemetry``).

Four pillars, all default-OFF and zero-overhead when off:

1. **Step-phase timing** (`timeline.py`) — every ``CapturedStep.__call__``
   records dataloader-wait / assembly / trace / compile / dispatch ms into a
   ring-buffered :class:`~.timeline.StepTimeline`.  The phases' spans
   (``atpu/step/assemble``, ``atpu/dispatch``, ``atpu/step/writeback``) are
   the flight recorder's (pillar 8): always on, and visible in an xprof trace
   collected through ``accelerator.profile()``.  A build's trace and compile
   times are the recorder's own compile-phase spans (``atpu/trace``,
   ``atpu/lower``, ``atpu/compile``), which its listener writes for every
   program JAX builds in the process.
2. **Recompile forensics** (`recompile.py`) — every new compiled variant is
   diffed against the previous cache key and emits a
   :class:`~.recompile.RecompileEvent` naming exactly what moved (arg
   shape/dtype, treedef, ``sync_gradients``, training mode, state structure /
   donation split, input-layout drift).
3. **Resource accounting** (`resources.py`) — per-device live HBM bytes from
   ``jax.live_arrays()`` plus per-program ``memory_analysis()`` /
   ``cost_analysis()`` (FLOPs, bytes accessed, collective bytes) sampled at
   capture and on demand.
4. **Export** (`export.py`) — events flow to the existing ``GeneralTracker``
   fleet through :class:`TelemetryTracker`, or to a schema'd JSONL file that
   ``tools/telemetry_report.py`` renders.
5. **Device-time attribution** (`profiler.py`) — every Nth step
   (``TelemetryKwargs(profile_every_n=...)``, default off) the dispatch runs
   inside a ``jax.profiler`` trace session parsed into a
   :class:`~.profiler.DeviceStepRecord` (per-device busy/idle,
   compute/collective/transfer split, top ops, MFU), joined 1:1 to the
   host-side ``StepRecord`` by step index.
6. **Fleet aggregation** (`aggregate.py`) — rank-0 ``gather_object`` merge
   of every hub's records with per-rank skew statistics
   (``Telemetry.aggregate_fleet``, collective; ``end_training`` calls it on
   multi-process runs so the JSONL dump is fleet-wide).
7. **Live metrics endpoint** (`metrics.py`) — a stdlib HTTP thread serving
   Prometheus text (``TelemetryKwargs(metrics_port=...)`` /
   ``Telemetry.serve_metrics()``): step-phase timings, recompile/fault
   counters, collective bytes, device-time gauges, and any registered
   provider (the decode service self-registers its ``metrics()`` snapshot).
8. **Black-box forensics** (`flightrec.py` / `watchdog.py` /
   `trace_export.py`) — the flight recorder is the ONE exception to the
   default-off convention: an always-on, bounded, per-process ring of
   instants (step dispatches, collective-sequence ticks, fleet/serving/
   checkpoint phases) and spans (the capture call's and the engine step's
   host phases, on the profiler's clock) that the default-off hang
   watchdog dumps — with faulthandler
   stacks — to a per-rank JSON on stall/signal/exit, and
   ``tools/blackbox_report.py`` merges across ranks by collective sequence
   number.  ``trace_export.py`` joins the ring with the host/device step
   records into one Chrome/Perfetto timeline.

Enable with ``ACCELERATE_TELEMETRY=1`` or
``Accelerator(kwargs_handlers=[TelemetryKwargs(enabled=True)])``.  With the
knob off (the default), ``CapturedStep.__call__`` executes the identical code
path as before this subsystem existed — the only cost anywhere is a
``None``-check.  Docs: docs/telemetry.md.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Optional

from .profiler import DeviceStepRecord
from .recompile import RecompileEvent, diff_keys, key_id
from .resources import (
    CollectiveRecord,
    KernelRecord,
    ProgramRecord,
    ResourceSample,
    program_stats,
    sample_live,
)
from .timeline import PHASES, StepRecord, StepTimeline

SCHEMA_VERSION = 1

# the active enabled Telemetry instance — fallback wait-time sink for data
# loaders never prepared through an Accelerator (prepared loaders carry a
# pinned hub instead); None when telemetry is off — every producer-side hook
# is gated on that None
_ACTIVE: Optional["Telemetry"] = None


def current_telemetry() -> Optional["Telemetry"]:
    return _ACTIVE


def _set_active(telemetry: Optional["Telemetry"]) -> None:
    global _ACTIVE
    _ACTIVE = telemetry


class Telemetry:
    """Per-Accelerator telemetry hub; the enabled instance is also published
    module-wide for producers (data loader) that have no accelerator handle."""

    def __init__(self, handler=None):
        if handler is None:
            from ..utils.dataclasses import TelemetryKwargs

            handler = TelemetryKwargs()
        self.enabled = bool(handler.enabled)
        self.resource_sampling = bool(handler.sample_resources)
        self.jsonl_path = handler.jsonl_path
        self.timeline = StepTimeline(capacity=handler.timeline_size)
        self.recompile_events: deque[RecompileEvent] = deque(maxlen=handler.max_events)
        self.program_records: deque[ProgramRecord] = deque(maxlen=handler.max_events)
        self.resource_samples: deque[ResourceSample] = deque(maxlen=handler.max_events)
        # per-policy dp-collective-bytes attribution (parallel/compress.py),
        # recorded at prepare() time — the bench A/B denominator
        self.collective_records: deque[CollectiveRecord] = deque(
            maxlen=handler.max_events
        )
        # resilience subsystem events (init/retry/rollback/preemption),
        # already kind-tagged dicts — see resilience/__init__.py
        self.resilience_events: deque[dict] = deque(maxlen=handler.max_events)
        # serving subsystem events (per-step occupancy/queue depth, per-
        # request TTFT/TPOT completions) — see serving/scheduler.py
        self.serving_events: deque[dict] = deque(maxlen=handler.max_events)
        # serving fault-tolerance events (decode retry, requeue, drain,
        # resume, recovered admissions) — see serving/recovery.py and
        # docs/serving.md §fault tolerance
        self.serving_recovery_events: deque[dict] = deque(maxlen=handler.max_events)
        # AOT executable cache events (hit/miss/store/warm with cause,
        # bytes, load vs avoided compile ms) — see native/aot_cache.py
        self.aot_cache_events: deque[dict] = deque(maxlen=handler.max_events)
        # elastic fleet runtime events (host_lost/restore_vote/resize,
        # kind="fleet_event") plus the periodic mid-run skew records
        # (kind="fleet") the aggregate cadence appends — see fleet/
        self.fleet_events: deque[dict] = deque(maxlen=handler.max_events)
        # armed Pallas hot-path kernels (docs/kernels.md), recorded at
        # prepare() like the collective-bytes attribution
        self.kernel_records: deque[KernelRecord] = deque(maxlen=handler.max_events)
        # compiled-variant key id -> {hlo op name -> atpu phase}: parsed
        # from the program's HLO metadata at build when sampling is armed,
        # joined by record_device_step into the per-phase device split
        self._scope_maps: dict = {}
        # native Prometheus histogram of replay step latency (metrics.py):
        # cumulative _bucket series for the endpoint instead of
        # point-in-time percentiles; observation is two int bumps per step
        from .metrics import LatencyHistogram

        self.step_hist = LatencyHistogram()
        # sampled device-time attribution (profiler.py): a DeviceStepRecord
        # per sampled step, joined to the host StepRecord by step index;
        # profiler is None unless the cadence knob armed it — the unsampled
        # hot path pays one None-check in CapturedStep.__call__
        self.profile_every_n = int(getattr(handler, "profile_every_n", 0) or 0)
        self.device_records: deque[DeviceStepRecord] = deque(
            maxlen=handler.max_events
        )
        self.profiler = None
        if self.enabled and self.profile_every_n > 0:
            from .profiler import StepProfiler

            profile_dir = getattr(handler, "profile_dir", None)
            self.profiler = StepProfiler(
                self.profile_every_n,
                base_dir=profile_dir,
                # a user-pinned dir means they want the raw traces on disk;
                # the default tempdir traces are deleted after parsing
                keep_traces=profile_dir is not None,
            )
        self.recompiles_total = 0
        self.steps_total = 0
        # fleet aggregation (aggregate.py): set by aggregate_fleet() on the
        # main rank — the JSONL dump then describes every rank, not one
        self._fleet_records: Optional[list] = None
        # first step index NOT yet covered by a periodic fleet tick: each
        # tick gathers only newer replay records, so the collective payload
        # is the delta and the skew record describes the CURRENT window
        self._fleet_agg_mark = 0
        # live metrics endpoint (metrics.py): providers registered here are
        # rendered by whatever MetricsServer is attached to this hub
        self._metrics_providers: list = []
        # /healthz readiness sources (metrics.py §healthz): fn() -> dict
        # with a "ready" bool; the endpoint ANDs them into one 200/503
        self._health_providers: list = []
        self.metrics_server = None
        self._dataloader_wait_ms = 0.0
        # wait that batches consumed OUTSIDE any captured step incurred
        # (eager eval epochs, early-broken loops) — discarded from step
        # attribution at loader-epoch end instead of dumped onto the next
        # captured step's record (docs/telemetry.md)
        self.eager_dataloader_wait_ms = 0.0
        self._wait_by_owner: dict = {}
        # export queue: every record lands here once, drained by the
        # TelemetryTracker bridge / flush(); bounded so an undrained run
        # cannot grow without limit.  Only the bridge consumes it, so
        # enqueueing (and the per-record to_dict()) is skipped entirely
        # until one attaches — sink-less runs like bench's primary loop pay
        # zero per-step export work (ROADMAP item)
        self._export_queue: deque[dict] = deque(maxlen=4096)
        self._export_sink = False
        self._drains_total = 0
        # latest-constructed wins the module slot: a later telemetry-off
        # Accelerator must clear it, or its data loaders keep crediting
        # wait time to the previous run's (possibly defunct) instance
        displaced = _ACTIVE
        _set_active(self if self.enabled else None)
        # black-box forensics (flightrec.py/watchdog.py): the recorder is
        # process-global and always-on; the watchdog arms from its knob
        # INDEPENDENTLY of `enabled` — hang forensics must not require the
        # full telemetry pipeline (docs/telemetry.md §watchdog)
        from . import flightrec as _flightrec

        self.flightrec = _flightrec.recorder()
        self.watchdog = None
        self.trace_export_path = getattr(handler, "trace_export_path", None)
        watchdog_s = getattr(handler, "watchdog_s", None)
        if watchdog_s:
            from .watchdog import HangWatchdog

            self.watchdog = HangWatchdog(
                timeout_s=watchdog_s,
                dump_dir=getattr(handler, "blackbox_dir", None) or "blackbox",
                recorder=self.flightrec,
            ).start()
        metrics_port = getattr(handler, "metrics_port", None)
        if self.enabled and metrics_port is not None:
            if displaced is not None and displaced.metrics_server is not None:
                # latest-constructed wins the endpoint too: the displaced
                # hub's server (typically on the same env-pinned port) would
                # otherwise squat the bind and serve frozen counters for the
                # rest of the process
                displaced.close_metrics()
            self.serve_metrics(port=metrics_port)

    # -- producers -----------------------------------------------------------
    def record_dataloader_wait(self, ms: float, owner=None) -> None:
        """Host time a loader spent producing one batch.  ``owner`` (the
        loader) keys the batch-scoped attribution: wait still pending when
        that loader's epoch ends was incurred by batches no captured step
        consumed, and is discarded rather than billed to the next step."""
        self._dataloader_wait_ms += ms
        if owner is not None:
            self._wait_by_owner[owner] = self._wait_by_owner.get(owner, 0.0) + ms

    def pop_dataloader_wait_ms(self) -> float:
        ms, self._dataloader_wait_ms = self._dataloader_wait_ms, 0.0
        if self._wait_by_owner:
            self._wait_by_owner.clear()
        return ms

    def discard_dataloader_wait(self, owner) -> float:
        """Epoch-end settlement for one loader: whatever wait it recorded
        that no captured step popped belongs to batches consumed *outside*
        the capture path (an eager eval epoch, an early-broken loop) — move
        it to ``eager_dataloader_wait_ms`` so the next captured step's
        record shows only its own batch's wait (docs/telemetry.md)."""
        ms = self._wait_by_owner.pop(owner, 0.0)
        if ms:
            self._dataloader_wait_ms = max(0.0, self._dataloader_wait_ms - ms)
            self.eager_dataloader_wait_ms += ms
        return ms

    def next_step_index(self) -> int:
        """Global captured-call counter (across every CapturedStep)."""
        index = self.steps_total
        self.steps_total += 1
        return index

    def record_step(self, record: StepRecord) -> None:
        self.timeline.append(record)
        if not record.built:
            # replay latencies only: a build's trace+compile would park the
            # whole histogram mass in the top bucket and say nothing about
            # the steady state the SLO cares about
            self.step_hist.observe(record.total_ms)
        if self._export_sink:
            self._export_queue.append(record.to_dict())

    def record_recompile(self, event: RecompileEvent) -> None:
        self.recompiles_total += 1
        self.recompile_events.append(event)
        if self._export_sink:
            self._export_queue.append(event.to_dict())

    def record_program(self, key, label: str, compiled, scopes=None) -> ProgramRecord:
        """``scopes`` is the program's entry in the process-wide registry
        (``profiler.register_program``); the per-phase join of a sampled
        step asks it for the op→scope map, which is the first time the HLO
        text is fetched and parsed."""
        record = ProgramRecord(key=key_id(key), label=label, stats=program_stats(compiled))
        self.program_records.append(record)
        if scopes is not None:
            self._scope_maps[record.key] = scopes
            if len(self._scope_maps) > len(self.program_records) + 8:
                # the deque rolls old program records off at max_events;
                # maps for rolled-off variants must roll too (each holds
                # thousands of op names — a churning long-lived process
                # would otherwise leak them for its lifetime)
                live = {p.key for p in self.program_records}
                for stale in [k for k in self._scope_maps if k not in live]:
                    del self._scope_maps[stale]
        if self._export_sink:
            self._export_queue.append(record.to_dict())
        return record

    def record_kernel(self, payload: dict) -> None:
        """Armed Pallas-kernel attribution (docs/kernels.md), kind-tagged
        ``"kernel"`` into the retained history and export stream — one
        record per armed kernel, written at ``prepare()``."""
        if not self.enabled:
            return
        stats = dict(payload)
        record = KernelRecord(kernel=stats.pop("kernel", "?"), stats=stats)
        self.kernel_records.append(record)
        if self._export_sink:
            self._export_queue.append(record.to_dict())

    def record_collectives(self, summary: dict) -> CollectiveRecord:
        """dp-axis collective-bytes attribution for one optimizer's update
        (``parallel.compress.collective_bytes`` output), kind-tagged
        ``"collectives"`` into the retained history and export stream."""
        stats = dict(summary)
        record = CollectiveRecord(policy=stats.pop("policy", "none"), stats=stats)
        self.collective_records.append(record)
        if self._export_sink:
            self._export_queue.append(record.to_dict())
        return record

    def record_resilience(self, payload: dict) -> None:
        """Resilience event (init report, dispatch retry, rollback,
        preemption, drain) — kind-tagged into the same retained history and
        export stream as the capture-path records."""
        if not self.enabled:
            return
        record = dict(payload)
        record["kind"] = "resilience"
        self.resilience_events.append(record)
        if self._export_sink:
            self._export_queue.append(dict(record))

    def record_serving(self, payload: dict) -> None:
        """Serving event (step occupancy, request completion, admission
        stall) from the decode service — kind-tagged ``"serving"`` into the
        same retained history and export stream as the capture records."""
        if not self.enabled:
            return
        record = dict(payload)
        record["kind"] = "serving"
        self.serving_events.append(record)
        if self._export_sink:
            self._export_queue.append(dict(record))

    def record_serving_recovery(self, payload: dict) -> None:
        """Serving fault-tolerance event (decode retry, exhaustion
        requeue, preemption drain, journal resume, recovered admission)
        from the decode service — kind-tagged ``"serving_recovery"`` into
        the same retained history and export stream as the capture records
        (docs/serving.md §fault tolerance)."""
        if not self.enabled:
            return
        record = dict(payload)
        record["kind"] = "serving_recovery"
        self.serving_recovery_events.append(record)
        if self._export_sink:
            self._export_queue.append(dict(record))

    def record_aot_cache(self, payload: dict) -> None:
        """AOT executable cache event (hit/miss/store/warm with cause,
        bytes, load_ms vs avoided compile_ms) — kind-tagged ``"aot_cache"``
        into the same retained history and export stream as the capture
        records (docs/aot_cache.md)."""
        if not self.enabled:
            return
        record = dict(payload)
        record["kind"] = "aot_cache"
        self.aot_cache_events.append(record)
        if self._export_sink:
            self._export_queue.append(dict(record))

    def record_fleet(self, payload: dict) -> None:
        """Elastic-fleet record: hub events (host_lost, restore_vote,
        resize, ...) default to ``kind="fleet_event"``; the periodic
        aggregation cadence passes ready-made ``kind="fleet"`` skew records
        through unchanged (docs/elastic.md)."""
        if not self.enabled:
            return
        record = dict(payload)
        record.setdefault("kind", "fleet_event")
        self.fleet_events.append(record)
        if self._export_sink:
            self._export_queue.append(dict(record))

    def record_device_step(self, record: DeviceStepRecord) -> DeviceStepRecord:
        """Sampled device-time record from the profiler: join the program's
        analytic FLOPs (``cost_analysis`` recorded at build) by variant key
        and derive MFU where a per-chip peak is known, then retain/export
        like every other kind."""
        if record.flops is None:
            for program in reversed(self.program_records):
                if program.key == record.key:
                    flops = program.stats.get("flops")
                    if isinstance(flops, (int, float)) and flops > 0:
                        record.flops = float(flops)
                    break
        if record.mfu is None and record.flops:
            from .profiler import derive_mfu

            record.mfu = derive_mfu(
                record.flops, record.window_ms, n_devices=len(record.devices)
            )
        if not record.phases:
            # per-phase split (docs/telemetry.md): join the sampled op
            # durations to the variant's op->scope map so the
            # compute/collective split reads per atpu phase, not one
            # whole-step window.  Fail-soft: no map (pre-build sample,
            # metadata-less backend) leaves phases empty.
            scopes = self._scope_maps.get(record.key)
            scope_map = scopes.scope_map() if scopes is not None and record.op_detail else None
            if scope_map:
                from .profiler import split_phases

                record.phases = split_phases(record.op_detail, scope_map)
        self.device_records.append(record)
        if self._export_sink:
            self._export_queue.append(record.to_dict())
        return record

    def rekey_last_device_step(self, new_key: str) -> None:
        """Re-key the most recent device-step record (and its pending export
        dict) — the first-call accumulate re-file moves the program record to
        the traced sync flag's key, and a sampled first call must follow or
        its device_step↔program join dangles."""
        if not self.device_records:
            return
        record = self.device_records[-1]
        old_key = record.key
        record.key = new_key
        for pending in reversed(self._export_queue):
            if pending.get("kind") == "device_step" and pending.get("key") == old_key:
                pending["key"] = new_key
                break

    def rekey_last_program(self, new_key: str) -> None:
        """Re-key the most recent program record (and its not-yet-drained
        export dict) — the capture path calls this when a first-call
        accumulate re-files the variant under the traced sync flag, so the
        per-program HBM/FLOP stats join to the right variant."""
        if not self.program_records:
            return
        record = self.program_records[-1]
        old_key = record.key
        record.key = new_key
        if old_key in self._scope_maps:
            # the per-phase join keys on the same variant id — follow the
            # re-file or the next sample of this variant loses its split
            self._scope_maps[new_key] = self._scope_maps.pop(old_key)
        for pending in reversed(self._export_queue):
            if pending.get("kind") == "program" and pending.get("key") == old_key:
                pending["key"] = new_key
                break

    def sample_resources(self, tag: str) -> ResourceSample:
        """Per-device live-bytes snapshot, on demand or at capture time."""
        sample = sample_live(tag)
        self.resource_samples.append(sample)
        if self._export_sink:
            self._export_queue.append(sample.to_dict())
        return sample

    # -- consumers -----------------------------------------------------------
    def attach_export_sink(self) -> None:
        """Called by the TelemetryTracker bridge: start feeding the export
        queue, and backfill it with the retained history recorded before the
        bridge existed (records were not enqueued then — sink-less gating)."""
        if self._export_sink:
            return
        self._export_sink = True
        if self._drains_total == 0 and not self._export_queue:
            for record in self.all_records():
                if record.get("kind") in (
                    "step", "recompile", "program", "collectives",
                    "resources", "resilience", "serving", "serving_recovery",
                    "device_step", "aot_cache", "fleet", "fleet_event",
                    "kernel", "autopilot",
                ):
                    self._export_queue.append(record)

    def drain(self) -> list[dict]:
        """Pop every not-yet-exported record (tracker-bridge feed)."""
        self._drains_total += 1
        out = list(self._export_queue)
        self._export_queue.clear()
        return out

    def summary(self) -> dict:
        out = self.timeline.summary()
        out["recompiles_total"] = self.recompiles_total
        out["schema_version"] = SCHEMA_VERSION
        out["eager_dataloader_wait_ms"] = round(self.eager_dataloader_wait_ms, 3)
        if self.aot_cache_events:
            events = list(self.aot_cache_events)
            out["aot_cache_hits"] = sum(1 for e in events if e.get("event") == "hit")
            out["aot_cache_misses"] = sum(
                1 for e in events if e.get("event") == "miss"
            )
        if self.device_records:
            records = list(self.device_records)
            out["device_samples"] = len(records)
            out["device_busy_ms_mean"] = round(
                sum(r.busy_ms for r in records) / len(records), 3
            )
            out["device_collective_share_mean"] = round(
                sum(r.collective_share for r in records) / len(records), 4
            )
        # flight-recorder health rides the summary record so a JSONL dump
        # documents whether the black box was recording (and how full)
        out["flightrec"] = self.flightrec.health()
        return out

    def all_records(self) -> list[dict]:
        """Full retained history in schema order (JSONL dump feed)."""
        records: list[dict] = [
            {
                "kind": "meta",
                "schema_version": SCHEMA_VERSION,
                "time": time.time(),
                "steps_total": self.steps_total,
                "recompiles_total": self.recompiles_total,
            }
        ]
        records += [r.to_dict() for r in self.timeline.records()]
        records += [d.to_dict() for d in self.device_records]
        records += [e.to_dict() for e in self.recompile_events]
        records += [p.to_dict() for p in self.program_records]
        records += [c.to_dict() for c in self.collective_records]
        records += [k.to_dict() for k in self.kernel_records]
        records += [s.to_dict() for s in self.resource_samples]
        records += [dict(e) for e in self.resilience_events]
        records += [dict(e) for e in self.serving_events]
        records += [dict(e) for e in self.serving_recovery_events]
        records += [dict(e) for e in self.aot_cache_events]
        records += [dict(e) for e in self.fleet_events]
        records.append(self.summary())
        return records

    def export_records(self) -> list[dict]:
        """What the JSONL dump writes: the fleet-merged view when
        ``aggregate_fleet`` ran (every record rank-tagged + the skew
        record), the rank-local history otherwise."""
        if self._fleet_records is not None:
            return self._fleet_records
        return self.all_records()

    def aggregate_fleet(self, periodic: bool = False) -> Optional[list[dict]]:
        """COLLECTIVE — every process must call (``end_training`` does on
        multi-process runs; the fleet hub's cadence does mid-run; safe and
        communication-free on one).  Gathers all ranks' retained records to
        the main process, rank-tags them, and appends the ``kind="fleet"``
        skew record; the main process also caches the merge so
        ``write_jsonl`` dumps the fleet view.  Returns the merged records
        on main, ``None`` elsewhere.

        ``periodic=True`` is the mid-run mode (docs/elastic.md): instead of
        freezing the final fleet dump, the skew/straggler record is
        computed and RETAINED (``record_fleet``) on EVERY rank — the
        allgather hands each rank the identical ballot, so each computes
        the identical record deterministically.  That symmetry is what
        makes the record usable as an *autoscaler input*: every rank's
        autopilot evaluates the same signal window and reaches the same
        resize decision at the same dispatch (rank-divergent signals would
        deadlock the collective resize).  Returns ``[skew_record]``."""
        from .aggregate import fleet_skew, gather_fleet, merge_rank_records

        if periodic:
            # mid-run payload discipline: only the replay step records the
            # skew summary consumes ride the collective, and only the DELTA
            # since the previous tick — re-gathering the whole retained
            # history every tick would pickle O(window × ranks) per tick
            # and dilute the "current straggler" signal with steps an
            # earlier tick already described
            from ..utils.operations import gather_object

            mark = self._fleet_agg_mark
            local = [
                r.to_dict()
                for r in self.timeline.records()
                if not r.built and r.step >= mark
            ]
            self._fleet_agg_mark = self.steps_total
            # NOT gather_fleet (which nulls non-main ranks): every rank
            # keeps the full gather and derives the same pure skew record
            per_rank = gather_object([local])
            skew = fleet_skew(per_rank)
            skew["periodic"] = True
            skew["at_step"] = self.steps_total
            skew["window_from_step"] = mark
            self.record_fleet(skew)
            return [skew]
        per_rank = gather_fleet(self.all_records())
        if per_rank is None:
            return None
        self._fleet_records = merge_rank_records(per_rank)
        return self._fleet_records

    # -- metrics endpoint ----------------------------------------------------
    def register_metrics_provider(self, name: str, fn) -> str:
        """Attach a live snapshot source (``fn() -> dict``) to whatever
        MetricsServer serves this hub; same-name re-registration replaces
        (latest service wins)."""
        from .metrics import register_provider

        return register_provider(self._metrics_providers, name, fn)

    def register_health_provider(self, name: str, fn) -> str:
        """Attach a readiness source (``fn() -> dict`` with a ``"ready"``
        bool) to whatever MetricsServer serves this hub's ``/healthz``;
        same-name re-registration replaces (latest service wins)."""
        from .metrics import register_provider

        return register_provider(self._health_providers, name, fn)

    def serve_metrics(self, port: int = 0, host: str = "127.0.0.1"):
        """Start (or return) the hub's Prometheus endpoint — idempotent;
        ``port=0`` binds ephemerally (read ``.port`` back).  A bind failure
        warns and returns ``None``: observability must not kill the job."""
        if self.metrics_server is not None:
            return self.metrics_server
        from .metrics import MetricsServer

        try:
            self.metrics_server = MetricsServer(
                telemetry=self, port=port, host=host
            ).start()
        except (OSError, OverflowError, ValueError) as exc:
            # OSError: port in use / denied; OverflowError/ValueError: an
            # out-of-range or malformed port — same contract for all three
            from ..logging import get_logger

            get_logger(__name__).warning(
                "metrics endpoint failed to bind %s:%s: %s", host, port, exc
            )
            return None
        return self.metrics_server

    def close_metrics(self) -> None:
        server, self.metrics_server = self.metrics_server, None
        if server is not None:
            server.close()

    def close_watchdog(self) -> None:
        watchdog, self.watchdog = self.watchdog, None
        if watchdog is not None:
            watchdog.stop()

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write the joined Chrome/Perfetto timeline (trace_export.py) when
        a path is configured or given; fail-soft ``None`` otherwise."""
        path = path or self.trace_export_path
        if path is None:
            return None
        from .trace_export import export_chrome_trace

        return export_chrome_trace(path, telemetry=self, recorder=self.flightrec)

    def write_jsonl(self, path: Optional[str] = None) -> Optional[str]:
        from .export import write_jsonl

        path = path or self.jsonl_path
        if path is None:
            return None
        from ..state import PartialState

        if PartialState._shared_state and not PartialState().is_main_process:
            # one writer per run: every process resolves the same path, and
            # concurrent mode-'w' writers would interleave a corrupt dump
            return None
        try:
            return write_jsonl(self, path)
        except OSError as exc:
            # telemetry is best-effort: a bad dump path (missing dir,
            # permissions) must not crash end_training or leave the
            # remaining trackers unfinished
            from ..logging import get_logger

            get_logger(__name__).warning(
                "telemetry JSONL dump to %r failed: %s", path, exc
            )
            return None


def __getattr__(name):
    # lazy: export.py imports tracking.py (the tracker fleet), which must not
    # load just because the data loader imported this package for the
    # current_telemetry() gate
    if name == "TelemetryTracker":
        from .export import TelemetryTracker

        return TelemetryTracker
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "PHASES",
    "CollectiveRecord",
    "DeviceStepRecord",
    "ProgramRecord",
    "RecompileEvent",
    "ResourceSample",
    "SCHEMA_VERSION",
    "StepRecord",
    "StepTimeline",
    "Telemetry",
    "TelemetryTracker",
    "current_telemetry",
    "diff_keys",
    "key_id",
    "program_stats",
]
