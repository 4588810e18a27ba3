"""Resilience subsystem (``accelerator.resilience``) — docs/resilience.md.

Four pillars, all default-OFF (off = byte-identical capture hot path, one
``None``-check, matching the telemetry precedent):

1. **Hardened backend init** (`backend.py`) — subprocess-isolated PJRT probe
   with retry/backoff/jitter and an opt-in platform fallback chain (none by
   default: the requested platform comes up or the init fails), emitting a
   structured :class:`~.backend.InitReport`.
2. **Preemption-safe checkpointing** (`preemption.py`) — SIGTERM/SIGINT set a
   sticky flag read via ``resilience.should_save`` / ``should_exit``
   (``check_trigger()``-style, collective on multi-process);
   :meth:`Resilience.drain` checkpoints through the existing async
   ``save_state``/``wait_for_checkpoint`` machinery so a preempted run always
   exits with a complete checkpoint.  An optional wall-clock deadline covers
   scheduled maintenance windows.
3. **Step retry with rollback** (`retry.py`) — transient dispatch failures
   are retried with bounded backoff; on exhaustion the last good checkpoint
   is restored and the step replayed against the same compiled program.
4. **Deterministic fault injection** (`inject.py`) — ``ACCELERATE_FAULT_PLAN``
   simulates init hangs, transient dispatch faults and mid-step SIGTERM so
   all of the above is testable on CPU.

Enable with ``ACCELERATE_RESILIENCE=1`` or
``Accelerator(kwargs_handlers=[ResilienceKwargs(enabled=True)])``.
"""

from __future__ import annotations

from typing import Optional

from .backend import InitReport, init_backend, probe_backend_once
from .inject import FaultInjector, FaultPlan, InjectedTransientError
from .preemption import PreemptionGuard
from .retry import StepRetrier, classify_failure


class Resilience:
    """Per-Accelerator resilience hub; inert when disabled."""

    def __init__(self, handler=None, telemetry=None):
        if handler is None:
            from ..utils.dataclasses import ResilienceKwargs

            handler = ResilienceKwargs()
        self.handler = handler
        self.enabled = bool(handler.enabled)
        # events always land here (tests / diagnostics need them with
        # telemetry off); they additionally flow into the telemetry export
        # stream as kind="resilience" records when telemetry is on
        self.telemetry = (
            telemetry if (telemetry is not None and getattr(telemetry, "enabled", False)) else None
        )
        self.events: list[dict] = []
        # the owning Accelerator's enabled Fleet hub, when the elastic fleet
        # runtime is armed (docs/elastic.md): the retrier consults it to
        # turn the historical multi-process rollback refusal into the
        # coordinated all-ranks restore protocol
        self.fleet = None
        self.injector: Optional[FaultInjector] = None
        self.guard: Optional[PreemptionGuard] = None
        self.retrier: Optional[StepRetrier] = None
        self.last_checkpoint: Optional[str] = None
        self.dispatch_calls = 0
        # preemption-poll memo: (dispatch_calls at poll time, result) — the
        # collective gather runs at most once per step even when the loop
        # reads both should_save and should_exit; a positive result is
        # sticky forever (the flags never un-trip)
        self._poll_cache: Optional[tuple[int, bool]] = None
        self._poll_resolved = False
        if not self.enabled:
            return
        self.injector = FaultInjector.from_spec(handler.fault_plan)
        if handler.preemption:
            self.guard = PreemptionGuard(
                deadline_s=handler.deadline_s, on_trigger=self._on_signal
            )
            self.guard.install()
        if handler.retry:
            self.retrier = StepRetrier(
                self,
                max_retries=handler.max_retries,
                backoff_s=handler.retry_backoff_s,
                rollback=handler.rollback,
            )
        # an init that ran before this hub existed (PartialState hardening)
        # still lands in the event stream; consumed on
        # pickup so a later hub in the same process doesn't re-emit a stale
        # report as its own
        from . import backend as _backend

        if _backend.LAST_INIT_REPORT is not None:
            self.record_event(**_backend.LAST_INIT_REPORT.to_event())
            _backend.LAST_INIT_REPORT = None

    # -- events --------------------------------------------------------------
    def record_event(self, event: str, **fields) -> dict:
        payload = {"event": event, **fields}
        self.events.append(payload)
        if self.telemetry is not None:
            self.telemetry.record_resilience(dict(payload))
        # scalar mirror into the flight ring: preemption / retry / rollback
        # phases are exactly what a postmortem needs, and the ring survives
        # where an unflushed telemetry JSONL does not (docs/telemetry.md)
        from ..telemetry import flightrec

        flightrec.record(
            "resilience",
            event=event,
            **{k: v for k, v in fields.items()
               if v is None or isinstance(v, (bool, int, float, str))},
        )
        return payload

    def _on_signal(self, signum: int) -> None:
        self.record_event(
            "preemption",
            signal=self.guard.signal_name if self.guard is not None else signum,
            dispatch_calls=self.dispatch_calls,
        )

    # -- capture-path hook ---------------------------------------------------
    def begin_dispatch(self) -> int:
        """Called by CapturedStep right before each dispatch; counts calls
        (the fault plan's step axis) and fires any scheduled SIGTERM."""
        index = self.dispatch_calls
        self.dispatch_calls += 1
        if self.injector is not None:
            self.injector.maybe_sigterm(index)
            self.injector.maybe_hang(index)
        return index

    # -- preemption flags ----------------------------------------------------
    def _poll(self) -> bool:
        if self._poll_resolved:
            return True  # sticky: a tripped flag never un-trips
        local = bool(
            self.guard is not None
            and (self.guard.triggered or self.guard.deadline_reached())
        )
        from ..state import PartialState

        if PartialState._shared_state and PartialState().num_processes > 1:
            # collective (check_trigger-style): ANY preempted rank means every
            # rank must drain — the save's gathers need all of them anyway.
            # Memoized per dispatch: reading should_save AND should_exit in
            # one loop iteration costs one gather, not two (every rank runs
            # the same loop, so the gather count stays aligned).
            if (
                self._poll_cache is not None
                and self._poll_cache[0] == self.dispatch_calls
            ):
                return self._poll_cache[1]
            from ..utils import operations as ops

            result = any(bool(flag) for flag in ops.gather_object([local]))
            self._poll_cache = (self.dispatch_calls, result)
        else:
            result = local
        if result:
            self._poll_resolved = True
        return result

    @property
    def should_save(self) -> bool:
        """True once a preemption signal landed or the deadline passed.
        Collective on multi-process — call it on every rank."""
        return self._poll()

    @property
    def should_exit(self) -> bool:
        """Alias flag for loop structure (save at should_save, break at
        should_exit); both read the same sticky trigger."""
        return self._poll()

    # -- checkpoint bookkeeping ----------------------------------------------
    def note_checkpoint(self, path: Optional[str]) -> None:
        """Record a durable checkpoint (rollback target).  Accelerator calls
        this after every successful ``save_state``."""
        if path:
            self.last_checkpoint = path

    def drain(self, accelerator, output_dir: Optional[str] = None) -> str:
        """Save a complete checkpoint NOW and block until it is durable —
        the preemption exit path.  Uses the async save machinery (prepare on
        the main thread, write on the writer, finalize on join) and returns
        the checkpoint directory."""
        target = output_dir or self.handler.checkpoint_dir
        out = accelerator.save_state(target, async_save=True)
        accelerator.wait_for_checkpoint()
        self.note_checkpoint(out)
        self.record_event(
            "drain",
            checkpoint=out,
            signal=self.guard.signal_name if self.guard is not None else None,
        )
        return out

    def close(self) -> None:
        """Restore signal handlers (end_training / test teardown)."""
        if self.guard is not None:
            self.guard.uninstall()


__all__ = [
    "FaultInjector",
    "FaultPlan",
    "InitReport",
    "InjectedTransientError",
    "PreemptionGuard",
    "Resilience",
    "StepRetrier",
    "classify_failure",
    "init_backend",
    "probe_backend_once",
]
