"""Pillar 3 — step retry with rollback.

A captured-step dispatch can fail for two very different reasons and they
must not be handled alike:

* **transient runtime faults** — the PJRT/XLA runtime path to the device
  hiccuped (UNAVAILABLE, DEADLINE_EXCEEDED, a dropped connection).
  The program and its inputs are fine; trying again is both safe and the
  right move.  Safe because of the donation guarantee the capture layer
  already relies on (capture.py ``_dispatch_aot``): argument validation —
  where these failures surface — happens BEFORE any buffer is donated, so a
  failed call leaves every donated leaf intact for the retry.
* **user/program errors** — a shape mismatch, a NaN assert, an OOM
  (RESOURCE_EXHAUSTED).  Retrying re-runs the same wrong program; these
  propagate immediately.

On retry exhaustion the step is rolled back: restore the last good
checkpoint (``Resilience.note_checkpoint`` records every successful
``save_state``), rebind the freshly restored state into the SAME compiled
entry (the cache key didn't change, so zero extra recompiles), and replay
the dispatch.  Every attempt/rollback is a kind-tagged telemetry event.

Two hard edges, handled explicitly:

* a fault that fires MID-EXECUTION (past argument validation) may already
  have consumed donated input buffers — re-invoking with the same leaves
  would die on "Array has been deleted".  The loop checks for deleted
  donated leaves before retrying and escalates straight to rollback (the
  restore rebinds fresh buffers) instead of burning retries it cannot win;
* rollback on a multi-process run needs COORDINATION: ``load_state`` is
  collective, and one rank restoring while its peers proceed to the next
  step's collectives would deadlock the mesh.  With the elastic fleet
  runtime armed (``accelerator.fleet``, docs/elastic.md) exhaustion enters
  the all-ranks restore protocol instead — every rank offers its visible
  complete checkpoints to a gather/vote barrier, all ranks agree on the
  newest all-ranks-visible restore point, and only then does every rank
  issue the collective ``load_state`` together (a dispatch fault is SPMD —
  it surfaces on every rank's dispatch of the same call, so all retriers
  exhaust and vote in lockstep).  Without the fleet, multi-process
  exhaustion propagates exactly as before.
"""

from __future__ import annotations

import random
import time
from typing import Callable, Optional

from .backend import backoff_delay
from .inject import InjectedTransientError

# substrings of transient PJRT/XLA status codes and transport failures; a
# dispatch error carrying one of these is worth retrying.  RESOURCE_EXHAUSTED
# (OOM) is deliberately absent — the same program will exhaust the same HBM.
TRANSIENT_MARKERS = (
    "unavailable",
    "deadline_exceeded",
    "deadline exceeded",
    "aborted",
    "cancelled",
    "connection reset",
    "socket closed",
    "failed to connect",
    "transient",
)

# errors that are the user's program talking, never the runtime flaking
_USER_ERROR_TYPES = (TypeError, ValueError, KeyError, AttributeError, AssertionError)


def _multi_process() -> bool:
    """Module-level so tests can pin the world-size read without touching
    the Borg PartialState."""
    from ..state import PartialState

    return bool(PartialState._shared_state and PartialState().num_processes > 1)


def classify_failure(exc: BaseException) -> str:
    """``"transient"`` (retry) or ``"user"`` (propagate)."""
    if isinstance(exc, InjectedTransientError):
        return "transient"
    if isinstance(exc, _USER_ERROR_TYPES):
        return "user"
    text = f"{type(exc).__name__}: {exc}".lower()
    if any(marker in text for marker in TRANSIENT_MARKERS):
        return "transient"
    return "user"


class StepRetrier:
    """Bounded-backoff retry around a captured-step dispatch, with one
    checkpoint rollback when retries run dry."""

    def __init__(
        self,
        hub,
        max_retries: int = 2,
        backoff_s: float = 0.5,
        backoff_cap_s: float = 8.0,
        jitter: float = 0.25,
        rollback: bool = True,
        sleep: Callable[[float], None] = time.sleep,
        rng: Optional[random.Random] = None,
    ):
        self.hub = hub
        self.max_retries = max(0, int(max_retries))
        self.backoff_s = float(backoff_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.jitter = float(jitter)
        self.rollback = bool(rollback)
        self.sleep = sleep
        self._rng = rng if rng is not None else random.Random()
        self.retries_total = 0
        self.rollbacks_total = 0
        # backoff sleep spent inside the most recent run_dispatch, so the
        # capture layer can split retry waits out of dispatch_ms (telemetry
        # StepRecord.retry_wait_ms) — retries must not inflate A/B timings
        self.last_wait_ms = 0.0

    def _delay(self, attempt: int) -> float:
        return backoff_delay(
            attempt, self.backoff_s, self.backoff_cap_s, self.jitter, self._rng
        )

    def _coordinator(self):
        """The enabled Fleet hub when this is a multi-process run that must
        (and can) coordinate its restore; None on single-process runs —
        where the local rollback needs no vote."""
        if not _multi_process():
            return None
        fleet = getattr(self.hub, "fleet", None)
        if fleet is not None and fleet.enabled and fleet.handler.coordinate_rollback:
            return fleet
        return None

    def _rollback_allowed(self) -> bool:
        if not self.rollback:
            return False
        if _multi_process():
            # load_state is collective; a single rank restoring while its
            # peers run the next step's collectives would hang the mesh —
            # only the fleet's all-ranks vote protocol makes it safe
            return self._coordinator() is not None
        return True

    def run_dispatch(self, step, dispatch, entry, dev_leaves, host_leaves, host_mask):
        """Drive ``dispatch(dev_leaves, host_leaves, entry)`` to completion.

        ``dispatch`` returns the capture layer's ``(new_state, out, entry,
        rebuilt)`` tuple.  ``step`` is the owning CapturedStep — needed to
        re-collect state after a rollback restore.  The injector's dispatch
        faults fire inside this loop so retries are exercised end-to-end.
        """
        hub = self.hub
        call_index = hub.dispatch_calls - 1  # begin_dispatch already counted
        attempt = 0
        rolled_back = False
        self.last_wait_ms = 0.0
        while True:
            try:
                if hub.injector is not None:
                    hub.injector.maybe_dispatch_fault(call_index)
                return dispatch(dev_leaves, host_leaves, entry)
            except Exception as exc:  # noqa: BLE001 — classified right below
                if classify_failure(exc) != "transient":
                    raise
                error = f"{type(exc).__name__}: {exc}"[:200]
                # a mid-execution fault may have consumed the donated input
                # buffers (validation-time faults never do) — re-invoking
                # with deleted leaves cannot succeed, so skip the retry
                # budget and go straight to the rollback decision
                consumed = any(
                    leaf.is_deleted()
                    for leaf in dev_leaves
                    if hasattr(leaf, "is_deleted")
                )
                if attempt < self.max_retries and not consumed:
                    delay = self._delay(attempt)
                    attempt += 1
                    self.retries_total += 1
                    hub.record_event(
                        "dispatch_retry",
                        step=call_index,
                        attempt=attempt,
                        max_retries=self.max_retries,
                        delay_s=round(delay, 3),
                        error=error,
                    )
                    t_sleep = time.perf_counter()
                    self.sleep(delay)
                    self.last_wait_ms += (time.perf_counter() - t_sleep) * 1e3
                    continue
                checkpoint = hub.last_checkpoint
                coordinator = self._coordinator()
                if (
                    not self._rollback_allowed()
                    or rolled_back
                    or (checkpoint is None and coordinator is None)
                ):
                    hub.record_event(
                        "dispatch_exhausted",
                        step=call_index,
                        attempts=attempt + 1,
                        rolled_back=rolled_back,
                        donated_consumed=consumed,
                        error=error,
                    )
                    raise
                if coordinator is not None:
                    # coordinated restore (docs/elastic.md): all ranks reach
                    # this vote together (the fault is SPMD), agree on the
                    # newest all-ranks-visible complete checkpoint, and only
                    # then issue the collective load_state below in lockstep
                    from ..fleet.coordinate import vote_restore_point

                    agreed = vote_restore_point(
                        step.accelerator, fleet=coordinator
                    )
                    if agreed is None:
                        hub.record_event(
                            "dispatch_exhausted",
                            step=call_index,
                            attempts=attempt + 1,
                            rolled_back=False,
                            donated_consumed=consumed,
                            error=error,
                            restore_vote="no all-ranks-visible checkpoint",
                        )
                        raise
                    checkpoint = agreed["path"]
                # rollback: restore the last good checkpoint and replay this
                # call against the SAME compiled entry — the cache key is a
                # function of arg shapes and flags, none of which the restore
                # moved, so the replay costs zero recompiles
                self.rollbacks_total += 1
                hub.record_event(
                    "rollback",
                    step=call_index,
                    checkpoint=checkpoint,
                    coordinated=coordinator is not None,
                    donated_consumed=consumed,
                    error=error,
                )
                # zero-cold-start coupling: load_state warms the AOT
                # executable cache before restoring, so even a rollback that
                # somehow lost the in-memory entry (a state-structure change
                # popped it) replays the serialized executable instead of
                # recompiling; record how many entries the warm staged
                cache = getattr(step.accelerator, "aot_cache", None)
                step.accelerator.load_state(checkpoint)
                if coordinator is not None:
                    # the collective restore landed on every rank — the
                    # event docs/elastic.md promises operators can grep for
                    coordinator.record_event(
                        "coordinated_rollback",
                        checkpoint=checkpoint,
                        dispatch_index=call_index,
                    )
                if cache is not None and cache.enabled and cache.warm_on_restore:
                    # warm_on_restore off means load_state ran NO prefetch —
                    # reporting a stale count would claim a warm that never
                    # happened on this restore
                    hub.record_event(
                        "aot_cache_warm",
                        step=call_index,
                        entries=cache.last_prefetch_count,
                    )
                import jax

                flat_state, _ = jax.tree_util.tree_flatten(step._collect_state())
                dev_leaves = tuple(
                    x for x, h in zip(flat_state, host_mask) if not h
                )
                host_leaves = tuple(x for x, h in zip(flat_state, host_mask) if h)
                rolled_back = True
                attempt = 0
