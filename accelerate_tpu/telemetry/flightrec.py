"""Black-box flight recorder (docs/telemetry.md §flight recorder).

A bounded, lock-cheap, per-process ring of structured events that is ON BY
DEFAULT — the one deliberate exception to the telemetry package's
default-off convention, because a recorder that must be switched on before
the hang is not a flight recorder.  Producers across the stack append
events the postmortem tooling needs to reconstruct *what the process was
doing when it stopped*:

* captured-step dispatch begin/end with the global step index
  (``capture.py``);
* a **collective-sequence counter** tick at every host collective —
  ``gather`` / ``gather_object`` / ``broadcast`` / ``reduce``
  (``utils/operations.py``) and every ``agree_*`` merge
  (``fleet/coordinate.py``) — the cross-rank alignment key
  ``tools/blackbox_report.py`` joins dumps on;
* stagewise 1F1B tick dispatch (``parallel/stagewise.py``);
* fleet vote / rendezvous / resize phases (``fleet/``);
* serving admissions and decode windows (``serving/``);
* checkpoint and AOT-store I/O (``checkpointing.py``, ``native/aot_cache.py``);
* set-up: ``Accelerator.prepare`` and ``DecodeService.__init__`` as spans.

Each event is stamped with ``time.monotonic_ns()`` and a per-process
sequence number; the rank is resolved lazily at dump time (recording must
work before — and during — distributed init).  The ring is a preallocated
slot list guarded by one tiny critical section per append (~100 ns
uncontended, far under the ≤1 % of ``step_ms`` budget the bench A/B row
asserts); when it wraps, the oldest events are overwritten and ``dropped``
counts them.

**Spans** (docs/telemetry.md §spans and scopes) share the ring with the
instants: :meth:`FlightRecorder.span` is a context manager that stamps its
entry and exit and appends ONE slot ``(seq, start, name, fields, dur)`` when
it closes; it also enters a ``jax.profiler.TraceAnnotation`` of the same
name, so an xprof trace shows what the ring's readers see.  It is the
package's one span mechanism.  Because a span is written when it closes,
the instants that mark work *about to start* (``step_begin``,
``decode_window``) stay instants: in a hang they are the proof of where the
process stopped.

**Compile phases and host pauses** are the recorder's own producers,
registered once when the process recorder is made, and only when it is on:
a ``jax.monitoring`` listener turns every jaxpr trace, MLIR lowering and
backend compile JAX reports into an ``atpu/trace`` / ``atpu/lower`` /
``atpu/compile`` span (``fun``; a compile also ``cache`` = ``hit`` where the
persistent cache served it, ``miss`` where it was consulted and compiled,
``off`` where it was not consulted), stamped when JAX reports it; a
``gc.callbacks`` hook writes one ``atpu/gc`` span per generation-2
collection.  They are the one producer of those names; an in-memory jit
cache hit reports nothing and so writes nothing.  Written once the phase has
ended, they carry no profiler annotation: they are on the ring alone.
:class:`CompilePhases` hands a caller (``CapturedStep``'s build) the
compile-phase spans written on its thread inside its block.

**The ring clock** is the clock the profiler stamps its host events with:
Unix-epoch nanoseconds (``CLOCK_REALTIME``; a profiler session then rebases
every plane to its own start, a constant per session).  Stamps are taken
from ``time.monotonic_ns()`` and moved by a one-time anchor, so durations
never see a wall-clock step.  :meth:`FlightRecorder.spans` hands a consumer
the retained spans and instants of an interval in that clock, and
:meth:`FlightRecorder.from_perf_counter` moves a ``time.perf_counter()``
stamp (``Request.submitted_t`` and friends) onto it.

The recorder never issues a collective, never raises into the hot path, and
its dump (:meth:`FlightRecorder.dump`) writes a *per-rank* JSON file — this
module is declared rank-local-by-design to the graftlint taint pass
(``analysis/taint.py``), which in exchange asserts it contains no
collective sink.

Kill switch: ``ACCELERATE_FLIGHTREC=0`` turns recording into a no-op (the
bench A/B's "off" arm) and registers no listener;
``ACCELERATE_FLIGHTREC_CAPACITY`` resizes the ring (default 65,536 events:
an engine step writes about seven, so a minute of serving at 15 ms a step
still fits).
"""

from __future__ import annotations

import collections
import functools
import gc
import json
import os
import socket
import threading
import time
from typing import Optional

_DEFAULT_CAPACITY = 65536


def _env_capacity() -> int:
    raw = os.environ.get("ACCELERATE_FLIGHTREC_CAPACITY")
    if not raw:
        return _DEFAULT_CAPACITY
    try:
        return max(16, int(raw))
    except ValueError:
        return _DEFAULT_CAPACITY


def _env_enabled() -> bool:
    return os.environ.get("ACCELERATE_FLIGHTREC", "1").strip().lower() not in (
        "0", "false", "off", "no",
    )


def resolve_rank() -> int:
    """Best-effort process rank, resolved at *dump* time only — jax may not
    be importable (or distributed-initialized) when events are recorded."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return int(os.environ.get("ACCELERATE_FLIGHTREC_RANK", "0") or 0)


def _clock_anchor() -> tuple:
    """``(wall_ns, monotonic_ns, perf_counter_ns)`` read at one moment: the
    tightest of a few sandwiched reads, so the anchor is good to well under
    a microsecond."""
    best = None
    for _ in range(5):
        m0 = time.monotonic_ns()
        p = time.perf_counter_ns()
        w = time.time_ns()
        m1 = time.monotonic_ns()
        if best is None or m1 - m0 < best[0]:
            best = (m1 - m0, w, (m0 + m1) // 2, p)
    return best[1:]


_trace_annotation = None  # jax.profiler.TraceAnnotation, or False without jax


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` (a no-op outside a
    profiler session, ~100 ns), or ``None`` where jax cannot be imported."""
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        except Exception:
            _trace_annotation = False
    return _trace_annotation(name) if _trace_annotation else None


class Span:
    """One open span (:meth:`FlightRecorder.span`).  ``start_ns``/``end_ns``
    are ring-clock stamps, readable after the block so a caller that also
    wants the duration (``StepRecord``) reads no second pair of clocks;
    ``fields`` may be filled in while the span is open.  On a disabled
    recorder the stamps are still taken and nothing else happens."""

    __slots__ = ("name", "fields", "start_ns", "end_ns", "_rec", "_ann")

    def __init__(self, rec: "FlightRecorder", name: str, fields: dict, start_ns: int = 0):
        self._rec = rec
        self.name = name
        self.fields = fields
        # a nonzero start is a stamp already taken (:meth:`then`)
        self.start_ns = start_ns
        self.end_ns = 0
        self._ann = None

    def then(self, name: str, /, **fields) -> "Span":
        """The span that follows this closed one: it starts at this span's
        end stamp, so adjacent spans share their boundary and together cover
        their interval exactly."""
        return Span(self._rec, name, fields, self.end_ns)

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    def __enter__(self) -> "Span":
        rec = self._rec
        if rec.enabled:
            self._ann = _annotation(self.name)
            if self._ann is not None:
                self._ann.__enter__()
        if not self.start_ns:
            self.start_ns = time.monotonic_ns() + rec._wall_offset_ns
        return self

    def __exit__(self, *exc) -> bool:
        rec = self._rec
        self.end_ns = time.monotonic_ns() + rec._wall_offset_ns
        if self._ann is not None:
            self._ann.__exit__(*exc)
        rec.record_span(self.name, self.start_ns, self.end_ns, **self.fields)
        return False


class FlightRecorder:
    """The per-process event ring.  One module-level instance
    (:func:`recorder`) serves the whole process; constructing private
    instances is for tests."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY, enabled: bool = True):
        self.capacity = max(16, int(capacity))
        self.enabled = bool(enabled)
        # slot: (seq, monotonic ns, kind or span name, fields, dur ns or None)
        self._slots: list = [None] * self.capacity
        self._n = 0  # events ever appended (monotone; ring head = n % cap)
        self._collective_seq = 0
        self._last_ns: Optional[int] = None
        # monotonic ns up to which overwritten events reached (spans())
        self._overwritten_until_ns: Optional[int] = None
        self._lock = threading.Lock()
        # ``(name, start_ns, end_ns, fields)`` of spans closed where the lock
        # may be held by the very thread closing them (a collection that
        # started inside ``_append``): appended at the next append or read
        self._aside: collections.deque = collections.deque()
        # monotonic↔wall anchor: collective seqs align ranks *ordinally*;
        # the wall anchor lets tools place per-rank monotonic stamps on one
        # absolute timeline, and is what
        # turns a monotonic stamp into the ring clock
        wall_ns, mono_ns, perf_ns = _clock_anchor()
        self._anchor_wall = wall_ns / 1e9
        self._anchor_monotonic = mono_ns / 1e9
        self._wall_offset_ns = wall_ns - mono_ns
        self._perf_offset_ns = wall_ns - perf_ns

    # -- the ring clock ------------------------------------------------------
    def now_ns(self) -> int:
        """The ring clock now: Unix-epoch ns, ticking with ``monotonic_ns``."""
        return time.monotonic_ns() + self._wall_offset_ns

    def from_perf_counter(self, t: Optional[float]) -> Optional[int]:
        """A ``time.perf_counter()`` stamp (seconds) on the ring clock."""
        return None if t is None else int(t * 1e9) + self._perf_offset_ns

    # -- producers (hot path) ------------------------------------------------
    @staticmethod
    def _shield_reserved(fields: dict, names: tuple) -> dict:
        """The ring owns the slot schema keys; a producer passing a payload
        dict through (``**payload``) must not collide with them — remap to a
        ``field_`` prefix instead of raising or silently clobbering."""
        for reserved in names:
            if reserved in fields:
                fields[f"field_{reserved}"] = fields.pop(reserved)
        return fields

    def _append(self, t_ns: int, kind: str, fields: dict, dur_ns: Optional[int]) -> None:
        # caller holds the lock
        self._take_aside()
        self._put(t_ns, kind, fields, dur_ns)

    def _take_aside(self) -> None:
        # caller holds the lock; a collection inside _put may set more aside
        while self._aside:
            name, start_ns, end_ns, fields = self._aside.popleft()
            self._put(start_ns - self._wall_offset_ns, name, fields, end_ns - start_ns)

    def _put(self, t_ns: int, kind: str, fields: dict, dur_ns: Optional[int]) -> None:
        i = self._n % self.capacity
        old = self._slots[i]
        if old is not None:
            self._overwritten_until_ns = old[1] + (old[4] or 0)
        self._slots[i] = (self._n, t_ns, kind, fields, dur_ns)
        self._n += 1
        self._last_ns = t_ns + (dur_ns or 0)

    def record(self, kind: str, /, **fields) -> None:
        """Append one event.  Never raises; no-op when disabled."""
        if not self.enabled:
            return
        fields = self._shield_reserved(fields, ("kind", "seq", "t"))
        now = time.monotonic_ns()
        with self._lock:
            self._append(now, kind, fields, None)

    def span(self, name: str, /, **fields) -> Span:
        """``with rec.span("atpu/serve/step", step=3) as sp:`` — one slot when
        the block closes, and a profiler annotation of the same name around
        it.  Keep spans out of per-token and per-slot loops: at most a
        handful per engine step or captured call."""
        return Span(self, name, fields)

    def record_span(self, name: str, start_ns: int, end_ns: int, /, **fields) -> None:
        """Append a span from ring-clock stamps the caller already holds."""
        if not self.enabled:
            return
        fields = self._shield_reserved(fields, ("kind", "seq", "t", "dur_ms"))
        with self._lock:
            self._append(
                start_ns - self._wall_offset_ns, name, fields, max(0, end_ns - start_ns)
            )

    def set_aside(self, name: str, start_ns: int, end_ns: int, /, **fields) -> None:
        """:meth:`record_span` for a caller that may run while its own thread
        holds the ring's lock (a ``gc.callbacks`` hook): takes no lock; the
        span enters the ring at the next append or read."""
        if self.enabled:
            self._aside.append((name, start_ns, end_ns, fields))

    def note_collective(self, op: str, /, **fields) -> int:
        """Tick the collective-sequence counter and record the event.
        Returns the 1-based sequence number of THIS collective — the value
        every rank must agree on, and the join key the blackbox report
        aligns per-rank dumps with."""
        if not self.enabled:
            return self._collective_seq
        fields = self._shield_reserved(fields, ("kind", "seq", "t", "cseq", "op"))
        now = time.monotonic_ns()
        with self._lock:
            self._collective_seq += 1
            seq = self._collective_seq
            fields["cseq"] = seq
            fields["op"] = op
            self._append(now, "collective", fields, None)
        return seq

    # -- consumers -----------------------------------------------------------
    @property
    def collective_seq(self) -> int:
        return self._collective_seq

    @property
    def events_total(self) -> int:
        return self._n

    @property
    def depth(self) -> int:
        return min(self._n, self.capacity)

    @property
    def dropped(self) -> int:
        return max(0, self._n - self.capacity)

    def seconds_since_last_event(self) -> Optional[float]:
        last = self._last_ns
        if last is None:
            return None
        return max(0.0, (time.monotonic_ns() - last) / 1e9)

    def health(self) -> dict:
        """Recorder self-diagnostics for the Prometheus endpoint
        (telemetry/metrics.py): ring depth, drop count, staleness."""
        age = self.seconds_since_last_event()
        return {
            "depth": self.depth,
            "capacity": self.capacity,
            "events_total": self._n,
            "dropped_total": self.dropped,
            "collective_seq": self._collective_seq,
            "last_event_age_seconds": round(age, 3) if age is not None else None,
        }

    def _retained(self) -> list:
        """Retained slots, oldest first — safe to call from the watchdog
        thread while producers keep appending."""
        with self._lock:
            self._take_aside()
            n, cap = self._n, self.capacity
            slots = list(self._slots)
        return [s for s in (slots[i % cap] for i in range(max(0, n - cap), n)) if s is not None]

    def snapshot(self) -> list[dict]:
        """Retained events, oldest first, as dicts stamped in monotonic
        seconds; a span carries its start as ``t`` and a ``dur_ms``."""
        out = []
        for seq, t_ns, kind, fields, dur_ns in self._retained():
            event = {"seq": seq, "t": round(t_ns / 1e9, 6), "kind": kind}
            if dur_ns is not None:
                event["dur_ms"] = round(dur_ns / 1e6, 6)
            if fields:
                event.update(fields)
            out.append(event)
        return out

    def spans(self, start_ns: int, end_ns: int) -> tuple:
        """``(events, dropped)`` for a ring-clock interval: the retained
        spans and instants that touch it, oldest first, each ``{"name",
        "start_ns", "end_ns", **fields}`` (an instant has ``end_ns ==
        start_ns``), and how many events the ring has overwritten that may
        have lain in it — all it ever dropped if the interval begins before
        the newest overwritten one ended, else 0.  A reader that sees drops
        has an incomplete interval and should say so, not guess."""
        off = self._wall_offset_ns
        events = []
        for _, t_ns, kind, fields, dur_ns in self._retained():
            s = t_ns + off
            e = s + (dur_ns or 0)
            if e >= start_ns and s <= end_ns:
                events.append({**fields, "name": kind, "start_ns": s, "end_ns": e})
        until = self._overwritten_until_ns
        lost = self.dropped if until is not None and start_ns <= until + off else 0
        return events, lost

    def to_dict(self, reason: str = "manual") -> dict:
        """The full per-rank dump payload (watchdog stall, fatal signal,
        atexit, or an explicit tool call)."""
        now_wall, now_mono = time.time(), time.monotonic()
        return {
            "kind": "blackbox",
            "reason": reason,
            "rank": resolve_rank(),
            "pid": os.getpid(),
            "host": socket.gethostname(),
            "time_unix": round(now_wall, 3),
            "monotonic": round(now_mono, 6),
            # wall = monotonic + (anchor_wall - anchor_monotonic): lets the
            # postmortem place every event on the absolute timeline
            "anchor_wall": round(self._anchor_wall, 3),
            "anchor_monotonic": round(self._anchor_monotonic, 6),
            "collective_seq": self._collective_seq,
            "events_total": self._n,
            "dropped": self.dropped,
            "events": self.snapshot(),
        }
    def dump(self, dir_or_path: str, reason: str = "manual",
             extra: Optional[dict] = None) -> Optional[str]:
        """Write the per-rank JSON dump.  ``dir_or_path`` naming a directory
        (or ending in a separator) gets the canonical ``blackbox_rank{N}.json``
        filename appended.  Fail-soft: returns the path, or ``None`` on any
        I/O error — a postmortem writer must never crash the job it is
        documenting."""
        try:
            payload = self.to_dict(reason=reason)
            if extra:
                payload.update(extra)
            path = dir_or_path
            if path.endswith(os.sep) or os.path.isdir(path) or not path.endswith(".json"):
                os.makedirs(path, exist_ok=True)
                path = os.path.join(path, f"blackbox_rank{payload['rank']}.json")
            else:
                parent = os.path.dirname(path)
                if parent:
                    os.makedirs(parent, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
            return path
        except Exception:
            return None


# the process-wide recorder: constructed eagerly (a few KB) so the very
# first event — backend init, distributed rendezvous — is never lost
_RECORDER = FlightRecorder(capacity=_env_capacity(), enabled=_env_enabled())


def recorder() -> FlightRecorder:
    return _RECORDER


def record(kind: str, /, **fields) -> None:
    """Module-level shortcut for producers: ``flightrec.record(...)``."""
    _RECORDER.record(kind, **fields)


def note_collective(op: str, /, **fields) -> int:
    return _RECORDER.note_collective(op, **fields)


def span(name: str, /, **fields) -> Span:
    """Module-level shortcut: ``with flightrec.span("atpu/serve/step"):``."""
    return _RECORDER.span(name, **fields)


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _RECORDER.span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


# -- compile phases and host pauses (the recorder's own producers) -------------

_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "atpu/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "atpu/lower",
    "/jax/core/compile/backend_compile_duration": "atpu/compile",
}
# fired inside a backend compile whenever the persistent cache is consulted,
# and when it answered (``cache_misses`` fires only where an entry is written,
# so it misses compiles under the cache's size or time thresholds)
_CACHE_CONSULTED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_compiling = threading.local()  # cache flags, open CompilePhases: per thread
_listening = False
_gc_start_ns = 0


def _on_jax_event(event: str, **_) -> None:
    if event == _CACHE_CONSULTED:
        _compiling.consulted = True
    elif event == _CACHE_HIT:
        _compiling.hit = True


def _on_jax_duration(event: str, duration_secs: float, **kwargs) -> None:
    """One span per compile phase, on the ring's own clock: it ends now, as
    JAX reports it, and started its duration before."""
    name = _JAX_PHASES.get(event)
    if name is None:
        return
    rec = _RECORDER
    end_ns = rec.now_ns()
    start_ns = end_ns - int(duration_secs * 1e9)
    fields = {"fun": str(kwargs.get("fun_name", ""))}
    if name == "atpu/compile":
        here = _compiling
        consulted, hit = getattr(here, "consulted", False), getattr(here, "hit", False)
        here.consulted = here.hit = False
        fields["cache"] = ("hit" if hit else "miss") if consulted else "off"
    rec.record_span(name, start_ns, end_ns, **fields)
    for phases in getattr(_compiling, "open", ()):
        phases.spans.append((start_ns, end_ns))


def _on_gc(phase: str, info: dict) -> None:
    """One ``atpu/gc`` span per generation-2 collection.  A collection may
    start inside any allocation, ``_append``'s under the lock among them, so
    the span is set aside, never appended here."""
    global _gc_start_ns
    if info.get("generation") != 2:
        return
    try:
        rec = _RECORDER
        if phase == "start":
            _gc_start_ns = rec.now_ns()
        elif _gc_start_ns:
            rec.set_aside("atpu/gc", _gc_start_ns, rec.now_ns(), gen=2,
                          collected=info.get("collected", 0))
            _gc_start_ns = 0
    except Exception:  # never raise into a collection
        pass


def _listen() -> None:
    """Register the listeners above, once per process, with the recorder on."""
    global _listening
    if _listening or not _RECORDER.enabled:
        return
    try:
        from jax import monitoring
    except ImportError:
        return
    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    gc.callbacks.append(_on_gc)
    _listening = True


def _union_ns(intervals) -> int:
    """Length of the union of ``(start_ns, end_ns)`` intervals: nested spans
    (an inner jit traced inside an outer one) count once, as the outermost."""
    total, reach = 0, None
    for s, e in sorted(intervals):
        if reach is None or s > reach:
            total += e - s
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total


class CompilePhases:
    """``with CompilePhases() as p:`` — collects the compile-phase spans the
    listener writes on this thread while the block runs; ``p.ms`` is their
    outermost time.  Without the listener (recorder off) it is the block's
    own time, so a caller's build times keep their meaning."""

    __slots__ = ("spans", "start_ns", "end_ns")

    def __init__(self):
        self.spans: list = []
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "CompilePhases":
        if not hasattr(_compiling, "open"):
            _compiling.open = []
        _compiling.open.append(self)
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.monotonic_ns()
        _compiling.open.remove(self)
        return False

    @property
    def ms(self) -> float:
        if not _listening:
            return (self.end_ns - self.start_ns) / 1e6
        return _union_ns(self.spans) / 1e6


_listen()
