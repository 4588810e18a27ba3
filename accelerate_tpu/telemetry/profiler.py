"""Pillar 5 — sampled device-time attribution (docs/telemetry.md).

``StepRecord.dispatch_ms`` is *launch* latency: under JAX's async dispatch
the host returns the moment the program is enqueued, so the one number the
EQuARX-style comms A/B and the serving hot path actually need — where the
*device* spends its time (compute vs collective vs host transfer vs idle) —
is invisible to host timers.  This module closes that gap without giving up
the async pipeline: every Nth captured call (``TelemetryKwargs(
profile_every_n=...)`` / ``$ACCELERATE_TELEMETRY_PROFILE_N``, default off)
the dispatch runs inside a ``jax.profiler`` trace session, the sampled call
blocks until the device finishes (that is the sampling overhead — bounded
by the cadence), and the resulting trace-event JSON is parsed into a
:class:`DeviceStepRecord` joined 1:1 to the host-side ``StepRecord`` by
step index.

The parser reads the ``*.trace.json.gz`` chrome-trace dump the profiler
writes on every backend — CPU included (XLA:CPU emits per-HLO-op events on
its Eigen worker threads), which is what lets the whole pillar test in
tier-1 without a TPU.  Device ops are the ``X`` events carrying an
``args.hlo_op`` tag (or living under a ``/device:...`` process); per-device
*busy* is the interval **union** of those ops (ops overlap across worker
threads, so summing durations would double-count), *idle* is the profiled
window minus busy, and the compute/collective/transfer split is classified
from op names.  MFU derives from the captured program's existing
``cost_analysis()`` FLOPs against a per-chip peak (``$ACCELERATE_PEAK_FLOPS``
override, known-TPU table otherwise; ``None`` where no peak is known).

Everything here is fail-soft: an unparseable or empty trace, a backend
without trace events, or a profiler session already held by the user's
``accelerator.profile()`` yields *no* record (and, after repeated start
failures, disables sampling for the run) — never an exception on the
capture path.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import math
import os
import re
import shutil
import tempfile
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Optional

from ..logging import get_logger

logger = get_logger(__name__)

# op-name classification for the device-time split.  HLO collective ops keep
# their names through fusion labels on every backend we parse.
_COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|partition-id|replica-id|psum|ragged-all-to-all",
    re.IGNORECASE,
)
_TRANSFER_RE = re.compile(
    r"\bcopy|infeed|outfeed|host-transfer|send\b|recv\b|dynamic-update-slice-host",
    re.IGNORECASE,
)

# (device_kind substring, peak dense FLOP/s per chip, bf16) — best-effort;
# $ACCELERATE_PEAK_FLOPS overrides, unknown kinds (CPU) yield None → no MFU
_PEAK_FLOPS_BY_KIND = (
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_device() -> Optional[float]:
    """Per-chip peak FLOP/s: env override first, TPU kind table second,
    ``None`` when unknown (CPU and friends — MFU is then not derivable)."""
    env = os.environ.get("ACCELERATE_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            logger.warning("ACCELERATE_PEAK_FLOPS=%r is not a number", env)
    try:
        import jax

        kind = jax.devices()[0].device_kind.lower()
    except Exception:
        return None
    for tag, peak in _PEAK_FLOPS_BY_KIND:
        if tag in kind:
            return peak
    return None


def derive_mfu(flops: float, window_ms: float, n_devices: int = 1) -> Optional[float]:
    """Model-FLOPs utilization of one profiled step: the program's analytic
    FLOPs (``cost_analysis`` — whole-program) over the device-time window
    against the fleet's aggregate peak.  ``None`` without a known peak."""
    peak = peak_flops_per_device()
    if not peak or window_ms <= 0 or not flops:
        return None
    return flops / (window_ms / 1e3) / (peak * max(1, n_devices))


@dataclass
class DeviceStepRecord:
    """Device-side view of one sampled captured call, joined to the host
    :class:`~.timeline.StepRecord` with the same ``step`` index."""

    step: int  # global captured-call index — the join key
    key: str  # compiled-variant key id (same as StepRecord.key)
    window_ms: float  # host wall of the profiled span (dispatch → blocked)
    busy_ms: float  # mean per-device op-interval union
    idle_ms: float  # mean per-device (window - busy), >= 0
    compute_ms: float  # mean per-device op-duration sums by class
    collective_ms: float
    transfer_ms: float
    devices: dict = field(default_factory=dict)  # per-device split
    top_ops: list = field(default_factory=list)  # [[name, ms], ...] desc
    op_events: int = 0  # device-op events parsed
    overhead_ms: float = 0.0  # stop_trace + parse cost (outside window_ms)
    flops: Optional[float] = None  # from the program's cost_analysis
    mfu: Optional[float] = None  # None without a known per-chip peak
    # per-atpu-phase compute/collective/transfer split (docs/telemetry.md):
    # op durations joined to the program's HLO op->scope map — empty when
    # no scope map exists for the variant (fail-soft)
    phases: dict = field(default_factory=dict)
    # raw {op name: [class, ms]} the phase join consumes; not exported
    op_detail: dict = field(default_factory=dict)

    @property
    def collective_share(self) -> float:
        """Collective fraction of device op time (the EQuARX headline)."""
        total = self.compute_ms + self.collective_ms + self.transfer_ms
        return self.collective_ms / total if total > 0 else 0.0

    def to_dict(self) -> dict:
        return {
            "kind": "device_step",
            "step": self.step,
            "key": self.key,
            "window_ms": round(self.window_ms, 3),
            "busy_ms": round(self.busy_ms, 3),
            "idle_ms": round(self.idle_ms, 3),
            "compute_ms": round(self.compute_ms, 3),
            "collective_ms": round(self.collective_ms, 3),
            "transfer_ms": round(self.transfer_ms, 3),
            "collective_share": round(self.collective_share, 4),
            "devices": {k: dict(v) for k, v in self.devices.items()},
            "top_ops": [[n, round(ms, 3)] for n, ms in self.top_ops],
            "op_events": self.op_events,
            "overhead_ms": round(self.overhead_ms, 3),
            "flops": self.flops,
            "mfu": self.mfu,
            "phases": {
                name: {k: (round(v, 3) if isinstance(v, float) else v)
                       for k, v in split.items()}
                for name, split in self.phases.items()
            },
        }


def _union_ms(intervals: list) -> float:
    """Total covered length (ms) of possibly-overlapping (start, end) µs
    intervals — per-device busy must not double-count ops that ran
    concurrently on different worker threads."""
    if not intervals:
        return 0.0
    intervals.sort()
    covered = 0.0
    cur_start, cur_end = intervals[0]
    for start, end in intervals[1:]:
        if start > cur_end:
            covered += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    covered += cur_end - cur_start
    return covered / 1e3


def classify_op(name: str) -> str:
    if _COLLECTIVE_RE.search(name):
        return "collective"
    if _TRANSFER_RE.search(name):
        return "transfer"
    return "compute"


_OP_LINE = "XLA Ops"  # the one device line whose events are executed ops


def parse_trace_events(events: list, top_k: int = 10) -> dict:
    """Trace-event JSON (chrome format, µs timestamps) → per-device busy +
    compute/collective/transfer split + top-k ops by device time.

    A *device op* is a complete (``ph == "X"``) event carrying an
    ``args.hlo_op`` tag, or a complete event under a process whose metadata
    name starts with ``/device:`` (the TPU layout) — there on the ``XLA
    Ops`` thread alone where the trace names its threads: a real v5e trace
    also has ``XLA Modules`` and ``Steps`` lines, whose events span the
    gaps between ops, and ``Async XLA Ops`` beside them, and counting
    those made an idle chip read busy (PERF.md, PR 26).  Everything else —
    python frames, runtime bookkeeping, thread markers — is host noise and
    ignored."""
    process_names: dict = {}
    thread_names: dict = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            process_names[ev.get("pid")] = ev.get("args", {}).get("name", "")
        elif ev.get("name") == "thread_name":
            thread_names[(ev.get("pid"), ev.get("tid"))] = ev.get("args", {}).get("name", "")
    # device processes whose op line is named: only that line is device work
    op_line_pids = {pid for (pid, _), name in thread_names.items() if name == _OP_LINE}
    per_device: dict[str, dict] = {}
    intervals: dict[str, list] = {}
    op_ms: dict[str, float] = {}
    op_detail: dict[str, list] = {}  # name -> [class, summed ms]
    n_ops = 0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args")
        pname = process_names.get(ev.get("pid"), "")
        pid = ev.get("pid")
        if pname.startswith("/device:"):
            is_op = (
                pid not in op_line_pids
                or thread_names.get((pid, ev.get("tid"))) == _OP_LINE
            )
        else:
            is_op = isinstance(args, dict) and "hlo_op" in args
        if not is_op:
            continue
        try:
            ts, dur = float(ev["ts"]), float(ev["dur"])
        except (KeyError, TypeError, ValueError):
            continue
        name = str(ev.get("name", "?"))
        device = pname or f"pid:{ev.get('pid')}"
        dev = per_device.setdefault(
            device,
            {"busy_ms": 0.0, "compute_ms": 0.0, "collective_ms": 0.0,
             "transfer_ms": 0.0, "idle_ms": 0.0, "ops": 0},
        )
        op_class = classify_op(name)
        dev[f"{op_class}_ms"] += dur / 1e3
        dev["ops"] += 1
        intervals.setdefault(device, []).append((ts, ts + dur))
        op_ms[name] = op_ms.get(name, 0.0) + dur / 1e3
        entry = op_detail.setdefault(name, [op_class, 0.0])
        entry[1] += dur / 1e3
        n_ops += 1
    for device, dev in per_device.items():
        dev["busy_ms"] = _union_ms(intervals[device])
    top_ops = sorted(op_ms.items(), key=lambda kv: kv[1], reverse=True)[:top_k]
    return {
        "devices": per_device,
        "top_ops": top_ops,
        "op_events": n_ops,
        "op_detail": op_detail,
    }


# HLO-text instruction metadata: `%name = ... metadata={... op_name="path"}`
# — the only place the atpu named scopes survive to (trace events carry
# bare instruction names on every backend we parse)
_HLO_OP_NAME_RE = re.compile(r"%?([\w.\-]+) = [^\n]*op_name=\"([^\"]+)\"")


# every instruction of a module's text, scoped or not (coverage checks)
_HLO_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.MULTILINE)


def scope_map_from_text(text: str) -> dict:
    """``{hlo instruction name: atpu phase}`` from a program's HLO text.
    The phase is the DEEPEST ``atpu``-prefixed segment of the op's scope
    path (``jit(f)/atpu_captured_body/atpu_update/add`` → ``atpu_update``);
    unscoped instructions are omitted.  Two consequences worth knowing: a
    fused instruction carries ONE scope, its root's; and the backward of a
    scoped forward region runs under ``transpose(jvp(<scope>))`` segments,
    which do not start with ``atpu``, so it falls to the scope that
    encloses the backward pass (``atpu_backward``)."""
    scope_map: dict = {}
    for match in _HLO_OP_NAME_RE.finditer(text):
        name, path = match.group(1), match.group(2)
        phase = None
        for segment in path.split("/"):
            if segment.startswith("atpu"):
                phase = segment  # keep walking: deepest wins
        if phase is not None:
            scope_map[name] = phase
    return scope_map


# `%name = type[dims]{layout} opcode(` — an instruction's result shape(s) and
# opcode; a tuple result lists several shapes before the opcode
_HLO_RESULT_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\(?[a-z]\w*\[[^=]*?) ([a-z][a-z\-]*)\(", re.MULTILINE
)
_HLO_DIMS_RE = re.compile(r"[a-z]\w*\[([\d,]*)\]")


def instructions_of_size(text: str, opcodes, min_elements: int) -> list:
    """``[(name, opcode, dims)]`` of every instruction of an HLO text —
    entry, loop bodies and fused computations alike — whose opcode is one of
    ``opcodes`` and whose largest result, of dimensions ``dims``, has
    ``min_elements`` or more.  What a guard on a compiled program asks: is a
    buffer of that size still copied, sliced out or written back anywhere
    (tests/test_tpu_compile.py, tools/tpu_aot_check.py)."""
    found = []
    for name, shapes, opcode in _HLO_RESULT_RE.findall(text):
        if opcode not in opcodes:
            continue
        dims = max(
            (tuple(int(d) for d in shape.split(",") if d)
             for shape in _HLO_DIMS_RE.findall(shapes)),
            key=math.prod, default=(),
        )
        if dims and math.prod(dims) >= min_elements:
            found.append((name, opcode, dims))
    return found


def scope_map_from_compiled(compiled) -> dict:
    """:func:`scope_map_from_text` of a compiled program.  Fail-soft: any
    error returns an empty map and the sample simply carries no phase
    split."""
    try:
        return scope_map_from_text(compiled.as_text())
    except Exception:
        return {}


# -- process-wide program registry (docs/telemetry.md §spans and scopes) ----
#
# A reader of a device trace sees bare instruction names; the scopes live in
# the program's HLO text.  Producers register HOW to get that text — nothing
# is lowered, compiled, fetched or parsed until a reader asks, after the
# timed window — and the registry outlives the Accelerator and the service.
# It holds no device buffer: the captured step's compiled handle is held
# weakly (``settle_programs`` reads its text while the owner still lives),
# the serving programs are re-lowered from shapes alone.
_PROGRAMS_MAX = 8
_programs: dict = {}  # key -> Program, oldest first
_programs_lock = threading.Lock()


class Program:
    """One registered program: its module name and, unevaluated until a
    reader asks, the way to its HLO text."""

    __slots__ = ("name", "perishable", "_text_fn", "_parsed")

    def __init__(self, name: str, text_fn=None, scope_map: Optional[dict] = None,
                 perishable: bool = False):
        self.name = name
        self.perishable = perishable  # the way to the text dies with its owner
        # (scope map, every instruction name); a persisted map (AOT warm
        # start) stands in for the text and knows only its own, scoped, names
        self._parsed = (dict(scope_map), frozenset(scope_map)) if scope_map else None
        # () -> HLO text or None; dropped once evaluated
        self._text_fn = None if scope_map else text_fn

    @property
    def evaluated(self) -> bool:
        return self._text_fn is None

    def evaluate(self) -> None:
        text_fn, self._text_fn = self._text_fn, None
        if text_fn is None:
            return
        try:
            text = text_fn()
        except Exception as exc:
            logger.warning("HLO text of program %s unavailable: %s", self.name, exc)
            text = None
        if text:
            self._parsed = (
                scope_map_from_text(text),
                frozenset(_HLO_INSTRUCTION_RE.findall(text)),
            )

    def scope_map(self) -> dict:
        self.evaluate()
        return self._parsed[0] if self._parsed else {}

    def instruction_names(self) -> frozenset:
        self.evaluate()
        return self._parsed[1] if self._parsed else frozenset()


def register_program(name: str, text_fn, key=None, scope_map: Optional[dict] = None,
                     perishable: bool = False) -> Program:
    """Remember how to get the HLO text of the program whose module is named
    ``name`` (``jit_traced``, ``jit__decode_jit``): ``text_fn()`` returns it,
    or ``None`` once it cannot.  ``key`` (default ``name``) identifies the
    program; registering a key again replaces the entry.  ``scope_map``
    hands over an already parsed map instead (the AOT store's).
    ``perishable`` marks a ``text_fn`` that stops answering when the
    program's owner goes (:func:`compiled_text_fn`): ``settle_programs``
    evaluates those.  Only the newest few programs are kept."""
    key = name if key is None else key
    program = Program(name, text_fn, scope_map, perishable)
    with _programs_lock:
        _programs.pop(key, None)
        _programs[key] = program
        while len(_programs) > _PROGRAMS_MAX:
            del _programs[next(iter(_programs))]
    return program


def program_registered(key) -> bool:
    return key in _programs


def registered_programs() -> list:
    with _programs_lock:
        return list(_programs.values())


def _find_program(module_needle: str) -> Optional[Program]:
    for program in reversed(registered_programs()):
        if module_needle in program.name:
            return program
    return None


def settle_programs() -> None:
    """Evaluate the perishable entries now.  Called where the owners of
    weakly held programs are about to go (``Accelerator.free_memory``),
    never on a timed path."""
    for program in registered_programs():
        if program.perishable:
            program.evaluate()


def scope_map(module_needle: str) -> dict:
    """``{instruction: atpu scope}`` of the newest registered program whose
    module name holds ``module_needle``; ``{}`` where none is known.  The
    first call for a program fetches and parses its text."""
    program = _find_program(module_needle)
    return program.scope_map() if program is not None else {}


def instruction_names(module_needle: str) -> frozenset:
    """Every instruction name in that program's text, scoped or not: what a
    reader checks a trace's names against before it trusts the map."""
    program = _find_program(module_needle)
    return program.instruction_names() if program is not None else frozenset()


@contextlib.contextmanager
def scopes_in_cache_key():
    """Lower and compile inside this where the program's scopes will be read
    from its executable.  JAX's persistent-cache key leaves metadata out by
    default ("executables loaded from the cache may have stale metadata,
    which may show up in, e.g., profiles"), so an executable compiled before
    a scope existed (a parent commit's, on a shared cache) would be served
    with its old names; here the key takes the metadata in.  The locations
    themselves are left as they are: cutting them to one frame makes JAX
    share primitive lowerings between call sites, and the shared copies lose
    the scope path (found on the chip, PERF.md PR 27)."""
    import jax

    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        yield
    finally:
        jax.config.update(name, before)


def compiled_text_fn(compiled):
    """``text_fn`` for a compiled handle, held weakly: the registry must not
    keep an executable (and its device memory) alive past its owner."""
    ref = weakref.ref(compiled)

    def text():
        live = ref()
        return live.as_text() if live is not None else None

    return text


def relowered_text_fn(jit_fn, args, statics: dict):
    """``text_fn`` for a plain ``jax.jit`` function called with ``args`` and
    ``statics``: keeps their shapes, dtypes and committed shardings — no
    buffer — and lowers and compiles again from those when asked (a
    persistent-cache hit where the cache is armed)."""
    import jax

    def spec(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding if x.committed else None,
                weak_type=x.weak_type,
            )
        return x

    specs = jax.tree_util.tree_map(spec, args)

    def text():
        with scopes_in_cache_key():  # the key the first call compiled under
            return jit_fn.lower(*specs, **statics).compile().as_text()

    return text


def split_phases(op_detail: dict, scope_map: dict) -> dict:
    """Join sampled per-op durations (``{name: [class, ms]}``) to the
    program's op->scope map: the whole-step compute/collective/transfer
    split re-read per atpu phase.  Ops outside every atpu scope (input
    copies, infeed, runtime bookkeeping) land in ``"unscoped"``."""
    phases: dict = {}
    for name, (op_class, ms) in op_detail.items():
        phase = scope_map.get(name, "unscoped")
        split = phases.setdefault(
            phase,
            {"total_ms": 0.0, "compute_ms": 0.0, "collective_ms": 0.0,
             "transfer_ms": 0.0, "ops": 0},
        )
        split[f"{op_class}_ms"] += ms
        split["total_ms"] += ms
        split["ops"] += 1
    return phases


def find_trace_json(trace_dir: str) -> Optional[str]:
    """Newest ``*.trace.json.gz`` under a profiler log dir (the profiler
    nests its dump under ``plugins/profile/<timestamp>/``)."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def parse_trace_dir(trace_dir: str) -> Optional[dict]:
    path = find_trace_json(trace_dir)
    if path is None:
        return None
    try:
        with gzip.open(path, "rt", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    events = data.get("traceEvents") if isinstance(data, dict) else data
    if not isinstance(events, list):
        return None
    return parse_trace_events(events)


class StepProfiler:
    """Sampled ``jax.profiler`` trace capture around captured-step dispatch.

    One instance per telemetry hub.  ``should_sample`` is the only call on
    the unsampled hot path (an int modulus); ``start``/``stop`` bracket the
    sampled call's dispatch and are deliberately synchronous — the sampled
    step blocks until the device drains so its ops land inside the session.
    Traces land under per-step subdirs of ``base_dir`` and are deleted
    after parsing unless the caller pinned a directory (``keep_traces``)."""

    _MAX_START_FAILURES = 3  # consecutive; then sampling is off for the run

    def __init__(self, every_n: int, base_dir: Optional[str] = None,
                 keep_traces: bool = False):
        self.every_n = max(0, int(every_n))
        self._base_dir = base_dir
        self.keep_traces = bool(keep_traces)
        self._active_dir: Optional[str] = None
        self._t0 = 0.0
        self._start_failures = 0
        self.samples = 0
        self.last_error: Optional[str] = None

    @property
    def base_dir(self) -> str:
        if self._base_dir is None:
            self._base_dir = tempfile.mkdtemp(prefix="atpu_profile_")
        return self._base_dir

    def should_sample(self, step_index: int) -> bool:
        return (
            self.every_n > 0
            and self._start_failures < self._MAX_START_FAILURES
            and step_index % self.every_n == 0
        )

    def start(self, step_index: int, t0: Optional[float] = None) -> bool:
        """Open a trace session for this step; False (and never raises) when
        the profiler is unavailable or already held (user xprof session).

        ``t0`` (a ``perf_counter`` stamp) backdates the measured window to
        the captured call's entry: the session itself brackets only the
        dispatch (so a raising build can never orphan it), but the step's
        device-visible wall clock — and the idle the device spends while
        the host assembles arguments — starts at call entry."""
        import jax

        if self._active_dir is not None:
            # a previous sampled call raised between start and stop: close
            # the orphaned session so sampling recovers instead of failing
            # every later start
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            if not self.keep_traces:
                shutil.rmtree(self._active_dir, ignore_errors=True)
            self._active_dir = None
        trace_dir = os.path.join(self.base_dir, f"step{step_index:08d}")
        try:
            jax.profiler.start_trace(trace_dir)
        except Exception as exc:
            self._start_failures += 1
            self.last_error = f"{type(exc).__name__}: {exc}"
            if self._start_failures == 1:
                logger.warning(
                    "sampled profiler trace could not start (%s); will retry "
                    "up to %d times before disabling sampling for this run",
                    self.last_error, self._MAX_START_FAILURES,
                )
            return False
        self._start_failures = 0
        self._active_dir = trace_dir
        # without a caller-provided call-entry stamp the window opens AFTER
        # start_trace returns: the first session of a process pays a
        # multi-second profiler init that is not device time
        self._t0 = time.perf_counter() if t0 is None else t0
        return True

    def abort(self) -> None:
        """Close an in-flight session without recording (the sampled call
        raised mid-dispatch): best-effort stop + dump cleanup, so the
        session cannot keep tracing every step until the next sample."""
        trace_dir, self._active_dir = self._active_dir, None
        if trace_dir is None:
            return
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        if not self.keep_traces:
            shutil.rmtree(trace_dir, ignore_errors=True)

    def stop(self, step_index: int, key: str, outputs) -> Optional[DeviceStepRecord]:
        """Block on ``outputs``, close the session, parse the dump.  Returns
        ``None`` (never raises) when the trace is empty or unparseable."""
        import jax

        trace_dir, self._active_dir = self._active_dir, None
        if trace_dir is None:
            return None
        try:
            jax.block_until_ready(outputs)
        except Exception:
            pass  # a dispatch error is the caller's to handle, not ours
        t1 = time.perf_counter()
        window_ms = (t1 - self._t0) * 1e3
        parsed = None
        try:
            jax.profiler.stop_trace()
            parsed = parse_trace_dir(trace_dir)
        except Exception as exc:
            self.last_error = f"{type(exc).__name__}: {exc}"
            logger.warning("sampled profiler trace failed: %s", self.last_error)
        finally:
            if not self.keep_traces:
                shutil.rmtree(trace_dir, ignore_errors=True)
        overhead_ms = (time.perf_counter() - t1) * 1e3
        if not parsed or not parsed["devices"]:
            self.last_error = self.last_error or "trace contained no device ops"
            return None
        devices = parsed["devices"]
        for dev in devices.values():
            dev["idle_ms"] = max(0.0, window_ms - dev["busy_ms"])
        n = len(devices)
        mean = lambda field: sum(d[field] for d in devices.values()) / n  # noqa: E731
        self.samples += 1
        return DeviceStepRecord(
            step=step_index,
            key=key,
            window_ms=window_ms,
            busy_ms=mean("busy_ms"),
            idle_ms=mean("idle_ms"),
            compute_ms=mean("compute_ms"),
            collective_ms=mean("collective_ms"),
            transfer_ms=mean("transfer_ms"),
            devices=devices,
            top_ops=[list(kv) for kv in parsed["top_ops"]],
            op_events=parsed["op_events"],
            overhead_ms=overhead_ms,
            op_detail=parsed.get("op_detail", {}),
        )
