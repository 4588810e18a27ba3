"""What every runner shares: the look for the chip, the device report, the
traced window, and the decision and printing of ``correct``."""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import time

from . import flops, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "out")
# host spans the benchmark writes into the profiler's trace; idle gaps are
# attributed to them
SPANS = (
    "loader.next", "step.dispatch", "step.sync",
    "service.submit", "service.step", "generator.sleep",
)
WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


def require_chips(chips: int) -> dict:
    """The devices as JAX reports them, or ``NoChip``: the benchmark has no
    CPU mode and takes no other number of chips than the cell names."""
    import jax

    devices = jax.devices()
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if report["platform"] != "tpu" or report["count"] != chips:
        raise NoChip(f"the cell needs {chips} tpu chip(s); JAX reports {report}")
    try:
        flops.load_peaks(report["kind"])
    except KeyError as e:
        raise NoChip(str(e)) from e
    return report


def arm_compile_cache() -> str:
    """The library's own rule places the cache (``$JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``); the benchmark only asks JAX to keep the
    quick compiles too, so that a second run finds every program."""
    import jax

    from accelerate_tpu import enable_compilation_cache

    path = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_in_use_bytes() -> int:
    import jax

    return max(int(d.memory_stats()["bytes_in_use"]) for d in jax.devices())


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, since the process began."""
    import jax

    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in jax.devices())


def free_program(prog: dict) -> None:
    """Drop the program's state (``prog`` holds its accelerator among the rest)
    so that the reference has the chip's memory."""
    import gc

    from accelerate_tpu import Accelerator

    prog["accelerator"].free_memory()
    prog.clear()
    Accelerator._reset_state()
    gc.collect()


def open_cell(workload: str, who: str):
    """``(cell, device)`` for a tool's command line, the compile cache armed; or
    ``None`` after saying on standard error that the chips are not there."""
    from . import cells

    cell = cells.resolve(workload)
    try:
        device = require_chips(cell.chips)
    except NoChip as e:
        print(f"benchmark/{who}: {e}", file=sys.stderr)
        return None
    log(f"{cell.name} cache={arm_compile_cache()}")
    return cell, device


@contextlib.contextmanager
def span(name: str):
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class TracedWindow:
    """With ``on``, records a profiler trace between ``start`` and ``stop``
    (a part of the measured window) and reduces it; without, does nothing."""

    def __init__(self, on: bool, label: str):
        self.on = on
        self.dir = os.path.join(OUT_DIR, "trace-" + label)
        self.planes = None
        self._ctx = None
        self.running = False

    def start(self) -> None:
        if not self.on or self.running:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the benchmark's own spans are enough
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._ctx = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ctx.__enter__()
        self.running = True

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        self._ctx.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False

    def reduce(self) -> dict:
        """``{"window_s", "busy_s", "breakdown", "planes"}``; the trace's files
        are removed once read (traces are large; the host keeps every block)."""
        path = trace_reduce.find_xplane(self.dir)
        self.planes = trace_reduce.load(path, keep_host_names=set(SPANS) | {WINDOW_SPAN})
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace_reduce.summarize(self.planes, SPANS, window_span=WINDOW_SPAN)


def decide(numbers: dict, limits: dict) -> tuple:
    """``correct`` and the compared numbers, each beside its limit.  A number
    without a limit, or a limit without a number, is a fault of the benchmark
    and reads as not correct."""
    compared, ok = {}, True
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        good = (
            value is not None and limit is not None
            and math.isfinite(value) and value <= limit
        )
        ok = ok and good
        compared[name] = {"value": value, "limit": limit}
    return ok, compared


def print_compared(compared: dict, correct: bool) -> None:
    lines = [f"correct={str(correct).lower()}"]
    lines += [f"  {k}: value={v['value']} limit={v['limit']}" for k, v in compared.items()]
    print("\n".join(lines), file=sys.stderr, flush=True)


def result_line(*, correct, attempted, failed, metrics, units, device, compared,
                breakdown=None) -> str:
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)
