"""Set-up and compilation on the flight recorder (docs/telemetry.md §spans and
scopes): JAX's own trace, lowering and compile events as ring spans, with the
persistent cache's answer on each compile; ``atpu/gc`` for every generation-2
collection; ``prepare`` and the decode service's construction as spans.
"""

import gc
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import accelerate_tpu.nn as nn
from accelerate_tpu import Accelerator
from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
from accelerate_tpu.serving import DecodeService, ServingConfig
from accelerate_tpu.telemetry import flightrec
from accelerate_tpu.telemetry.flightrec import FlightRecorder

COMPILE_PHASES = ("atpu/trace", "atpu/lower", "atpu/compile")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_ring(monkeypatch):
    monkeypatch.setattr(flightrec, "_RECORDER", FlightRecorder(capacity=4096))


def _ring():
    rec = flightrec.recorder()
    return rec.spans(0, rec.now_ns())[0]


def _named(events, *names):
    return [e for e in events if e["name"] in names]


@pytest.fixture
def private_cache(tmp_path):
    """A persistent cache of its own with both thresholds at 0, and JAX's
    in-memory executables cleared on entry and exit; the suite's cache
    directory and thresholds are restored afterwards, never written."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (True, str(tmp_path), 0.0, 0)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        yield tmp_path
    finally:
        jax.clear_caches()
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_a_compile_writes_its_phases_and_a_cache_load_says_hit(private_cache):
    @jax.jit
    def seventeen_x_plus_three(x):
        return 17.0 * x + 3.0

    x = jnp.arange(8.0)
    seventeen_x_plus_three(x).block_until_ready()
    first = _ring()
    outer = {e["name"]: e for e in first if e.get("fun") in ("seventeen_x_plus_three",
                                                             "jit(seventeen_x_plus_three)")}
    assert set(outer) == set(COMPILE_PHASES)
    assert outer["atpu/compile"]["cache"] == "miss"
    trace, lower, compiled = (outer[n] for n in COMPILE_PHASES)
    assert trace["end_ns"] <= lower["start_ns"] + 1_000_000 and lower["end_ns"] <= compiled["start_ns"] + 1_000_000
    assert all(e["end_ns"] > e["start_ns"] for e in outer.values())
    assert os.listdir(private_cache), "the compile was not written to the private cache"

    jax.clear_caches()  # the in-memory executable is gone; the file is not
    n = flightrec.recorder().events_total
    seventeen_x_plus_three(x).block_until_ready()
    loaded = [e for e in _ring()[n:] if e["name"] == "atpu/compile"]
    assert [(e["fun"], e["cache"]) for e in loaded] == [("jit(seventeen_x_plus_three)", "hit")]

    n = flightrec.recorder().events_total
    seventeen_x_plus_three(x).block_until_ready()
    assert flightrec.recorder().events_total == n  # an in-memory hit writes nothing


def test_a_compile_with_the_persistent_cache_off_says_off(compiled_in_this_process):
    x = jnp.ones(4)

    @jax.jit
    def five_x_less_one(x):
        return x * 5.0 - 1.0

    five_x_less_one(x).block_until_ready()
    compiled = _named(_ring(), "atpu/compile")
    assert "jit(five_x_less_one)" in [e["fun"] for e in compiled]
    assert {e["cache"] for e in compiled} == {"off"}


def test_a_steady_loop_adds_no_compile_span():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones(16)
    f(x).block_until_ready()
    n = flightrec.recorder().events_total
    for _ in range(1000):
        x = f(x)
    x.block_until_ready()
    assert flightrec.recorder().events_total == n
    assert not _named(_ring()[n:], *COMPILE_PHASES)


def test_compile_phases_hand_the_build_its_own_spans():
    f = jax.jit(lambda x: jnp.tanh(x) @ x.T)
    x = jnp.ones((8, 8))
    with flightrec.CompilePhases() as lowering:
        lowered = f.lower(x)
    with flightrec.CompilePhases() as compiling:
        lowered.compile()
    spans = {e["name"]: e for e in _ring() if e.get("fun") in ("<lambda>", "jit(<lambda>)")}
    ms = {n: (e["end_ns"] - e["start_ns"]) / 1e6 for n, e in spans.items()}
    assert lowering.ms == pytest.approx(ms["atpu/trace"] + ms["atpu/lower"], abs=0.01)
    assert compiling.ms == pytest.approx(ms["atpu/compile"])


def test_compile_phases_without_the_listener_are_the_block_itself(monkeypatch):
    monkeypatch.setattr(flightrec, "_listening", False)
    with flightrec.CompilePhases() as block:
        time.sleep(0.01)
    assert block.spans == [] and block.ms >= 10.0


def test_a_disabled_recorder_writes_no_compile_span(monkeypatch):
    monkeypatch.setattr(flightrec, "_RECORDER", FlightRecorder(enabled=False))
    jax.jit(lambda x: x - 7.0)(jnp.ones(3)).block_until_ready()
    gc.collect()
    assert flightrec.recorder().events_total == 0


def test_with_the_recorder_off_no_listener_is_registered():
    code = (
        "import gc, jax, jax.numpy as jnp\n"
        "from jax._src import monitoring\n"
        "from accelerate_tpu.telemetry import flightrec\n"
        "jax.jit(lambda x: x + 1)(jnp.ones(2)).block_until_ready()\n"
        "gc.collect()\n"
        "ours = [cb for cb in monitoring.get_event_listeners() + monitoring.get_event_duration_listeners()"
        " + gc.callbacks if getattr(cb, '__module__', '') == flightrec.__name__]\n"
        "print(flightrec._listening, len(ours), flightrec.recorder().events_total)\n"
    )
    env = dict(os.environ, ACCELERATE_FLIGHTREC="0", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["False", "0", "0"]


def test_the_listener_is_registered_once_with_the_recorder_on():
    from jax._src import monitoring

    ours = [cb for cb in monitoring.get_event_listeners() + monitoring.get_event_duration_listeners()
            + gc.callbacks if getattr(cb, "__module__", "") == flightrec.__name__]
    assert flightrec._listening and len(ours) == 3
    flightrec._listen()
    assert len([cb for cb in gc.callbacks if cb is flightrec._on_gc]) == 1


# -- generation-2 collections ------------------------------------------------------

@pytest.fixture
def no_automatic_gc():
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _done_within(fn, seconds=60.0):
    """Run ``fn`` on a thread of its own; False if it has not returned."""
    t = threading.Thread(target=fn, daemon=True)
    t.start()
    t.join(seconds)
    return not t.is_alive()


def test_a_full_collection_writes_one_gc_span(no_automatic_gc):
    garbage = [[] for _ in range(100)]
    for a, b in zip(garbage, garbage[1:] + garbage[:1]):
        a.append(b)  # a cycle only the collector frees
    del garbage, a, b
    t0 = flightrec.recorder().now_ns()
    gc.collect()
    gc.collect(1)  # a younger generation writes nothing
    spans = _named(_ring(), "atpu/gc")
    assert len(spans) == 1
    assert spans[0]["gen"] == 2 and spans[0]["collected"] >= 100
    assert t0 <= spans[0]["start_ns"] <= spans[0]["end_ns"] <= flightrec.recorder().now_ns()


def test_a_collection_inside_the_rings_critical_section_does_not_deadlock(no_automatic_gc):
    rec = flightrec.recorder()

    def collect_holding_the_lock():
        with rec._lock:  # as a collection that starts inside _append would
            gc.collect()

    assert _done_within(collect_holding_the_lock)
    assert len(_named(_ring(), "atpu/gc")) == 1


def test_collections_while_another_thread_appends_in_a_tight_loop(no_automatic_gc, monkeypatch):
    rec = FlightRecorder(capacity=2**19)  # holds every append below: none is overwritten
    monkeypatch.setattr(flightrec, "_RECORDER", rec)
    stop = threading.Event()

    def append():
        k = 0
        while not stop.is_set() and k < 2**17:
            rec.record("tick", k=k)
            with rec.span("atpu/tick"):
                k += 1

    appender = threading.Thread(target=append, daemon=True)
    appender.start()
    try:
        assert _done_within(lambda: [gc.collect() for _ in range(5)])
    finally:
        stop.set()
        appender.join(30)
    assert not appender.is_alive()
    assert len(_named(_ring(), "atpu/gc")) == 5
    ticks = [e["k"] for e in _named(_ring(), "tick")]
    assert ticks == sorted(ticks) and len(ticks) > 5


# -- the program's own set-up phases -------------------------------------------------

def test_prepare_and_the_service_construction_are_spans():
    nn.manual_seed(0)
    model = GPTLMHeadModel(GPTConfig.tiny())
    acc = Accelerator()
    model = acc.prepare(model)
    model.eval()
    DecodeService(model, ServingConfig(max_slots=2, block_size=16, prompt_bucket=16, max_request_len=64))
    events = _ring()
    prepare, init = _named(events, "atpu/setup/prepare"), _named(events, "atpu/serve/init")
    assert len(prepare) == 1 and len(init) == 1
    assert prepare[0]["start_ns"] < prepare[0]["end_ns"] <= init[0]["start_ns"] < init[0]["end_ns"]
