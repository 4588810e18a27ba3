"""AOT executable cache (docs/aot_cache.md): warm restarts must dispatch the
deserialized executable with ZERO trace/compile phase time and bitwise-equal
losses; any fingerprint/entry problem must fall through to a normal compile
with a loud miss — never a crash, never a wrong-program dispatch; the
cache-off path is pinned to the pre-cache code."""

import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu.nn as nn
import accelerate_tpu.optim as optim
from accelerate_tpu import (
    Accelerator,
    CompilationCacheKwargs,
    TelemetryKwargs,
)
from accelerate_tpu.native.aot_cache import (
    AOTCompilationCache,
    fingerprint_mismatch,
    topology_fingerprint,
)
from accelerate_tpu.nn.tape import Tensor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

pytestmark = pytest.mark.usefixtures("compiled_in_this_process")


@pytest.fixture(autouse=True)
def _reset_active_cache():
    """A DecodeService constructed without an accelerator resolves the
    process-active cache (current_aot_cache) — intended for real processes,
    but between tests it would leak this file's tmp-dir caches into serving
    tests that never opted in.  Clear the module slot after every test."""
    yield
    from accelerate_tpu.native.aot_cache import _set_active

    _set_active(None)


def _fresh_accelerator(cache_dir, telemetry=True, **acc_kwargs):
    """Process-simulated fresh start: reset the library singletons and drop
    every in-memory jit/pjit cache, so only the on-disk store can skip
    trace+compile."""
    Accelerator._reset_state()
    jax.clear_caches()
    nn.manual_seed(0)
    handlers = []
    if telemetry:
        handlers.append(TelemetryKwargs(enabled=True))
    if cache_dir is not None:
        handlers.append(CompilationCacheKwargs(cache_dir=str(cache_dir)))
    return Accelerator(kwargs_handlers=handlers, **acc_kwargs)


def _linear_step(acc):
    model = nn.Linear(4, 2)
    opt = optim.SGD(model.parameters(), lr=0.1)
    model, opt = acc.prepare(model, opt)

    def step_fn(xb):
        opt.zero_grad()
        loss = model(Tensor(xb)).sum()
        acc.backward(loss)
        opt.step()
        return loss

    return acc.compile_step(step_fn)


def _run(cache_dir, n_steps=2, telemetry=True):
    acc = _fresh_accelerator(cache_dir, telemetry=telemetry)
    step = _linear_step(acc)
    xb = jnp.ones((8, 4))
    losses = [float(step(xb)) for _ in range(n_steps)]
    return acc, step, losses


# ---------------------------------------------------------------------------
# the zero-cold-start contract
# ---------------------------------------------------------------------------

def test_warm_reload_skips_trace_and_compile_bitwise_loss(tmp_path):
    cache_dir = tmp_path / "cache"
    acc1, step1, losses1 = _run(cache_dir)
    assert acc1.aot_cache.misses >= 1 and acc1.aot_cache.stores >= 1
    cold_first = acc1.telemetry.timeline.records()[0]
    assert cold_first.compile_ms > 0

    acc2, step2, losses2 = _run(cache_dir)
    warm_first = acc2.telemetry.timeline.records()[0]
    assert warm_first.built  # a build — just one that came off disk
    assert warm_first.trace_ms == 0.0 and warm_first.compile_ms == 0.0
    assert acc2.aot_cache.hits >= 1
    assert not any(
        e["event"] == "miss" and e.get("scope") == "train"
        for e in acc2.telemetry.aot_cache_events
    )
    assert losses2 == losses1  # bitwise: same program, same state
    # the loaded entry is an executable, not the plain-jit fallback
    entry = next(iter(step2._cache.values()))
    assert not hasattr(entry[0], "lower")


def test_cache_off_is_pinned(tmp_path):
    """No cache dir → the pre-cache path byte-for-byte: disabled hub handle,
    a None pin on the CapturedStep, no events, no files; with telemetry
    also off the entry is the plain jitted callable exactly as before."""
    acc, step, _ = _run(None)
    assert not acc.aot_cache.enabled
    assert step._aot_cache is None
    assert not list(acc.telemetry.aot_cache_events)
    entry = next(iter(step._cache.values()))
    assert not hasattr(entry[0], "lower")  # telemetry AOT build, as before

    acc2, step2, _ = _run(None, telemetry=False)
    assert step2._aot_cache is None
    entry2 = next(iter(step2._cache.values()))
    assert hasattr(entry2[0], "lower")  # plain jit, as before


def test_env_surface(tmp_path, monkeypatch):
    monkeypatch.setenv("ACCELERATE_AOT_CACHE", str(tmp_path / "envcache"))
    assert CompilationCacheKwargs().enabled
    monkeypatch.setenv("ACCELERATE_AOT_CACHE", "0")
    assert not CompilationCacheKwargs().enabled
    monkeypatch.delenv("ACCELERATE_AOT_CACHE")
    assert not CompilationCacheKwargs().enabled


# ---------------------------------------------------------------------------
# invalidation: stale fingerprints fall through LOUDLY, broken entries softly
# ---------------------------------------------------------------------------

def _tamper_fingerprints(cache_dir, **overrides):
    """Re-file every entry under a fake topology fingerprint (digest suffix
    AND metadata), simulating entries written by a different fleet shape."""
    for meta_path in glob.glob(os.path.join(str(cache_dir), "*-*.json")):
        if os.path.basename(meta_path).startswith("profile-"):
            continue
        with open(meta_path, encoding="utf-8") as f:
            meta = json.load(f)
        meta["fingerprint"].update(overrides)
        stem = meta_path[: -len(".json")]
        variant = os.path.basename(stem).split("-")[0]
        fake = os.path.join(str(cache_dir), f"{variant}-deadbeefdeadbeef")
        os.rename(stem + ".pkl", fake + ".pkl")
        os.remove(meta_path)
        with open(fake + ".json", "w", encoding="utf-8") as f:
            json.dump(meta, f)


def test_stale_fingerprint_falls_through_with_loud_miss(tmp_path):
    cache_dir = tmp_path / "cache"
    _, _, losses1 = _run(cache_dir)
    _tamper_fingerprints(cache_dir, device_count=999, jax="0.0.1")

    acc2, _, losses2 = _run(cache_dir)
    misses = [
        e for e in acc2.telemetry.aot_cache_events if e["event"] == "miss"
    ]
    assert misses, "stale entry produced no miss record"
    assert any(
        "device_count" in (e.get("cause") or "") and "jax" in (e.get("cause") or "")
        for e in misses
    ), misses
    # fell through to a NORMAL compile: same math, no crash
    warm_first = acc2.telemetry.timeline.records()[0]
    assert warm_first.compile_ms > 0
    assert losses2 == losses1


def test_corrupt_entry_is_fail_soft_miss(tmp_path):
    cache_dir = tmp_path / "cache"
    _, _, losses1 = _run(cache_dir)
    for pkl in glob.glob(os.path.join(str(cache_dir), "*-*.pkl")):
        with open(pkl, "wb") as f:
            f.write(b"\x00truncated")
    acc2, _, losses2 = _run(cache_dir)
    assert losses2 == losses1
    causes = [
        e.get("cause") or ""
        for e in acc2.telemetry.aot_cache_events
        if e["event"] == "miss"
    ]
    assert any("unpicklable" in c or "deserialize" in c for c in causes), causes


def test_fingerprint_mismatch_names_moved_fields():
    live = topology_fingerprint()
    stale = dict(live, device_count=3, jaxlib="9.9.9")
    cause = fingerprint_mismatch(stale, live)
    assert "device_count" in cause and "jaxlib" in cause
    assert fingerprint_mismatch(None, live) == "entry metadata carries no fingerprint"


def test_compiler_flags_in_fingerprint():
    """ROADMAP carried item: the store is keyed on compiler-mode flags too.
    The fingerprint carries them as flat ``flag:*`` fields so a stale-flag
    miss names the exact flag that moved."""
    from accelerate_tpu.native.aot_cache import FINGERPRINT_FLAGS

    live = topology_fingerprint()
    for flag in FINGERPRINT_FLAGS:
        assert f"flag:{flag}" in live, flag
    assert "flag:jax_default_matmul_precision" in live


def test_flag_flip_is_loud_miss_naming_the_flag(tmp_path):
    """A ``jax_default_matmul_precision`` flip between the storing and the
    loading process would deserialize a program compiled under the other
    numerics — it must be a fall-through miss whose cause NAMES the flag,
    never a silent wrong-precision dispatch."""
    cache_dir = tmp_path / "cache"
    prev = jax.config.jax_default_matmul_precision
    _, _, losses1 = _run(cache_dir)
    try:
        jax.config.update("jax_default_matmul_precision", "float32")
        acc2, _, _ = _run(cache_dir)
        misses = [
            e for e in acc2.telemetry.aot_cache_events if e["event"] == "miss"
        ]
        assert misses, "flag flip produced no miss record"
        assert any(
            "flag:jax_default_matmul_precision" in (e.get("cause") or "")
            for e in misses
        ), misses
        # fell through to a NORMAL compile under the new flag: no crash
        warm_first = acc2.telemetry.timeline.records()[0]
        assert warm_first.compile_ms > 0
    finally:
        jax.config.update("jax_default_matmul_precision", prev)


# ---------------------------------------------------------------------------
# size bound
# ---------------------------------------------------------------------------

def test_lru_eviction_bounds_size(tmp_path):
    from accelerate_tpu.utils.dataclasses import CompilationCacheKwargs as K

    cache = AOTCompilationCache(K(cache_dir=str(tmp_path / "lru"), max_bytes=1))
    fp = cache.fingerprint()

    def compiled_for(n):
        return jax.jit(lambda x: x * n).lower(jnp.ones((4,))).compile()

    assert cache.store("variant0", fp, compiled_for(1), None, "train", "k0")
    assert cache.store("variant1", fp, compiled_for(2), None, "train", "k1")
    # 1-byte budget: storing entry 1 evicted entry 0 (the just-written entry
    # itself is exempt, so exactly one survives)
    assert cache.evictions >= 1
    pkls = glob.glob(os.path.join(str(tmp_path / "lru"), "*-*.pkl"))
    assert len(pkls) == 1 and "variant1" in pkls[0]
    assert cache.lookup("variant0", fp, "train", "k0") is None
    assert cache.lookup("variant1", fp, "train", "k1") is not None


# ---------------------------------------------------------------------------
# trace-time side effects survive the skipped trace
# ---------------------------------------------------------------------------

def _scheduler_run(cache_dir, n_steps=3):
    acc = _fresh_accelerator(cache_dir)
    model = nn.Linear(2, 1)
    opt = optim.SGD(model.parameters(), lr=1.0)
    sched = optim.LambdaLR(opt, lambda s: 1.0 / (s + 1))
    model, opt, sched = acc.prepare(model, opt, sched)

    def step_fn(xb):
        opt.zero_grad()
        loss = model(Tensor(xb)).sum()
        acc.backward(loss)
        opt.step()
        sched.step()
        return loss

    step = acc.compile_step(step_fn)
    lrs = []
    for _ in range(n_steps):
        step(jnp.ones((2, 2)))
        lrs.append(float(opt.optimizer.lr))
    return acc, lrs


def test_scheduler_replay_survives_warm_restart(tmp_path):
    """Deferred scheduler steps are recorded at TRACE time — a warm restart
    never traces, so they ride the entry's side metadata (scheduler registry
    index) and must replay identically."""
    cache_dir = tmp_path / "cache"
    _, lrs_cold = _scheduler_run(cache_dir)
    acc2, lrs_warm = _scheduler_run(cache_dir)
    warm_first = acc2.telemetry.timeline.records()[0]
    assert warm_first.trace_ms == 0.0 and warm_first.compile_ms == 0.0
    assert acc2.aot_cache.hits >= 1
    assert lrs_warm == lrs_cold


def _accum_run(cache_dir, n_calls=4):
    acc = _fresh_accelerator(cache_dir, gradient_accumulation_steps=2)
    model = nn.Linear(4, 1)
    opt = optim.SGD(model.parameters(), lr=0.1)
    model, opt = acc.prepare(model, opt)

    def step_fn(xb):
        with acc.accumulate(model):
            loss = model(Tensor(xb)).sum()
            acc.backward(loss)
            opt.step()
            opt.zero_grad()
        return loss

    step = acc.compile_step(step_fn)
    data = np.random.default_rng(0).normal(size=(n_calls, 2, 4)).astype(np.float32)
    return acc, [float(step(jnp.asarray(data[i]))) for i in range(n_calls)]


def test_accumulate_step_warm_restart(tmp_path):
    """An accumulate-using body bakes sync_gradients into each variant and
    advances the schedule during its FIRST trace — the warm process (no
    trace) must advance it host-side via the profile sidecar, land on the
    stored keys, and reproduce the micro/sync step pattern bitwise."""
    cache_dir = tmp_path / "cache"
    acc1, losses_cold = _accum_run(cache_dir)
    assert acc1.aot_cache.stores >= 2  # one per sync variant
    acc2, losses_warm = _accum_run(cache_dir)
    warm_first = acc2.telemetry.timeline.records()[0]
    assert warm_first.trace_ms == 0.0 and warm_first.compile_ms == 0.0
    assert acc2.aot_cache.hits >= 2
    assert not any(
        e["event"] == "miss" and e.get("scope") == "train"
        for e in acc2.telemetry.aot_cache_events
    )
    assert losses_warm == losses_cold


def test_restore_prefetch_then_first_step_hits(tmp_path):
    """The preemption-resume flow: ``load_state`` runs its cache prefetch
    BEFORE the process's first captured build, so the prefetch must hash
    the same (mesh/compression-pinned) fingerprint the cold run stored
    under — a context-less fingerprint here would stage nothing and every
    later lookup would miss.  The restored step must then run off the
    deserialized executable, bitwise-continuing the interrupted run."""
    cache_dir = tmp_path / "cache"
    ckpt = tmp_path / "ckpt"
    acc1 = _fresh_accelerator(cache_dir)
    step1 = _linear_step(acc1)
    xb = jnp.ones((8, 4))
    for _ in range(2):
        float(step1(xb))
    acc1.save_state(str(ckpt))
    loss_ref = float(step1(xb))  # the step a resumed process runs next

    acc2 = _fresh_accelerator(cache_dir)
    step2 = _linear_step(acc2)
    acc2.load_state(str(ckpt))  # prefetch fires here, before any build
    assert acc2.aot_cache.last_prefetch_count >= 1
    loss2 = float(step2(xb))
    warm_first = acc2.telemetry.timeline.records()[0]
    assert warm_first.trace_ms == 0.0 and warm_first.compile_ms == 0.0
    assert acc2.aot_cache.hits >= 1
    assert loss2 == loss_ref


# ---------------------------------------------------------------------------
# serving: replica spin-up warms every bucket program from disk
# ---------------------------------------------------------------------------

def _serving_run(cache_dir):
    from accelerate_tpu import DecodeService, ServingConfig
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    acc = _fresh_accelerator(cache_dir)
    cfg = GPTConfig(vocab_size=128, n_positions=96, n_embd=32, n_layer=2, n_head=2)
    model = acc.prepare(GPTLMHeadModel(cfg))
    model.eval()
    service = DecodeService(
        model,
        ServingConfig(max_slots=2, block_size=16, prompt_bucket=16),
        telemetry=acc.telemetry,
    )
    rid = service.submit(
        np.random.default_rng(0).integers(0, 128, (9,), dtype=np.int32),
        max_new_tokens=4,
    )
    service.run()
    return service, service.results[rid].tokens


def test_serving_warm_from_disk(tmp_path):
    """Replica spin-up: every bucket program the first service STORED comes
    off disk in the second, and anything XLA:CPU's serializer refused (its
    executable export can drop function symbols once the process
    JIT-compiled other programs; verify-on-store catches that and records
    store_failed) recompiles soundly — warmed + compiles covers both
    programs, zero steady-state recompile events, identical greedy tokens.
    The cross-process zero-cold-start proof is
    ``test_scope_map_persists_across_processes``."""
    cache_dir = tmp_path / "cache"
    svc1, tokens1 = _serving_run(cache_dir)
    assert svc1.watcher.compiles_total == 2  # prefill bucket + decode
    assert svc1._aot is not None and svc1._aot.warmed == 0
    stored = len(
        [p for p in glob.glob(os.path.join(str(cache_dir), "*-*.pkl"))]
    )

    svc2, tokens2 = _serving_run(cache_dir)
    assert svc2._aot.warmed == stored  # everything stored must warm
    assert svc2._aot.warmed + svc2.watcher.compiles_total == 2
    assert svc2.recompile_events == 0
    assert tokens2 == tokens1
    if stored == 0:
        # both programs hit the XLA:CPU symbol-dedup store refusal in this
        # process — the fall-through path above is proven, but the warm
        # path ran empty; say so instead of silently passing
        pytest.skip("XLA:CPU refused to serialize both serving programs "
                    "in this process; warm path exercised with 0 entries")


# ---------------------------------------------------------------------------
# observability: metrics provider, record schema, report section
# ---------------------------------------------------------------------------

def test_metrics_provider_and_report_section(tmp_path):
    cache_dir = tmp_path / "cache"
    _run(cache_dir)
    acc, _, _ = _run(cache_dir)
    assert any(
        name == "aot_cache" for name, _ in acc.telemetry._metrics_providers
    )
    metrics = acc.aot_cache.metrics()
    assert metrics["hits_total"] >= 1 and metrics["entries"] >= 1
    assert {"misses_total", "stores_total", "bytes"} <= set(metrics)

    jsonl = str(tmp_path / "run.jsonl")
    acc.telemetry.write_jsonl(jsonl)
    from telemetry_report import load_records, render, validate

    records = load_records(jsonl)
    assert validate(records, min_steps=1) == []
    assert any(r.get("kind") == "aot_cache" for r in records)
    assert "aot executable cache" in render(records)


# ---------------------------------------------------------------------------
# scope-map persistence: warm processes keep the per-phase device split
# ---------------------------------------------------------------------------

_SCOPE_MAP_CHILD = '''
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# the suite's persistent XLA compilation cache (tests/conftest.py) strips
# HLO metadata from deserialized programs — the very failure mode this
# feature exists to survive, but here it would ALSO blank the cold child's
# store-side parse, so the children run without it
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
sys.path.insert(0, "@REPO@")
cache_dir, out_path = sys.argv[1], sys.argv[2]

import numpy as np

import accelerate_tpu.nn as nn
import accelerate_tpu.optim as optim
from accelerate_tpu import Accelerator, CompilationCacheKwargs, TelemetryKwargs
from accelerate_tpu.data_loader import batch_to_global_array
from accelerate_tpu.nn import Tensor

nn.manual_seed(0)
acc = Accelerator(
    kwargs_handlers=[
        TelemetryKwargs(enabled=True, profile_every_n=1),
        CompilationCacheKwargs(cache_dir=cache_dir),
    ]
)
model = nn.Linear(16, 8)
opt = optim.AdamW(model.parameters(), lr=1e-2)
model, opt = acc.prepare(model, opt)


def step_fn(x):
    opt.zero_grad()
    loss = model(Tensor(x)).sum()
    acc.backward(loss)
    opt.step()
    return loss


step = acc.compile_step(step_fn)
rng = np.random.default_rng(0)
x = batch_to_global_array(
    np.asarray(rng.normal(size=(8, 16)), np.float32), mesh=acc.mesh
)
losses = [repr(float(step(x))) for _ in range(2)]  # repr keeps the whole float
first = acc.telemetry.timeline.records()[0]
result = {
    "losses": losses,
    "first_trace_ms": first.trace_ms,
    "first_compile_ms": first.compile_ms,
    "hits": acc.aot_cache.hits,
    "stores": acc.aot_cache.stores,
    "phases_per_sample": [
        sorted(r.phases) for r in acc.telemetry.device_records
    ],
}
with open(out_path, "w") as f:
    json.dump(result, f)
'''


def test_scope_map_persists_across_processes(tmp_path):
    """ROADMAP carried item: programs deserialized from the AOT store carry
    no HLO metadata, so a warm process used to sample EMPTY ``phases`` —
    the op→scope map is now persisted beside the executable and restored on
    load.  Two real subprocesses (nothing in memory survives: the shape of a
    preempted-and-rescheduled job or an autoscaled replica): the cold one
    compiles/stores with every step profiled, the warm one deserializes
    (zero trace/compile, losses bitwise the cold run's) and its samples must
    STILL split by atpu phase."""
    import subprocess

    child = tmp_path / "child.py"
    child.write_text(_SCOPE_MAP_CHILD.replace("@REPO@", REPO))
    cache_dir = str(tmp_path / "aot")

    def run(label):
        out = str(tmp_path / f"{label}.json")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, str(child), cache_dir, out],
            env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
        )
        assert proc.returncode == 0, (
            f"{label} child failed\n{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}"
        )
        with open(out, encoding="utf-8") as f:
            return json.load(f)

    cold = run("cold")
    assert cold["stores"] >= 1 and cold["first_compile_ms"] > 0
    # the cold process compiled in-process: its samples carry phases from
    # the live HLO parse — the baseline the warm process must match
    assert cold["phases_per_sample"], "cold run sampled nothing"
    assert any(
        any(p.startswith("atpu") for p in phases)
        for phases in cold["phases_per_sample"]
    ), cold["phases_per_sample"]

    warm = run("warm")
    assert warm["hits"] >= 1
    assert warm["first_trace_ms"] == 0.0 and warm["first_compile_ms"] == 0.0, (
        "warm child recompiled — the store did not serve the program"
    )
    assert warm["losses"] == cold["losses"]  # the same program, bit for bit
    # THE pin: a metadata-less deserialized program still splits by phase,
    # because the stored scope map was restored into the telemetry hub
    assert warm["phases_per_sample"], "warm run sampled nothing"
    assert any(
        any(p.startswith("atpu") for p in phases)
        for phases in warm["phases_per_sample"]
    ), f"warm samples lost the per-phase split: {warm['phases_per_sample']}"


_WARM_CACHE_CHILD = r'''
import sys

sys.path.insert(0, "@REPO@/tests")
sys.path.insert(0, "@REPO@")
from conftest import fresh_executables  # the suite's setup: cache at $JAX_COMPILATION_CACHE_DIR

import jax
import jax.numpy as jnp
import numpy as np

from accelerate_tpu import CompilationCacheKwargs
from accelerate_tpu.native.aot_cache import AOTCompilationCache, _deserialize


def greedy_pick(lengths, logits):
    """A serving program in small: each row's greedy token among its live
    positions."""
    live = jnp.arange(logits.shape[1])[None, :] < lengths[:, None]
    return jnp.argmax(jnp.where(live, logits, -jnp.inf), axis=-1)


args = (jnp.array([3, 5], jnp.int32), jnp.arange(32.0).reshape(2, 16))
pick = jax.jit(greedy_pick)
want = np.asarray(pick(*args))  # compiled, or loaded from the warm cache
if sys.argv[1] == "store":
    with fresh_executables():
        cache = AOTCompilationCache(CompilationCacheKwargs(cache_dir=sys.argv[2]))
        fp = cache.fingerprint()
        assert cache.store("pick", fp, pick.lower(*args).compile(), None, "serve", "pick")
        entry = cache.lookup("pick", fp, "serve", "pick")
        got = np.asarray(_deserialize(entry, jax.devices()[:1])(*args))
    np.testing.assert_array_equal(got, want)
'''


def test_a_warm_persistent_cache_never_reaches_the_store(tmp_path):
    """What the AOT store holds was compiled by the process that stored it.
    A first process fills a private persistent XLA cache with a tiny
    serving program; a second loads that program from the cache and
    dispatches it, then stores and loads it under ``fresh_executables`` and
    dispatches the entry.  An executable that came out of the persistent
    cache would load from the store and die at its first dispatch
    ("Function iota_reduce_fusion not found"); the in-memory caches the
    first dispatch filled are what would hand it back."""
    import subprocess

    child = tmp_path / "child.py"
    child.write_text(_WARM_CACHE_CHILD.replace("@REPO@", REPO))
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    for phase in ("warm", "store"):
        proc = subprocess.run(
            [sys.executable, str(child), phase, str(tmp_path / "aot")],
            env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
        )
        assert proc.returncode == 0, f"{phase} child failed\n{proc.stderr[-4000:]}"
    assert os.listdir(tmp_path / "jax_cache"), "the first child cached nothing"


def test_jax_cache_layer_disarmed_for_scope_dependent_runs(tmp_path, monkeypatch):
    """ROADMAP carried item, second layer: executables served by jax's OWN
    XLA compilation cache (``jax_cache_dir``) carry no HLO metadata and no
    side payload to persist a scope map in — a device-time-sampling run
    would read empty ``phases`` from every cache-served program.  Attaching
    a profiler-armed telemetry hub must therefore DISARM that layer (with a
    kind="aot_cache" record saying why); a hub without device-time sampling
    keeps it, because nothing scope-dependent ever reads the maps.  The
    disarm is a PROCESS-WIDE latch: jax's config is global, so a cache
    constructed after the disarm must not silently re-arm the layer while
    the sampler is still live (review-pinned)."""
    from accelerate_tpu.native import aot_cache as aot_mod
    from accelerate_tpu.telemetry import Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryKwargs

    saved = jax.config.jax_compilation_cache_dir
    jax_dir = str(tmp_path / "jaxcache")
    # the suite's own placement (conftest) would win over jax_cache_dir —
    # the one rule, tested in test_compile_cache_placement.py; this test is
    # about arming and disarming, so it runs with the environment unset
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        # a hub WITHOUT device-time sampling: the layer stays armed
        cache = AOTCompilationCache(CompilationCacheKwargs(
            cache_dir=str(tmp_path / "aot1"), jax_cache_dir=jax_dir,
        ))
        hub_plain = Telemetry(TelemetryKwargs(enabled=True))
        assert hub_plain.profiler is None
        cache.attach_telemetry(hub_plain)
        assert jax.config.jax_compilation_cache_dir == jax_dir

        # a scope-dependent hub (profile_every_n): the layer is disarmed
        cache2 = AOTCompilationCache(CompilationCacheKwargs(
            cache_dir=str(tmp_path / "aot2"), jax_cache_dir=jax_dir,
        ))
        hub = Telemetry(TelemetryKwargs(enabled=True, profile_every_n=1))
        assert hub.profiler is not None
        cache2.attach_telemetry(hub)
        assert jax.config.jax_compilation_cache_dir is None
        events = [
            r for r in hub.all_records()
            if r.get("kind") == "aot_cache"
            and r.get("event") == "jax_cache_layer_disarmed"
        ]
        assert events and "metadata" in events[0]["cause"]

        # THE latch pin: a cache constructed AFTER the disarm (a second
        # Accelerator, a serving replica) must NOT re-arm the global layer
        # while the profiler-armed hub is still sampling
        AOTCompilationCache(CompilationCacheKwargs(
            cache_dir=str(tmp_path / "aot3"), jax_cache_dir=jax_dir,
        ))
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
        aot_mod._set_jax_cache_layer_disarmed(False)
