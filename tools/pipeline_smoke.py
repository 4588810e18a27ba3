#!/usr/bin/env python
"""pipeline_smoke — `make pipeline-smoke`: the per-stage captured programs
round-trip the AOT store across two FRESH subprocesses (docs/parallel_plan.md,
docs/aot_cache.md): the cold leg compiles and stores every ``(stage, chunk,
role)`` program of a 2-stage, V=2 interleaved 1F1B step, the warm leg loads
every one off disk with ZERO compiles at a bitwise-equal loss.

The legs run on one device each: ``StagewisePrograms.program`` loads a stored
executable onto every device of the backend, so in a process with a virtual
mesh (the test suite's 8 devices) the load of a one-device program fails its
first dispatch, and no tier-1 test can hold this yet (ROADMAP D9).  What the
rest of the plan proves — the acceptance geometry, loss parity, bubbles, the
committed layout's lowering — is in tests/test_parallel_plan.py,
tests/test_1f1b.py and tests/test_pipeline.py.
"""

import json
import os
import subprocess
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _stagewise_leg(cache_dir: str, out_path: str) -> None:
    """One stagewise process against the AOT store — runs in a FRESH
    subprocess both cold (compile + store every per-stage program) and
    warm (load every program off disk; XLA:CPU only serializes reliably
    from a process that hasn't accumulated unrelated JIT state, which is
    exactly the restart shape this leg proves anyway)."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.native.aot_cache import AOTCompilationCache
    from accelerate_tpu.parallel.pipeline import apply_layer_order
    from accelerate_tpu.parallel.plan import StagePlan
    from accelerate_tpu.parallel.stagewise import (
        StagewisePrograms,
        stagewise_train_1f1b,
    )
    from accelerate_tpu.utils.dataclasses import CompilationCacheKwargs

    S, V, L, M, dim = 2, 2, 4, 4, 8
    stage = StagePlan(num_stages=S, virtual=V, num_microbatches=M,
                      schedule="interleaved")
    plan_desc = {"schedule": "interleaved", "virtual": V, "microbatches": M,
                 "layer_layout": stage.layout}
    ks = jax.random.split(jax.random.key(0), L)
    plain = {
        "w": jnp.stack([jax.random.normal(k, (dim, dim)) * 0.5 for k in ks]),
        "b": jnp.zeros((L, dim)),
    }
    committed = apply_layer_order(plain, stage.layer_order(L))
    x = jax.random.normal(jax.random.key(1), (M, dim))
    labels = jax.random.normal(jax.random.key(2), (M, dim))
    extra = {"head": jnp.eye(dim) + 0.1}

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def loss_fn(out, lbl, e):
        err = (out @ e["head"] - lbl) ** 2
        return err.sum(), jnp.float32(err.size)

    cache = AOTCompilationCache(CompilationCacheKwargs(cache_dir=cache_dir))
    cache.set_context(plan=plan_desc)
    programs = StagewisePrograms(
        stage_fn, loss_fn, num_stages=S, virtual=V,
        cache=cache, plan_desc=plan_desc,
    )
    loss, *_ = stagewise_train_1f1b(
        stage_fn, committed, x, labels, extra, loss_fn, M,
        num_stages=S, virtual=V, programs=programs,
    )
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({
            "loss": repr(float(loss)),  # bitwise contract
            "compiled": programs.compiled,
            "loaded": programs.loaded,
            "stores": cache.stores,
            "hits": cache.hits,
            "programs": 2 * S * V,
        }, f)


def _run_stagewise_leg(cache_dir: str, label: str) -> dict:
    out_path = os.path.join(cache_dir, f"{label}.result.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # single device: no virtual mesh needed
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--stagewise-leg",
         cache_dir, out_path],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    if proc.returncode != 0:
        print(f"pipeline_smoke: stagewise {label} leg failed "
              f"rc={proc.returncode}", file=sys.stderr)
        print(proc.stderr[-4000:], file=sys.stderr)
        sys.exit(1)
    with open(out_path, encoding="utf-8") as f:
        return json.load(f)


def main() -> int:
    failures = []

    # per-stage captured programs round-trip the AOT store across fresh
    # processes: cold compiles+stores all 2·S·V programs, warm loads every
    # one with zero compiles at a bitwise-equal loss
    cache_dir = tempfile.mkdtemp(prefix="atpu_pipeline_smoke_")
    cold = _run_stagewise_leg(cache_dir, "cold")
    warm = _run_stagewise_leg(cache_dir, "warm")
    if cold["compiled"] != cold["programs"] or cold["loaded"] != 0:
        failures.append(f"stagewise cold leg: {cold}")
    if cold["stores"] != cold["programs"]:
        failures.append(
            f"stagewise cold leg stored {cold['stores']}/{cold['programs']} "
            "programs"
        )
    if warm["compiled"] != 0 or warm["loaded"] != warm["programs"]:
        failures.append(
            f"stagewise warm leg paid compiles: compiled={warm['compiled']} "
            f"loaded={warm['loaded']}/{warm['programs']}"
        )
    if warm["loss"] != cold["loss"]:
        failures.append(
            f"stagewise warm loss not bitwise-equal: cold={cold['loss']} "
            f"warm={warm['loss']}"
        )

    print(
        f"pipeline_smoke: stagewise warm {warm['loaded']}/{warm['programs']} "
        f"programs from store, {warm['compiled']} compiles"
    )
    for failure in failures:
        print(f"pipeline_smoke: FAIL: {failure}", file=sys.stderr)
    print(f"pipeline_smoke: {'FAILED' if failures else 'ok'}")
    return 1 if failures else 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--stagewise-leg":
        _stagewise_leg(sys.argv[2], sys.argv[3])
        sys.exit(0)
    sys.exit(main())
