#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, at
the full width of GPT-2-small (124M parameters, all 12 layers, vocab 50257
padded to 50304, bf16), and checks what comes out by the repo's own means.
Run with no arguments on a machine with one TPU chip:

1. **launch** — ``accelerate-tpu launch chip_smoke.py --launched-worker``:
   the CLI starts a child that trains a few steps.  It runs FIRST because a
   chip belongs to one process at a time and a process cannot hand it back:
   this parent has not touched a JAX backend yet (the CLI never does), so
   the child gets the chip, exits, and only then does the parent take it.
2. **train** — ``Accelerator(mixed_precision="bf16")`` → ``prepare`` →
   ``compile_step`` → 8 steps at 12×1024 from a prepared data loader.
   Losses finite and falling, the flash kernels (and nothing else) in the
   compiled step's HLO as ``tpu_custom_call``, zero recompiles after warm-up.
3. **serve** — ``DecodeService`` over the trained model, paged KV cache,
   staggered requests of different prompt lengths at ``decode_steps`` 1 and
   8.  Greedy tokens equal ``model.generate()`` per request — or leave it
   at a tie, the two tokens one step of the logits' dtype apart
   (``first_divergence``) — zero recompile events, no leaked cache blocks.

``--chips 4`` runs, and runs only, the sharded path: the same model and
batches under ``ParallelismConfig(fsdp_size=4)`` and under dp=4, compared
with each other and with a plain single-device ``jax.jit`` forward; then
the serve phase over the model through ``shard_for_inference`` on an
fsdp=4 mesh (weights sharded, the KV pools replicated on the mesh: the
decode program's attention kernel runs per device under ``shard_map``, and
only a TPU's lowering refuses it bare).  ``--sharded train`` or ``--sharded
serve`` runs one of the two.

``--rehearse-cpu`` is the rehearsal of the on-chip-measurement guide (§2
step 1, and step 2 with ``--chips 4``): the same control flow at tiny sizes
on the CPU backend with the flash kernels interpreted.  Its last line names
``"platform": "cpu"``, so it cannot be read as a chip pass.

Any phase that fails raises: the script exits non-zero with the traceback
and prints no result line.  Without ``--rehearse-cpu`` a platform other
than ``tpu`` is a failure.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
everything else (step times, losses, first-step seconds, cache directory,
HLO checks, per-phase results) is on earlier lines.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import subprocess
import sys
import time

SELF = os.path.abspath(__file__)
SEED = 0
# bf16 compute, fp32 loss: two layouts of one step differ by the order of
# their sums, a bf16 ulp (2^-8) at a time — allow about two on the loss
LOSS_RTOL = 1e-2
LAUNCH_TIMEOUT_S = 900


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized to: the chip's real widths, or the rehearsal's."""

    cfg: object
    batch: int
    seq: int
    steps: int
    worker_steps: int
    # the last request fills its slot: prompt + budget = max_request_len, so
    # at decode_steps 8 its last block overruns to a position past the
    # slot's table (docs/serving.md §multi-token: overrun safety)
    prompt_lens: tuple
    budgets: tuple
    max_request_len: int


def sizes_for(rehearse: bool) -> Sizes:
    from accelerate_tpu.models import GPTConfig

    if rehearse:
        # tiny() with two heads instead of four: head_dim 64 is the smallest
        # the flash kernel tiles, so the rehearsal traces the same attention
        # path the chip runs
        return Sizes(
            cfg=dataclasses.replace(GPTConfig.tiny(), n_head=2),
            batch=4, seq=128, steps=8, worker_steps=3,
            prompt_lens=(5, 19, 40, 33, 105), budgets=(12, 17, 9, 20, 23),
            max_request_len=128,
        )
    return Sizes(
        cfg=GPTConfig.small(),
        batch=12, seq=1024, steps=8, worker_steps=3,
        prompt_lens=(5, 19, 40, 70, 33, 12, 233), budgets=(20, 33, 17, 25, 40, 9, 23),
        max_request_len=256,
    )


def require_device(expect_platform: str, expect_count: int) -> dict:
    """The device as JAX reports it — or a failure: no result is printed
    for another platform or another number of chips than was asked."""
    import jax

    devices = jax.devices()
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if (report["platform"], report["count"]) != (expect_platform, expect_count):
        raise RuntimeError(
            f"chip_smoke needs {expect_count} {expect_platform!r} device(s), "
            f"JAX reports {report}"
        )
    return report


def require_native_loader() -> None:
    """The data loader collates through the C++ host library when it is
    there and through numpy when it is not; here a failed build is a
    failure, not a quieter path."""
    from accelerate_tpu import native

    if not native.available():
        raise RuntimeError(f"native host library unavailable: {native.load_error()}")


# ---------------------------------------------------------------------------
# training: the README quickstart, at the given sizes
# ---------------------------------------------------------------------------
def make_rows(sizes: Sizes, n_batches: int = 2):
    import numpy as np

    rng = np.random.default_rng(SEED)
    rows = rng.integers(
        0, sizes.cfg.vocab_size, (n_batches * sizes.batch, sizes.seq), dtype=np.int32
    )
    return [{"input_ids": r, "labels": r} for r in rows]


def build_trainer(sizes: Sizes, parallelism_config=None):
    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator, TelemetryKwargs, prepare_data_loader
    from accelerate_tpu.models import GPTLMHeadModel

    nn.manual_seed(SEED)
    # mixed precision comes from the launcher's env protocol in the launched
    # worker and from the argument everywhere else — the same value
    accelerator = Accelerator(
        mixed_precision=os.environ.get("ACCELERATE_MIXED_PRECISION", "bf16"),
        parallelism_config=parallelism_config,
        kwargs_handlers=[TelemetryKwargs(enabled=True)],
    )
    model = GPTLMHeadModel(sizes.cfg)
    optimizer = optim.AdamW(model.parameters(), lr=3e-4)
    # batch_size is per batch shard; the global batch stays sizes.batch
    shards = accelerator.mesh.shape["dp"] * accelerator.mesh.shape["fsdp"]
    loader = prepare_data_loader(
        dataset=make_rows(sizes), batch_size=sizes.batch // shards
    )
    model, optimizer, loader = accelerator.prepare(model, optimizer, loader)

    def train_step(batch):
        optimizer.zero_grad()
        out = model(batch["input_ids"], labels=batch["labels"])
        accelerator.backward(out["loss"])
        optimizer.step()
        return out["loss"]

    return accelerator, model, optimizer, loader, accelerator.compile_step(train_step)


def run_steps(accelerator, loader, step, n_steps: int) -> dict:
    """``n_steps`` over the loader (epochs repeat its two batches, so the
    loss falls by memorisation).  The first two steps are warm-up — the
    compile, then the first replay on a carried-over state layout — and any
    recompile after them is counted."""
    losses, times = [], []
    recompiles_after_warmup = None
    while len(losses) < n_steps:
        for batch in loader:
            if len(losses) == 2:
                recompiles_after_warmup = accelerator.telemetry.recompiles_total
            t0 = time.perf_counter()
            losses.append(float(step(batch)))  # float() waits for the device
            times.append(time.perf_counter() - t0)
            if len(losses) == n_steps:
                break
    recompiled = (
        accelerator.telemetry.recompiles_total - recompiles_after_warmup
        if recompiles_after_warmup is not None
        else 0
    )
    steady = sorted(times[2:])
    build = accelerator.telemetry.timeline.first_build()
    return {
        "losses": [round(l, 4) for l in losses],
        "first_step_s": round(times[0], 2),
        # of which: tracing the step, and compiling it or loading it from
        # the compilation cache
        "first_trace_s": round(build.trace_ms / 1e3, 2),
        "first_compile_s": round(build.compile_ms / 1e3, 2),
        "step_ms_median": round(steady[len(steady) // 2] * 1e3, 2) if steady else None,
        "recompiles_after_warmup": recompiled,
    }


def check_losses(losses: list) -> None:
    if not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")


# a Pallas kernel in compiled HLO: one custom-call instruction, named after
# the pallas_call's ``name`` ("%flash_fwd.3", "%transpose_jvp_flash_bwd__.1").
# Instruction names, unlike op metadata, survive the compilation cache.
_CUSTOM_CALL_RE = re.compile(
    r'%([\w\-]+?)[.\d]* = [^\n]*?custom_call_target="tpu_custom_call"'
)


def pallas_kernels(hlo: str) -> list:
    return _CUSTOM_CALL_RE.findall(hlo)


def check_step_hlo(step, on_tpu: bool) -> dict:
    """The compiled step really holds the flash kernels — it did not take
    the ``sdpa_reference`` branch — and, with no ``KernelPolicy`` armed, no
    other Pallas kernel was traced at all."""
    texts = step.compiled_hlo()
    if not texts:
        raise AssertionError("the step holds no compiled executable to inspect")
    calls = [name for t in texts for name in pallas_kernels(t)]
    n_calls, names = len(calls), sorted(set(calls))
    report = {"tpu_custom_calls": n_calls, "kernels": names, "variants": len(texts)}
    if on_tpu:
        if n_calls == 0:
            raise AssertionError("no tpu_custom_call in the compiled step's HLO")
        strangers = [n for n in names if "flash" not in n]
        if not names or strangers:
            raise AssertionError(
                f"expected exactly the flash kernels as tpu_custom_call, found {names}"
            )
    elif not any("_flash_kernel" in t for t in texts):
        # interpreted kernels leave no custom call, only their source frames
        raise AssertionError("the rehearsal did not trace the flash kernel")
    return report


def train_phase(sizes: Sizes, on_tpu: bool):
    accelerator, model, _, loader, step = build_trainer(sizes)
    result = run_steps(accelerator, loader, step, sizes.steps)
    result["hlo"] = check_step_hlo(step, on_tpu)
    result["params_m"] = round(model.num_parameters / 1e6, 1)
    say("train", batch=sizes.batch, seq=sizes.seq, **result)
    check_losses(result["losses"])
    if result["recompiles_after_warmup"] != 0:
        raise AssertionError(
            f"{result['recompiles_after_warmup']} recompile(s) after warm-up"
        )
    return model


# ---------------------------------------------------------------------------
# launch: the CLI parent stays off JAX, the worker takes the chip and leaves
# ---------------------------------------------------------------------------
def launched_worker(rehearse: bool) -> None:
    """What ``accelerate-tpu launch`` runs: a few of the train phase's steps."""
    sizes = sizes_for(rehearse)
    device = require_device("cpu" if rehearse else "tpu", 1)
    accelerator, _, _, loader, step = build_trainer(sizes)
    result = run_steps(accelerator, loader, step, sizes.worker_steps)
    check_losses(result["losses"])
    say("launch-worker", device=device,
        mixed_precision=accelerator.mixed_precision, **result)


def launch_phase(rehearse: bool) -> None:
    argv = [
        sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
        "--mixed_precision", "bf16", SELF, "--launched-worker",
    ]
    if rehearse:
        argv.append("--rehearse-cpu")
    env = os.environ.copy()
    # one device for the worker, whatever this process was given
    env.pop("XLA_FLAGS", None)
    t0 = time.perf_counter()
    proc = subprocess.run(
        argv, env=env, stdout=subprocess.PIPE, text=True, timeout=LAUNCH_TIMEOUT_S
    )
    sys.stdout.write(proc.stdout)
    sys.stdout.flush()
    if proc.returncode != 0:
        raise RuntimeError(f"accelerate-tpu launch exited with {proc.returncode}")
    lines = [l for l in proc.stdout.splitlines() if l.startswith('{"phase": "launch-worker"')]
    if not lines:
        raise RuntimeError("the launched worker printed no result")
    worker = json.loads(lines[-1])
    if worker["mixed_precision"] != "bf16":
        raise AssertionError(
            f"the launcher's env protocol did not reach the worker: {worker}"
        )
    say("launch", wall_s=round(time.perf_counter() - t0, 1),
        worker_first_step_s=worker["first_step_s"])


# ---------------------------------------------------------------------------
# serving: continuous batching over the paged cache vs generate()
# ---------------------------------------------------------------------------
def first_divergence(model, got, want, prompt_len: int) -> dict:
    """Where a served request left ``generate()``, and how close the call
    was: the top-2 margin of teacher-forced logits at that position.
    ``tie``: the two tokens ARE that top 2 and lie one step of the logits'
    dtype apart or less.  The decode kernel sums in chunks, so its logits
    agree with ``generate()``'s to summation order and not bitwise
    (docs/serving.md §parity): at such a position either token is the
    argmax of some program, this forward included, and what follows a flip
    is another sequence."""
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn

    n = min(len(got), len(want))
    pos = next((i for i in range(n) if got[i] != want[i]), n)
    with nn.no_grad():
        raw = model(np.asarray(want[:pos])[None])["logits"].data[0, -1]
    logits = np.asarray(raw, np.float32)
    order = np.argsort(logits)
    margin = float(logits[order[-1]] - logits[order[-2]])
    # one step of the dtype at the best logit's size
    step = float(jnp.finfo(raw.dtype).eps) * 2.0 ** math.floor(math.log2(abs(float(logits[order[-1]])) or 1.0))
    both = pos < len(got) and pos < len(want)
    return {
        "position": pos,
        "new_token_index": pos - prompt_len,
        "served": int(got[pos]) if pos < len(got) else None,
        "generate": int(want[pos]) if pos < len(want) else None,
        "top2_margin": margin,
        "logit_served": float(logits[got[pos]]) if pos < len(got) else None,
        "logit_generate": float(logits[want[pos]]) if pos < len(want) else None,
        "tie": both and {int(got[pos]), int(want[pos])} == {int(order[-1]), int(order[-2])}
        and margin <= step,
    }


def serve_leg(model, sizes: Sizes, decode_steps: int) -> list:
    import numpy as np

    from accelerate_tpu import DecodeService, ServingConfig
    from accelerate_tpu.serving import bucket_length

    bucket = 32
    service = DecodeService(
        model,
        ServingConfig(
            max_slots=4, block_size=16, prompt_bucket=bucket,
            max_request_len=sizes.max_request_len, decode_steps=decode_steps,
        ),
    )
    rng = np.random.default_rng(SEED + 1)
    prompts = [
        rng.integers(0, sizes.cfg.vocab_size, (n,), dtype=np.int32)
        for n in sizes.prompt_lens
    ]
    # warm-up: one request per prefill bucket, and the decode program
    t0 = time.perf_counter()
    for b in sorted({bucket_length(n, bucket) for n in sizes.prompt_lens}):
        room = sizes.max_request_len - (decode_steps + 1)  # the last bucket is the capacity
        service.submit(np.ones(min(b, room), np.int32), max_new_tokens=decode_steps + 1)
    service.run()
    warm_s = time.perf_counter() - t0
    warm_compiles = service.watcher.compiles_total

    # staggered arrivals: requests join while earlier ones are mid-decode
    t0 = time.perf_counter()
    rids, pending = [], list(zip(prompts, sizes.budgets))
    while pending or service.has_work:
        for _ in range(2):
            if pending:
                prompt, budget = pending.pop(0)
                rids.append(service.submit(prompt, max_new_tokens=budget))
        service.step()
    serve_s = time.perf_counter() - t0

    left = []
    for rid, prompt, budget in zip(rids, prompts, sizes.budgets):
        want = np.asarray(model.generate(prompt[None], max_new_tokens=budget))[0]
        got = service.results[rid].output_ids
        if not np.array_equal(got, want):
            left.append(
                {"request": rid, "prompt_len": len(prompt),
                 **first_divergence(model, got, want, len(prompt))}
            )
    diverged = [d for d in left if not d["tie"]]
    service.pool.check_no_leaks()
    say(
        "serve", decode_steps=decode_steps, requests=len(rids),
        prompt_lens=list(sizes.prompt_lens), new_tokens=list(sizes.budgets),
        equal_to_generate=len(rids) - len(left),
        left_at_a_tie=[d for d in left if d["tie"]], diverged=diverged,
        warmup_compiles=warm_compiles, warmup_s=round(warm_s, 2),
        recompile_events=service.recompile_events,
        host_syncs_per_token=round(service.host_syncs_per_token, 3),
        serve_s=round(serve_s, 2),
    )
    if service.recompile_events != 0:
        raise AssertionError(
            f"decode_steps={decode_steps}: {service.recompile_events} recompile "
            "event(s) after warm-up"
        )
    if service.pool.free_blocks != service.pool.usable_blocks:
        raise AssertionError(f"decode_steps={decode_steps}: the block pool did not drain")
    return diverged


def serve_phase(model, sizes: Sizes) -> None:
    model.eval()
    # both legs report before either fails: one run shows the whole picture
    diverged = {n: serve_leg(model, sizes, n) for n in (1, 8)}
    if any(diverged.values()):
        raise AssertionError(
            "served tokens diverge from generate(): "
            + ", ".join(f"decode_steps={n}: {len(d)} request(s)" for n, d in diverged.items())
        )


# ---------------------------------------------------------------------------
# four chips: the sharded path and what it is compared with
# ---------------------------------------------------------------------------
def plain_forward_loss(cfg, host_params, host_ids) -> float:
    """Step-0 loss by a plain single-device ``jax.jit`` forward of the same
    parameters on ``jax.devices()[0]`` — a fresh module, no Accelerator, no
    mesh, no capture."""
    import jax

    import accelerate_tpu.nn as nn
    from accelerate_tpu.models import GPTLMHeadModel

    device = jax.devices()[0]
    model = GPTLMHeadModel(cfg)

    @jax.jit
    def forward(params, ids):
        with nn.no_grad():
            model.bind_params(params)
            return model(ids, labels=ids)["loss"].data

    return float(
        forward(jax.device_put(host_params, device), jax.device_put(host_ids, device))
    )


def count_collectives(hlo: str) -> dict:
    """Collective instructions in compiled HLO, by kind (async ``-start``
    forms included)."""
    return {
        name: len(re.findall(rf"(?<![\w-]){name}(?:-start)?\(", hlo))
        for name in ("all-gather", "reduce-scatter", "all-reduce", "collective-permute")
    }


def sharded_leg(sizes: Sizes, label: str, parallelism_config, expect_spread: bool,
                on_tpu: bool) -> dict:
    import jax

    accelerator, model, optimizer, loader, step = build_trainer(
        sizes, parallelism_config
    )
    n_dev = len(jax.devices())
    report = {
        "label": label,
        "mesh": {k: v for k, v in accelerator.mesh.shape.items() if v > 1},
        "zero1": accelerator.state.zero1_enabled,
    }
    if expect_spread:
        report.update(check_spread(model, optimizer, n_dev))
    if on_tpu:
        in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        report["bytes_in_use_after_prepare"] = in_use
        if expect_spread and max(in_use) > 1.25 * min(in_use):
            raise AssertionError(
                f"{label}: per-device memory after prepare is uneven: {in_use}"
            )
    # what the single-device reference forward will be given: the prepared
    # parameters and the first batch, copied to the host before any update
    host_params = jax.device_get(model.param_pytree())
    host_ids = jax.device_get(next(iter(loader))["input_ids"])
    report.update(run_steps(accelerator, loader, step, 5))
    hlo = "\n".join(step.compiled_hlo())
    report["collectives"] = count_collectives(hlo)
    report["tpu_custom_calls"] = len(pallas_kernels(hlo))
    say("sharded", **report)
    check_losses(report["losses"])
    if report["recompiles_after_warmup"] != 0:
        raise AssertionError(
            f"{label}: {report['recompiles_after_warmup']} recompile(s) after warm-up"
        )
    # the sharded state is gathered for use and the gradients are reduced
    # back onto the shards.  The reduction has no one name: XLA:CPU leaves it
    # as all-reduce + slice, and the v5e compiler, on the four real chips,
    # leaves no instruction named reduce-scatter either — combined
    # all-reduces, and collective-permutes inside matmul-fused
    # async_collective_fusions (PERF.md, PR 22)
    reducing = ("reduce-scatter", "all-reduce", "collective-permute")
    if expect_spread and not (
        report["collectives"]["all-gather"]
        and any(report["collectives"][n] for n in reducing)
    ):
        raise AssertionError(
            f"{label}: the compiled step lacks an all-gather or any of "
            f"{reducing}: {report['collectives']}"
        )
    if on_tpu and report["tpu_custom_calls"] == 0:
        raise AssertionError(f"{label}: no tpu_custom_call in the compiled step")
    accelerator.free_memory()
    type(accelerator)._reset_state()
    return {**report, "host_params": host_params, "host_ids": host_ids}


def check_spread(model, optimizer, n_dev: int) -> dict:
    """Parameters and optimizer state really live on every device: each
    sharded array's addressable shards sit on ``n_dev`` distinct devices
    and hold 1/n_dev of it."""

    def spread(arrays, what):
        sharded = 0
        for leaf in arrays:
            if leaf.sharding.is_fully_replicated:
                continue
            sharded += 1
            shards = leaf.addressable_shards
            devices = {s.device for s in shards}
            if len(devices) != n_dev or shards[0].data.size * n_dev != leaf.size:
                raise AssertionError(
                    f"{what} leaf {leaf.shape} is not spread over {n_dev} devices: "
                    f"{len(devices)} devices, shard {shards[0].data.shape}"
                )
        if sharded == 0:
            raise AssertionError(f"no {what} array is sharded")
        return {f"{what}_sharded": sharded, f"{what}_arrays": len(arrays)}

    out = spread([p.data for p in model.parameters()], "params")
    state_arrays, _ = optimizer.optimizer.sharded_state_arrays()
    out.update(spread(list(state_arrays.values()), "opt_state"))
    return out


def close(a: float, b: float) -> bool:
    return abs(a - b) <= LOSS_RTOL * abs(b)


def sharded_phase(sizes: Sizes, on_tpu: bool) -> None:
    import jax
    import numpy as np

    from accelerate_tpu import ParallelismConfig

    fsdp = sharded_leg(sizes, "fsdp=4", ParallelismConfig(fsdp_size=4), True, on_tpu)
    dp = sharded_leg(sizes, "dp=4", ParallelismConfig(), False, on_tpu)
    # same seed: both legs start from the same parameters and batch
    leaves = [jax.tree_util.tree_leaves(leg["host_params"]) for leg in (fsdp, dp)]
    same = np.array_equal(fsdp["host_ids"], dp["host_ids"]) and all(
        np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(*leaves)
    )
    if not same:
        raise AssertionError("the two legs did not start from the same parameters and batch")
    plain = plain_forward_loss(sizes.cfg, fsdp["host_params"], fsdp["host_ids"])
    say("sharded-compare", loss_rtol=LOSS_RTOL, fsdp=fsdp["losses"], dp=dp["losses"],
        single_device_forward_step0=round(plain, 4))
    for i, (a, b) in enumerate(zip(fsdp["losses"], dp["losses"])):
        if not close(a, b):
            raise AssertionError(
                f"step {i}: fsdp=4 loss {a} and dp=4 loss {b} differ by more "
                f"than {LOSS_RTOL:.0e} relative"
            )
    for leg in (fsdp, dp):
        if not close(leg["losses"][0], plain):
            raise AssertionError(
                f"{leg['label']}: step-0 loss {leg['losses'][0]} and the "
                f"single-device forward {plain} differ by more than "
                f"{LOSS_RTOL:.0e} relative"
            )


def sharded_serve_phase(sizes: Sizes) -> None:
    """The serve phase over a model whose weights are sharded on every chip
    (fsdp): the service commits its pools replicated on that mesh, and the
    decode program is one program over all of them."""
    import jax
    import jax.numpy as jnp

    import accelerate_tpu.nn as nn
    from accelerate_tpu import shard_for_inference
    from accelerate_tpu.models import GPTLMHeadModel
    from accelerate_tpu.parallel.mesh import make_mesh

    nn.manual_seed(SEED)
    model = GPTLMHeadModel(sizes.cfg)
    for p in model.parameters():
        p.data = p.data.astype(jnp.bfloat16)
    model = shard_for_inference(model, mesh=make_mesh({"fsdp": len(jax.devices())}))
    spread = sorted({len(p.data.sharding.device_set) for p in model.parameters()})
    say("sharded-serve", mesh={k: v for k, v in model.atpu_mesh.shape.items() if v > 1},
        devices_a_parameter_spans=spread)
    if spread[-1] != len(jax.devices()):
        raise AssertionError(f"no parameter spans every device: {spread}")
    serve_phase(model, sizes)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: run only the sharded path and what it is compared with")
    parser.add_argument("--sharded", default="all", choices=("all", "train", "serve"),
                        help="with --chips 4: the sharded train legs, the sharded serve "
                        "phase, or both")
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="same control flow at tiny sizes on the CPU backend; "
                        "the result line names platform cpu")
    parser.add_argument("--launched-worker", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    if args.rehearse_cpu:
        # the environment, set before jax is imported, alone selects the backend
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.chips}"
            )
        # trace the flash kernels (interpreted off-TPU), not the reference
        os.environ["ACCELERATE_TPU_FLASH"] = "1"
    elif "tpu" not in os.environ.get("JAX_PLATFORMS", "tpu").lower().split(","):
        raise SystemExit(
            f"chip_smoke: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']!r} holds JAX "
            "off the TPU; run on the chip, or pass --rehearse-cpu"
        )
    expect_platform = "cpu" if args.rehearse_cpu else "tpu"

    from accelerate_tpu import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    if args.launched_worker:
        launched_worker(args.rehearse_cpu)
        return 0

    sizes = sizes_for(args.rehearse_cpu)
    say("start", chips=args.chips, rehearse_cpu=args.rehearse_cpu,
        compilation_cache_dir=cache_dir)
    t0 = time.perf_counter()
    if args.chips == 4:
        device = require_device(expect_platform, 4)
        require_native_loader()
        if args.sharded != "serve":
            sharded_phase(sizes, on_tpu=not args.rehearse_cpu)
        if args.sharded != "train":
            sharded_serve_phase(sizes)
    else:
        launch_phase(args.rehearse_cpu)  # before this process touches a backend
        device = require_device(expect_platform, 1)
        require_native_loader()
        model = train_phase(sizes, on_tpu=not args.rehearse_cpu)
        serve_phase(model, sizes)
    say("done", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
