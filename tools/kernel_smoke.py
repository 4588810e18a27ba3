#!/usr/bin/env python
"""kernel_smoke — `make kernel-smoke`: prove the Pallas hot-path kernels
end-to-end on CPU in seconds (docs/kernels.md, ISSUE 12 acceptance).

Tiny GPT on the virtual 4-device mesh, every kernel armed, interpreter
mode.  Exit 0 requires:

* the IR-inspection harness passes for the policy's kernels (no all-gather
  in the collective-matmul lowering, narrow payload + in-region rounding for
  quantize-rs);
* a kernel-armed captured training run (collective_matmul + quantized_rs
  over int8 compression) is loss-BITWISE-equal to the reference run and
  replays with zero recompiles;
* the decode service, whose attention is the paged kernel (interpreted
  here), emits greedy tokens identical to ``generate()``, zero steady-state
  recompiles, and walks fewer pages than its tables hold;
* telemetry retained one ``kind="kernel"`` record per armed kernel.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _train(kernels: str, steps: int = 3):
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import (
        Accelerator,
        CompressionKwargs,
        KernelKwargs,
        TelemetryKwargs,
    )
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    Accelerator._reset_state()
    nn.manual_seed(0)
    acc = Accelerator(
        mixed_precision="bf16",
        kwargs_handlers=[
            TelemetryKwargs(enabled=True),
            CompressionKwargs(policy="int8"),
            KernelKwargs(kernels=kernels),
        ],
    )
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    opt = optim.AdamW(model.parameters(), lr=3e-4)
    model, opt = acc.prepare(model, opt)

    def step_fn(ids):
        opt.zero_grad()
        out = model(ids, labels=ids)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    step = acc.compile_step(step_fn)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(steps):
        ids = batch_to_global_array(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
            mesh=acc.mesh,
        )
        losses.append(float(step(ids)))
    return losses, acc.telemetry.recompiles_total, list(acc.telemetry.kernel_records)


def main() -> int:
    failures = []

    # 1. IR-inspection harness: the fusion structurally happened
    from accelerate_tpu.native.kernels import inspect as kernel_inspect

    try:
        facts = kernel_inspect.run_all()
        print(f"kernel_smoke: IR inspection ok ({', '.join(sorted(facts))})")
    except AssertionError as exc:
        failures.append(f"IR inspection: {exc}")

    # 2. kernel-armed captured training: bitwise losses, zero recompiles
    ref_losses, _, _ = _train("none")
    kern_losses, recompiles, records = _train("collective_matmul,quantized_rs")
    if ref_losses != kern_losses:
        failures.append(
            f"kernel-armed losses diverged: {ref_losses} vs {kern_losses}"
        )
    if recompiles != 0:
        failures.append(f"kernel-armed run recompiled {recompiles}x")
    armed = sorted(r.kernel for r in records)
    if armed != ["collective_matmul", "quantized_rs"]:
        failures.append(f"kind='kernel' records wrong: {armed}")

    # 3. the decode service over the paged-attention kernel against generate()
    import numpy as np

    import accelerate_tpu.nn as nn
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
    from accelerate_tpu.serving import DecodeService, ServingConfig

    Accelerator._reset_state()
    nn.manual_seed(0)
    model = GPTLMHeadModel(GPTConfig.tiny())
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, 100, (int(n),)).astype(np.int32) for n in (5, 11, 3)
    ]

    svc = DecodeService(
        model,
        ServingConfig(max_slots=4, block_size=8, prompt_bucket=16, max_request_len=64),
    )
    rids = [svc.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(30):
        svc.step()
        if all(r in svc.results for r in rids):
            break
    paged_toks = [list(svc.results[r].tokens) for r in rids]
    ref_toks = [
        np.asarray(model.generate(p[None], max_new_tokens=6))[0, len(p):].tolist()
        for p in prompts
    ]
    if ref_toks != paged_toks:
        failures.append(f"paged decode diverged from generate(): {ref_toks} vs {paged_toks}")
    if svc.watcher.recompile_events != 0:
        failures.append(f"paged decode recompiled {svc.watcher.recompile_events}x")
    walked, tabled = svc.stats["kv_pages_walked"], svc.stats["kv_pages_tabled"]
    if not 0 < walked < tabled:
        failures.append(f"kv_pages_walked {walked} of kv_pages_tabled {tabled}")

    print(
        f"kernel_smoke: losses {kern_losses} (bitwise vs reference), "
        f"{recompiles} recompiles, paged tokens match={ref_toks == paged_toks}, "
        f"pages walked {walked} of {tabled} tabled"
    )
    for failure in failures:
        print(f"kernel_smoke: FAIL: {failure}", file=sys.stderr)
    print(f"kernel_smoke: {'FAILED' if failures else 'ok'}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
