"""Measure the eager tape path vs compile_step on the same training step.

The migration docs promise "your unmodified imperative loop runs" (eager
op-by-op through jax.vjp closures, nn/tape.py) — this script attaches the
honest cost to that promise.  Prints one JSON line:
{"model", "platform", "eager_steps_per_sec", "captured_steps_per_sec",
 "capture_speedup"}.

Usage: python tools/eager_vs_capture.py [tiny|small] [batch] [seq]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    size = sys.argv[1] if len(sys.argv) > 1 else "tiny"
    import jax
    import jax.numpy as jnp
    import numpy as np

    import accelerate_tpu.nn as nn
    import accelerate_tpu.optim as optim
    from accelerate_tpu import Accelerator
    from accelerate_tpu.data_loader import batch_to_global_array
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel

    platform = jax.devices()[0].platform
    on_accel = platform == "tpu"
    cfg = {"tiny": GPTConfig.tiny, "small": GPTConfig.small}[size]()
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else (8 if on_accel else 2)
    seq = int(sys.argv[3]) if len(sys.argv) > 3 else (1024 if on_accel else 64)
    seq = min(seq, cfg.n_positions)
    steps = 20 if on_accel else 5

    nn.manual_seed(0)
    acc = Accelerator(mixed_precision="bf16" if on_accel else "no")
    model = GPTLMHeadModel(cfg)
    opt = optim.AdamW(model.parameters(), lr=3e-4)
    model, opt = acc.prepare(model, opt)
    ids = batch_to_global_array(
        jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
            jnp.int32,
        ),
        mesh=acc.mesh,
    )

    def step_fn(x):
        opt.zero_grad()
        out = model(x, labels=x)
        acc.backward(out["loss"])
        opt.step()
        return out["loss"]

    # -- eager: op-by-op through the tape, no capture -----------------------
    float(step_fn(ids))  # warm (per-op jit caches)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step_fn(ids)
    float(loss)
    eager_sps = steps / (time.perf_counter() - t0)

    # -- captured: one XLA program ------------------------------------------
    step = acc.compile_step(step_fn)
    float(step(ids))  # compile
    float(step(ids))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    float(loss)
    cap_sps = steps / (time.perf_counter() - t0)

    print(
        json.dumps(
            {
                "model": f"gpt-{size}",
                "platform": platform,
                "batch": batch,
                "seq": seq,
                "params_m": round(model.num_parameters / 1e6, 1),
                "eager_steps_per_sec": round(eager_sps, 2),
                "captured_steps_per_sec": round(cap_sps, 2),
                "capture_speedup": round(cap_sps / eager_sps, 1),
            }
        )
    )


if __name__ == "__main__":
    main()
