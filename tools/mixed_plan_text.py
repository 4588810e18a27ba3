#!/usr/bin/env python
"""What a mixed layer plan's serving programs lower to, as hashes of their
StableHLO text (``.lower().as_text()``, locations off), at the shapes of the
``nemotron-3-nano-30b-a3b.serve-chat`` cell: the four prefill buckets and the
decode program, 26 layers, 64 slots, 3073 blocks of 16.  Nothing is compiled
and no weight is drawn (shapes only), so it runs on the CPU in seconds.

Run it from the root of two checkouts and compare the lines: a change that
leaves a mixed plan's programs alone prints the same five hashes.  The decode
program holds one lowered state-space kernel for its Mamba layers
(``pallas_imported true``; its attention layers keep the gather, PERF.md §6):

    JAX_PLATFORMS=cpu python tools/mixed_plan_text.py          # this tree
    (cd <parent checkout> && JAX_PLATFORMS=cpu python <this file>)
"""

import hashlib
import json
import os
import sys

sys.path.insert(0, os.getcwd())  # the checkout it is run FROM, not the one it lies in


def main() -> int:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.nemotron_h import NEMOTRON_H_DECODER, layer_shapes
    from accelerate_tpu.serving import engine, make_pools, make_state_pool
    from benchmark.families.nemotron_h import program_config

    with open("benchmark/configs/nemotron-3-nano-30b-a3b.json") as f:
        cfg = program_config(json.load(f))
    with open("benchmark/traffic/serve-chat.json") as f:
        service = json.load(f)["service"]
    slots, block, bucket = service["max_slots"], service["block_size"], service["prompt_bucket"]
    bps = service["max_request_len"] // block
    bf16 = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def weights(kind):
        return {k: sds(s, bf16) for k, s in layer_shapes(cfg, kind).items()}

    def ints(*shape):
        return sds(shape, jnp.int32)

    kinds = cfg.kinds
    n_attention = sum(k == "attention" for k in kinds)
    n_mamba = cfg.pattern.count("M")
    pools = jax.eval_shape(lambda: make_pools(
        n_attention, service["num_blocks"], cfg.n_kv_head, block, cfg.head_dim, bf16
    ))
    state = jax.eval_shape(lambda: make_state_pool(
        n_mamba, slots, (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
        (cfg.conv_kernel - 1, cfg.conv_width), bf16,
    ))
    layers = (tuple(weights(kind) for kind in kinds), {}, {})
    statics = dict(family=NEMOTRON_H_DECODER, cfg=cfg, qbits=0, temperature=0.0)

    def line(name, lowered):
        text = lowered.as_text()
        print(json.dumps({
            "program": name, "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "bytes": len(text), "tpu_custom_call": "tpu_custom_call" in text,
        }))

    for length in range(bucket, 4 * bucket + 1, bucket):
        line(f"prefill_{length}", engine._prefill_jit.lower(
            *pools, weights("globals"), layers, ints(1, length), ints(bps), ints(),
            sds((2,), jnp.uint32), ints(), state, **statics,
        ))
    line("decode", engine._decode_jit.lower(
        *pools, weights("globals"), layers, ints(slots, bps), ints(slots), ints(slots),
        sds((slots, 2), jnp.uint32), state, **statics,
    ))
    print(json.dumps({
        "layers": len(kinds), "attention": n_attention, "mamba2": n_mamba,
        "pallas_imported": "jax.experimental.pallas" in sys.modules,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
