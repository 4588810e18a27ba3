#!/usr/bin/env python
"""tpu_aot_check — compile the main path's programs for a described v5e:2x2
without a chip (on-chip-measurement guide §2 step 3).

What `chip_smoke.py` runs on the chip, asked of the chip's compiler here:

* the whole GPT-2-small captured train step (12×1024, bf16) on one chip,
  and on four under ``fsdp=4`` and ``dp=4``;
* the serving prefill and decode programs (``decode_steps`` 1 and 8) at
  GPT-2-small width.

The library builds its mesh from ``jax.devices()`` and places its own
parameters, and nothing can be placed on a described device.  So the
trainer is built for real on (virtual) CPU devices, the step function and
its arguments are taken at the moment the library would lower them, and the
function is traced again with the TPU branches steered on, the state's mesh
swapped for a mesh of the described devices, and every sharding the library
had stored on the CPU mesh moved onto that mesh as it is applied.  Nothing
runs: a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python tools/tpu_aot_check.py [--only train1|fsdp4|dp4|serve]
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (the program under check; imports no jax itself)


class _Recorded(Exception):
    """Raised in place of the library's own ``lower()``: the CPU backend
    cannot lower a Mosaic kernel, and the arguments are all we came for."""


def record_lowering(call):
    """Run ``call()`` with ``jax.jit`` replaced by a recorder; return the
    ``(fun, jit_kwargs, args)`` of the first ``.lower(*args)`` made."""
    import jax

    real_jit = jax.jit
    seen = {}

    class Recorder:
        def __init__(self, fun, kwargs):
            self.fun, self.kwargs = fun, kwargs

        def lower(self, *args):
            seen["hit"] = (self.fun, self.kwargs, args)
            raise _Recorded

    def recording_jit(fun=None, **kwargs):
        if fun is None:
            return real_jit(**kwargs)
        if getattr(fun, "__name__", "") == "traced":  # CapturedStep._build's body
            return Recorder(fun, kwargs)
        return real_jit(fun, **kwargs)

    jax.jit = recording_jit
    try:
        call()
    except _Recorded:
        pass
    finally:
        jax.jit = real_jit
    return seen["hit"]


def on_mesh(sharding, cpu_mesh, tpu_mesh):
    from jax.sharding import NamedSharding

    if isinstance(sharding, NamedSharding) and sharding.mesh == cpu_mesh:
        return NamedSharding(tpu_mesh, sharding.spec, memory_kind=sharding.memory_kind)
    return sharding


class moved_constraints:
    """While open, ``jax.lax.with_sharding_constraint`` applies shardings the
    library stored on ``cpu_mesh`` (ZeRO/FSDP layouts, pinned state
    layouts) on ``tpu_mesh`` instead."""

    def __init__(self, cpu_mesh, tpu_mesh):
        self.meshes = (cpu_mesh, tpu_mesh)

    def __enter__(self):
        import jax

        self.real = real = jax.lax.with_sharding_constraint
        meshes = self.meshes

        def constraint(x, shardings):
            moved = jax.tree_util.tree_map(
                lambda s: on_mesh(s, *meshes), shardings,
                is_leaf=lambda s: isinstance(s, jax.sharding.Sharding),
            )
            return real(x, moved)

        jax.lax.with_sharding_constraint = constraint

    def __exit__(self, *exc):
        import jax

        jax.lax.with_sharding_constraint = self.real


def steer_to_tpu():
    """The library's backend questions, answered as the chip would."""
    import accelerate_tpu.ops.attention as attention
    import accelerate_tpu.ops.flash_attention as flash

    attention._on_tpu = lambda: True
    flash._interpret = lambda: False


def report(label, compiled, seconds):
    text = compiled.as_text()
    if os.environ.get("AOT_DUMP_DIR"):
        name = re.sub(r"\W+", "_", label)
        with open(os.path.join(os.environ["AOT_DUMP_DIR"], name + ".hlo"), "w") as f:
            f.write(text)
    mem = compiled.memory_analysis()
    kernels = chip_smoke.pallas_kernels(text)
    collectives = {
        "all-gather": len(re.findall(r"\ball-gather(?:-start)?\(", text)),
        "reduce-scatter": len(
            re.findall(r"\breduce-scatter(?:-start)?\(|calls=%all-reduce-scatter", text)
        ),
        "all-reduce": len(re.findall(r"\ball-reduce(?:-start)?\(", text)),
        "collective-permute": len(re.findall(r"\bcollective-permute(?:-start)?\(", text)),
    }
    gib = lambda n: round(n / 2**30, 2)  # noqa: E731
    print(
        f"[{label}] compiled in {seconds:.0f}s  "
        f"args {gib(mem.argument_size_in_bytes)} GiB  "
        f"temp {gib(mem.temp_size_in_bytes)} GiB  "
        f"out {gib(mem.output_size_in_bytes)} GiB  "
        f"alias {gib(mem.alias_size_in_bytes)} GiB per device\n"
        f"    tpu_custom_call x{len(kernels)}: {sorted(set(kernels))}\n"
        f"    collectives: { {k: v for k, v in collectives.items() if v} }",
        flush=True,
    )


def check_train(label, n_devices, parallelism_config, topo):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from accelerate_tpu import Accelerator, AcceleratorState

    Accelerator._reset_state()
    real_devices = jax.devices
    jax.devices = lambda *a, **k: real_devices(*a, **k)[:n_devices]
    try:
        sizes = chip_smoke.sizes_for(rehearse=False)
        accelerator, _, _, loader, step = chip_smoke.build_trainer(
            sizes, parallelism_config
        )
        batch = next(iter(loader))
        fun, jit_kwargs, args = record_lowering(lambda: step(batch))
    finally:
        jax.devices = real_devices
    cpu_mesh = accelerator.mesh
    tpu_mesh = Mesh(
        np.array(topo.devices[:n_devices]).reshape(cpu_mesh.devices.shape),
        cpu_mesh.axis_names,
    )

    def abstract(x):
        sharding = on_mesh(x.sharding, cpu_mesh, tpu_mesh)
        if not isinstance(sharding, NamedSharding):
            # an uncommitted host scalar/array: replicated on the mesh
            sharding = NamedSharding(tpu_mesh, jax.sharding.PartitionSpec())
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    t0 = time.time()
    AcceleratorState._shared_state["mesh"] = tpu_mesh
    with moved_constraints(cpu_mesh, tpu_mesh):
        compiled = (
            jax.jit(fun, **jit_kwargs)
            .lower(*jax.tree_util.tree_map(abstract, args))
            .compile()
        )
    report(label, compiled, time.time() - t0)
    Accelerator._reset_state()


def check_serving(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import accelerate_tpu.nn as nn
    from accelerate_tpu.models import GPTConfig, GPTLMHeadModel
    from accelerate_tpu.models.generation import stacked_params_for_mode
    from accelerate_tpu.serving import engine, make_pools
    from accelerate_tpu.telemetry.profiler import instructions_of_size

    one = SingleDeviceSharding(topo.devices[0])
    nn.manual_seed(0)
    model = GPTLMHeadModel(GPTConfig.small()).eval()
    for p in model.parameters():  # what prepare(mixed_precision="bf16") serves
        p.data = p.data.astype(jnp.bfloat16)
    spec = model._decoder_spec()
    g, layers = stacked_params_for_mode(model, 0, spec.stack)
    slots, block, max_len = 4, 16, 256
    bps = max_len // block
    k_pool, v_pool = make_pools(
        12, slots * bps + 1, spec.cfg.n_kv_head, block, spec.cfg.head_dim, jnp.bfloat16
    )

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree
        )

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    statics = dict(family=spec.family, cfg=spec.cfg, qbits=0, temperature=0.0)
    pools = abstract((k_pool, v_pool))
    weights = abstract((g, layers))
    for bucket in (32, 64, 96):
        t0 = time.time()
        compiled = engine._prefill_jit.lower(
            *pools, *weights, sds((1, bucket)), sds((bps,)), sds(()),
            sds((2,), jnp.uint32), **statics,
        ).compile()
        report(f"serve prefill bucket={bucket}", compiled, time.time() - t0)
    decode_args = (
        *pools, *weights, sds((slots, bps)), sds((slots,)), sds((slots,)),
        sds((slots, 2), jnp.uint32),
    )
    t0 = time.time()
    compiled = engine._decode_jit.lower(*decode_args, **statics).compile()
    report("serve decode_steps=1", compiled, time.time() - t0)
    # the layer loop indexes the carried pools in place (docs/serving.md §1);
    # tests/test_tpu_compile.py holds this at GPT-2-XL width.  At this width a
    # layer's weights outweigh its 65 pages: count buffers made of pages only
    moved = [
        (name, opcode, dims) for name, opcode, dims in instructions_of_size(
            compiled.as_text(), ("copy", "dynamic-slice", "dynamic-update-slice"),
            k_pool[0].size,
        ) if dims[-2:] == k_pool.shape[-2:]
    ]
    print(f"    copies or slices of a layer's pool or more: {len(moved)} {moved}", flush=True)
    t0 = time.time()
    compiled = engine._decode_n_jit.lower(
        *decode_args, decode_steps=8, **statics
    ).compile()
    report("serve decode_steps=8", compiled, time.time() - t0)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--only", choices=("train1", "fsdp4", "dp4", "serve"))
    args = parser.parse_args()

    import jax
    from jax.experimental import topologies

    # a compile for a described device is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    from accelerate_tpu import ParallelismConfig

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    steer_to_tpu()
    wanted = lambda name: args.only in (None, name)  # noqa: E731
    if wanted("train1"):
        check_train("train step, 1 chip", 1, None, topo)
    if wanted("fsdp4"):
        check_train("train step, fsdp=4", 4, ParallelismConfig(fsdp_size=4), topo)
    if wanted("dp4"):
        check_train("train step, dp=4", 4, ParallelismConfig(), topo)
    if wanted("serve"):
        check_serving(topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
