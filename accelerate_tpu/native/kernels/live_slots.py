"""The scaffolding both recurrent-step kernels share (``ssm_step.py``:
Mamba-2; ``gdn_step.py``: the gated delta rule): one grid step a LIVE slot,
in place in the whole state pool (docs/kernels.md §state-space step).

The program compacts the decode's live mask into the live slots' ids
(ascending, then the last live id repeated) and a count; both are
scalar-prefetched with the layer's rank in the pool.  Every per-slot block
and the pool's block map grid step ``k`` to slot ``ids[k]``, so a step past
the count maps to the last live step's blocks: the pipeline starts no copy
for it, and the kernel's ``pl.when(step < count)`` skips its compute — a dead
slot's state is neither read nor written.  The whole ``(layers, slots, ...)``
pool is the call's last operand and its first output, aliased: the layer's
rank picks the rows, and no slice of the pool is ever materialised as an
operand.

On a mesh of several devices (a sharded model's replicated pools,
``serving/engine.py::_pool_mesh``) the call runs per device under
``shard_map``: GSPMD cannot partition a Mosaic kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .pallas_import import import_pallas

pl, pltpu = import_pallas()

from ...parallel.mesh import shard_map_compat  # noqa: E402

__all__ = ["live_list", "per_slot", "over_live_slots"]


def live_list(live):
    """``(ids, count)`` of a ``(slots,)`` live mask: the live slots' ids in
    ascending order, then the last of them repeated to ``slots`` entries (the
    last slot's where none is live), and how many are live."""
    slots = live.shape[0]
    seen = jnp.cumsum(live.astype(jnp.int32))  # live slots up to and including each
    count = seen[-1]
    step = jnp.minimum(jnp.arange(slots, dtype=jnp.int32), jnp.maximum(count - 1, 0))
    # the (k+1)-th live slot is the number of slots with k or fewer live up to them
    ids = jnp.sum(seen[None, :] <= step[:, None], axis=1, dtype=jnp.int32)
    return jnp.minimum(ids, slots - 1), count


def per_slot(*block, memory_space=None):
    """The block of a ``(slots, ...)`` operand that grid step ``k`` works on:
    slot ``ids[k]``'s."""
    return pl.BlockSpec(
        block, lambda k, ids, count, layer: (ids[k],) + (0,) * (len(block) - 1), memory_space=memory_space
    )


def over_live_slots(kernel, pool, layer, ids, count, operands, in_specs, out_specs, out_shape, *,
                    name: str, interpret: bool, mesh):
    """``kernel(ids, count, layer, *operands, pool, pool out, *outs)`` over
    the live slots; returns ``[pool, *outs]``.

    ``operands`` are the per-slot inputs, ``in_specs`` their blocks, and
    ``out_specs`` / ``out_shape`` the outputs beside the pool.  The pool's
    block is one slot's rows of layer ``layer``: ``(1, 1, *pool.shape[2:])``."""
    rest = pool.shape[2:]
    rows = pl.BlockSpec((1, 1, *rest), lambda k, ids, count, layer: (layer[0], ids[k]) + (0,) * len(rest))
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # ids, count, layer
            grid=(pool.shape[1],),
            in_specs=[*in_specs, rows],
            out_specs=[rows, *out_specs],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype), *out_shape],
        input_output_aliases={3 + len(operands): 0},  # the pool, after ids, count, layer and the operands
        interpret=interpret,
        name=name,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
    )
    if mesh is not None and mesh.size > 1:
        whole = PartitionSpec()
        call = shard_map_compat(call, mesh, (whole,) * (4 + len(operands)), (whole,) * (1 + len(out_shape)))
    return call(ids, count[None], layer[None], *operands, pool)
