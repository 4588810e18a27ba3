"""Paged-attention decode: walk the block table in VMEM (docs/kernels.md
§paged-attention; the vLLM move).

The reference decode (``serving/engine.py``) attends each slot with
``kp[layer·NB + row]`` — a gather that MATERIALIZES the slot's full page
span ``(blocks_per_slot, block_size, n_kv · d)`` in HBM for every slot × every
layer × every token, then hands the copy to ``cached_attention``.  The
kernel here runs one grid program per slot: it walks the slot's block-table
row, streams each page into VMEM scratch (direct dynamic-index loads in
interpreter mode; double-buffered ``make_async_copy`` DMA from
HBM-resident pools on TPU), and attends over the virtually-contiguous span
in place — the batched full-span gather never exists, which
``inspect.check_paged_attention`` proves from the lowered IR (no tensor of
the gathered ``(slots, blocks_per_slot, n_kv, block_size, d)`` shape).

Numerics contract: the attend math IS ``cached_attention`` — the kernel
body calls it on the walked span, so per-slot logits (and therefore greedy
serving tokens) are **bitwise-identical** to the gather-then-attend path
under jit.  Verified end-to-end against ``DecodeService`` in
tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["paged_attention", "reference_paged_attention"]


def _paged_attn_kernel(table_ref, pos_ref, q_ref, kp_ref, vp_ref, o_ref,
                       k_scratch, v_scratch, *, bps: int, cfg,
                       interpret: bool):
    from ...models.generation import cached_attention

    # the block table and the positions are scalar-prefetched (SMEM): the
    # walk reads this slot's row entry by entry, as addresses, not as a
    # vector block
    slot = pl.program_id(0)
    p_s = pos_ref[slot]
    if interpret:
        # interpreter lowering: dynamic-index loads walk the table; each
        # page lands in scratch one block at a time — no batched gather
        for j in range(bps):
            k_scratch[j] = kp_ref[table_ref[slot, j]]
            v_scratch[j] = vp_ref[table_ref[slot, j]]
    else:
        from jax.experimental.pallas import tpu as pltpu

        def dma_pages(sems):
            # pools stay HBM-resident; pages stream into VMEM per walk step
            # (trash-block pages — table entries past the live span — are
            # masked out by cached_attention's causal mask, same as the
            # reference's gathered padding)
            for j in range(bps):
                kd = pltpu.make_async_copy(
                    kp_ref.at[table_ref[slot, j]], k_scratch.at[j], sems.at[0]
                )
                vd = pltpu.make_async_copy(
                    vp_ref.at[table_ref[slot, j]], v_scratch.at[j], sems.at[1]
                )
                kd.start()
                vd.start()
                kd.wait()
                vd.wait()

        pl.run_scoped(dma_pages, pltpu.SemaphoreType.DMA((2,)))
    n_kv, bs, d = k_scratch.shape[1], k_scratch.shape[2], k_scratch.shape[3]
    # table order IS logical order: the flattened walk is a virtually
    # contiguous cache, so the ONE attention implementation applies
    # unchanged — which is the bitwise-parity contract
    kc = k_scratch[:].transpose(1, 0, 2, 3).reshape(n_kv, bps * bs, d)
    vc = v_scratch[:].transpose(1, 0, 2, 3).reshape(n_kv, bps * bs, d)
    q_s = q_ref[0]  # (H, 1, d)
    o_ref[0] = cached_attention(
        q_s[None], kc[None], vc[None], p_s[None], cfg
    )[0].astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, positions, *, cfg,
                    interpret: bool):
    """Attend the whole slot batch one token against the paged KV pool.

    ``q: (slots, H, 1, d)``; ``k_pool/v_pool: (num_blocks, n_kv, bs, d)``
    (ONE layer's pools in the kernel's own layout — the engine's layer loop
    builds that view of the current layer from the pool it carries);
    ``block_tables: (slots, blocks_per_slot)``; ``positions: (slots,)``.
    Returns ``(slots, H, 1, d)`` in the pool dtype, bitwise-equal to the
    reference gather-then-attend."""
    from jax.experimental.pallas import tpu as pltpu

    slots, n_heads, _, d = q.shape
    bps = block_tables.shape[1]
    kernel = functools.partial(
        _paged_attn_kernel, bps=bps, cfg=cfg, interpret=interpret
    )
    if interpret:
        pool_spec = pl.BlockSpec(k_pool.shape, lambda i, t, p: (0, 0, 0, 0))
    else:
        # TPU: pools are far too big for VMEM — leave them where they live
        # and DMA pages on demand (the whole point of the walk)
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((1, n_heads, 1, d), lambda i, t, p: (i, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_tables, positions
            grid=(slots,),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((bps,) + k_pool.shape[1:], k_pool.dtype),
                pltpu.VMEM((bps,) + v_pool.shape[1:], v_pool.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, n_heads, 1, d), v_pool.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, positions, q, k_pool, v_pool)


def reference_paged_attention(q, k_pool, v_pool, block_tables, positions, *,
                              cfg):
    """The unfused reference (``serving/engine.py``'s ``attend_one`` shape):
    materialize each slot's full page span, then attend — the contrast half
    of ``inspect.check_paged_attention`` and the parity baseline."""
    from ...models.generation import cached_attention

    def attend_one(q_s, row, p_s):
        kc = k_pool[row].transpose(1, 0, 2, 3).reshape(
            k_pool.shape[1], -1, k_pool.shape[3]
        )
        vc = v_pool[row].transpose(1, 0, 2, 3).reshape(
            v_pool.shape[1], -1, v_pool.shape[3]
        )
        return cached_attention(q_s[None], kc[None], vc[None], p_s[None], cfg)[0]

    return jax.vmap(attend_one)(q, block_tables, positions).astype(v_pool.dtype)
