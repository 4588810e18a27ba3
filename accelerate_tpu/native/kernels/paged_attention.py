"""Paged-attention decode: one slot's live pages, as they lie (docs/kernels.md
§paged-attention; docs/serving.md §decode attention).

How the decode program of a scanned layer plan attends
(``serving/engine.py::_decode_body``: under the layer scan of an all-attention
plan, or under the scan over the repeats of a mixed plan's period; an unrolled
mixed plan keeps gather + ``cached_attention`` and never imports this module).  The KV pools stay in HBM as the page rows the layer loop carries,
``(L·NB, bs, lanes)``: a page is one lane-dense ``[bs, lanes]`` slab — a
token's ``n_kv·d`` first, zeros up to whole 128-lane tiles
(``kv_blocks.page_lanes``) — and layer ``l``'s block ``b`` is row
``l·NB + b``.  One grid program a slot walks that slot's block-table row for ``positions[slot] // bs + 1`` pages — the live ones
and no other — in chunks of ``_CHUNK_TOKENS`` tokens whose page DMAs are in
flight together and double-buffered against the compute, under a running
(online) softmax.  Neither the gathered span, nor a ``(Hkv, S, d)`` relayout
of it, nor a product over dead positions is ever built.

**Heads on sublanes, lanes as they lie.**  The query of head ``h`` is laid
out over all the page's lanes with zeros outside its kv head's ``d`` lanes
(``_spread_heads``), so that

* scores are ONE 2-D product a chunk, ``[H, lanes] · [T, lanes]ᵀ → [H, T]``:
  the zeros select the head's lanes, so no lane slice narrower than a tile is
  taken (GPT-2-XL: 25 heads of 64 on 1600 of 1664 lanes) and no batch
  dimension is asked of the matrix unit (the refusal the gather path's
  einsum drew);
* values are ONE product a chunk, ``[H, T] · [T, lanes] → [H, lanes]``, of
  which head ``h`` keeps its kv head's ``d`` lanes (``_own_lanes``).

What differs between 25 heads of 64 and 32 query heads on 2 kv heads of 128 is
only where the zeros fall: ``n_kv``, the group size and ``d`` decide it.

**The same mathematics as** ``models.generation.cached_attention``: q·k
accumulated in float32 and scaled there, the causal rule ``T <= position``
and ``cfg.sliding_window``'s band, the running maximum, sum and output in
float32, probabilities cast to the values' dtype for the value product.  The
summation order is chunked; nothing else changes.  Logits therefore agree with
the single-request engine's to float32 summation order (bfloat16 caches: to
bfloat16 rounding of the probabilities), not bitwise; greedy tokens on the
tests' models are the same.

A slot whose table starts at the trash block (block 0: dead) does no work and
returns zeros.  Pages past a slot's length are never read, and the rows of a
chunk's buffer that no page was copied to are zeroed on the value side: a
weight of 0.0 times whatever lay there would be NaN if that were NaN.

Off the TPU the same kernel runs through the Pallas interpreter
(``ops/flash_attention.py::_interpret``: the code asks its backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .pallas_import import _GPU_INTERPRETER, import_pallas  # noqa: F401 (the name the tests ask for)

pl, pltpu = import_pallas()

from ...models.generation import _NEG_INF  # noqa: E402
from ...ops import flash_attention  # noqa: E402
from ...parallel.mesh import shard_map_compat  # noqa: E402

__all__ = ["paged_attention"]

# tokens a chunk: the lane width of a chunk's score tile, and with GPT-2-XL's
# 16 × 1664 pages 8 DMAs of 53 KB a side in flight.  On the chip 256 and 512
# tokens a chunk, and head rows left unpadded, were no faster (PERF.md, PR 32)
_CHUNK_TOKENS = 128
_Q_ROWS = 16  # a bfloat16 tile's sublanes: the head rows are padded to it


def _chunk_pages(block_size: int, blocks_per_slot: int) -> int:
    """Pages a chunk: ``_CHUNK_TOKENS`` tokens' worth, at least one, at most
    the table's width."""
    return max(1, min(blocks_per_slot, _CHUNK_TOKENS // block_size))


def _kv_lanes(n_kv: int, d: int, lanes: int):
    """``(n_kv, lanes)`` bool: the ``d`` lanes of a page that hold kv head ``k``
    (none of the pad lanes)."""
    return (jnp.arange(lanes) // d)[None, :] == jnp.arange(n_kv)[:, None]


def _spread_heads(q, n_kv: int, lanes: int):
    """``(slots, H, d) → (slots, rows, lanes)``: head ``h``'s query on its kv
    head's ``d`` lanes, zeros on every other lane (the page's pad lanes among
    them), and zero rows up to a whole tile.  Lane-dense all the way: the
    ``group`` heads of one kv head are laid side by side over the lanes as
    their kv heads lie in a page, and each kv head's rows keep its own."""
    slots, n_heads, d = q.shape
    group = n_heads // n_kv
    side_by_side = q.reshape(slots, n_kv, group, d).transpose(0, 2, 1, 3)
    side_by_side = jnp.pad(
        side_by_side.reshape(slots, 1, group, n_kv * d),
        ((0, 0), (0, 0), (0, 0), (0, lanes - n_kv * d)),
    )
    spread = jnp.where(_kv_lanes(n_kv, d, lanes)[None, :, None, :], side_by_side, 0)
    rows = -(-n_heads // _Q_ROWS) * _Q_ROWS
    return jnp.pad(spread.reshape(slots, n_heads, lanes), ((0, 0), (0, rows - n_heads), (0, 0)))


def _own_lanes(out, n_heads: int, n_kv: int, d: int):
    """``(slots, rows, lanes) → (slots, H, d)``: each head's own kv lanes —
    ``_spread_heads`` backwards (the heads of different kv heads hold
    different lanes, so summing over kv heads puts them side by side)."""
    slots, _, lanes = out.shape
    group = n_heads // n_kv
    per_kv = out[:, :n_heads].reshape(slots, n_kv, group, lanes)
    own = jnp.where(_kv_lanes(n_kv, d, lanes)[None, :, None, :], per_kv, 0).sum(axis=1)
    side_by_side = own[..., : n_kv * d].reshape(slots, group, n_kv, d)
    return side_by_side.transpose(0, 2, 1, 3).reshape(slots, n_heads, d)


def _kernel(tables, positions, first_row, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, m_ref, l_ref, acc_ref, sems, *,
            block_size: int, pages: int, window: int, scale: float):
    slot = pl.program_id(0)
    # a position past the table (an overrun micro-step of ``decode_steps > 1``
    # at capacity: kv_blocks.blocks_for_request) attends as the table's last
    # one: every table index below stays inside the slot's row.  A table
    # entry becomes a DMA's HBM row, and nothing on the chip checks either
    pos = jnp.minimum(positions[slot], tables.shape[1] * block_size - 1)
    tokens = pages * block_size  # a chunk's
    n_pages = pos // block_size + 1  # the slot's own length, in pages
    # a dead slot's table starts at the trash block: no chunk, zeros out
    end = jnp.where(tables[slot, 0] > 0, pos // tokens + 1, 0)
    start = jnp.maximum(pos - window + 1, 0) // tokens if window > 0 else 0

    def copies(chunk, buf, j):
        row = first_row[0] + tables[slot, chunk * pages + j]
        dst = pl.ds(pl.multiple_of(j * block_size, block_size), block_size)
        return (
            pltpu.make_async_copy(k_hbm.at[row], k_buf.at[buf, dst], sems.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[row], v_buf.at[buf, dst], sems.at[1, buf]),
        )

    def held(chunk):  # how many of the chunk's pages the slot holds
        return jnp.clip(n_pages - chunk * pages, 0, pages)

    def fetch(chunk, buf):
        def issue(j, carry):
            for copy in copies(chunk, buf, j):
                copy.start()
            return carry

        def blank(j, carry):
            # no page comes here: what the buffer held is weighted 0.0, and
            # must not be NaN
            rows = pl.ds(pl.multiple_of(j * block_size, block_size), block_size)
            v_buf[buf, rows, :] = jnp.zeros((block_size, v_buf.shape[2]), v_buf.dtype)
            return carry

        jax.lax.fori_loop(0, held(chunk), issue, None)
        jax.lax.fori_loop(held(chunk), pages, blank, None)

    def wait(chunk, buf):
        def done(j, carry):
            for copy in copies(chunk, buf, j):
                copy.wait()
            return carry

        jax.lax.fori_loop(0, held(chunk), done, None)

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(start < end)
    def _():
        fetch(start, 0)

    def attend(chunk, carry):
        buf = (chunk - start) % 2

        @pl.when(chunk + 1 < end)
        def _():
            fetch(chunk + 1, 1 - buf)

        wait(chunk, buf)
        q = q_ref[0]  # (rows, lanes)
        scores = jax.lax.dot_general(
            q, k_buf[buf], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (rows, tokens)
        t = chunk * tokens + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        seen = t <= pos
        if window > 0:
            seen = jnp.logical_and(seen, pos - t < window)
        scores = jnp.where(seen, scores, _NEG_INF)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, scores.max(axis=1, keepdims=True))
        # a masked score is _NEG_INF and the chunk holds a seen one, so its
        # weight is exp(-huge) = 0.0 exactly
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_buf.dtype), v_buf[buf], preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(start, end, attend, None)
    total = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(total == 0.0, 1.0, total)).astype(o_ref.dtype)


def paged_attention(q, k_rows, v_rows, block_tables, positions, first_row, cfg,
                    *, n_kv: int, mesh=None):
    """One token of every slot against that slot's live pages.

    ``q: (slots, H, d)``; ``k_rows, v_rows: (L·NB, bs, lanes)`` — the carried
    page rows of every layer, left where they are, ``n_kv`` heads of ``d`` on
    the first ``n_kv·d`` lanes (``kv_blocks.page_lanes``); ``block_tables:
    (slots, blocks_per_slot)``, ``positions: (slots,)`` — the position of the
    token being fed, already written to its page; ``first_row``: the layer's
    first page row ``layer·NB``.  Returns ``(slots, H, d)`` in the values'
    dtype.

    ``mesh``: the mesh the pools are committed to, where it has several
    devices (a prepared or ``shard_for_inference`` model: the service holds
    its pools replicated on the params' mesh).  GSPMD cannot partition a
    Mosaic kernel — the TPU lowering refuses one in a program over more than
    one device — so there the kernel runs per device under ``shard_map``, every
    operand replicated as the pools are (``ops/attention._flash_on_mesh`` is
    the same cure for the flash kernel)."""
    slots, n_heads, d = q.shape
    block_size, lanes = k_rows.shape[1], k_rows.shape[2]
    pages = _chunk_pages(block_size, block_tables.shape[1])
    q_spread = _spread_heads(q.astype(k_rows.dtype), n_kv, lanes)
    rows = q_spread.shape[1]
    interpret = flash_attention._interpret()  # Mosaic on a TPU, the interpreter elsewhere
    kernel = functools.partial(
        _kernel, block_size=block_size, pages=pages,
        window=getattr(cfg, "sliding_window", 0) or 0, scale=d ** -0.5,
    )
    per_slot = pl.BlockSpec((1, rows, lanes), lambda s, *_: (s, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # block_tables, positions, first_row
            grid=(slots,),
            in_specs=[per_slot, in_hbm, in_hbm],
            out_specs=per_slot,
            scratch_shapes=[
                pltpu.VMEM((2, pages * block_size, lanes), k_rows.dtype),
                pltpu.VMEM((2, pages * block_size, lanes), v_rows.dtype),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, rows, lanes), v_rows.dtype),
        interpret=interpret,
        name="paged_attention",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )
    if mesh is not None and mesh.size > 1:
        whole = PartitionSpec()
        call = shard_map_compat(call, mesh, (whole,) * 6, whole)
    out = call(block_tables, positions, jnp.reshape(first_row, (1,)).astype(jnp.int32),
               q_spread, k_rows, v_rows)
    return _own_lanes(out, n_heads, n_kv, d)
