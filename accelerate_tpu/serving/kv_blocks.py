"""Block/paged KV cache for the serving engine (docs/serving.md).

The single-request decode engine (models/generation.py) allocates one
contiguous ``(b, n_kv, prompt+max_new, d)`` cache per call — the cache
*shape* encodes the request geometry, so every distinct length compiles a
fresh program and two requests can never share a batch.  Serving inverts
that: the cache is ONE preallocated pool of fixed-size blocks

    ``k_pool, v_pool : (L, num_blocks, block_size, n_kv_head · head_dim)``

(a page is one ``[block_size, n_kv_head · head_dim]`` slab; layer ``l``'s
block ``b`` is row ``l · num_blocks + b`` of the ``(L · num_blocks, …)``
view the programs index) plus an int32 **block table** per batch slot
mapping logical position ``p`` to pool block
``table[slot, p // block_size]``.  Every shape the captured programs see
(pool, tables, per-slot scalars) is fixed at service construction, so slots
holding a 7-token and a 900-token sequence replay the SAME pinned program — the zero-recompile contract continuous batching
needs (PAPERS.md #1: serving economics are batch occupancy + recompile
avoidance).

Block 0 is the **trash block**: it is never handed to a request, and empty
slots' table rows point at it, so the decode program's unconditional
scatter (writing every slot's current-token k/v) lands harmlessly for
inactive slots instead of corrupting a neighbour's cache.  Allocation is
host-side and O(blocks) — the pool itself never moves; only tables do.

Blocks for a request are reserved up front at admission
(:func:`blocks_for_request`) and freed the step the request finishes, so a
full pool back-pressures admission (requests wait in the queue) rather than
failing mid-decode.  With a multi-token decode block (``decode_steps=n``,
docs/serving.md §device-resident decode) the reservation additionally
covers the ≤ ``n-1`` micro-step OVERRUN past a request's budget/eos — the
device cannot know a sequence finished until the host reads the token
block, so the discarded trailing micro-steps still scatter k/v, and those
writes must land inside the slot's own reservation, never a neighbour's.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


def bucket_length(n: int, multiple: int, cap: Optional[int] = None) -> int:
    """Round ``n`` up to a multiple of ``multiple`` (optionally clamped to
    ``cap``, never below ``n``) — the shape-bucketing helper every captured
    serving/decode entry must sit behind (graftlint's recompile-hazard rule
    checks the contract): feeding raw request-length shapes into a pinned
    program compiles one variant per distinct length.  Delegates to the one
    rounding implementation (``models.generation.bucket_up``) so serving
    and one-shot ``generate()`` can never bucket differently."""
    if n < 1:
        raise ValueError(f"bucket_length({n}, {multiple}): n must be >= 1")
    from ..models.generation import bucket_up

    return bucket_up(n, multiple, cap)


def blocks_for_request(prompt_len: int, max_new: int, bucket_len: int,
                       block_size: int, decode_steps: int = 1,
                       blocks_per_slot: Optional[int] = None) -> int:
    """Up-front block reservation for one request — the ONE place the
    admission math lives (submit validation and the pool gate both read it).

    The decode span is rounded up to whole ``decode_steps`` blocks: an
    n-token captured decode executes up to ``n-1`` micro-steps past the
    request's budget/eos before the host sees the token block, and every
    overrun micro-step scatters one (discarded) k/v row at the next
    position.  Covering the bucketed horizon keeps those writes inside the
    slot's own reservation — at most one extra block per request.
    ``decode_steps=1`` reduces to the classic
    ``ceil(max(bucket_len, prompt_len + max_new) / block_size)`` exactly.

    ``blocks_per_slot`` clamps the result to the slot's table length: a
    near-capacity request's overrun horizon may round past the table, and
    those tail writes are already safe without blocks behind them (table
    entries past the row are the trash block: masked stale data for any
    future owner; a position past the whole table is written nowhere — no
    column names a block for it and the scatter drops the row — and the
    attention kernel reads it as the table's last position, clamped there
    because nothing on the chip checks a table index)."""
    # prefill emits token 1; the decode loop emits the remaining max_new-1
    # in ceil((max_new-1)/n) blocks of n micro-steps
    steps = max(1, decode_steps)
    horizon = 1 + -(-(max_new - 1) // steps) * steps
    needed = -(-max(bucket_len, prompt_len + horizon) // block_size)
    if blocks_per_slot is not None:
        needed = min(needed, blocks_per_slot)
    return needed


@dataclasses.dataclass
class BlockPool:
    """Host-side allocator over the device block pool.

    ``num_blocks`` INCLUDES the reserved trash block 0; requests draw from
    ids ``1..num_blocks-1``.  Per-slot allocations keep logical order —
    ``rows[slot][j]`` covers logical positions ``[j*bs, (j+1)*bs)`` — so a
    gathered table row reads back as a contiguous (virtually addressed)
    cache and the causal mask stays the plain ``t <= q_pos`` formula.
    """

    num_blocks: int
    block_size: int
    max_slots: int
    blocks_per_slot: int
    # a plan with recurrent layers keeps a second kind of cache, which is not
    # paged: one state a slot (``make_state_pool``).  The slot that holds
    # blocks holds its state: one map owns both, so neither can leak alone.
    # The slot's prefill writes the state whole (the reset: a prefill starts
    # from a zero state); ``state_resets`` counts them
    has_state: bool = False

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError("BlockPool needs >= 2 blocks (block 0 is trash)")
        self._free: list[int] = list(range(self.num_blocks - 1, 0, -1))
        self._rows: dict[int, list[int]] = {}
        self.state_resets = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1

    def can_alloc(self, n_blocks: int) -> bool:
        return n_blocks <= len(self._free)

    def alloc(self, slot: int, n_blocks: int) -> list[int]:
        """Reserve ``n_blocks`` for ``slot``; the returned ids are in logical
        order.  Raises when the pool is short — the scheduler must gate
        admission on :meth:`can_alloc` (back-pressure, not failure)."""
        if slot in self._rows:
            raise ValueError(f"slot {slot} already holds an allocation")
        if n_blocks > self.blocks_per_slot:
            raise ValueError(
                f"request needs {n_blocks} blocks > blocks_per_slot "
                f"({self.blocks_per_slot}) — raise max_request_len or block_size"
            )
        if not self.can_alloc(n_blocks):
            raise ValueError(
                f"pool exhausted: need {n_blocks}, free {len(self._free)}"
            )
        row = [self._free.pop() for _ in range(n_blocks)]
        self._rows[slot] = row
        self.state_resets += self.has_state
        return row

    def free_slot(self, slot: int) -> int:
        """Return ``slot``'s blocks to the free list (eviction/completion);
        returns how many were freed.  Freed ids are immediately reusable —
        stale pool contents are masked by the causal ``t <= q_pos`` until
        the new owner overwrites them."""
        row = self._rows.pop(slot, None)
        if row is None:
            return 0
        self._free.extend(reversed(row))
        return len(row)

    def row(self, slot: int) -> list[int]:
        return list(self._rows.get(slot, ()))

    def check_no_leaks(self) -> None:
        """Invariant: every non-trash block is exactly once free or owned."""
        owned = [b for row in self._rows.values() for b in row]
        seen = set(owned) | set(self._free)
        if len(owned) + len(self._free) != self.usable_blocks or len(seen) != self.usable_blocks or 0 in seen:
            raise AssertionError(
                f"block accounting broken: {len(owned)} owned + "
                f"{len(self._free)} free != {self.usable_blocks} usable"
            )


def page_lanes(n_kv_head: int, head_dim: int) -> int:
    """Lanes of a page: the ``n_kv·d`` of one token's keys (or values), up to
    whole 128-lane tiles.  The pad lanes cost no memory the chip was not
    already spending (its tiled layout pads the minor dimension itself: 1600
    lanes lie as 1664), they hold zeros for ever, and with them a page is a
    slab the decode kernel can copy whole (Mosaic refuses a DMA of 1600
    lanes out of 1664: docs/kernels.md)."""
    return -(-n_kv_head * head_dim // 128) * 128


def make_pools(n_layers: int, num_blocks: int, n_kv_head: int,
               block_size: int, head_dim: int, dtype):
    """Zero-initialised device pools ``(L, NB, bs, lanes)`` — a page is one
    lane-dense ``[bs, lanes]`` slab (``page_lanes``: a token's ``n_kv·d`` first,
    zeros up to whole tiles) and the block index a major dimension, so the
    programs scatter and read pages in place (a ``[…, bs, d]`` tail put the
    block index on the lanes, and every access paid a transpose of the whole
    layer's pool).  Zeros (not empty) so never-written trash/stale positions
    stay finite: masked attention multiplies their probs by exactly 0.0, and
    0 * finite is 0 while 0 * inf would poison the row with NaN."""
    import jax.numpy as jnp

    shape = (n_layers, num_blocks, block_size, page_lanes(n_kv_head, head_dim))
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def make_state_pool(n_layers: int, slots: int, state_shape, tail_shape, tail_dtype):
    """The recurrent layers' cache: ``{"ssm": (L, slots, *state_shape) float32,
    "conv": (L, slots, *tail_shape)}`` — per recurrent layer and slot the
    state (Mamba-2: heads x head size x state size; the delta rule: heads x
    packed k rows x lanes, ``ops/delta_rule.pack_state``) and the rows the next
    token's convolution reads (kernel - 1 rows of the convolved channels).
    Not paged: its size does not grow with the sequence.  Zeros, so that a
    slot nobody has claimed yet computes on finite numbers."""
    import jax.numpy as jnp

    return {
        "ssm": jnp.zeros((n_layers, slots, *state_shape), jnp.float32),
        "conv": jnp.zeros((n_layers, slots, *tail_shape), tail_dtype),
    }
