"""Pallas flash attention for TPU — forward AND backward kernels.

Blockwise-softmax attention that never materialises the (seq × seq) score
matrix: per (batch·head, q-block) the forward kernel streams k/v blocks
through VMEM, carrying the running max/denominator/accumulator in fp32
scratch (the online softmax recurrence).  Q·Kᵀ and P·V land on the MXU via
``lax.dot_general`` with fp32 accumulation; the causal variant skips
fully-masked k-blocks.

The backward is the FlashAttention-2 recompute scheme, also in Pallas: the
forward additionally emits the per-row logsumexp (LSE); the backward
recomputes each (q-block, k-block) probability tile from q/k/LSE inside ONE
fused kernel and contracts it against dO for dq, dk AND dv — so no O(S²)
tensor ever reaches HBM in either direction and the QKᵀ recompute + input
DMA streams are paid once, not twice.  dq is carried as ONE whole-q-length
output block per (batch·head) whose index map ignores the k/q grid dims, so
Pallas keeps it VMEM-resident across the entire tile walk and flushes it to
HBM exactly once per bh — row-exact sq·d writes however fine the k tiling
(the previous per-q-block output spec flushed on every inner q step, write-
amplifying by the k-block count).  A cheap XLA-fused
``delta = rowsum(dO·O)`` precomputation feeds it.

The reference framework has no attention kernels at all (SURVEY.md §2.7 —
fused kernels came from vendored TE/Megatron binaries); this is the TPU-native
equivalent written directly against Mosaic.  LSE/delta are stored as
single-lane (bh, seq, 1) arrays — kernels read (block_q, 1) tiles and let
the VPU broadcast them against score tiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .attention import sdpa_reference

import os

DEFAULT_BLOCK_Q = int(os.environ.get("ACCELERATE_TPU_FLASH_BLOCK_Q", 1024))
DEFAULT_BLOCK_K = int(os.environ.get("ACCELERATE_TPU_FLASH_BLOCK_K", 1024))
# the backward kernels keep (block_q, block_k) f32 score/ds tiles live at
# once, so they get their own tiling knobs
DEFAULT_BWD_BLOCK_Q = int(os.environ.get("ACCELERATE_TPU_FLASH_BWD_BLOCK_Q", 1024))
DEFAULT_BWD_BLOCK_K = int(os.environ.get("ACCELERATE_TPU_FLASH_BWD_BLOCK_K", 1024))
_LANES = 128  # TPU lane count: last-dim tile width for every dtype
_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

def _interpret() -> bool:
    """Mosaic on a TPU backend, the Pallas interpreter anywhere else — the
    only way these kernels can run off-TPU (the CPU tests, and
    ``ACCELERATE_TPU_FLASH=1`` there).  Never the interpreter on a TPU."""
    return jax.default_backend() != "tpu"


def _compiler_params(semantics=("parallel", "parallel", "arbitrary")):
    """Grid dimension semantics + VMEM budget for the kernels.

    Without explicit semantics Mosaic treats every grid dimension as
    sequential: no cross-iteration DMA pipelining and no core-level
    parallelism — measured ~5× slower than XLA's fused attention at seq 1024
    on v5e.  Dimensions that carry accumulator state across iterations
    (scratch or revisited output blocks) MUST be "arbitrary".
    """
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=100 * 1024 * 1024,
    )



def _fit_block(block: int, seq: int) -> int:
    """Largest block ≤ ``block`` that divides ``seq``.

    First clamps to ``seq`` (so seq 640 with the 1024 default yields 640 —
    no halving happens when the clamped block already divides seq), then
    halves until it divides (seq 768 with block 512 halves once to 256,
    which divides 768).  The result must stay a multiple of 128 — Mosaic
    lane tiling requires it — which holds for any 128-multiple seq and
    power-of-two default, but an env-overridden non-128-multiple block
    (e.g. ``ACCELERATE_TPU_FLASH_BLOCK_K=192`` with seq 384) would pass the
    divisibility check and then die inside Mosaic with an opaque error, so
    we validate here instead."""
    block = min(block, seq)
    while block > 1 and seq % block:
        block //= 2
    if block % 128 != 0:
        raise ValueError(
            f"flash-attention block size resolved to {block} for seq {seq}, "
            "which is not a multiple of 128 (Mosaic lane-tile requirement). "
            "Check ACCELERATE_TPU_FLASH_BLOCK_Q/K overrides: they must be "
            "multiples of 128 that divide the sequence length."
        )
    return block


def _window_tiles(window: int, block: int, num_tiles: int) -> int:
    """Tiles a band of ``window`` positions can span from a tile's edge —
    the ONE formula both the forward k-walk and the backward dq/dkv walks
    use, so their band geometries cannot drift."""
    return min(num_tiles, (window - 1) // block + 2)


def _causal_mask(s, qi, ki, block_q, block_k, q_off=0, k_off=0, window=0):
    """Causal mask on GLOBAL positions: local tile indices plus the chunk
    offsets a ring-attention hop supplies (0 for plain self-attention).
    ``window`` > 0 adds a sliding-window band (Mistral-style): position i
    attends to [i-window+1, i]."""
    q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k_off + ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = q_pos >= k_pos
    if window > 0:
        keep = jnp.logical_and(keep, q_pos - k_pos < window)
    return jnp.where(keep, s, _NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _flash_kernel(
    off_ref,  # (2,) int32 SMEM: [q_offset, k_offset] global chunk offsets
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, block_k, d)
    v_ref,  # (1, block_k, d)
    o_ref,  # (1, block_q, d)
    lse_ref,  # (1, block_q, 1) f32 or None
    m_scratch,  # (block_q, 128) f32
    l_scratch,  # (block_q, 128) f32
    acc_scratch,  # (block_q, d) f32
    *,
    scale: float,
    is_causal: bool,
    block_q: int,
    block_k: int,
    window: int = 0,
    window_tiles: int = 0,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    # narrowed k-grid (window_tiles > 0): ki is window-RELATIVE; the global
    # k-tile is qi - (window_tiles-1) + ki, clamped to 0 by the index map —
    # clamped duplicates are invalidated so tile 0 is counted once
    if window_tiles > 0:
        raw = qi - (window_tiles - 1) + ki
        kg = jnp.maximum(raw, 0)
        valid = raw >= 0
    else:
        kg = ki
        valid = True

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # causal: skip blocks strictly above the (offset-aware) diagonal — a
    # dynamic scalar predicate, so ring hops skip real MXU work, not a select;
    # a sliding window additionally skips blocks wholly BELOW the band
    should_compute = valid
    if is_causal:
        q_off, k_off = off_ref[0], off_ref[1]
        causal_ok = q_off + qi * block_q + block_q - 1 >= k_off + kg * block_k
        should_compute = jnp.logical_and(should_compute, causal_ok)
        if window > 0:
            in_band = (
                q_off + qi * block_q - (k_off + kg * block_k + block_k - 1) < window
            )
            should_compute = jnp.logical_and(should_compute, in_band)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * scale
        if is_causal:
            s = _causal_mask(
                s, qi, kg, block_q, block_k, off_ref[0], off_ref[1], window
            )

        m_prev = m_scratch[:, 0:1]
        l_prev = l_scratch[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scratch[:, 0:1] = m_new
        l_scratch[:, 0:1] = l_new
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = l_scratch[:, 0:1]
        # guard fully-masked rows (shouldn't occur with causal q>=k blocks)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            # single-lane store: the backward reads (block_q, 1) and lets the
            # VPU broadcast against score tiles, so the O(S·128) lane
            # broadcast (≈50 MB/layer on GPT-2-small) never touches HBM
            lse_ref[0] = m_scratch[:, 0:1] + jnp.log(l_safe)


def _offsets_arr(q_offset, k_offset) -> jax.Array:
    """Pack the (possibly traced) chunk offsets for SMEM prefetch."""
    return jnp.stack(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    )


def _off_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: float,
    is_causal: bool,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    return_lse: bool = False,
    q_offset=0,
    k_offset=0,
    window: int = 0,
):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash attention needs seq divisible by the block size: got "
            f"q_seq={sq} (block {block_q}), k_seq={sk} (block {block_k}); "
            "rows beyond the last full block would be silently dropped"
        )
    # Narrowed k-grid for sliding windows: only the <= window_tiles k-tiles
    # that can intersect each q-tile's band are visited (and DMA'd) at all,
    # so long-seq cost scales with the window.  Needs equal tiles and static
    # zero offsets (ring hops pass traced offsets the index map cannot see).
    window_tiles = 0
    if (
        window > 0
        and is_causal
        and block_q == block_k
        and sq == sk  # kg = f(qi) indexes k-tiles; cross-length grids would
        # clamp out-of-range tiles to 0 and mislabel their positions
        and isinstance(q_offset, int) and q_offset == 0
        and isinstance(k_offset, int) and k_offset == 0
    ):
        window_tiles = _window_tiles(window, block_k, sk // block_k)
    if window_tiles > 0:
        grid = (bh, sq // block_q, window_tiles)

        def _k_index(bh_, qi, ki):
            return (bh_, jnp.maximum(qi - (window_tiles - 1) + ki, 0), 0)

    else:
        grid = (bh, sq // block_q, sk // block_k)

        def _k_index(bh_, qi, ki):
            return (bh_, ki, 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        is_causal=is_causal,
        block_q=block_q,
        block_k=block_k,
        window=window,
        window_tiles=window_tiles,
    )
    out_shapes = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype)]
    out_specs = [
        pl.BlockSpec(
            (1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0), memory_space=pltpu.VMEM
        )
    ]
    if return_lse:
        out_shapes.append(jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32))
        out_specs.append(
            pl.BlockSpec(
                (1, block_q, 1),
                lambda bh_, qi, ki: (bh_, qi, 0),
                memory_space=pltpu.VMEM,
            )
        )
    else:
        kernel = functools.partial(_drop_lse_arg, kernel)

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _off_spec(),
            pl.BlockSpec(
                (1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((1, block_k, d), _k_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), _k_index, memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs if return_lse else out_specs[0],
        out_shape=out_shapes if return_lse else out_shapes[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
        compiler_params=_compiler_params(),
    )(_offsets_arr(q_offset, k_offset), q3, k3, v3)
    if return_lse:
        out, lse = outs
        return out.reshape(b, h, sq, d), lse
    return outs.reshape(b, h, sq, d)


def _drop_lse_arg(kernel, off_ref, q_ref, k_ref, v_ref, o_ref, *scratch, **kw):
    return kernel(off_ref, q_ref, k_ref, v_ref, o_ref, None, *scratch, **kw)


# ---------------------------------------------------------------------------
# backward: ONE fused kernel for dq, dk, dv (FlashAttention-2 recompute)
# ---------------------------------------------------------------------------
def _flash_bwd_kernel(
    off_ref,  # (2,) int32 SMEM: [q_offset, k_offset]
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, block_k, d)
    v_ref,  # (1, block_k, d)
    do_ref,  # (1, block_q, d)
    lse_ref,  # (1, block_q, 1) f32
    delta_ref,  # (1, block_q, 1) f32
    dq_ref,  # (1, seq_q, d) out — ONE whole-length block per bh
    dk_ref,  # (1, block_k, d) out
    dv_ref,  # (1, block_k, d) out
    dq_scratch,  # (seq_q, d) f32 — FULL q-length accumulator
    dk_scratch,  # (block_k, d) f32
    dv_scratch,  # (block_k, d) f32
    *,
    scale: float,
    is_causal: bool,
    block_q: int,
    block_k: int,
    window: int = 0,
):
    """Grid (bh, k-block, q-block).  Per tile the probability block ``p`` is
    recomputed ONCE and contracted into all three gradients — the split
    dkv/dq kernel pair paid the QKᵀ recompute and the q/k/v/do DMA streams
    twice.

    dq needs accumulation across the OUTER k dimension while dk/dv accumulate
    across the inner q dimension, so dq lives in a full-q-length fp32 VMEM
    scratch (seq·d·4 B — 256 KB at seq 1024; ring hops keep per-chip seq
    bounded): Pallas does NOT reload non-consecutively revisited output
    blocks, so accumulating into dq_ref across ki would silently read stale
    buffer contents whenever the k grid exceeds the VMEM window, and bf16
    output accumulation would round partial sums every hop.  The dq OUTPUT is
    likewise one whole-q-length block whose index map ignores (ki, qi): the
    buffer stays VMEM-resident for the whole per-bh tile walk and Pallas
    flushes it to HBM once per bh, so finalized rows written at the last ki
    cost exactly sq·d HBM traffic regardless of the k-block count (a
    per-q-block output spec would flush block_q·d on EVERY inner q step —
    sk/block_k× write amplification)."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    num_q = pl.num_programs(2)
    num_k = pl.num_programs(1)
    q_rows = pl.ds(qi * block_q, block_q)

    @pl.when(ki == 0)
    def _zero_dq():
        dq_scratch[q_rows, :] = jnp.zeros((block_q, dq_scratch.shape[1]), jnp.float32)

    @pl.when(qi == 0)
    def _zero_dkv():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    should_compute = True
    if is_causal:
        q_off, k_off = off_ref[0], off_ref[1]
        should_compute = q_off + qi * block_q + block_q - 1 >= k_off + ki * block_k
        if window > 0:
            in_band = (
                q_off + qi * block_q - (k_off + ki * block_k + block_k - 1) < window
            )
            should_compute = jnp.logical_and(should_compute, in_band)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # (block_q, 1), broadcasts against score tiles
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * scale
        if is_causal:
            s = _causal_mask(
                s, qi, ki, block_q, block_k, off_ref[0], off_ref[1], window
            )
        p = jnp.exp(s - lse)  # forward softmax tile; masked entries exp(-inf)=0
        # dv += pᵀ · dO
        dv_scratch[:] += jax.lax.dot_general(
            p.astype(do.dtype),
            do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp = dO · vᵀ ; ds = p ⊙ (dp − delta) · scale
        dp = jax.lax.dot_general(
            do,
            v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        ds_cast = ds.astype(q.dtype)
        # dk += dsᵀ · q
        dk_scratch[:] += jax.lax.dot_general(
            ds_cast,
            q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dq_block += ds · k   (fp32 scratch row-slice for this q block)
        dq_scratch[q_rows, :] += jax.lax.dot_general(
            ds_cast,
            k,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_k - 1)
    def _flush_dq():
        dq_ref[0, q_rows, :] = dq_scratch[q_rows, :].astype(dq_ref.dtype)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_tile_ds(q, k, v, do, lse, delta, scale, qg, kg, block_q, block_k,
                 window):
    """Shared backward tile math: recompute p, return (p, ds) for one
    (q-tile qg, k-tile kg) pair under the causal+band mask (offsets 0 —
    the narrowed kernels never run for ring hops)."""
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    s = _causal_mask(s, qg, kg, block_q, block_k, 0, 0, window)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, (p * (dp - delta) * scale).astype(q.dtype)


def _flash_bwd_dkv_window_kernel(
    off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scratch, dv_scratch, *,
    scale: float, block_q: int, block_k: int, window: int,
    window_tiles: int, num_q: int,
):
    """Windowed dk/dv: grid (bh, k-tile, q-slot) where the q dimension spans
    only the ``window_tiles`` q-tiles that can see k-tile ``ki`` (qg = ki+qr,
    clamped at the top; clamped duplicates invalidated)."""
    ki = pl.program_id(1)
    qr = pl.program_id(2)
    raw = ki + qr
    qg = jnp.minimum(raw, num_q - 1)
    valid = raw <= num_q - 1

    @pl.when(qr == 0)
    def _zero():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    # qg >= ki always (causal tile test trivially true); band lower edge:
    in_band = qg * block_q - (ki * block_k + block_k - 1) < window

    @pl.when(jnp.logical_and(valid, in_band))
    def _compute():
        p, ds = _bwd_tile_ds(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
            scale, qg, ki, block_q, block_k, window,
        )
        dv_scratch[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scratch[:] += jax.lax.dot_general(
            ds, q_ref[0], dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qr == window_tiles - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_bwd_dq_window_kernel(
    off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_scratch, *,
    scale: float, block_q: int, block_k: int, window: int,
    window_tiles: int,
):
    """Windowed dq: mirrors the forward narrowed grid (kg = qi-(Wt-1)+kr,
    clamped at 0; duplicates invalidated); dq accumulates in a block-local
    fp32 scratch — the inner k dimension is consecutive per q-tile, so no
    full-length accumulator is needed."""
    qi = pl.program_id(1)
    kr = pl.program_id(2)
    raw = qi - (window_tiles - 1) + kr
    kg = jnp.maximum(raw, 0)
    valid = raw >= 0

    @pl.when(kr == 0)
    def _zero():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    # kg <= qi always (kr <= Wt-1), so the causal tile test is trivially
    # true — only the band's lower edge can exclude a visited tile
    in_band = qi * block_q - (kg * block_k + block_k - 1) < window

    @pl.when(jnp.logical_and(valid, in_band))
    def _compute():
        _, ds = _bwd_tile_ds(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0], delta_ref[0],
            scale, qi, kg, block_q, block_k, window,
        )
        dq_scratch[:] += jax.lax.dot_general(
            ds, k_ref[0], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kr == window_tiles - 1)
    def _finalize():
        dq_ref[0] = dq_scratch[:].astype(dq_ref.dtype)


def _flash_backward_window(q3, k3, v3, do3, lse3, delta3, scale, block,
                           window, dtype_q, dtype_k, dtype_v):
    """Narrowed-grid backward pair: dq mirrors the forward band walk, dk/dv
    walk the transpose — both visit (and DMA) only in-band tiles, so
    backward cost scales with the window too.  Recomputes p twice (once per
    kernel) over O(S·window) tiles, which beats the fused kernel's single
    recompute over O(S²/2) tiles whenever window < seq/2."""
    bh, sq, d = q3.shape
    num_q = sq // block
    window_tiles = _window_tiles(window, block, num_q)
    offs = _offsets_arr(0, 0)

    def q_side(bh_, ki, qr):  # dkv grid: q specs follow the clamped q-slot
        return (bh_, jnp.minimum(ki + qr, num_q - 1), 0)

    def k_side_dq(bh_, qi, kr):  # dq grid: k specs follow the clamped k-slot
        return (bh_, jnp.maximum(qi - (window_tiles - 1) + kr, 0), 0)

    kv_fixed = pl.BlockSpec((1, block, d), lambda bh_, ki, qr: (bh_, ki, 0),
                            memory_space=pltpu.VMEM)
    q_follow = pl.BlockSpec((1, block, d), q_side, memory_space=pltpu.VMEM)
    row_follow = pl.BlockSpec((1, block, 1), q_side, memory_space=pltpu.VMEM)

    dk3, dv3 = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_window_kernel, scale=scale, block_q=block,
            block_k=block, window=window, window_tiles=window_tiles,
            num_q=num_q,
        ),
        grid=(bh, sq // block, window_tiles),
        in_specs=[_off_spec(), q_follow, kv_fixed, kv_fixed, q_follow,
                  row_follow, row_follow],
        out_specs=[kv_fixed, kv_fixed],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), dtype_k),
            jax.ShapeDtypeStruct((bh, sq, d), dtype_v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, d), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv_window",
        # ki carries no loop state here (scratch re-zeroed at qr==0, one
        # output write per ki) — parallel is safe and pipelines
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
    )(offs, q3, k3, v3, do3, lse3, delta3)

    q_fixed = pl.BlockSpec((1, block, d), lambda bh_, qi, kr: (bh_, qi, 0),
                           memory_space=pltpu.VMEM)
    row_fixed = pl.BlockSpec((1, block, 1), lambda bh_, qi, kr: (bh_, qi, 0),
                             memory_space=pltpu.VMEM)
    kv_follow = pl.BlockSpec((1, block, d), k_side_dq, memory_space=pltpu.VMEM)

    dq3 = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_window_kernel, scale=scale, block_q=block,
            block_k=block, window=window, window_tiles=window_tiles,
        ),
        grid=(bh, sq // block, window_tiles),
        in_specs=[_off_spec(), q_fixed, kv_follow, kv_follow, q_fixed,
                  row_fixed, row_fixed],
        out_specs=q_fixed,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), dtype_q),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq_window",
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
    )(offs, q3, k3, v3, do3, lse3, delta3)
    return dq3, dk3, dv3


def _flash_backward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,  # (bh, sq) f32
    g: jax.Array,
    scale: float,
    is_causal: bool,
    block_q: int = DEFAULT_BWD_BLOCK_Q,
    block_k: int = DEFAULT_BWD_BLOCK_K,
    q_offset=0,
    k_offset=0,
    delta_adjust=None,
    window: int = 0,
):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash attention backward needs seq divisible by the block size: "
            f"got q_seq={sq} (block {block_q}), k_seq={sk} (block {block_k})"
        )
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    do3 = g.reshape(bh, sq, d)
    o3 = out.reshape(bh, sq, d)

    # compact O(S) per-row tensors; the kernel broadcasts (block_q, 1) tiles
    lse3 = lse[..., None]
    # delta_i = Σ_d dO_i·O_i  — cheap rank-reduction, XLA fuses it
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)
    if delta_adjust is not None:
        # hop-level vjp: the lse output's own cotangent g_lse enters as
        # ds += p·g_lse, equivalent to delta' = delta - g_lse
        delta = delta + delta_adjust.astype(jnp.float32)
    delta3 = delta[..., None]

    if (
        window > 0
        and is_causal
        and block_q == block_k
        and sq == sk
        and delta_adjust is None
        and isinstance(q_offset, int) and q_offset == 0
        and isinstance(k_offset, int) and k_offset == 0
    ):
        dq3, dk3, dv3 = _flash_backward_window(
            q3, k3, v3, do3, lse3, delta3, scale, block_q, window,
            q.dtype, k.dtype, v.dtype,
        )
        return (
            dq3.reshape(b, h, sq, d),
            dk3.reshape(b, h, sk, d),
            dv3.reshape(b, h, sk, d),
        )

    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda bh_, ki, qi: (bh_, qi, 0), memory_space=pltpu.VMEM
    )
    kv_spec = pl.BlockSpec(
        (1, block_k, d), lambda bh_, ki, qi: (bh_, ki, 0), memory_space=pltpu.VMEM
    )
    row_spec = pl.BlockSpec(
        (1, block_q, 1), lambda bh_, ki, qi: (bh_, qi, 0), memory_space=pltpu.VMEM
    )

    kernel = functools.partial(
        _flash_bwd_kernel,
        scale=scale,
        is_causal=is_causal,
        block_q=block_q,
        block_k=block_k,
        window=window,
    )
    offs = _offsets_arr(q_offset, k_offset)
    dq3, dk3, dv3 = pl.pallas_call(
        kernel,
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[_off_spec(), q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            # dq: one whole-q-length block per bh (index map ignores ki/qi) —
            # VMEM-resident across the tile walk, flushed once per bh
            pl.BlockSpec(
                (1, sq, d), lambda bh_, ki, qi: (bh_, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, block_k, d), lambda bh_, ki, qi: (bh_, ki, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, block_k, d), lambda bh_, ki, qi: (bh_, ki, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd",
        # ki carries the dq scratch, qi carries the dk/dv scratch: both are
        # loop-carried, only bh is safe to parallelize
        compiler_params=_compiler_params(("parallel", "arbitrary", "arbitrary")),
    )(offs, q3, k3, v3, do3, lse3, delta3)

    return (
        dq3.reshape(b, h, sq, d),
        dk3.reshape(b, h, sk, d),
        dv3.reshape(b, h, sk, d),
    )


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    is_causal: bool = False,
    scale: Optional[float] = None,
    window: int = 0,
) -> jax.Array:
    """Flash attention, (batch, heads, seq, head_dim) layout.

    Requires seq divisible by 128 and head_dim in the MXU-friendly set; the
    dispatcher in ops/attention.py enforces this and falls back otherwise.
    ``window`` > 0 = causal sliding-window attention (Mistral-style band,
    position i attends to [i-window+1, i]).  Both directions visit (and DMA)
    only in-band tiles when block_q == block_k (the default): the forward
    narrows its k-grid per q-tile, and the backward runs a narrowed dq/dkv
    kernel pair (_flash_backward_window) — total cost scales with the
    window, not seq².  Requires ``is_causal=True``.
    """
    if window > 0 and not is_causal:
        raise ValueError("sliding window requires is_causal=True")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash_forward(q, k, v, scale, is_causal, window=window)


def _fwd(q, k, v, is_causal, scale, window):
    if window > 0 and not is_causal:
        raise ValueError("sliding window requires is_causal=True")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_forward(
        q, k, v, scale, is_causal, return_lse=True, window=window
    )
    # squeeze the kernel's single-lane (bh, sq, 1) output to the compact
    # (bh, sq) residual held across the whole forward
    return out, (q, k, v, out, lse[..., 0])


def _bwd(is_causal, scale, window, residuals, g):
    q, k, v, out, lse = residuals
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash_backward(
        q, k, v, out, lse, g, scale, is_causal, window=window
    )


flash_attention.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# hop-level API for ring attention: per-(q-chunk, kv-chunk) partial attention
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention_hop(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset,
    k_offset,
    is_causal: bool = True,
    scale: Optional[float] = None,
    window: int = 0,
):
    """One ring-attention hop: q attends to ONE k/v chunk, masked on global
    positions (q_offset/k_offset are traced scalars from ``axis_index``).

    Returns ``(out, lse)`` where ``out`` is normalized over this chunk only
    and ``lse`` is the per-row logsumexp — the pair composes across hops via
    the standard logsumexp merge (ops/ring_attention.py).  Offset-aware tile
    skipping inside the kernel means diagonal hops do triangle work only.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_forward(
        q, k, v, scale, is_causal, return_lse=True,
        q_offset=q_offset, k_offset=k_offset, window=window,
    )
    return out, lse[..., 0].reshape(q.shape[0], q.shape[1], q.shape[2])


def _hop_fwd(q, k, v, q_offset, k_offset, is_causal, scale, window):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = flash_attention_hop(
        q, k, v, q_offset, k_offset, is_causal, scale, window
    )
    return (out, lse), (q, k, v, out, lse, q_offset, k_offset)


def _hop_bwd(is_causal, scale, window, residuals, g):
    q, k, v, out, lse, q_offset, k_offset = residuals
    b, h, sq, _ = q.shape
    g_out, g_lse = g
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # lse's own cotangent: d lse/d s = p (the normalized probs), which the
    # delta term already encodes — fold g_lse into delta:
    #   ds = p * (dp - delta);  with L-cotangent ds += p * g_lse
    # i.e. delta' = delta - g_lse.  _flash_backward computes delta from
    # (dO, O); shift it by feeding dO' = dO and delta adjustment via out:
    # simplest correct route: recompute here with an adjusted delta by
    # passing g_lse through the XLA-side delta precomputation.
    lse_flat = lse.reshape(b * h, sq)
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse_flat, g_out, scale, is_causal,
        q_offset=q_offset, k_offset=k_offset,
        delta_adjust=(-g_lse.reshape(b * h, sq) if g_lse is not None else None),
        window=window,
    )
    return dq, dk, dv, None, None


flash_attention_hop.defvjp(_hop_fwd, _hop_bwd)
