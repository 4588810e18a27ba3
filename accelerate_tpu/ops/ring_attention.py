"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

NEW capability relative to the reference: HF Accelerate has no native
sequence parallelism at all (SURVEY.md §2.2 — grep-verified; only Megatron
pass-through flags).  Here it is first-class and TPU-native:

* the sequence dimension is sharded over the ``sp`` mesh axis;
* each device holds one q-chunk permanently and streams k/v chunks around the
  ring with ``lax.ppermute`` over ICI — communication overlaps the blockwise
  attention compute of the previous chunk (XLA schedules the permute
  concurrently with the einsums);
* softmax is computed online (running max/denominator, the flash-attention
  recurrence) so the full (S × S) score matrix never exists anywhere and the
  per-device memory is O(S/n · S/n) per block pair;
* causal masking: fully-masked hops are skipped by a per-device ``lax.cond``
  (a real branch — shard_map bodies are scalar programs, not vmapped lanes)
  and, on TPU, partially-masked hops run the Pallas hop kernel whose
  offset-aware tile predicate skips MXU work above the diagonal.  The saving
  is ~half the *FLOPs/energy*; ring *latency* is still n lockstep hops, so
  per-step wall-clock is bounded by the busiest device (a zigzag/striped
  layout would balance that and is future work).

Design follows the blockwise/ring attention literature (see PAPERS.md);
no reference code exists for this path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block_update(q, k, v, m, l, acc, q_offset, k_offset, scale, is_causal,
                  window=0):
    """One online-softmax accumulation of q against a k/v chunk."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if is_causal:
        sq, sk = s.shape[-2], s.shape[-1]
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        keep = q_pos >= k_pos
        if window > 0:
            keep = jnp.logical_and(keep, q_pos - k_pos < window)
        s = jnp.where(keep, s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc_new


# test hook: force the Pallas hop path off-TPU (kernels in interpret mode)
_FORCE_FLASH_HOPS = False


def _use_flash_hops(chunk: int, d: int) -> bool:
    from .attention import _MXU_HEAD_DIMS, _on_tpu

    if _FORCE_FLASH_HOPS:
        return True
    return _on_tpu() and chunk % 128 == 0 and d in _MXU_HEAD_DIMS


def _ring_hops(k, v, carry0, do_step, *, axis_name: str, is_causal: bool,
               chunk: int, window: int = 0):
    """Shared ring skeleton: rotate k/v with ``ppermute``, apply ``do_step``
    per hop, skip fully-masked hops under causal masking.

    The causal skip is a real branch: shard_map bodies are per-device scalar
    programs, not vmapped lanes, so ``lax.cond`` lowers to an HLO conditional.
    The final hop's rotation is NOT issued — XLA cannot DCE a collective
    inside a loop, so the loop runs n-1 hops-with-rotation and the last hop
    happens outside it.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(step, k_cur, v_cur, inner):
        # after `step` rotations this device holds the chunk that started at
        # ring position (my_idx - step) mod n
        k_idx = jax.lax.rem(my_idx - step + n, n)
        q_offset = my_idx * chunk
        k_offset = k_idx * chunk
        update = functools.partial(do_step, k_cur, v_cur, q_offset, k_offset)
        if is_causal:
            # whole chunk strictly in the future — or, with a sliding
            # window, entirely beyond the band in the past — contributes
            # nothing: skip the hop's compute (a real HLO branch)
            fully_masked = k_offset > q_offset + chunk - 1
            if window > 0:
                fully_masked = jnp.logical_or(
                    fully_masked, q_offset - (k_offset + chunk - 1) >= window
                )
            return jax.lax.cond(fully_masked, lambda args: args, update, inner)
        return update(inner)

    def body(step, carry):
        k_cur, v_cur, inner = carry
        inner = hop(step, k_cur, v_cur, inner)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, inner

    k_last, v_last, inner = jax.lax.fori_loop(0, n - 1, body, (k, v, carry0))
    return hop(n - 1, k_last, v_last, inner)


def _ring_attention_local(q, k, v, *, axis_name: str, is_causal: bool,
                          scale: float, window: int = 0):
    """Per-device body under shard_map: q stays, k/v ride the ring.

    Two inner-block engines on the shared ``_ring_hops`` skeleton:

    * **Pallas hop kernel** (TPU, MXU-tileable chunks): each hop calls
      ``flash_attention_hop`` — offset-aware causal masking with tile-level
      skipping inside the kernel — and hops merge by the logsumexp rule.
      Diagonal hops do triangle work only.  The causal saving is in
      FLOPs/energy, not ring latency — hops are lockstep (ppermute), so the
      wall-clock lower bound is the busiest device's diagonal+past hops.
    * **jnp online-softmax** (CPU tests, odd shapes): the m/l/acc recurrence,
      fused by XLA.
    """
    b, h, sq, d = q.shape
    chunk = sq  # local chunk length (== global_seq / n)

    if _use_flash_hops(chunk, d):
        from .flash_attention import flash_attention_hop

        def do_step(k_cur, v_cur, q_offset, k_offset, inner):
            out, lse = inner
            o_hop, lse_hop = flash_attention_hop(
                q, k_cur, v_cur, q_offset, k_offset, is_causal, scale, window
            )
            lse_new = jnp.logaddexp(lse, lse_hop)
            w_old = jnp.exp(lse - lse_new)[..., None]
            w_hop = jnp.exp(lse_hop - lse_new)[..., None]
            return out * w_old + o_hop.astype(jnp.float32) * w_hop, lse_new

        carry0 = (
            jnp.zeros((b, h, sq, d), dtype=jnp.float32),
            jnp.full((b, h, sq), _NEG_INF, dtype=jnp.float32),
        )
        out, _ = _ring_hops(
            k, v, carry0, do_step, axis_name=axis_name, is_causal=is_causal,
            chunk=chunk, window=window,
        )
        return out.astype(q.dtype)

    q32 = q.astype(jnp.float32)

    def do_step(k_cur, v_cur, q_offset, k_offset, inner):
        m, l, acc = inner
        return _block_update(
            q32, k_cur.astype(jnp.float32), v_cur, m, l, acc,
            q_offset, k_offset, scale, is_causal, window,
        )

    carry0 = (
        jnp.full((b, h, sq, 1), _NEG_INF, dtype=jnp.float32),
        jnp.zeros((b, h, sq, 1), dtype=jnp.float32),
        jnp.zeros((b, h, sq, d), dtype=jnp.float32),
    )
    m, l, acc = _ring_hops(
        k, v, carry0, do_step, axis_name=axis_name, is_causal=is_causal,
        chunk=chunk, window=window,
    )
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)


def _ulysses_attention_local(
    q, k, v, *, axis_name: str, is_causal: bool, scale: float, window: int = 0
):
    """Per-device body of Ulysses-style (all-to-all) sequence parallelism.

    Instead of rotating k/v around a ring, an ``all_to_all`` re-partitions
    the problem: heads split across the ``sp`` devices, each device then
    holding h/n heads at FULL sequence length, runs ordinary causal
    attention locally (the Pallas flash kernel on TPU — no per-hop masking
    logic at all), and a second ``all_to_all`` restores the seq-sharded
    layout.  q/k/v are stacked so the inbound redistribution is ONE
    collective (two per attention call total, vs the ring's n-1 ppermute
    hops): better at moderate sequence lengths when h >= n; the ring wins
    when per-device memory must stay O(s/n) (Ulysses holds full-seq k/v
    for its head slice).
    """
    # heads -> devices, seq gathered: (3, b, h, s/n, d) -> (3, b, h/n, s, d)
    qkv = jnp.stack([q, k, v])
    qkv = jax.lax.all_to_all(qkv, axis_name, split_axis=2, concat_axis=3, tiled=True)
    from .attention import sdpa_tpu

    out = sdpa_tpu(qkv[0], qkv[1], qkv[2], is_causal=is_causal, scale=scale,
                   window=window)
    # seq -> devices, heads gathered back
    return jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1, tiled=True)


def _shard_mapped_attention(
    local_fn, q, k, v, mesh, is_causal, scale, axis_name, batch_axes, window=0
):
    """Shared wrapper: resolve mesh/scale, sp=1 fast path, shard_map setup."""
    if window > 0 and not is_causal:
        # validate HERE so sp>1 meshes fail like sp=1 does (the per-device
        # bodies only band-mask under is_causal — silently ignoring the
        # window on one mesh shape and raising on another is worse)
        raise ValueError("sliding window requires is_causal=True")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if mesh is None:
        from ..state import AcceleratorState

        mesh = AcceleratorState().mesh
    if mesh.shape.get(axis_name, 1) == 1:
        return None, mesh, scale  # caller runs the single-device path
    batch_spec = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    spec = P(batch_spec, None, axis_name, None)
    from ..parallel.mesh import shard_map_compat

    fn = shard_map_compat(
        functools.partial(
            local_fn, axis_name=axis_name, is_causal=is_causal, scale=scale,
            window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn, mesh, scale


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    is_causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = "sp",
    batch_axes: tuple = ("dp", "fsdp"),
    window: int = 0,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention.

    Same contract as :func:`ring_attention` — (batch, heads, seq, head_dim)
    with seq sharded over ``axis_name`` — but the parallelism re-partitions
    heads across devices with an ``all_to_all`` pair instead of streaming
    k/v chunks.  Requires ``heads % sp_size == 0``; falls back to the ring
    otherwise.  Select per model via ``SequenceParallelPlugin(mode=...)``.
    """
    fn, mesh, scale = _shard_mapped_attention(
        _ulysses_attention_local, q, k, v, mesh, is_causal, scale, axis_name,
        batch_axes, window,
    )
    if fn is None:
        from .attention import sdpa_tpu

        return sdpa_tpu(q, k, v, is_causal=is_causal, scale=scale, window=window)
    if q.shape[1] % mesh.shape[axis_name] != 0:
        return ring_attention(
            q, k, v, mesh, is_causal, scale, axis_name, batch_axes, window
        )
    return fn(q, k, v)


_SP_MODES = ("ring", "all_to_all")


def sequence_parallel_attention(
    q,
    k,
    v,
    mesh: Optional[Mesh] = None,
    is_causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = "sp",
    batch_axes: tuple = ("dp", "fsdp"),
    mode: str = "ring",
    window: int = 0,
):
    """Dispatch on ``SequenceParallelPlugin.mode``: "ring" | "all_to_all"."""
    if mode not in _SP_MODES:
        raise ValueError(f"unknown sequence-parallel mode {mode!r}; use one of {_SP_MODES}")
    impl = ulysses_attention if mode == "all_to_all" else ring_attention
    return impl(q, k, v, mesh, is_causal, scale, axis_name, batch_axes, window)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    is_causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = "sp",
    batch_axes: tuple = ("dp", "fsdp"),
    window: int = 0,
) -> jax.Array:
    """Sequence-parallel attention over (batch, heads, seq, head_dim) arrays
    whose seq dimension is sharded on the ``axis_name`` mesh axis.

    Differentiable (pure jnp + collectives inside shard_map — JAX transposes
    ppermute automatically), jit-compatible, composes with dp/fsdp batch
    sharding.  ``window`` > 0 (causal sliding band): ring hops whose chunk
    lies entirely beyond the band are skipped as whole branches — with
    window <= chunk each device runs at most TWO hops regardless of ring
    size, so windowed long-context cost stops growing with sp.
    """
    fn, mesh, scale = _shard_mapped_attention(
        _ring_attention_local, q, k, v, mesh, is_causal, scale, axis_name,
        batch_axes, window,
    )
    if fn is None:
        from .attention import sdpa_tpu

        return sdpa_tpu(q, k, v, is_causal=is_causal, scale=scale, window=window)
    return fn(q, k, v)
