"""Fused quantize+reduce-scatter (docs/kernels.md §quantize-rs).

``parallel/compress.py``'s reference wire is four separate XLA ops with an
HBM round-trip between each: per-block ``amax`` → scale divide →
round/clip/narrow → widen-by-scales.  This module collapses scale compute,
rounding and widening into ONE Pallas kernel region, so on TPU the whole
quantize→dequantize ride happens in VMEM next to the shard boundary the
payload crosses ("scale+round ride the RDMA hops" — the EQuARX move,
PAPERS.md #3), and the StableHLO the captured program commits to keeps the
narrow (int8 / f8E4M3FN) payload at the boundary instead of a widened fp32
intermediate (asserted by ``inspect.check_quantize_rs``).

Numerics contract: the kernel body runs the reference's EXACT op sequence
(``compress.quantize`` then ``compress.dequantize``), so under jit the wire
is **bitwise-identical** to the reference path — which makes the
error-feedback residual evolution bitwise too (the residual math stays
outside the kernel, shared with the reference).  Verified on CPU through
interpreter mode in tests/test_kernels.py.

The stochastic-rounding wire (``stochastic_quantize_dequantize`` /
``zero2_stochastic_wire``) reopens the ZeRO-2 first scatter: PR 6 kept that
scatter layout-only because deterministically re-rounding a running fp32
accumulation every micro-step compounds bias ``num_steps`` times.
Stochastic rounding (``floor(y + u)``, ``u ~ U[0,1)``) is unbiased —
``E[wire] == sum`` at every micro-step — so the accumulated gradient can
cross the dp boundary narrow during accumulation without systematic drift
(int8 wire only; fp8 stays deterministic).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...parallel.compress import _qmax, _to_layout, dequantize, quantize

__all__ = [
    "fused_quantize_dequantize",
    "fused_reduce_scatter",
    "stochastic_quantize_dequantize",
    "zero2_stochastic_wire",
]


# One block's fp32 bytes.  The input and output windows are double-buffered
# and the body keeps a few block-sized fp32 temporaries live, about seven
# copies in all, against a 16 MiB scoped-VMEM limit: a grid-less call over a
# 768×3072 leaf asked for 27 MB there, over the 50304×768 embedding for 154.
_BLOCK_BYTES = 1 << 20


def _block_len(shape: tuple, axis: int):
    """Block length along ``axis`` for a leaf too big for one VMEM window,
    else ``None`` (whole array, no grid).  Aligned to the TPU tile: 128 on
    the last dimension, 8 on the one before it."""
    total = 4 * math.prod(shape)
    if total <= _BLOCK_BYTES or len(shape) < 2:
        return None
    align = {len(shape) - 1: 128, len(shape) - 2: 8}.get(axis, 1)
    step = _BLOCK_BYTES // (total // shape[axis]) // align * align
    step = max(step, align)
    return step if step < shape[axis] else None


def _call_over_axis_blocks(kernel, *operands, axis: int, interpret: bool, name: str):
    """``pallas_call`` of a kernel whose scales are per index of ``axis``:
    slices along ``axis`` never see each other, so a grid over blocks of
    that axis computes exactly what the whole-array call does (a ragged
    last block reads padding that only ever scales itself, and its writes
    past the edge are dropped)."""
    x = operands[0]
    out_shape = jax.ShapeDtypeStruct(x.shape, jnp.float32)
    step = _block_len(x.shape, axis)
    if step is None:
        return pl.pallas_call(
            kernel, out_shape=out_shape, interpret=interpret, name=name
        )(*operands)
    block = tuple(step if i == axis else d for i, d in enumerate(x.shape))
    spec = pl.BlockSpec(
        block, lambda b: tuple(b if i == axis else 0 for i in range(x.ndim))
    )
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(x.shape[axis], step),),
        in_specs=[spec] * len(operands),
        out_specs=spec,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(*operands)


def _qdq_kernel(x_ref, o_ref, *, axis: int, wire_dtype):
    """One region: per-block amax → scale → round/clip → narrow → widen —
    by calling the reference's own ``compress.quantize``/``dequantize`` on
    the loaded value (they are pure jnp, so they trace into the kernel
    body unchanged), which is what makes the fused wire bitwise-identical
    BY CONSTRUCTION: a future edit to the reference math cannot silently
    diverge the kernel."""
    payload, scales = quantize(x_ref[:], axis, wire_dtype)
    o_ref[:] = dequantize(payload, scales)


def fused_quantize_dequantize(x, axis: int, wire_dtype, *, interpret: bool):
    """``x`` (fp32) → the wire value (fp32, same shape): what the far side
    of the quantized reduce-scatter reconstructs, computed in one kernel."""
    kernel = functools.partial(_qdq_kernel, axis=axis, wire_dtype=wire_dtype)
    return _call_over_axis_blocks(
        kernel, x, axis=axis, interpret=interpret, name="quantize_dequantize"
    )


def fused_reduce_scatter(x32, sharding, axis: int, err, policy, *,
                         interpret: bool):
    """Drop-in for :meth:`CompressionPolicy.reduce_scatter` with the wire
    computed by the fused kernel.  Returns ``(g_used, err_new)`` with the
    identical contract — and identical bits: the residual update
    (``used = wire + err``, ``err_new = truth - wire``) is the reference's
    own math on a bitwise-equal wire."""
    wire = fused_quantize_dequantize(
        x32, axis, policy.wire_dtype, interpret=interpret
    )
    wire = _to_layout(wire, sharding)
    if err is None:
        return wire, None
    used = wire + err
    truth = _to_layout(x32, sharding)
    return used, truth - wire


# ---------------------------------------------------------------------------
# stochastic-rounding wire (the ZeRO-2 first scatter)
# ---------------------------------------------------------------------------
def _sr_kernel(x_ref, u_ref, o_ref, *, axis: int, qmax: float):
    """Same fused region with ``floor(y + u)`` in place of ``round(y)`` —
    unbiased over ``u ~ U[0,1)``: int8 wire for the mid-accumulation
    scatter."""
    x = x_ref[:]
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    amax = jnp.max(jnp.abs(x), axis=reduce_axes, keepdims=True)
    scales = amax / qmax
    safe = jnp.where(scales > 0, scales, 1.0)
    y = x / safe
    payload = jnp.clip(jnp.floor(y + u_ref[:]), -qmax, qmax).astype(jnp.int8)
    o_ref[:] = payload.astype(jnp.float32) * scales


def stochastic_quantize_dequantize(x, axis: int, key, *, interpret: bool):
    """Stochastically-rounded int8 wire value of ``x``: deterministic for a
    fixed ``key`` (replay-stable under capture — the key threads through
    the captured RNG state), unbiased across keys."""
    u = jax.random.uniform(key, x.shape, jnp.float32)
    kernel = functools.partial(_sr_kernel, axis=axis, qmax=_qmax(jnp.int8))
    return _call_over_axis_blocks(
        kernel, x, u, axis=axis, interpret=interpret,
        name="stochastic_quantize_dequantize",
    )


def zero2_stochastic_wire(grad, sharding, axis: int, key, *,
                          interpret: bool):
    """The ZeRO-2 mid-accumulation scatter, narrow: stochastic int8 wire +
    the same layout constraint ``compress.shard_accumulation`` applies.

    PR 6's layout-only scatter refused to quantize here because
    deterministic rounding would bias the running sum ``num_steps`` times;
    the stochastic wire's per-micro-step re-round is unbiased
    (``E[wire] == sum``), which is what reopens the narrow first scatter
    (docs/kernels.md §stochastic wire; armed only when the kernel policy
    AND an int8 collective policy AND ZeRO-2 are all on)."""
    wire = stochastic_quantize_dequantize(grad, axis, key, interpret=interpret)
    return _to_layout(wire, sharding)
