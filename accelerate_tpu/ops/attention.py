"""Attention ops: XLA reference implementation + TPU routing.

``sdpa_tpu`` picks the Pallas flash-attention kernel
(ops/flash_attention.py) when running on TPU with MXU-friendly shapes, else
the jnp reference (which XLA still fuses into a few kernels on any backend).

Layout convention everywhere: (batch, num_heads, seq, head_dim) — torch SDPA
parity so reference-style model code ports untouched.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def sdpa_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    is_causal: bool = False,
    scale: Optional[float] = None,
    window: int = 0,
) -> jax.Array:
    if window > 0 and not is_causal:
        raise ValueError("sliding window requires is_causal=True")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # accumulate logits/softmax in fp32 regardless of input dtype
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if is_causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((q_len, k_len), dtype=bool), k_len - q_len)
        if window > 0:
            # sliding band: query i sees keys (i-window, i]
            causal &= ~jnp.tril(
                jnp.ones((q_len, k_len), dtype=bool), k_len - q_len - window
            )
        logits = jnp.where(causal, logits, _NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, _NEG_INF)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# single source of truth for "the Pallas kernels are safe here" — shared
# with ops/ring_attention.py so the two dispatchers cannot drift
_MXU_HEAD_DIMS = (64, 128, 256)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sdpa_tpu(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    is_causal: bool = False,
    scale: Optional[float] = None,
    window: int = 0,
) -> jax.Array:
    """Dispatch: Pallas flash kernel on TPU for MXU-tileable shapes.

    ``ACCELERATE_TPU_FLASH=0`` forces the XLA reference path, ``=1`` forces the
    Pallas kernel on any backend (interpreted off-TPU); unset picks per
    shape and backend.  XLA's fused attention is often faster at short
    sequences where the S×S scores fit comfortably in VMEM; the Pallas
    kernel wins when S is large enough that materializing scores thrashes
    HBM.  A shape the kernel cannot tile runs the reference — that is a
    property of the input, visible to the caller; a kernel that fails to
    import on a TPU is not, and raises.
    """
    import os

    seq_q, seq_k, head_dim = q.shape[-2], k.shape[-2], q.shape[-1]
    force = os.environ.get("ACCELERATE_TPU_FLASH", "").strip()
    tileable = (
        mask is None
        and seq_q % 128 == 0
        and seq_k % 128 == 0
        and head_dim in _MXU_HEAD_DIMS
    )
    if force != "0" and tileable and (force == "1" or _on_tpu()):
        return _flash_on_mesh(
            q, k, v, is_causal=is_causal, scale=scale, window=window
        )
    return sdpa_reference(
        q, k, v, mask=mask, is_causal=is_causal, scale=scale, window=window
    )


def _flash_on_mesh(q, k, v, **kwargs):
    """The flash kernel, per shard on a mesh of several devices.

    GSPMD cannot partition a Mosaic kernel — lowering one into a program
    over more than one TPU device fails with "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" — so
    under the state's mesh the kernel runs inside ``shard_map``: batch over
    the data axes, heads over ``tp``, each taken only where it divides the
    dimension (an axis left out is computed redundantly, never wrongly).
    A caller already inside a ``shard_map`` body (ring/Ulysses attention,
    the pipelined trunk) is per-device code and calls the kernel directly.
    """
    from jax.sharding import PartitionSpec

    from ..parallel.mesh import shard_map_compat
    from ..state import AcceleratorState
    from .flash_attention import flash_attention

    kernel = partial(flash_attention, **kwargs)
    mesh = AcceleratorState._shared_state.get("mesh")
    if (
        mesh is None
        or mesh.size == 1
        or jax.sharding.get_abstract_mesh().manual_axes
    ):
        return kernel(q, k, v)

    def dividing(dim: int, axes: tuple):
        axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
        size = math.prod(mesh.shape[a] for a in axes)
        return axes if axes and dim % size == 0 else None

    spec = PartitionSpec(
        dividing(q.shape[0], ("dp", "fsdp")), dividing(q.shape[1], ("tp",)), None, None
    )
    return shard_map_compat(kernel, mesh, (spec, spec, spec), spec)(q, k, v)
