"""Collective-matmul: the ZeRO-1 all-gather as a chunked ring feeding
partial matmuls as shards arrive (docs/kernels.md §collective-matmul).

The reference formulation leaves the gather to GSPMD: the updated dp-sharded
master is constrained back to the replica layout, XLA emits one monolithic
``all-gather``, and the first matmul of the step waits for the LAST chunk
before its first MAC.  The collective-matmul decomposition (the same one
behind XLA's ``--xla_tpu_enable_async_collective_fusion`` family and the
EQuARX paper's overlap analysis) ring-passes the shards instead: on hop
``t`` every device computes the partial product for the chunk it currently
holds while the next chunk is in flight, so the interconnect and the MXU
run concurrently and the exposed gather cost is ONE hop, not ``dp``.

Two lowerings behind one call:

* ``interpret=True`` (any non-TPU backend, tier-1): the per-hop transport
  is ``jax.lax.ppermute`` under ``shard_map`` and the partial matmul is a
  Pallas kernel in interpreter mode — plain partitionable StableHLO, which
  is what makes the fusion *inspectable* (``inspect.py``: no ``all_gather``
  op, chunked ``collective_permute`` + per-chunk dots instead) and the data
  movement bitwise-testable;
* ``interpret=False`` (TPU): one Pallas kernel per shard holds the ring in
  VMEM — ``make_async_remote_copy`` RDMA with explicit send/recv semaphores
  double-buffers the neighbour chunk behind the current hop's
  ``jnp.dot`` (SNIPPETS.md [1] pattern).

``ring_all_gather`` is the matmul-free version of the same ring (pure data
movement, bitwise-identical to the reference gather by construction) — the
transport ``Optimizer.step`` routes the ZeRO-1 param writeback through when
the policy arms ``collective_matmul``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...parallel.mesh import shard_map_compat

__all__ = [
    "collective_matmul",
    "reference_collective_matmul",
    "ring_all_gather",
    "zero1_gather_eligible",
    "zero1_all_gather",
]


def _ring_perm(n: int) -> list:
    """The +1 ring: device i sends to (i+1) % n."""
    return [(i, (i + 1) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# ring all-gather (pure transport — the ZeRO-1 writeback wire)
# ---------------------------------------------------------------------------
def _ring_gather_local(shard, *, n: int, axis: int, axis_name: str):
    """shard_map body: my shard + n-1 ppermute hops → the full axis,
    chunk-ordered by source device so the concatenation equals the
    reference gather bitwise (movement only, no arithmetic)."""
    lead = jnp.moveaxis(shard, axis, 0)
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((n,) + lead.shape, lead.dtype)
    out = out.at[idx].set(lead)
    chunk = lead
    perm = _ring_perm(n)
    for hop in range(n - 1):
        chunk = jax.lax.ppermute(chunk, axis_name, perm)
        src = (idx - hop - 1) % n
        out = out.at[src].set(chunk)
    full = out.reshape((n * lead.shape[0],) + lead.shape[1:])
    return jnp.moveaxis(full, 0, axis)


def ring_all_gather(arr, sharding, axis: int, *, axis_name: str = "dp"):
    """Gather ``arr`` (globally shaped, dp-sharded at ``axis`` under
    ``sharding``) onto the same layout with the dp entry dropped, through an
    explicit chunked ring instead of GSPMD's monolithic all-gather.

    Pure data movement — bitwise-identical values to the reference
    constraint-based gather; what changes is the schedule the IR commits to
    (per-hop ``collective-permute`` the compiler can overlap with the
    consuming matmuls, asserted by ``inspect.check_collective_matmul``).
    Composable inside a captured jit trace (``shard_map`` nests in ``jit``).
    """
    mesh = sharding.mesh
    n = mesh.shape[axis_name]
    if n <= 1:
        return arr
    in_spec = _padded_spec(sharding.spec, getattr(arr, "ndim", len(arr.shape)))
    out_entries = list(in_spec)
    out_entries[axis] = None
    out_spec = jax.sharding.PartitionSpec(*out_entries)
    body = functools.partial(
        _ring_gather_local, n=n, axis=axis, axis_name=axis_name
    )
    return shard_map_compat(body, mesh, in_spec, out_spec)(arr)


def _padded_spec(spec, ndim: int) -> jax.sharding.PartitionSpec:
    entries = list(spec) + [None] * (ndim - len(spec))
    return jax.sharding.PartitionSpec(*entries[:ndim])


def zero1_gather_eligible(sharding, axis, *, axis_name: str = "dp") -> bool:
    """The ring handles the plain ZeRO-1 layout: a NamedSharding whose
    ``axis`` entry is exactly the dp mesh axis (tuple entries — dp nested
    with another axis — keep the reference constraint gather)."""
    if axis is None or not isinstance(sharding, jax.sharding.NamedSharding):
        return False
    spec = list(sharding.spec)
    if axis >= len(spec) or spec[axis] != axis_name:
        return False
    return sharding.mesh.shape.get(axis_name, 1) > 1


def zero1_all_gather(arr, sharding, axis: int, *, interpret: bool):
    """The ZeRO-1 writeback wire: ``Optimizer.step`` hands the updated
    param (already cast to the param dtype, still on the dp-sharded state
    layout) to this instead of the GSPMD layout constraint when the kernel
    policy arms ``collective_matmul``.  ``interpret`` is accepted for
    signature parity with the other kernels — the transport itself is
    backend-agnostic (``ppermute`` lowers to ICI RDMA on TPU natively)."""
    del interpret  # transport-only entry: no pallas body to interpret
    return ring_all_gather(arr, sharding, axis)


# ---------------------------------------------------------------------------
# collective matmul (the first-matmul-of-the-step fusion)
# ---------------------------------------------------------------------------
def _partial_dot_kernel(x_ref, w_ref, o_ref):
    o_ref[:] = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)


def _partial_dot(xs, chunk, *, interpret: bool):
    return pl.pallas_call(
        _partial_dot_kernel,
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], chunk.shape[1]), jnp.float32),
        interpret=interpret,
    )(xs, chunk)


def _cm_interpret_body(x_full, w_shard, *, n: int, axis_name: str,
                       interpret: bool):
    """shard_map body, interpreter/off-TPU lowering: hop the weight shards
    around the ring, multiplying the chunk in hand each hop — the chunk for
    hop t+1 is in flight while hop t's partial dot runs, which is exactly
    the schedule the monolithic all-gather forbids.

    Each device meets the chunks in a DIFFERENT ring order (device idx
    holds chunk idx−t at hop t), so the partials are buffered per source
    chunk and summed in fixed chunk order 0..n−1 at the end — the declared
    replicated output must be bitwise-consistent across devices (fp32
    addition is not associative; a running per-hop accumulation would make
    'replicated' replicas disagree in the last bits)."""
    idx = jax.lax.axis_index(axis_name)
    kc = w_shard.shape[0]
    chunk = w_shard
    partials = jnp.zeros((n, x_full.shape[0], w_shard.shape[1]), jnp.float32)
    perm = _ring_perm(n)
    for hop in range(n):
        src = (idx - hop) % n
        xs = jax.lax.dynamic_slice_in_dim(x_full, src * kc, kc, axis=1)
        partials = jax.lax.dynamic_update_index_in_dim(
            partials, _partial_dot(xs, chunk, interpret=interpret), src, axis=0
        )
        if hop < n - 1:
            chunk = jax.lax.ppermute(chunk, axis_name, perm)
    acc = partials[0]
    for src in range(1, n):
        acc = acc + partials[src]
    return acc


def _cm_rdma_kernel(x_ref, w_ref, o_ref, comm_buf, partials, send_sem,
                    recv_sem, *, n_devices: int, chunk_k: int,
                    axis_name: str):
    """TPU lowering: the whole ring in ONE Pallas kernel.  The neighbour's
    chunk streams into the spare comm-buffer slot over RDMA while the MXU
    consumes the chunk in hand; explicit send/recv semaphores sequence the
    double buffer (SNIPPETS.md [1]; guide §ring collectives).  Partials
    buffer per SOURCE chunk and sum in fixed chunk order at the end — same
    cross-replica bitwise-consistency argument as the interpret body."""
    from jax.experimental.pallas import tpu as pltpu

    my_id = jax.lax.axis_index(axis_name)
    right = (my_id + 1) % n_devices
    left = (my_id + n_devices - 1) % n_devices
    # both neighbours must be inside the kernel before a chunk lands in
    # their comm buffer (what collective_id in the compiler params is for)
    barrier = pltpu.get_barrier_semaphore()
    for neighbour in (left, right):
        pltpu.semaphore_signal(
            barrier, inc=1, device_id={axis_name: neighbour},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
    pltpu.semaphore_wait(barrier, 2)
    comm_buf[0] = w_ref[:]
    for hop in range(n_devices):
        slot = hop % 2
        if hop < n_devices - 1:
            rdma = pltpu.make_async_remote_copy(
                src_ref=comm_buf.at[slot],
                dst_ref=comm_buf.at[(hop + 1) % 2],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[(hop + 1) % 2],
                # the neighbour along the ring's mesh axis, every other
                # mesh coordinate kept — a LOGICAL id would be the global
                # device number, which an axis index is not on a mesh of
                # more than one axis
                device_id={axis_name: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
        src = (my_id - hop) % n_devices
        xs = x_ref[:, pl.ds(src * chunk_k, chunk_k)]
        partials[src] = jnp.dot(
            xs, comm_buf[slot], preferred_element_type=jnp.float32
        )
        if hop < n_devices - 1:
            rdma.wait()
    o_ref[:] = partials[0]
    for src in range(1, n_devices):
        o_ref[:] += partials[src]


def _cm_tpu_body(x_full, w_shard, *, n: int, axis_name: str):
    from jax.experimental.pallas import tpu as pltpu

    # collective_id sequences the RDMA ring; no has_side_effects needed —
    # the kernel has a real output, so it cannot be DCE'd.
    kc, nc = w_shard.shape
    return pl.pallas_call(
        functools.partial(
            _cm_rdma_kernel, n_devices=n, chunk_k=kc, axis_name=axis_name
        ),
        out_shape=jax.ShapeDtypeStruct((x_full.shape[0], nc), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, kc, nc), w_shard.dtype),
            pltpu.VMEM((n, x_full.shape[0], nc), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(collective_id=0),
        interpret=False,
        name="collective_matmul_rdma",
    )(x_full, w_shard)


def collective_matmul(x, w, *, mesh, axis_name: str = "dp",
                      interpret: bool):
    """``x @ w`` where ``w`` arrives sharded along its contraction (first)
    axis over ``axis_name`` and ``x`` is replicated — WITHOUT ever
    materializing the gathered ``w``.

    This is the "first matmul of the step" primitive: fed the ZeRO-1
    dp-sharded updated weight directly, it subsumes the update's exposed
    all-gather into the matmul's own schedule.  Partials are summed in
    fixed chunk order 0..dp−1 on every device (bitwise-consistent across
    replicas, deterministic for a fixed mesh) — but that reduction ORDER
    still differs from the monolithic dot's, so parity with the reference
    is allclose, not bitwise (docs/kernels.md §numerics); the ZeRO-1
    writeback itself uses :func:`ring_all_gather`, which IS bitwise.
    """
    n = mesh.shape[axis_name]
    if n <= 1:
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
    P = jax.sharding.PartitionSpec
    body = functools.partial(
        _cm_interpret_body if interpret else _cm_tpu_body,
        n=n,
        axis_name=axis_name,
        **({"interpret": True} if interpret else {}),
    )
    return shard_map_compat(body, mesh, (P(), P(axis_name, None)), P())(x, w)


def reference_collective_matmul(x, w):
    """The unfused reference: plain dot on the logically-full ``w`` — GSPMD
    partitions it as all-gather-then-dot when ``w`` is committed dp-sharded
    (the contrast half of ``inspect.check_collective_matmul``)."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)
