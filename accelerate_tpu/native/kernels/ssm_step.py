"""The one-token Mamba-2 recurrence over the live slots, in place in the state
pool (docs/kernels.md §state-space step; docs/serving.md §layer plan).

``ops/ssm.py::ssm_step`` states the math for every slot; this is how the
decode program runs it.  For head ``h`` of group ``g = h // (H // G)``::

    S'[h] = exp(dt[h] A[h]) S[h] + (dt[h] x[h]) (outer) B[g]        # (P, N)
    y[h]  = S'[h] @ C[g] + D[h] x[h]

One grid step a LIVE slot, in place in the whole pool: the live list, the
scalar prefetch, the aliasing and the ``shard_map`` on a mesh are
``live_slots.py``'s, shared with the delta rule's step (``gdn_step.py``).  A
live slot's ``(H, P, N)`` float32 state (2 MiB at Nemotron-H's 64 × 64 × 128)
is read into VMEM once, updated, summed against ``C`` from the same tile and
written back once; a dead slot's is neither read nor written.

**Heads down the lanes.**  Inside the tile the state's ``P`` runs down the
sublanes and ``N`` across the lanes, so a head's ``x·dt`` is wanted as a column
and its ``y`` comes out as one.  The program hands the kernel ``x·dt`` laid
``(P, H)``, a slot's heads side by side on the lanes, and takes ``y`` back the
same way: a head's column is its lane, selected on an iota and summed across
the lanes (exact: one value plus zeros), and written into its lane of the
slot's ``(P, H)`` result by a select.  Nothing is transposed in the kernel and
no lane is indexed by a traced number.

**The same float32 math as** ``ssm_step``: ``exp(dt A)``, ``x·dt`` and
``D·x`` are the program's own ops around the kernel, the update is
``S·keep + (x·dt)·B`` in that order, and ``y`` sums ``S'·C`` over ``N``
across the lanes — the one sum whose order differs from the plain step's.

A dead slot's ``y`` row is zeros.  With no live slot at all the first step
copies its block onto itself (a block the pipeline writes back must have been
written).

**One lowering a program.**  The kernel is called through one ``jax.jit``
function whose arguments have the same shapes at every Mamba layer (the pool,
the rank as a traced scalar, the layer's rows): jax lowers it once per module
and the layers call it, so an unrolled plan of 12 Mamba layers pays for one
Mosaic lowering, not twelve.

Off the TPU the same kernel runs through the Pallas interpreter
(``ops/flash_attention.py::_interpret``: the code asks its backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_import import import_pallas

pl, pltpu = import_pallas()

from ...ops import flash_attention  # noqa: E402  (imports Pallas: after import_pallas)
from . import live_slots  # noqa: E402
from .live_slots import per_slot  # noqa: E402

__all__ = ["ssm_step_live"]


def _kernel(ids, count, layer, keep_ref, xdt_ref, b_ref, c_ref, s_ref, o_ref, y_ref, *, rep: int):
    step = pl.program_id(0)
    heads = s_ref.shape[2]

    @pl.when(step < count[0])
    def _():
        xdt = xdt_ref[0]  # (P, H): head h's x·dt down lane h
        keep = keep_ref[0]  # (1, H)
        lane = jax.lax.broadcasted_iota(jnp.int32, xdt.shape, 1)

        def head(h, y):
            at = lane == h
            x_col = jnp.sum(jnp.where(at, xdt, 0.0), axis=1, keepdims=True)  # (P, 1)
            decay = jnp.sum(jnp.where(at[:1], keep, 0.0), axis=1, keepdims=True)  # (1, 1)
            group = pl.ds(h // rep, 1)
            new = s_ref[0, 0, h] * decay + x_col * b_ref[0, group, :]  # (P, N)
            o_ref[0, 0, h] = new
            return jnp.where(at, jnp.sum(new * c_ref[0, group, :], axis=1, keepdims=True), y)

        y_ref[0] = jax.lax.fori_loop(0, heads, head, jnp.zeros(xdt.shape, jnp.float32))

    @pl.when(jnp.logical_and(step == 0, count[0] == 0))
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


def _call(pool, layer, ids, count, keep, xdt, b, c, *, interpret: bool, mesh):
    _, slots, heads, p, n = pool.shape
    groups = b.shape[1]
    return live_slots.over_live_slots(
        functools.partial(_kernel, rep=heads // groups), pool, layer, ids, count, (keep, xdt, b, c),
        [per_slot(1, 1, heads), per_slot(1, p, heads), per_slot(1, groups, n), per_slot(1, groups, n)],
        [per_slot(1, p, heads)], [jax.ShapeDtypeStruct((slots, p, heads), jnp.float32)],
        name="ssm_step", interpret=interpret, mesh=mesh,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "mesh"))
def _step(pool, layer, live, x, dt, a, b, c, d, *, interpret: bool, mesh):
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    keep = jnp.exp(dt * a.astype(f32))  # (S, H)
    ids, count = live_slots.live_list(live)
    pool, y = _call(
        pool, layer, ids, count, keep[:, None, :], jnp.swapaxes(x * dt[..., None], 1, 2),
        b.astype(f32), c.astype(f32), interpret=interpret, mesh=mesh,
    )
    y = jnp.swapaxes(y, 1, 2) + x * d.astype(f32)[None, :, None]
    return jnp.where(live[:, None, None], y, 0.0), pool


def ssm_step_live(pool, layer, live, x, dt, a, b, c, d, *, mesh=None):
    """One token for every live slot, in place in ``pool``.

    ``pool: (L, S, H, P, N)`` float32 — every recurrent layer's states, as the
    state pool holds them; ``layer``: this layer's rank in it (an int or a
    traced int32); ``live: (S,)`` bool; ``x: (S, H, P)``, ``dt: (S, H)``,
    ``a, d: (H,)``, ``b, c: (S, G, N)``.  Returns ``(y (S, H, P) float32, the
    pool)``: the live slots' rows of layer ``layer`` updated, every other row
    as it was, and a dead slot's ``y`` zeros.

    ``mesh``: the mesh the pool is committed to, where it has several devices
    (``serving/engine.py::_pool_mesh``): GSPMD cannot partition a Mosaic
    kernel, so there it runs per device under ``shard_map`` on replicated
    operands, as ``paged_attention`` does."""
    return _step(
        pool, jnp.asarray(layer, jnp.int32), live, x, dt, a, b, c, d,
        interpret=flash_attention._interpret(), mesh=mesh,
    )
