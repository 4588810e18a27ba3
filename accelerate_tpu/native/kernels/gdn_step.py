"""The one-token gated delta rule over the live slots, in place in the state
pool (docs/kernels.md §delta-rule step; docs/serving.md §layer plan).

``ops/delta_rule.py::delta_rule_step`` states the math for every slot; this is
how the decode program runs it.  Per head, on the packed state ``S``
(``delta_rule.pack_state``: ``(d_k / r, r · d_v)``, packed row ``j`` holding
``k`` rows ``r·j … r·j + r − 1`` side by side, ``d_v`` lanes each)::

    o  = exp(g) Sᵀq + (k·q) β (v − exp(g) Sᵀk)          # from the OLD state
    S' = S exp(g) + k ⊗ β (v − exp(g) Sᵀk)

One grid step a LIVE slot, in place in the whole pool: the live list, the
scalar prefetch, the aliasing and the ``shard_map`` on a mesh are
``live_slots.py``'s, shared with Mamba-2's step (``ssm_step.py``).  A live
slot's ``(H, d_k / r, r · d_v)`` float32 state (2.2 MB at Olmo-Hybrid's 30
heads of 96 × 192, packed ``(48, 384)``) is read into VMEM once; both sums
over ``k`` rows and the update are taken from that one tile, which is written
back once.  A dead slot's state is neither read nor written.

**Heads down the lanes.**  Inside the tile ``k`` rows run down the sublanes,
so a head's ``k`` and ``q`` are wanted as columns, one for each of the ``r``
rows a packed row holds.  The program hands the kernel a slot's ``k`` and
``q`` laid ``(d_k / r, lanes)``: lane ``r·h + m`` holds ``k[h, r·j + m]`` down
row ``j``, and ``q`` follows from lane ``r·H`` on.  A column is selected on an
iota and summed across the lanes (exact: one value plus zeros), then spread
over the ``d_v`` lanes of its part of the packed row by a select.  The sums
over ``k`` rows run down the sublanes and come out as a lane row; the ``r``
parts of that row are folded by rolls of ``d_v`` lanes, which leaves the fold
tiled ``r`` times, as the update wants it.  Nothing is transposed in the
kernel and no lane is indexed by a traced number.  ``exp(g)``, ``β`` and
``k·q`` are three scalars a head, read from SMEM.

**The same float32 math as** ``delta_rule_step``: the l2 norms, ``exp(g)``,
``β``, ``k·q`` and the convolution are the program's own ops around the
kernel; ``u = β (v − exp(g) fold(Σ S·k))``, ``o = exp(g) fold(Σ S·q) + (k·q)
u`` and ``S' = S exp(g) + k u`` in that order.  The sums over ``k`` rows are
the ones whose order differs from the plain step's.

``o`` comes back tiled ``r`` times along its lanes, as the update uses ``u``;
the program keeps the first ``d_v``.  A dead slot's ``o`` row is zeros.  With
no live slot at all the first step copies its block onto itself (a block the
pipeline writes back must have been written).

**One lowering a program.**  The kernel is called through one ``jax.jit``
function whose arguments have the same shapes at every linear layer (the
pool, the rank as a traced scalar, the layer's rows): jax lowers it once per
module and the layers call it.

Off the TPU the same kernel runs through the Pallas interpreter
(``ops/flash_attention.py::_interpret``: the code asks its backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_import import import_pallas

pl, pltpu = import_pallas()

from ...ops import delta_rule, flash_attention  # noqa: E402  (imports Pallas: after import_pallas)
from . import live_slots  # noqa: E402
from .live_slots import per_slot  # noqa: E402

__all__ = ["gdn_step_live"]


def _columns(kq, lane, part, first: int, r: int):
    """``(rows, r · d_v)``: the ``r`` columns of ``kq`` from lane ``first`` on,
    column ``m`` over the lanes of part ``m`` of a packed row."""
    out = None
    for m in range(r):
        col = jnp.sum(jnp.where(lane == first + m, kq, 0.0), axis=1, keepdims=True)  # (rows, 1)
        out = jnp.broadcast_to(col, (kq.shape[0], part.shape[1])) if out is None else jnp.where(part == m, col, out)
    return out


def _kernel(ids, count, layer, coef_ref, kq_ref, v_ref, s_ref, o_ref, y_ref, *, r: int, d_v: int):
    step = pl.program_id(0)
    heads = s_ref.shape[2]

    @pl.when(step < count[0])
    def _():
        kq = kq_ref[0]  # (d_k / r, lanes): k's columns, then q's
        lanes = s_ref.shape[-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, kq.shape, 1)
        part = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // d_v  # which k row of a packed row

        def fold(x):  # (1, r · d_v): the r parts summed, tiled r times
            out = x
            for m in range(1, r):
                out = out + pltpu.roll(x, m * d_v, 1)
            return out

        def head(h, carry):
            keep, beta, kdotq = coef_ref[0, 0, h], coef_ref[0, 1, h], coef_ref[0, 2, h]
            k_s = _columns(kq, lane, part, r * h, r)
            q_s = _columns(kq, lane, part, r * (heads + h), r)
            s = s_ref[0, 0, h]  # (d_k / r, r · d_v)
            s_k = keep * fold(jnp.sum(s * k_s, axis=0, keepdims=True))
            s_q = keep * fold(jnp.sum(s * q_s, axis=0, keepdims=True))
            u = beta * (v_ref[0, pl.ds(h, 1), :] - s_k)
            y_ref[0, pl.ds(h, 1), :] = s_q + kdotq * u
            o_ref[0, 0, h] = s * keep + k_s * u
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(jnp.logical_and(step == 0, count[0] == 0))
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "mesh"))
def _gdn_step(pool, layer, live, q, k, v, g, beta, *, interpret: bool, mesh):
    f32 = jnp.float32
    _, slots, heads, rows, lanes = pool.shape
    d_k, d_v = k.shape[-1], v.shape[-1]
    r = lanes // d_v
    q, k = delta_rule.l2norm(q) * d_k ** -0.5, delta_rule.l2norm(k)
    coef = jnp.stack([jnp.exp(g.astype(f32)), beta.astype(f32), jnp.sum(k * q, axis=-1)], axis=1)  # (S, 3, H)

    def columns(x):  # (S, H, d_k) -> (S, d_k / r, H · r): lane r·h + m holds x[h, r·j + m] down row j
        return x.reshape(slots, heads, rows, r).transpose(0, 2, 1, 3).reshape(slots, rows, heads * r)

    width = -(-2 * heads * r // 128) * 128
    kq = jnp.concatenate([columns(k), columns(q)], axis=-1)
    kq = jnp.pad(kq, ((0, 0), (0, 0), (0, width - 2 * heads * r)))
    ids, count = live_slots.live_list(live)
    pool, o = live_slots.over_live_slots(
        functools.partial(_kernel, r=r, d_v=d_v), pool, layer, ids, count,
        (coef, kq, jnp.tile(v.astype(f32), r)),
        [per_slot(1, 3, heads, memory_space=pltpu.SMEM), per_slot(1, rows, width), per_slot(1, heads, lanes)],
        [per_slot(1, heads, lanes)], [jax.ShapeDtypeStruct((slots, heads, lanes), f32)],
        name="gdn_step", interpret=interpret, mesh=mesh,
    )
    return jnp.where(live[:, None, None], o[..., :d_v], 0.0), pool


def gdn_step_live(pool, layer, live, q, k, v, g, beta, *, mesh=None):
    """One token for every live slot, in place in ``pool``.

    ``pool: (L, S, H, d_k / r, r · d_v)`` float32 — every linear layer's
    packed states, as the state pool holds them; ``layer``: this layer's rank
    in it (an int or a traced int32); ``live: (S,)`` bool; ``q, k: (S, H,
    d_k)`` as convolved (l2-normed here, as ``delta_rule_step`` does), ``v:
    (S, H, d_v)``, ``g, beta: (S, H)``.  Returns ``(o (S, H, d_v) float32, the
    pool)``: the live slots' rows of layer ``layer`` updated, every other row
    as it was, and a dead slot's ``o`` zeros.

    ``mesh``: the mesh the pool is committed to, where it has several devices
    (``serving/engine.py::_pool_mesh``): there the kernel runs per device
    under ``shard_map`` on replicated operands."""
    return _gdn_step(
        pool, jnp.asarray(layer, jnp.int32), live, q, k, v, g, beta,
        interpret=flash_attention._interpret(), mesh=mesh,
    )
