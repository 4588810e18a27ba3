"""The gated delta rule (Gated DeltaNet; Yang, Kautz, Hatamizadeh 2024) in plain
``jax.numpy``: the chunked scan a prefill runs, and the one-token recurrence
as the plain statement of what a decode step runs.  The decode program runs
that step as a Pallas kernel over the live slots, in place in the state pool
(``native/kernels/gdn_step.py``: the same float32 math, the sums over ``k``
rows in another order); ``delta_rule_step`` is the kernel's reference in the
tests.

Per head, with a state ``S`` of ``(d_k, d_v)``, a log-decay ``g_t <= 0`` and a
write strength ``beta_t`` (up to 2 where negative eigenvalues are allowed)::

    S_t = exp(g_t) S_{t-1}
    S_t = S_t + k_t (outer) beta_t (v_t - S_t^T k_t)          # the delta rule
    o_t = S_t^T q_t

The transition is ``exp(g)(I - beta k k^T)``: not diagonal, so this is not the
state-space recurrence of ``ops/ssm.py``.  ``q`` and ``k`` come in as the
convolved projections; both are l2-normalised here per head (eps 1e-6) and
``q`` scaled by ``d_k**-0.5``, in float32, as is everything else: the norms,
the decays, the state.

``delta_rule_chunked`` computes the same recurrence in chunks (the WY / UT
transform): inside a chunk the writes depend on one another through the
unit-lower-triangular system ``I + tril(beta K K^T * decay, -1)``, inverted by
block forward substitution (``_unit_lower_inverse``); between chunks only ``S``
is carried.  Every product inside runs at ``Precision.HIGHEST`` (chunk x chunk
and chunk x state: small) — the inverse too: ``solve_triangular`` runs its
blocks' products in one bfloat16 pass on a TPU, and the served logits left
the reference by 1.0 where they leave it by 0.1 (PERF.md, PR 36).

A position with ``beta = 0`` and ``g = 0`` neither decays the state nor writes
to it, which is how a bucket-padded prompt leaves the state where its last
true token put it.

**The state's layout on the chip** (``pack_state``).  Float32 ``(d_k, d_v)``
with ``d_v = 192`` lies on 256 lanes: a third more memory and a third more
bytes every step.  The pool therefore holds ``r`` consecutive ``k`` rows side
by side, ``(d_k / r, r * d_v)`` with ``r * d_v`` a whole number of 128-lane
tiles (192: r = 2, 384 lanes).  ``delta_rule_step`` works on that layout as it
lies: ``k`` and ``q`` are spread over it by selects, the sums over ``k`` rows
are reductions over the major axis, and only a head's ``(r * d_v,)`` vectors
are ever folded to ``(d_v,)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis (FLA's form), float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def rows_packed(d_k: int, d_v: int) -> int:
    """``r``: how many consecutive ``k`` rows of a state share one row of
    lanes, the least that makes ``r * d_v`` whole 128-lane tiles (1 where
    ``d_k`` cannot be divided so)."""
    r = 128 // math.gcd(d_v, 128)
    return r if d_k % r == 0 else 1


def pack_state(state):
    """``(..., d_k, d_v) -> (..., d_k / r, r * d_v)``: row ``i`` goes to row
    ``i // r``, lanes ``(i % r) * d_v`` on."""
    *lead, d_k, d_v = state.shape
    r = rows_packed(d_k, d_v)
    return state.reshape(*lead, d_k // r, r * d_v)


def unpack_state(packed, d_v: int):
    *lead, rows, lanes = packed.shape
    return packed.reshape(*lead, rows * (lanes // d_v), d_v)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a: (..., n, n)``, by block
    forward substitution in matrix products alone.  With the inverses ``T`` of
    the diagonal blocks of size ``b`` in hand, those of size ``2b`` are
    ``[[T11, 0], [-T22 A21 T11, T22]]``: one masked ``T - T (A * lower_left) T``
    a level, ``log2 n`` levels.  No power of ``a`` is ever formed, so keys that
    repeat (``a`` full of 2s at ``beta = 2``, whose powers reach 1e27) cost
    nothing: every intermediate is a sum of products of entries of ``a`` and
    of inverses, as in forward substitution."""
    n = a.shape[-1]
    i = jnp.arange(n)
    t = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    b = 1
    while b < n:
        same_pair = (i[:, None] // (2 * b)) == (i[None, :] // (2 * b))
        lower_left = same_pair & ((i[:, None] // b) % 2 == 1) & ((i[None, :] // b) % 2 == 0)
        a21 = jnp.where(lower_left, a, 0.0)
        t = t - jnp.matmul(t, jnp.matmul(a21, t, precision=_HI), precision=_HI)
        b *= 2
    return t


def delta_rule_chunked(q, k, v, g, beta, chunk: int):
    """The recurrence over a whole sequence from a zero state.

    ``q, k: (T, H, d_k)``, ``v: (T, H, d_v)``, ``g, beta: (T, H)`` float32
    (both 0 where the state must stand still); ``T`` a multiple of ``chunk``.
    Returns ``(o (T, H, d_v) float32, final state (H, d_k, d_v) float32)``."""
    t, h, dk = k.shape
    dv = v.shape[-1]
    nc = t // chunk
    # (H, nc, Q, ·): a head's chunks are what every product below batches over
    split = lambda x: x.astype(F32).reshape(nc, chunk, h, -1).transpose(2, 0, 1, 3)  # noqa: E731
    q, k, v = split(l2norm(q) * dk ** -0.5), split(l2norm(k)), split(v)
    g = g.astype(F32).reshape(nc, chunk, h).transpose(2, 0, 1)  # (H, nc, Q)
    beta = beta.astype(F32).reshape(nc, chunk, h).transpose(2, 0, 1)
    cs = jnp.cumsum(g, axis=-1)  # log-decay from the chunk's start, inclusive
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    decay = jnp.exp(jnp.where(lower, cs[..., :, None] - cs[..., None, :], -jnp.inf))  # (H, nc, t, s)
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]

    # inside a chunk, write t sees the writes before it:
    # (I + A) W = [beta v | beta k exp(cs)],  A = tril(beta K K^T * decay, -1)
    a = jnp.where(strict, jnp.einsum("hctd,hcsd->hcts", k_beta, k, precision=_HI) * decay, 0.0)
    rhs = jnp.concatenate([v_beta, k_beta * jnp.exp(cs)[..., None]], axis=-1)
    solved = jnp.matmul(_unit_lower_inverse(a), rhs, precision=_HI)
    value, k_cumdecay = solved[..., :dv], solved[..., dv:]
    qk = jnp.where(lower, jnp.einsum("hctd,hcsd->hcts", q, k, precision=_HI) * decay, 0.0)
    to_end = jnp.exp(cs[..., -1:] - cs)  # (H, nc, Q)
    survive = jnp.exp(cs[..., -1])  # (H, nc)

    def one_chunk(state, inp):
        q_c, k_c, value_c, kcd_c, qk_c, cs_c, to_end_c, survive_c = inp
        v_new = value_c - jnp.einsum("htk,hkv->htv", kcd_c, state, precision=_HI)
        out = jnp.einsum("htk,hkv->htv", q_c * jnp.exp(cs_c)[..., None], state, precision=_HI)
        out = out + jnp.einsum("hts,hsv->htv", qk_c, v_new, precision=_HI)
        state = state * survive_c[:, None, None] + jnp.einsum(
            "htk,htv->hkv", k_c * to_end_c[..., None], v_new, precision=_HI
        )
        return state, out

    by_chunk = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731  (nc, H, ...)
    final, out = jax.lax.scan(
        one_chunk, jnp.zeros((h, dk, dv), F32),
        tuple(by_chunk(x) for x in (q, k, value, k_cumdecay, qk, cs, to_end, survive)),
    )
    return out.transpose(0, 2, 1, 3).reshape(t, h, dv), final  # (nc, H, Q, dv) -> (T, H, dv)


def _spread(x, rows: int, r: int, d_v: int):
    """``(..., d_k) -> (..., d_k / r, r * d_v)``: entry ``i`` over the ``d_v``
    lanes that hold row ``i`` of a packed state.  Selects on an iota, so that
    it fuses into whatever reads it and is never laid out on its own."""
    x = x.reshape(*x.shape[:-1], rows, r)
    part = jnp.arange(r * d_v) // d_v  # which of the r rows a lane belongs to
    out = x[..., 0:1] * jnp.ones((r * d_v,), F32)
    for m in range(1, r):
        out = jnp.where(part == m, x[..., m:m + 1], out)
    return out


def delta_rule_step(state, q, k, v, g, beta):
    """One token for every slot.  ``state: (S, H, d_k / r, r * d_v)`` float32,
    packed (``pack_state``); ``q, k: (S, H, d_k)``, ``v: (S, H, d_v)``,
    ``g, beta: (S, H)`` float32.  Returns ``(o (S, H, d_v) float32, new
    state)``.  Slots never mix: every term is per slot.

    ``o = S_new^T q`` is taken from the OLD state, ``exp(g) S^T q + (k . q)
    beta (v - exp(g) S^T k)``: both sums over ``k`` rows then read the state
    in one pass, and the pass that writes it is elementwise."""
    rows, lanes = state.shape[-2:]
    dk, dv = k.shape[-1], v.shape[-1]
    r = lanes // dv
    q, k = l2norm(q) * dk ** -0.5, l2norm(k)
    v, g, beta = v.astype(F32), g.astype(F32), beta.astype(F32)
    k_s, q_s = _spread(k, rows, r, dv), _spread(q, rows, r, dv)
    fold = lambda x: jnp.sum(x.reshape(*x.shape[:-1], r, dv), axis=-2)  # noqa: E731
    keep = jnp.exp(g)[..., None]  # (S, H, 1)
    s_k = keep * fold(jnp.sum(state * k_s, axis=-2))  # exp(g) S^T k: (S, H, d_v)
    s_q = keep * fold(jnp.sum(state * q_s, axis=-2))
    u = beta[..., None] * (v - s_k)
    o = s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    state = state * keep[..., None] + k_s * jnp.tile(u, r)[..., None, :]
    return o, state
