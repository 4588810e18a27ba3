"""Mamba-2 (state-space duality) in plain ``jax.numpy``: the chunked scan a
prefill runs and the one-token recurrence a decode step runs.

For head ``h`` of group ``g = h // (H // G)``, with ``a_t = dt_t[h] * A[h]``::

    S_t[h] = exp(a_t) * S_{t-1}[h] + dt_t[h] * x_t[h] (outer) B_t[g]      # (P, N)
    y_t[h] = S_t[h] @ C_t[g] + D[h] * x_t[h]

``ssd_chunked`` computes the same recurrence in chunks (Dao & Gu 2024, the
"SSD" algorithm): inside a chunk the outputs are one masked product of decays
and ``C B^T``; between chunks only the ``(H, P, N)`` state is carried.
``dt``, ``A``, every decay and the state are float32, as published; the
products inside run at ``Precision.HIGHEST`` (they are small: chunk x chunk and
chunk x state — the projections around them take the time).

A position whose ``dt`` is 0 neither decays the state nor adds to it, which is
how a bucket-padded prompt leaves the state where its last true token put it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def causal_conv(xbc, w, b=None):
    """Depthwise causal convolution over time with zero history.
    ``xbc: (T, C)``, ``w: (C, K)`` (tap ``K-1`` meets the current row),
    ``b: (C,)`` or ``None`` (no bias); float32 out."""
    t, k = xbc.shape[0], w.shape[1]
    x = jnp.pad(xbc.astype(jnp.float32), ((k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    out = 0.0 if b is None else b.astype(jnp.float32)[None]
    for j in range(k):
        out = out + x[j:j + t] * w[:, j][None]
    return out


def conv_tail(xbc, true_len, k: int):
    """The last ``k-1`` true rows of the pre-convolution ``xbc: (T, C)``
    (zero rows where the prompt is shorter): what the next token's
    convolution reads."""
    x = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    return jax.lax.dynamic_slice_in_dim(x, true_len, k - 1, axis=0)


def conv_step(tail, row, w, b=None):
    """One token for every slot.  ``tail: (S, K-1, C)`` earlier rows,
    ``row: (S, C)`` the current one, ``b: (C,)`` or ``None``.  Returns (float32
    output ``(S, C)``, new tail in ``tail``'s dtype)."""
    window = jnp.concatenate([tail, row[:, None].astype(tail.dtype)], axis=1)
    out = jnp.einsum("skc,ck->sc", window.astype(jnp.float32), w.astype(jnp.float32), precision=_HI)
    if b is not None:
        out = out + b.astype(jnp.float32)[None]
    return out, window[:, 1:]


def ssd_chunked(x, dt, a, b, c, d, chunk: int):
    """The recurrence over a whole sequence from a zero state.

    ``x: (T, H, P)``, ``dt: (T, H)`` float32 (0 where the state must stand
    still), ``a: (H,)`` float32 negative, ``b, c: (T, G, N)``, ``d: (H,)``;
    ``T`` a multiple of ``chunk``.  Returns ``(y (T, H, P) float32,
    final state (H, P, N) float32)``."""
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    nc, rep = t // chunk, h // g
    f32 = jnp.float32
    x = x.astype(f32).reshape(nc, chunk, h, p)
    dt = dt.astype(f32).reshape(nc, chunk, h)
    b = b.astype(f32).reshape(nc, chunk, g, n)
    c = c.astype(f32).reshape(nc, chunk, g, n)
    cs = jnp.cumsum(dt * a.astype(f32), axis=1)  # (nc, Q, H) log-decay from the chunk's start, inclusive
    xdt = x * dt[..., None]

    # inside a chunk: y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
    cb = jnp.einsum("ctgn,csgn->cgts", c, b, precision=_HI)  # (nc, G, Q, Q)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    gap = cs[:, :, None, :] - cs[:, None, :, :]  # (nc, t, s, H)
    decay = jnp.exp(jnp.where(causal[None, :, :, None], gap, -jnp.inf))  # 0 above the diagonal
    scores = jnp.repeat(cb, rep, axis=1) * decay.transpose(0, 3, 1, 2)  # (nc, H, t, s)
    y = jnp.einsum("chts,cshp->cthp", scores, xdt, precision=_HI)

    # what each chunk adds to the state by its end, and how much of the
    # incoming state survives it
    to_end = jnp.exp(cs[:, -1:, :] - cs)  # (nc, Q, H)
    b_h = jnp.repeat(b, rep, axis=2)  # (nc, Q, H, N)
    added = jnp.einsum("cshp,cshn->chpn", xdt * to_end[..., None], b_h, precision=_HI)
    survive = jnp.exp(cs[:, -1, :])  # (nc, H)

    def carry(state, inp):
        add, keep = inp
        return state * keep[:, None, None] + add, state  # ys: the state each chunk starts from

    final, starts = jax.lax.scan(carry, jnp.zeros((h, p, n), f32), (added, survive))
    # the incoming state's part: y_t += exp(cs_t) C_t . S_start
    c_h = jnp.repeat(c, rep, axis=2)
    y = y + jnp.einsum("cthn,chpn->cthp", c_h * jnp.exp(cs)[..., None], starts, precision=_HI)
    y = y + x * d.astype(f32)[None, None, :, None]
    return y.reshape(t, h, p), final


def ssm_step(state, x, dt, a, b, c, d):
    """One token for every slot.  ``state: (S, H, P, N)`` float32,
    ``x: (S, H, P)``, ``dt: (S, H)`` float32, ``b, c: (S, G, N)``.  Returns
    ``(y (S, H, P) float32, new state)``.  Slots never mix: every term is
    per slot.  The plain statement of the step: the decode program runs it
    over the live slots alone, in place in the state pool
    (``native/kernels/ssm_step.py``), and the tests hold that kernel to
    this."""
    f32 = jnp.float32
    h, g = x.shape[1], b.shape[1]
    x, dt = x.astype(f32), dt.astype(f32)
    b_h = jnp.repeat(b.astype(f32), h // g, axis=1)  # (S, H, N)
    c_h = jnp.repeat(c.astype(f32), h // g, axis=1)
    keep = jnp.exp(dt * a.astype(f32))  # (S, H)
    state = state * keep[..., None, None] + (x * dt[..., None])[..., None] * b_h[:, :, None, :]
    y = jnp.sum(state * c_h[:, :, None, :], axis=-1) + x * d.astype(f32)[None, :, None]
    return y, state
