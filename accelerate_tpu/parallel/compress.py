"""Quantized dp-axis collectives: ONE compression layer for gradient and
ZeRO-1 weight-update traffic inside the captured step.

EQuARX (PAPERS.md #3) shows ~2x effective-bandwidth wins from quantized
all-reduce inside XLA; the cross-replica weight-update paper (PAPERS.md #2)
is the basis of our ZeRO-1 reduce-scatter → 1/dp-local-update → all-gather
shape.  This module is where both meet: a :class:`CompressionPolicy`
(``none`` / ``int8`` / ``fp8`` / ``powersgd``) that owns

* **the dp-collective pair of the ZeRO-1 captured update** —
  :meth:`CompressionPolicy.reduce_scatter` quantizes the gradient's trip to
  the dp-sharded update (per-block scales, one scale per index of the
  sharded axis so every block is shard-local) and
  :meth:`CompressionPolicy.all_gather` transports the updated param back as
  a quantized *delta* against the replica's current value;
* **error feedback** — the reduce-scatter side carries a residual with the
  SAME ``NamedSharding`` as the ZeRO-1 optimizer state (1/dp bytes per
  replica), threaded through ``CapturedStep`` exactly like optax moments
  (``Optimizer.capture_state``) so replays cost zero extra recompiles; the
  all-gather side needs none — transporting the *delta* against the
  replica's current value is implicitly error-feedback (see
  :meth:`CompressionPolicy.all_gather`);
* **the comm-hook boundary** — PowerSGD's rank-k + error-feedback
  recurrence lives here now (moved from ``utils/powersgd.py``, which
  delegates), selected through the same policy surface, so hook selection,
  eligibility gates and error-feedback state management are one code path;
* **collective-bytes attribution** — :func:`collective_bytes` computes the
  analytic per-step dp-axis wire bytes for a policy, recorded through
  telemetry (``kind="collectives"``).

Error-feedback semantics (docs/compression.md): in the GSPMD formulation
the dp gradient *sum* happens inside the backward (XLA's psum), so the
summed gradient is replicated when it reaches the update.  The
reduce-scatter entry transmits ``Q(g)`` and corrects shard-locally:
``g_used = Q(g) + err_prev``, ``err_new = g_shard - Q(g)_shard`` — the
injected error telescopes across steps, the standard EF guarantee, and the
residual never needs gathering.  The all-gather entry transports the
quantized delta against the replica's current value, whose feedback is
implicit (the untransmitted part of this step's delta IS next step's).

Quantization grid: one fp32 scale per index of the dp-sharded axis
("per-block", block = one slice), ``amax``-scaled, so quantize/dequantize
are shard-local for every dp extent dividing the axis.  int8 rounds to
±127; fp8 rides ``float8_e4m3fn`` (±448).

Enable with ``ACCELERATE_COMPRESSION=int8`` (or ``fp8``/``powersgd``/
``batched_powersgd``) or
``Accelerator(kwargs_handlers=[CompressionKwargs(policy="int8")])``.
``none`` (the default) leaves every existing code path byte-identical.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .sharding import canonical_spec

__all__ = [
    "CompressionPolicy",
    "NoneCompression",
    "Int8Compression",
    "Fp8Compression",
    "PowerSGDCompression",
    "quantize",
    "dequantize",
    "shard_accumulation",
    "collective_bytes",
    "resolve_policy",
    "eligible_matrix_shape",
    "init_powersgd_state",
    "apply_powersgd",
    "init_batched_powersgd_state",
    "apply_batched_powersgd",
]


# ---------------------------------------------------------------------------
# quantization primitives (per-block scales along the sharded axis)
# ---------------------------------------------------------------------------

# saturation value of each wire dtype: int8 rounds onto ±127, float8_e4m3fn
# encodes ±448 natively
_QMAX = {"int8": 127.0, "float8_e4m3fn": 448.0}


def _qmax(wire_dtype) -> float:
    name = jnp.dtype(wire_dtype).name
    if name not in _QMAX:
        raise ValueError(f"unsupported wire dtype {name!r}; use int8 or float8_e4m3fn")
    return _QMAX[name]


def quantize(x, axis: int, wire_dtype=jnp.int8):
    """``x`` (fp32) → ``(payload, scales)`` with one scale per index of
    ``axis``.

    Blocks are the slices along ``axis`` — the axis ZeRO-1 shards over dp —
    so quantization is independent per block and therefore shard-local for
    any dp extent dividing the axis.  Zero blocks quantize to zero payload
    with a zero scale (dequantize returns exact zeros).
    """
    qmax = _qmax(wire_dtype)
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    amax = jnp.max(jnp.abs(x), axis=reduce_axes, keepdims=True)
    scales = amax / qmax
    safe = jnp.where(scales > 0, scales, 1.0)
    y = x / safe
    if jnp.issubdtype(jnp.dtype(wire_dtype), jnp.integer):
        payload = jnp.clip(jnp.round(y), -qmax, qmax).astype(wire_dtype)
    else:
        payload = jnp.clip(y, -qmax, qmax).astype(wire_dtype)
    return payload, scales


def dequantize(payload, scales):
    """Inverse of :func:`quantize`: broadcast-multiply the per-block scales
    back in.  The ONLY sanctioned way to widen a wire payload — a bare
    ``payload.astype(float32)`` discards the scales (graftlint's
    ``dtype-widen`` rule flags exactly that outside this module)."""
    return payload.astype(jnp.float32) * scales


def _to_layout(x, sharding):
    """Commit/constrain ``x`` to ``sharding`` — ``with_sharding_constraint``
    for tracers (captured step), ``device_put`` eagerly (same split as
    ``Optimizer._on_param_layout``)."""
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)


def _scales_sharding(sharding: jax.sharding.NamedSharding, axis: int, ndim: int):
    """Sharding for the keepdims scale vector: same mesh, the sharded-axis
    entry preserved, every size-1 dim unsharded."""
    spec = list(sharding.spec) + [None] * (ndim - len(sharding.spec))
    out = [None] * ndim
    out[axis] = spec[axis]
    return jax.sharding.NamedSharding(
        sharding.mesh, canonical_spec(jax.sharding.PartitionSpec(*out), sharding.mesh)
    )


def _drop_axis_entry(sharding: jax.sharding.NamedSharding, axis: int, ndim: int):
    """The same layout with the dp entry at ``axis`` removed — the
    replicated-over-dp target of the all-gather."""
    spec = list(sharding.spec) + [None] * (ndim - len(sharding.spec))
    spec[axis] = None
    return jax.sharding.NamedSharding(
        sharding.mesh, canonical_spec(jax.sharding.PartitionSpec(*spec), sharding.mesh)
    )


def shard_accumulation(grad, sharding):
    """ZeRO-2 entry point: keep an accumulated gradient reduce-scattered
    between micro-steps, so the accumulation buffer is 1/dp per replica.

    Layout-only by design: re-quantizing a running fp32 accumulation every
    micro-step would pass the sum through wire rounding ``num_steps`` times
    (the same reason ``Accelerator.backward`` compresses only at the sync
    boundary).  On hardware the backward's psum against a dp-sharded
    consumer lowers to a reduce-scatter; the value is unchanged.
    """
    return _to_layout(grad, sharding)


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------
class CompressionPolicy:
    """One compression strategy for dp-axis traffic.

    Two independent capabilities, so one abstraction covers both stories:

    * ``quantizes_collectives`` — the policy compresses the ZeRO-1
      reduce-scatter / all-gather pair (:meth:`reduce_scatter` /
      :meth:`all_gather`), with per-param residuals managed by the
      Optimizer (dp-sharded, capture-threaded);
    * ``hook_name`` — the policy runs at the backward sync boundary as a
      comm hook (PowerSGD); ``None`` for the quantizing policies.
    """

    name: str = "none"
    wire_dtype = None
    quantizes_collectives: bool = False
    hook_name: Optional[str] = None

    def __init__(self, min_size: int = 2048, min_block: int = 8,
                 error_feedback: bool = True):
        self.min_size = int(min_size)
        self.min_block = int(min_block)
        self.error_feedback = bool(error_feedback)

    # -- eligibility (shared gate for both directions) -----------------------
    def eligible(self, shape: tuple, dtype, axis: Optional[int]) -> bool:
        """min-size / dtype / block-geometry gates: tiny tensors, non-float
        tensors, and tensors whose per-block slice is too small to amortize
        the fp32 scale vector pass through uncompressed."""
        if not self.quantizes_collectives or axis is None:
            return False
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            return False
        n = int(math.prod(shape))
        if n < self.min_size:
            return False
        block = n // int(shape[axis])
        return block >= self.min_block

    # -- ZeRO-1 collective pair ---------------------------------------------
    def reduce_scatter(self, x32, sharding, axis: int, err):
        """Transport a (dp-replicated, already psum'd) fp32 gradient to the
        dp-sharded update layout through the wire dtype.

        Returns ``(g_used, err_new)`` — both dp-sharded fp32.  ``g_used``
        is what the local update consumes; ``err_new`` replaces the
        residual (``None`` stays ``None`` when error feedback is off).
        """
        payload, scales = quantize(x32, axis, self.wire_dtype)
        payload = _to_layout(payload, sharding)  # the wire: 1-byte scatter
        scales = _to_layout(scales, _scales_sharding(sharding, axis, x32.ndim))
        wire = dequantize(payload, scales)
        if err is None:
            return wire, None
        used = wire + err
        # shard-local truth: the replicated input's own slice (no comms)
        truth = _to_layout(x32, sharding)
        return used, truth - wire

    def all_gather(self, new_shard32, base, sharding, axis: int):
        """Transport the dp-sharded updated value back to the replica layout
        as a quantized delta against ``base`` (the replica's current param).

        Returns ``full32`` on the base's layout with the dp entry dropped.
        No explicit residual: the delta formulation is IMPLICITLY
        error-feedback — the replica accumulates every transmitted wire, so
        whatever Q dropped this step reappears in the next step's delta
        (``m_t − w_{t−1}``) automatically, and the replica tracks the exact
        master within ONE quantization step of the (lr-small) delta.
        Carrying an explicit residual on top would only widen the worst
        case to two steps while doubling the threaded state.
        """
        base32 = base.astype(jnp.float32)
        base_shard = _to_layout(base32, sharding)
        delta = new_shard32 - base_shard
        payload, scales = quantize(delta, axis, self.wire_dtype)
        # the wire: all-gather of the 1-byte payload + the tiny scale vector
        out = _drop_axis_entry(sharding, axis, new_shard32.ndim)
        payload = _to_layout(payload, out)
        scales = _to_layout(scales, _scales_sharding(out, axis, new_shard32.ndim))
        return base32 + dequantize(payload, scales)

    def init_residual(self, shape: tuple, sharding) -> Any:
        """Zero residual on the ZeRO-1 state sharding (1/dp per replica)."""
        if not self.error_feedback:
            return None
        return jax.device_put(jnp.zeros(shape, jnp.float32), sharding)

    # -- wire accounting ------------------------------------------------------
    def wire_bytes(self, shape: tuple, axis: int) -> int:
        """Analytic bytes one direction moves for one tensor: payload at the
        wire width plus the fp32 per-block scale vector."""
        n = int(math.prod(shape))
        return n * jnp.dtype(self.wire_dtype).itemsize + int(shape[axis]) * 4

    # -- comm-hook surface (PowerSGD overrides) -------------------------------
    def init_hook_state(self, named_shapes: dict, key):
        return None

    def apply_hook(self, named_grads: dict, state, rng_key=None):
        return named_grads, state

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name!r})"


class NoneCompression(CompressionPolicy):
    """The default: no compression anywhere; every path byte-identical to
    the pre-compression library."""

    name = "none"


class Int8Compression(CompressionPolicy):
    name = "int8"
    wire_dtype = jnp.int8
    quantizes_collectives = True


class Fp8Compression(CompressionPolicy):
    name = "fp8"
    wire_dtype = jnp.float8_e4m3fn
    quantizes_collectives = True


class PowerSGDCompression(CompressionPolicy):
    """Rank-k + error-feedback gradient compression at the backward sync
    boundary (Vogels et al., arXiv:1905.13727) — the reference's
    ``DDPCommunicationHookType.POWER_SGD`` / ``BATCHED_POWER_SGD``.

    Selected through the same :class:`CompressionPolicy` surface as the
    wire-dtype policies; the (Q, error) hook state is built by
    :meth:`init_hook_state` and applied by :meth:`apply_hook` (the
    Accelerator threads it through the captured step like optimizer state).
    The algorithm lives in this module now; ``utils/powersgd.py`` delegates.
    """

    quantizes_collectives = False

    def __init__(self, rank: int = 1, use_error_feedback: bool = True,
                 warm_start: bool = True, batched: bool = False,
                 wrapper_dtype=None, **kwargs):
        super().__init__(error_feedback=use_error_feedback, **kwargs)
        self.rank = int(rank)
        self.use_error_feedback = bool(use_error_feedback)
        self.warm_start = bool(warm_start)
        self.batched = bool(batched)
        self.wrapper_dtype = wrapper_dtype
        self.name = "batched_powersgd" if batched else "powersgd"
        self.hook_name = self.name

    def init_hook_state(self, named_shapes: dict, key):
        init = init_batched_powersgd_state if self.batched else init_powersgd_state
        return init(named_shapes, self.rank, key)

    def apply_hook(self, named_grads: dict, state, rng_key=None):
        apply = apply_batched_powersgd if self.batched else apply_powersgd
        return apply(
            named_grads,
            state,
            use_error_feedback=self.use_error_feedback,
            warm_start=self.warm_start,
            rng_key=rng_key,
            wrapper_dtype=self.wrapper_dtype,
        )


_POLICY_NAMES = ("none", "int8", "fp8", "powersgd", "batched_powersgd")


def resolve_policy(handler=None, ddp_handler=None) -> CompressionPolicy:
    """Resolve the active policy from a ``CompressionKwargs`` handler (or
    the ``ACCELERATE_COMPRESSION`` env var it reads), with the legacy
    ``DistributedDataParallelKwargs(comm_hook="powersgd")`` spelling folding
    into the SAME :class:`PowerSGDCompression` object — one code path for
    hook selection, eligibility and error-feedback state.
    """
    if handler is None:
        from ..utils.dataclasses import CompressionKwargs

        handler = CompressionKwargs()
    name = str(handler.policy).lower()
    if name not in _POLICY_NAMES:
        raise ValueError(
            f"unsupported compression policy {handler.policy!r}; use one of "
            f"{_POLICY_NAMES}"
        )
    gates = dict(
        min_size=handler.min_size,
        min_block=handler.min_block,
        error_feedback=handler.error_feedback,
    )
    if name in ("powersgd", "batched_powersgd"):
        return PowerSGDCompression(
            rank=handler.powersgd_rank,
            use_error_feedback=handler.error_feedback,
            warm_start=handler.powersgd_warm_start,
            batched=name == "batched_powersgd",
            wrapper_dtype=_wrapper_dtype(handler.powersgd_wrapper),
            min_size=handler.min_size,
            min_block=handler.min_block,
        )
    if name == "none":
        legacy = powersgd_from_ddp(ddp_handler)
        if legacy is not None:
            return legacy
    if name == "int8":
        return Int8Compression(**gates)
    if name == "fp8":
        return Fp8Compression(**gates)
    return NoneCompression(**gates)


def powersgd_from_ddp(ddp_handler) -> Optional["PowerSGDCompression"]:
    """The legacy ``DistributedDataParallelKwargs(comm_hook="powersgd")``
    spelling as a policy object — also what lets the powersgd hook compose
    with an int8/fp8 collective policy when both are configured."""
    if ddp_handler is None:
        return None
    hook = _normalize_hook(getattr(ddp_handler, "comm_hook", None))
    if hook not in ("powersgd", "batched_powersgd"):
        return None
    opts = dict(getattr(ddp_handler, "comm_state_option", None) or {})
    return PowerSGDCompression(
        rank=int(opts.get("matrix_approximation_rank", 1)),
        use_error_feedback=bool(opts.get("use_error_feedback", True)),
        warm_start=bool(opts.get("warm_start", True)),
        batched=hook == "batched_powersgd",
        wrapper_dtype=_wrapper_dtype(
            _normalize_hook(getattr(ddp_handler, "comm_wrapper", None))
        ),
    )


def _normalize_hook(value) -> Optional[str]:
    """Bare value or its enum stringification → canonical lowercase name
    (``DDPCommunicationHookType.POWER_SGD`` → ``powersgd``)."""
    if value is None:
        return None
    hook = str(value).lower().rsplit(".", 1)[-1]
    if hook in ("no", "none"):
        return None
    if hook in ("power_sgd", "batched_power_sgd"):
        hook = hook.replace("_sgd", "sgd")
    return hook


def _wrapper_dtype(wrapper: Optional[str]):
    if wrapper is None:
        return None
    w = str(wrapper).lower()
    if w == "fp16":
        return jnp.float16
    if w == "bf16":
        return jnp.bfloat16
    raise ValueError(f"unsupported powersgd wrapper {wrapper!r}; use 'fp16' or 'bf16'")


# ---------------------------------------------------------------------------
# collective-bytes attribution (telemetry kind="collectives"; bench A/B)
# ---------------------------------------------------------------------------
def collective_bytes(policy: CompressionPolicy, entries: list) -> dict:
    """Analytic per-step dp-axis collective bytes under ``policy``.

    ``entries`` — one ``(shape, axis, param_itemsize[, ag_wire_ok])`` per
    parameter whose ZeRO-1 state actually carries the dp axis (``axis`` is
    that axis; ``None`` marks the replicated fallback, which moves nothing
    over dp; ``ag_wire_ok=False`` marks params whose all-gather stays exact
    — fp32 params keep no master, so the quantized delta has no exact base).
    Two directions per step: the gradient's trip to the sharded update
    (fp32 uncompressed) and the updated param's trip back (param dtype
    uncompressed).  Joined with the backend's ``cost_analysis`` collective
    keys by telemetry when the compiler reports them
    (``telemetry/resources.py``); this analytic figure exists so the A/B is
    measurable on every backend, CPU mesh included.
    """
    rs = ag = rs_raw = ag_raw = 0
    compressed = 0
    for entry in entries:
        shape, axis, itemsize = entry[0], entry[1], entry[2]
        ag_wire_ok = entry[3] if len(entry) > 3 else True
        if axis is None:
            continue  # replicated fallback: no dp traffic for this tensor
        n = int(math.prod(shape))
        raw_rs = n * 4  # fp32 gradient
        raw_ag = n * int(itemsize)  # param dtype
        rs_raw += raw_rs
        ag_raw += raw_ag
        if policy.eligible(tuple(shape), jnp.float32, axis):
            rs += policy.wire_bytes(tuple(shape), axis)
            ag += policy.wire_bytes(tuple(shape), axis) if ag_wire_ok else raw_ag
            compressed += 1
        else:
            rs += raw_rs
            ag += raw_ag
    total, total_raw = rs + ag, rs_raw + ag_raw
    return {
        "policy": policy.name,
        "dp_rs_bytes": rs,
        "dp_ag_bytes": ag,
        "dp_collective_bytes": total,
        "dp_collective_bytes_uncompressed": total_raw,
        "compression_ratio": round(total_raw / total, 3) if total else 1.0,
        "tensors_total": len(entries),
        "tensors_compressed": compressed,
    }


# ---------------------------------------------------------------------------
# PowerSGD core (moved verbatim in behavior from utils/powersgd.py, which
# now delegates here — the torch-parity notes live in that module docstring)
# ---------------------------------------------------------------------------
def eligible_matrix_shape(shape, rank: int) -> Optional[tuple[int, int]]:
    """(n, m) matrix view for tensors PowerSGD compresses, else None.

    Mirrors torch's rule: tensors are viewed as ``(shape[0], rest)``; only
    tensors where the rank-k factors are actually smaller than the matrix
    (both dims > rank) are compressed — 1-D tensors (biases, norms) and
    tiny matrices pass through uncompressed.
    """
    if len(shape) < 2:
        return None
    n = int(shape[0])
    m = int(math.prod(shape[1:]))
    if n <= rank or m <= rank:
        return None
    return n, m


def _orthonormalize(p):
    # torch orthogonalizes with modified Gram-Schmidt; reduced QR spans the
    # same subspace (up to column signs, which cancel in P·Qᵀ) and maps to
    # one fused XLA op
    q, _ = jnp.linalg.qr(p)
    return q


def _compress_matrix(m32, q_prev, err, *, use_error_feedback: bool, wrapper_dtype=None):
    """One warm-started subspace iteration on fp32 matrix ``m32``.

    ``wrapper_dtype`` rounds the transported factors (the reference's
    fp16/bf16 comm wrappers): the decompressed gradient AND the error
    residual are computed from the rounded factors, so error feedback also
    carries the rounding error forward.  The warm-start Q stays unrounded
    (state quality is a local concern, not wire traffic)."""
    if use_error_feedback:
        m32 = m32 + err
    p = _orthonormalize(m32 @ q_prev)
    q_new = m32.T @ p
    if wrapper_dtype is not None:
        p_used = p.astype(wrapper_dtype).astype(jnp.float32)
        q_used = q_new.astype(wrapper_dtype).astype(jnp.float32)
    else:
        p_used, q_used = p, q_new
    approx = p_used @ q_used.T
    new_err = m32 - approx if use_error_feedback else err
    return approx, q_new, new_err


def init_powersgd_state(named_shapes: dict, rank: int, key) -> dict:
    """Per-tensor state: warm-start Q (m, k) gaussian + fp32 error buffer.

    ``named_shapes`` maps param name → shape; ineligible tensors get no
    entry (and pass through uncompressed at apply time).  Built eagerly at
    ``prepare()`` so the captured-step state pytree is structurally stable
    from the first call.
    """
    qs, errs = {}, {}
    names = sorted(n for n in named_shapes if eligible_matrix_shape(named_shapes[n], rank))
    keys = jax.random.split(key, max(len(names), 1))
    for sub, name in zip(keys, names):
        n, m = eligible_matrix_shape(named_shapes[name], rank)
        qs[name] = jax.random.normal(sub, (m, rank), jnp.float32)
        errs[name] = jnp.zeros((n, m), jnp.float32)
    return {"q": qs, "err": errs}


def apply_powersgd(
    named_grads: dict,
    state: dict,
    *,
    use_error_feedback: bool = True,
    warm_start: bool = True,
    rng_key=None,
    wrapper_dtype=None,
) -> tuple[dict, dict]:
    """Compress every eligible gradient in place of its full-rank value.

    Returns ``(new_named_grads, new_state)`` — pure function of arrays, so
    it works identically eagerly and inside a captured trace.
    ``wrapper_dtype`` emulates the reference's fp16/bf16 comm wrappers: the
    transported factors P/Q are rounded through that dtype before
    decompression.
    """
    new_grads = dict(named_grads)
    qs, errs = dict(state["q"]), dict(state["err"])
    names = sorted(qs)
    if not warm_start:
        if rng_key is None:
            raise ValueError("warm_start=False needs an rng_key to re-draw Q")
        subkeys = dict(zip(names, jax.random.split(rng_key, max(len(names), 1))))
    for name in names:
        g = named_grads.get(name)
        if g is None:
            continue
        shape, dtype = g.shape, g.dtype
        m32 = g.reshape(shape[0], -1).astype(jnp.float32)
        q_prev = qs[name]
        if not warm_start:
            q_prev = jax.random.normal(subkeys[name], q_prev.shape, jnp.float32)
        approx, q_new, err_new = _compress_matrix(
            m32, q_prev, errs[name],
            use_error_feedback=use_error_feedback, wrapper_dtype=wrapper_dtype,
        )
        new_grads[name] = approx.reshape(shape).astype(dtype)
        qs[name] = q_new
        errs[name] = err_new
    return new_grads, {"q": qs, "err": errs}


def init_batched_powersgd_state(named_shapes: dict, rank: int, key) -> dict:
    """Batched variant: ONE square matrix over the concatenation of every
    gradient (torch batched_powerSGD_hook): flat length padded up to
    side², side = ceil(sqrt(total))."""
    total = sum(int(math.prod(s)) for s in named_shapes.values())
    side = int(math.ceil(math.sqrt(max(total, 1))))
    return {
        "q": jax.random.normal(key, (side, rank), jnp.float32),
        "err": jnp.zeros((side, side), jnp.float32),
    }


def apply_batched_powersgd(
    named_grads: dict,
    state: dict,
    *,
    use_error_feedback: bool = True,
    warm_start: bool = True,
    rng_key=None,
    wrapper_dtype=None,
) -> tuple[dict, dict]:
    """Compress the whole gradient set as one padded square matrix.

    CONTRACT: the caller must pass the SAME name set on every call (the
    accelerator passes every parameter, zero-filling absent grads) — the
    error buffer is a flat layout over the concatenation, so a name set
    that varies between calls would shift the offsets and add one tensor's
    residual into another's gradient region."""
    names = sorted(named_grads)
    flats = [named_grads[n].astype(jnp.float32).ravel() for n in names]
    sizes = [f.shape[0] for f in flats]
    flat = jnp.concatenate(flats) if flats else jnp.zeros((0,), jnp.float32)
    side = state["q"].shape[0]
    pad = side * side - flat.shape[0]
    m32 = jnp.pad(flat, (0, pad)).reshape(side, side)
    q_prev = state["q"]
    if not warm_start:
        if rng_key is None:
            raise ValueError("warm_start=False needs an rng_key to re-draw Q")
        q_prev = jax.random.normal(rng_key, q_prev.shape, jnp.float32)
    approx, q_new, err_new = _compress_matrix(
        m32, q_prev, state["err"],
        use_error_feedback=use_error_feedback, wrapper_dtype=wrapper_dtype,
    )
    out_flat = approx.ravel()[: flat.shape[0]]
    new_grads = dict(named_grads)
    off = 0
    for name, size in zip(names, sizes):
        g = named_grads[name]
        new_grads[name] = out_flat[off : off + size].reshape(g.shape).astype(g.dtype)
        off += size
    return new_grads, {"q": q_new, "err": err_new}
