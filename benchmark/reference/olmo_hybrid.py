"""Plain Olmo-Hybrid in ``jax.numpy``: the benchmark's reference for every cell
of the family.  Imports nothing of the program.

Architecture (``model_type olmo_hybrid``; the equations as ISSUE 36 and PERF.md
section 4 write them down).  ``layer_types`` names each layer's mixer; every
layer is Olmo 2/3's reordered-norm block with a dense SwiGLU MLP::

    h  = x + RMSNorm(mixer(x))
    x' = h + RMSNorm(W_down(silu(W_gate h) * W_up h))

then a final RMSNorm and an untied head.  RMSNorm in float32, eps
``rms_norm_eps``.  ``u`` is a layer's input.

* ``linear_attention`` — Gated DeltaNet (Yang, Kautz, Hatamizadeh 2024).
  ``q, k, v, z, a, b = u W_q, u W_k, u W_v, u W_g, u W_a, u W_b``; a causal
  depthwise convolution (``linear_conv_kernel_dim`` taps, no bias) then SiLU
  over each of ``q, k, v``; per head ``q <- l2norm(q) / sqrt(d_k)``, ``k <-
  l2norm(k)`` (``x / sqrt(sum x^2 + 1e-6)``), ``beta = 2 sigmoid(b)`` (the 2 is
  ``linear_allow_neg_eigval``), ``g = -exp(A_log) softplus(a + dt_bias)``.
  With ``S`` of ``(d_k, d_v)`` a head: ``S <- exp(g_t) S``; ``S <- S + k_t
  (outer) beta_t (v_t - S^T k_t)``; ``o_t = S^T q_t``.  Then ``y_h =
  RMSNorm(o_h; w) * silu(z_h)`` (norm first, then the gate, over each head's
  ``d_v``, one weight of ``d_v``), ``y W_o``.  Computed here **token by token**
  (``lax.scan`` over the positions), not in chunks: independent of the
  program's algorithm (the WY form).
* ``full_attention`` — ``q = RMSNorm(u W_q)``, ``k = RMSNorm(u W_k)`` (each norm
  over the whole projection, the Olmo 2/3 form), ``v = u W_v``; causal softmax
  attention, ``W_o``; no bias.

Departures and assumptions, shared with the program and listed with their
reasons in the configuration's ``assumed``: **no rotary term** in the
full-attention layers (the published ``rope_parameters.rope_theta`` is null);
the reordered-norm block and QK-norm after Olmo 3; separate
``q/k/v/g/a/b`` projections, no convolution bias, norm-then-gate, after FLA and
transformers' ``qwen3_next`` module; the initialisation below.  The depth is the
configuration's (its ``layer_types``).

Weights come from the seed alone (``init_params``), are rounded to bfloat16
values and handed to the program and to the reference alike.  Where
``layer_types`` is two or more repeats of one period they are held as one
stack per position in the period, ``(repeats, ...)`` leaves — how the program
holds them, so that the weights are held once — and the reference widens ONE
layer's rows to float32 at a time, so 8.2 GB of weights stay 8.2 GB.

``precision`` chooses how matrix products are computed (everything between
them — norms, softmax, the convolution, the recurrence — is float32 in every
mode):

* ``float32``  — float32 operands, ``Precision.HIGHEST``: the reference.
* ``bfloat16`` — operands rounded to bfloat16, float32 accumulation.
* ``int8``     — operands scaled per tensor and rounded to int8: the nearest
  precision below what the configuration states, the control of ``correct``.

``served_logit_gap`` for this family is GPT-2's statistic, each served token's
own gap and the cell's number the widest of them: the family is dense, no
router's near-tie moves a token's logits by more than rounding does, so one
wrong token has nothing to hide behind and no window is needed.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "int8")
F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"


class Static(NamedTuple):
    """What the forward needs beside the arrays (``params["static"]``)."""

    layer_types: tuple
    num_attention_heads: int
    linear_num_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_allow_neg_eigval: bool
    rms_norm_eps: float


def static_of(cfg: dict) -> Static:
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("the reference states equal numbers of key and value heads")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the reference states ungrouped attention")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers disagree")
    return Static(
        layer_types=tuple(cfg["layer_types"]), num_attention_heads=cfg["num_attention_heads"],
        linear_num_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_allow_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        rms_norm_eps=cfg["rms_norm_eps"],
    )


def period_of(layer_types) -> int:
    """Layers in one period where ``layer_types`` is two or more repeats of it
    (the shortest such), else their number."""
    types, n = tuple(layer_types), len(layer_types)
    for p in range(1, n // 2 + 1):
        if n % p == 0 and types == types[:p] * (n // p):
            return p
    return n


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def layer_shapes(cfg: dict) -> dict:
    """``{kind: {name: shape}}`` of one layer of each kind, and ``"globals"``.
    Matrices are (in, out)."""
    d, f, h = cfg["hidden_size"], cfg["intermediate_size"], cfg["linear_num_value_heads"]
    kw, vw = h * cfg["linear_key_head_dim"], h * cfg["linear_value_head_dim"]
    mlp = {"mixer_norm": (d,), "gate_w": (d, f), "up_w": (d, f), "down_w": (f, d), "mlp_norm": (d,)}
    return {
        "globals": {"embed": (cfg["vocab_size"], d), "norm_f": (d,), "head": (cfg["vocab_size"], d)},
        LINEAR: {
            "q_w": (d, kw), "k_w": (d, kw), "v_w": (d, vw), "g_w": (d, vw), "a_w": (d, h), "b_w": (d, h),
            "conv_w": (2 * kw + vw, cfg["linear_conv_kernel_dim"]), "dt_bias": (h,), "a_log": (h,),
            "gate_norm": (cfg["linear_value_head_dim"],), "o_w": (vw, d), **mlp,
        },
        FULL: {"q_w": (d, d), "k_w": (d, d), "v_w": (d, d), "q_norm": (d,), "k_norm": (d,), "o_w": (d, d), **mlp},
    }


def param_count(cfg: dict) -> int:
    shapes = layer_shapes(cfg)
    size = lambda kind: sum(math.prod(s) for s in shapes[kind].values())  # noqa: E731
    return size("globals") + sum(size(t) for t in cfg["layer_types"])


def _draw(name: str, shape, key):
    """One leaf in float32."""
    if name.endswith("_norm") or name == "norm_f":
        return 1.0 + 0.02 * jax.random.normal(key, shape, F32)
    if name == "a_log":  # log U(0, 16), floored away from log 0
        return jnp.log(jnp.maximum(jax.random.uniform(key, shape, F32, 0.0, 16.0), 1e-2))
    if name == "dt_bias":  # the inverse softplus of a dt log-uniform in 1e-3..1e-1
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3), math.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    return 0.02 * jax.random.normal(key, shape, F32)  # matrices, the convolution's taps


@functools.partial(jax.jit, static_argnames=("sizes", "kind", "layers", "dtype"))
def _init_leaves(key, sizes, kind, layers, dtype):
    """The leaves of the layers numbered ``layers`` (all of ``kind``), stacked
    where there are several; ``layers=None``: the globals.  A program of its
    own, so that the float32 draws of these layers are all that is ever live
    beside the weights."""
    out = {}
    for n, (name, shape) in enumerate(layer_shapes(dict(sizes))[kind].items()):
        def leaf(layer_key):
            return _draw(name, shape, jax.random.fold_in(layer_key, n)).astype(jnp.bfloat16).astype(dtype)

        if layers is None:
            out[name] = leaf(jax.random.fold_in(key, 0))
        elif len(layers) == 1:
            out[name] = leaf(jax.random.fold_in(key, 1 + layers[0]))
        else:
            out[name] = jnp.stack([leaf(jax.random.fold_in(key, 1 + i)) for i in layers])
    return out


_SIZE_KEYS = (
    "hidden_size", "vocab_size", "intermediate_size", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
)


def init_params(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """``{"embed", "norm_f", "head", "layers": [...], "static"}`` on the default
    device, bfloat16 values in ``dtype``: matrices and the convolution's taps
    N(0, 0.02); RMSNorm gains 1 + N(0, 0.02); ``A_log`` the log of uniform
    0-16 floored at 0.01; ``dt_bias`` the inverse softplus of a ``dt``
    log-uniform in 0.001-0.1.  ``layers`` holds one dict per position in the
    period, its leaves stacked over the repeats (module docstring), or one dict
    a layer where the depth is no two repeats.  Layer ``i``'s draws depend on
    the seed and ``i`` alone.  ``seed`` is any whole number up to 2**63; both
    32-bit words of it are used."""
    seed = int(seed)
    sizes = tuple((k, cfg[k]) for k in _SIZE_KEYS)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)
    dtype = jnp.dtype(dtype)
    types = tuple(cfg["layer_types"])
    period = period_of(types)
    out = _init_leaves(key, sizes, "globals", None, dtype)
    out["layers"] = [
        _init_leaves(key, sizes, types[j], tuple(range(j, len(types), period)), dtype)
        for j in range(period)
    ]
    out["static"] = static_of(cfg)
    return out


def layer_of(params: dict, i: int, n_layers: int) -> dict:
    """Layer ``i``'s own leaves out of ``params["layers"]``."""
    held = params["layers"]
    if len(held) == n_layers:
        return held[i]
    return {k: v[i // len(held)] for k, v in held[i % len(held)].items()}


# ---------------------------------------------------------------------------
# matrix products at a stated precision
# ---------------------------------------------------------------------------
def _quant8(x):
    """Per-tensor scaled round trip through int8."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 127.0 / amax, 1.0)
    return jnp.clip(jnp.round(x * scale), -127, 127) / scale


def _mm(spec: str, a, b, precision: str):
    if precision == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=F32)
    if precision == "int8":
        a, b = _quant8(a), _quant8(b)
    elif precision != "float32":
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)


# ---------------------------------------------------------------------------
# forward: one sequence, every position, no cache
# ---------------------------------------------------------------------------
def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _gated_delta_net(u, p, st: Static, precision):
    t = u.shape[0]
    h, dk, dv = st.linear_num_heads, st.linear_key_head_dim, st.linear_value_head_dim
    proj = lambda name: _mm("td,de->te", u, p[name], precision)  # noqa: E731
    qkv = jnp.concatenate([proj("q_w"), proj("k_w"), proj("v_w")], axis=-1)
    z, a, b = proj("g_w"), proj("a_w"), proj("b_w")
    taps = p["conv_w"].shape[1]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + t] * p["conv_w"][:, j][None] for j in range(taps)))
    q = _l2norm(qkv[:, :h * dk].reshape(t, h, dk)) * dk ** -0.5
    k = _l2norm(qkv[:, h * dk:2 * h * dk].reshape(t, h, dk))
    v = qkv[:, 2 * h * dk:].reshape(t, h, dv)
    beta = (2.0 if st.linear_allow_neg_eigval else 1.0) * jax.nn.sigmoid(b)  # (t, h)
    g = -jnp.exp(p["a_log"])[None] * jax.nn.softplus(a + p["dt_bias"][None])

    def token(state, inp):  # state: (h, dk, dv)
        q_t, k_t, v_t, g_t, beta_t = inp
        state = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.sum(state * k_t[:, :, None], axis=1)  # S^T k: (h, dv)
        state = state + k_t[:, :, None] * (beta_t[:, None] * (v_t - seen))[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((h, dk, dv), F32), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + st.rms_norm_eps)
    y = o * p["gate_norm"][None, None] * jax.nn.silu(z.reshape(t, h, dv))
    return _mm("te,ed->td", y.reshape(t, h * dv), p["o_w"], precision)


def _attention(u, p, st: Static, precision):
    t, d = u.shape
    n = st.num_attention_heads
    q = _rmsnorm(_mm("td,de->te", u, p["q_w"], precision), p["q_norm"], st.rms_norm_eps).reshape(t, n, d // n)
    k = _rmsnorm(_mm("td,de->te", u, p["k_w"], precision), p["k_norm"], st.rms_norm_eps).reshape(t, n, d // n)
    v = _mm("td,de->te", u, p["v_w"], precision).reshape(t, n, d // n)
    scores = _mm("qhd,shd->hqs", q, k, precision) * (d // n) ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = _mm("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1), v, precision)
    return _mm("te,ed->td", att.reshape(t, d), p["o_w"], precision)


_MIXERS = {LINEAR: _gated_delta_net, FULL: _attention}


def mixer(kind: str, p: dict, u, st: Static, precision="float32"):
    """One layer's mixer for inputs ``u: (t, d)`` (for tests)."""
    return _MIXERS[kind](u.astype(F32), {k: v.astype(F32) for k, v in p.items()}, st, precision)


def logits(params: dict, ids, st: Static, precision="float32"):
    """``(T, V)`` float32 logits of one sequence ``ids: (T,)``.  ``params``
    without its ``static`` entry."""
    x = params["embed"][ids].astype(F32)
    n = len(st.layer_types)
    for i, kind in enumerate(st.layer_types):
        p = {k: v.astype(F32) for k, v in layer_of(params, i, n).items()}  # this layer alone, widened
        h = x + _rmsnorm(_MIXERS[kind](x, p, st, precision), p["mixer_norm"], st.rms_norm_eps)
        inner = jax.nn.silu(_mm("td,df->tf", h, p["gate_w"], precision)) * _mm("td,df->tf", h, p["up_w"], precision)
        x = h + _rmsnorm(_mm("tf,fd->td", inner, p["down_w"], precision), p["mlp_norm"], st.rms_norm_eps)
    x = _rmsnorm(x, params["norm_f"].astype(F32), st.rms_norm_eps)
    return _mm("td,vd->tv", x, params["head"].astype(F32), precision)


# ---------------------------------------------------------------------------
# serving: one full forward over prompt + served tokens, no cache
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("st", "precision"))
def _token_gaps(arrays, ids, n_valid, served_from, st, precision):
    lg = logits(arrays, ids, st, "float32")  # (S, V)
    best = jnp.max(lg, axis=-1)
    pos = jnp.arange(ids.shape[0])
    # position t predicts token t+1; served tokens sit at served_from..n_valid-1
    predicts_served = (pos + 1 >= served_from) & (pos + 1 < n_valid)
    if precision == "float32":
        chosen = jnp.roll(ids, -1)
    else:
        chosen = jnp.argmax(logits(arrays, ids, st, precision), axis=-1)
    gap = best - jnp.take_along_axis(lg, chosen[:, None], axis=-1)[:, 0]
    return jnp.where(predicts_served, gap, 0.0)


def served_token_gaps(params, ids, prompt_len: int, n_head: int, pad_to: int,
                      precision="float32"):
    """What ``runners/serve.py::reference_gaps`` asks of every family's
    reference; its largest entry is the cell's ``served_logit_gap``.  For one
    request (``ids`` = prompt then served tokens): how far the reference's
    logit of each chosen token lies below the reference's best, a float32
    vector over the served positions.  With ``precision="float32"`` the chosen
    token is the served one; with a lower precision it is the token that
    precision puts first, at the same prompts and tokens (the control).
    ``n_head`` is what the serve runner passes every family; this one reads its
    sizes from ``params["static"]``."""
    import numpy as np

    n = len(ids)
    padded = np.zeros(pad_to, np.int32)
    padded[:n] = ids
    arrays = {k: v for k, v in params.items() if k != "static"}
    gaps = _token_gaps(arrays, jnp.asarray(padded), jnp.int32(n), jnp.int32(prompt_len),
                       params["static"], precision)
    return np.asarray(gaps)[prompt_len - 1:n - 1]
