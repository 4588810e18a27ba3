"""Plain Nemotron-H in ``jax.numpy``: the benchmark's reference for every cell
of the family.  Imports nothing of the program.

Published architecture (``model_type nemotron_h``; the equations as PERF.md §4
and ISSUE 31 write them down).  Every layer is one mixer behind a pre-norm,
``x <- x + mixer_i(RMSNorm_i(x))`` (RMSNorm in float32, eps ``norm_eps``), the
kind of layer ``i`` being character ``i`` of ``hybrid_override_pattern``; then
a final RMSNorm and an untied head.

* ``M`` Mamba-2.  ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) + b)``,
  the convolution causal, depthwise, ``conv_kernel`` taps; ``[x | B | C] =
  xBC``; ``dt <- softplus(dt + dt_bias)``; ``A = -exp(A_log)``; for head ``h``
  of group ``g``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``,
  ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm_groups(y * silu(z))`` (gate first,
  then the norm over each of ``n_groups`` groups of channels, one weight);
  ``y W_out``.  Computed here **token by token** (``lax.scan`` over the
  positions), not in chunks: independent of the program's algorithm.
* ``*`` attention.  Grouped-query causal softmax attention, no bias, **no
  rotary or other positional term** (an assumption: see the configuration's
  ``assumed``).
* ``E`` experts.  ``s = sigmoid(u W_r^T)``; chosen = top-k of ``s + b``;
  weights = ``s`` of the chosen / their sum x ``routed_scaling_factor``; an
  expert is ``W_down relu(W_up u)^2``; one shared expert of the same form.

Departures, shared with the program (the chip's share of a stated deployment,
model-configs guide section 4): the model holds ``n_routed_experts`` of the
``router_width`` experts, from ``expert_offset`` on — a pick of an expert that
is not held adds nothing; every held expert is applied densely to every token
here and weighted by the router's picks.  The vocabulary is the held slice.

Weights come from the seed alone (``init_params``), are rounded to bfloat16
values and handed to the program and to the reference alike.  The reference
widens them to float32 **a layer at a time**, so 8.9 GB of weights stay 8.9 GB.

``precision`` chooses how matrix products are computed (everything between
them — norms, softmax, the recurrence, the router's sigmoid and top-k — is
float32 in every mode):

* ``float32``  — float32 operands, ``Precision.HIGHEST``: the reference.
* ``bfloat16`` — operands rounded to bfloat16, float32 accumulation.
* ``int8``     — operands scaled per tensor and rounded to int8: the nearest
  precision below what the configuration states, the control of ``correct``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "int8")
KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}
F32 = jnp.float32


class Static(NamedTuple):
    """What the forward needs beside the arrays (``params["static"]``)."""

    pattern: str
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts_per_tok: int
    expert_offset: int
    routed_scaling_factor: float
    norm_eps: float


def static_of(cfg: dict) -> Static:
    return Static(
        pattern=cfg["hybrid_override_pattern"],
        expert_offset=int(cfg.get("expert_offset", 0)),
        **{k: cfg[k] for k in Static._fields if k not in ("pattern", "expert_offset")},
    )


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def layer_shapes(cfg: dict, stored: bool = True) -> dict:
    """``{kind: {name: shape}}`` of one layer of each kind, and ``"globals"``.
    Matrices are (in, out); an expert stack is (experts held, in, out).  With
    ``stored`` the routed experts' width is padded with zeros to whole 128-lane
    tiles (the published 1856 to 1920 = 15 x 128, which is what the chip's
    tiling makes of it anyway; a zero column gives relu(0)^2 = 0 and meets a
    zero row, so the products are the published ones)."""
    d = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    di = h * p
    cw = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    f, fs = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    if stored:
        f = -(-f // 128) * 128
    held, wide = cfg["n_routed_experts"], cfg.get("router_width", cfg["n_routed_experts"])
    return {
        "globals": {"embed": (cfg["vocab_size"], d), "norm_f": (d,), "head": (cfg["vocab_size"], d)},
        "mamba2": {
            "norm": (d,), "in_w": (d, di + cw + h), "conv_w": (cw, cfg["conv_kernel"]), "conv_b": (cw,),
            "dt_bias": (h,), "a_log": (h,), "d": (h,), "gate_norm": (di,), "out_w": (di, d),
        },
        "attention": {"norm": (d,), "qkv_w": (d, qd + 2 * kvd), "o_w": (qd, d)},
        "experts": {
            "norm": (d,), "router_w": (wide, d), "router_bias": (wide,),
            "up_w": (held, d, f), "down_w": (held, f, d),
            "shared_up_w": (d, fs), "shared_down_w": (fs, d),
        },
    }


def kinds_of(cfg: dict) -> list:
    kinds = [KINDS[ch] for ch in cfg["hybrid_override_pattern"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers disagree")
    return kinds


def param_count(cfg: dict, stored: bool = False) -> int:
    """Parameters at the published widths (``stored``: with the padding)."""
    shapes = layer_shapes(cfg, stored)
    size = lambda kind: sum(math.prod(s) for s in shapes[kind].values())  # noqa: E731
    return size("globals") + sum(size(kind) for kind in kinds_of(cfg))


def _draw(name: str, shape, key, dt_range):
    """One leaf in float32."""
    if name in ("norm", "gate_norm", "norm_f"):
        return 1.0 + 0.02 * jax.random.normal(key, shape, F32)
    if name == "d":
        return jnp.ones(shape, F32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == "dt_bias":
        lo, hi, floor = dt_range
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus
    if name == "conv_w":
        bound = shape[-1] ** -0.5  # the published module's default for a depthwise conv
        return jax.random.uniform(key, shape, F32, -bound, bound)
    return 0.02 * jax.random.normal(key, shape, F32)  # matrices, conv and router biases


@functools.partial(jax.jit, static_argnames=("sizes", "kind", "dtype"))
def _init_layer(key, sizes, kind, dtype):
    """One layer's leaves (or the globals): a program of its own, so that the
    float32 draws of one layer are all that is ever live beside the weights."""
    cfg = dict(sizes)
    dt_range = (cfg["time_step_min"], cfg["time_step_max"], cfg["time_step_floor"])
    stored = layer_shapes(cfg)[kind]
    out = {}
    for n, (name, shape) in enumerate(layer_shapes(cfg, stored=False)[kind].items()):
        x = _draw(name, shape, jax.random.fold_in(key, n), dt_range).astype(jnp.bfloat16).astype(dtype)
        out[name] = jnp.pad(x, [(0, s - d) for s, d in zip(stored[name], shape)])  # zeros, where stored wider
    return out


_SIZE_KEYS = (
    "hidden_size", "vocab_size", "num_hidden_layers", "hybrid_override_pattern",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "n_routed_experts", "router_width", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "time_step_min", "time_step_max", "time_step_floor",
)


def init_params(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """``{"embed", "norm_f", "head", "layers": [one dict a layer], "static"}``
    on the default device, bfloat16 values in ``dtype``: matrices N(0, 0.02);
    RMSNorm gains about 1; ``A_log`` the log of uniform 1-16; ``dt_bias`` the
    inverse softplus of a ``dt`` log-uniform in ``time_step_min``-
    ``time_step_max`` floored at ``time_step_floor``; ``D`` 1; the
    convolution's taps uniform in +-1/sqrt(kernel); the router's correction
    biases N(0, 0.02), so that they decide some picks.  ``static`` carries the
    sizes no array's shape gives.  ``seed`` is any whole number up to 2**63;
    both 32-bit words of it are used."""
    seed = int(seed)
    cfg = dict(cfg, router_width=cfg.get("router_width", cfg["n_routed_experts"]))
    sizes = tuple((k, cfg[k]) for k in _SIZE_KEYS)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)
    dtype = jnp.dtype(dtype)
    out = _init_layer(jax.random.fold_in(key, 0), sizes, "globals", dtype)
    out["layers"] = [
        _init_layer(jax.random.fold_in(key, 1 + i), sizes, kind, dtype)
        for i, kind in enumerate(kinds_of(cfg))
    ]
    out["static"] = static_of(cfg)
    return out


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


def _mamba2(u, p, st: Static, precision):
    t = u.shape[0]
    h, hd, g, n = st.mamba_num_heads, st.mamba_head_dim, st.n_groups, st.ssm_state_size
    di = h * hd
    zxd = _mm("td,de->te", u, p["in_w"], precision)
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + di + 2 * g * n], zxd[:, 2 * di + 2 * g * n:]
    k = p["conv_w"].shape[1]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = p["conv_b"][None] + sum(padded[j:j + t] * p["conv_w"][:, j][None] for j in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(t, h, hd)
    b = xbc[:, di:di + g * n].reshape(t, g, n)
    c = xbc[:, di + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"][None])  # (t, h)
    a = -jnp.exp(p["a_log"])
    rep = h // g

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        b_h, c_h = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)  # (h, n)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        y_t = jnp.sum(state * c_h[:, None, :], axis=-1) + p["d"][:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(token, jnp.zeros((h, hd, n), F32), (x, b, c, dt))
    y = y.reshape(t, di) * jax.nn.silu(z)
    grouped = y.reshape(t, g, di // g)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + st.norm_eps)
    y = grouped.reshape(t, di) * p["gate_norm"][None]
    return _mm("te,ed->td", y, p["out_w"], precision)


def _attention(u, p, st: Static, precision):
    t = u.shape[0]
    nq, nkv, hd = st.num_attention_heads, st.num_key_value_heads, st.head_dim
    qkv = _mm("td,de->te", u, p["qkv_w"], precision)
    q = qkv[:, :nq * hd].reshape(t, nkv, nq // nkv, hd)
    k = qkv[:, nq * hd:(nq + nkv) * hd].reshape(t, nkv, hd)
    v = qkv[:, (nq + nkv) * hd:].reshape(t, nkv, hd)
    scores = _mm("qkgd,skd->kgqs", q, k, precision) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = _mm("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v, precision)
    return _mm("te,ed->td", att.reshape(t, nq * hd), p["o_w"], precision)


def _experts(u, p, st: Static, precision):
    held = p["up_w"].shape[0]
    s = jax.nn.sigmoid(_mm("td,ed->te", u, p["router_w"], precision))
    _, chosen = jax.lax.top_k(s + p["router_bias"][None], st.num_experts_per_tok)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * st.routed_scaling_factor
    local = chosen - st.expert_offset
    here = (local >= 0) & (local < held)
    # (t, held): the weight each held expert's output gets for each token
    combine = jnp.sum(
        jnp.where(here, w, 0.0)[..., None] * jax.nn.one_hot(jnp.where(here, local, 0), held, dtype=F32),
        axis=1,
    )
    hidden = jnp.square(jax.nn.relu(_mm("td,edf->etf", u, p["up_w"], precision)))
    routed = jnp.einsum("etd,te->td", _mm("etf,efd->etd", hidden, p["down_w"], precision), combine,
                        precision=jax.lax.Precision.HIGHEST)
    shared = jnp.square(jax.nn.relu(_mm("td,df->tf", u, p["shared_up_w"], precision)))
    return routed + _mm("tf,fd->td", shared, p["shared_down_w"], precision)


_MIXERS = {"mamba2": _mamba2, "attention": _attention, "experts": _experts}


def experts_layer(p: dict, u, st: Static, precision="float32"):
    """One expert layer's output for normed inputs ``u: (t, d)`` (for tests)."""
    return _experts(u.astype(F32), {k: v.astype(F32) for k, v in p.items()}, st, precision)


def logits(params: dict, ids, st: Static, precision="float32"):
    """``(T, V)`` float32 logits of one sequence ``ids: (T,)``.  ``params``
    without its ``static`` entry."""
    x = params["embed"][ids].astype(F32)
    for ch, layer in zip(st.pattern, params["layers"], strict=True):
        p = {k: v.astype(F32) for k, v in layer.items()}  # this layer alone, widened
        x = x + _MIXERS[KINDS[ch]](_rmsnorm(x, p["norm"], st.norm_eps), p, st, precision)
    x = _rmsnorm(x, params["norm_f"].astype(F32), st.norm_eps)
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


GAP_WINDOW = 64
FAR_OFF = 4.0


def token_gaps(params, ids, prompt_len: int, pad_to: int, precision="float32"):
    """For one request (``ids`` = prompt then served tokens): how far the
    reference's logit of each chosen token lies below the reference's best, a
    float32 vector over the served positions.  With ``precision="float32"``
    the chosen token is the served one; with a lower precision it is the token
    that precision puts first, at the same prompts and tokens (the control)."""
    import numpy as np

    n = len(ids)
    padded = np.zeros(pad_to, np.int32)
    padded[:n] = ids
    arrays = {k: v for k, v in params.items() if k != "static"}
    gaps = _token_gaps(arrays, jnp.asarray(padded), jnp.int32(n), jnp.int32(prompt_len),
                       params["static"], precision)
    return np.asarray(gaps)[prompt_len - 1:n - 1]


def windowed(gaps):
    """Each position's gap **averaged over the ``GAP_WINDOW`` served positions
    that end at it** (over all of them where the request served fewer; the
    first positions read their first whole window) -- but a token whose own gap
    is over ``FAR_OFF`` stands for itself.

    Why a window and not each token's own gap, as the GPT-2 reference gives: a
    top-6-of-128 router has near-ties, and any rounding upstream of it flips
    one now and then, which moves that token's logits by 1-2 whatever the
    precision -- the widest single gap of a thousand tokens reads 1.2-2.1 for
    the bfloat16 program, 1.3-1.8 for the reference in bfloat16 and 1.9-3.2
    for int8 (PERF.md section 2).  How OFTEN tokens are off, and by how much,
    is what tells the precisions apart: over 64 tokens the program's widest
    mean is 0.09 and int8's 0.4.  Why ``FAR_OFF``: one token that is simply
    wrong (another slot's state read for one step, a misplaced row of the
    head) moves a 64-token mean by a sixty-fourth of its gap and would pass.
    The logits have a standard deviation of about 1 and the best of 32,768 lies
    some 4.3 above the mean, which is where a token picked for no reason
    reads (2.4-5.6 in 18 readings on the chip, 11 of them over 4); the
    program's widest near-tie read 2.1 over 17,000 tokens and int8's 3.2."""
    import numpy as np

    gaps = np.asarray(gaps, np.float32)
    width = min(GAP_WINDOW, len(gaps))
    if not width:
        return gaps
    means = np.convolve(gaps, np.full(width, 1.0 / width, np.float32), mode="valid")
    means = np.concatenate([np.full(width - 1, means[0], np.float32), means]).astype(np.float32)
    return np.where(gaps > FAR_OFF, gaps, means)


def served_token_gaps(params, ids, prompt_len: int, n_head: int, pad_to: int,
                      precision="float32"):
    """What ``runners/serve.py::reference_gaps`` asks of every family's
    reference; its largest entry is the cell's ``served_logit_gap``.  For this
    family: ``windowed(token_gaps(...))``.  ``n_head`` is what the serve runner
    passes every family; this one reads its sizes from ``params["static"]``."""
    return windowed(token_gaps(params, ids, prompt_len, pad_to, precision))
