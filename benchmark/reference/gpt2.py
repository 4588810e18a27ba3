"""Plain GPT-2 in ``jax.numpy``: the benchmark's reference for every cell of
the family.  Imports nothing of the program.

Published architecture (Radford et al. 2019; huggingface ``GPT2LMHeadModel``):
learned token and position tables, pre-norm blocks (LayerNorm eps 1e-5, fused
qkv projection, causal softmax attention scaled by 1/sqrt(head), output
projection, residual; LayerNorm, 4x MLP with tanh-GELU, residual), final
LayerNorm, head tied to the token table, next-token cross entropy averaged
over every position but each row's last.  Departure, shared with the program:
the vocabulary table holds ``vocab_rows`` rows (50,257 padded to 50,304) and
the softmax runs over all of them; ids are drawn from the published 50,257.

Weights come from the seed alone (``init_params``), are rounded to bfloat16
values and handed to the program and to the reference alike, so the two
differ only in how they compute.

``precision`` chooses how matrix products are computed:

* ``float32``  — float32 operands, ``Precision.HIGHEST``: the reference.
* ``bfloat16`` — operands rounded to bfloat16, float32 accumulation: what the
  configurations state.
* ``fp8``      — operands scaled per tensor and rounded to float8 (e4m3
  forward, e5m2 for the incoming gradient), float32 accumulation: the nearest
  precision below, the control of ``correct``.
* ``int8``     — the same with operands and gradients rounded to int8.

Everything between the products (LayerNorm, softmax, GELU, residual, loss,
AdamW) is float32 in every mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8", "int8")
BLOCK_KEYS = (
    "ln1_w", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
    "ln2_w", "ln2_b", "fc_w", "fc_b", "fcproj_w", "fcproj_b",
)
GLOBAL_KEYS = ("wte", "wpe", "ln_f_w", "ln_f_b")
LN_EPS = 1e-5


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def param_shapes(cfg: dict) -> dict:
    """``{"wte": shape, ..., "blocks": {key: (n_layer, ...)}}``; linear
    weights are (out, in)."""
    e, n_layer = cfg["n_embd"], cfg["n_layer"]
    blocks = {
        "ln1_w": (e,), "ln1_b": (e,), "qkv_w": (3 * e, e), "qkv_b": (3 * e,),
        "proj_w": (e, e), "proj_b": (e,), "ln2_w": (e,), "ln2_b": (e,),
        "fc_w": (4 * e, e), "fc_b": (4 * e,), "fcproj_w": (e, 4 * e), "fcproj_b": (e,),
    }
    return {
        "wte": (cfg["vocab_rows"], e), "wpe": (cfg["n_positions"], e),
        "ln_f_w": (e,), "ln_f_b": (e,),
        "blocks": {k: (n_layer, *v) for k, v in blocks.items()},
    }


_SIZE_KEYS = ("n_embd", "n_layer", "vocab_rows", "n_positions")


@functools.partial(jax.jit, static_argnames=("sizes", "dtype"))
def _init(seed_words, sizes, dtype):
    shapes = param_shapes(dict(zip(_SIZE_KEYS, sizes)))
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(0), seed_words[0]), seed_words[1]
    )
    std = 0.02
    resid = std / math.sqrt(2 * sizes[_SIZE_KEYS.index("n_layer")])

    def leaf(name, shape, k):
        if name.startswith("ln") and name.endswith("_w"):
            x = 1.0 + std * jax.random.normal(k, shape, jnp.float32)
        else:
            x = (resid if name in ("proj_w", "fcproj_w") else std) * jax.random.normal(
                k, shape, jnp.float32
            )
        # bfloat16 values, whatever type carries them
        return x.astype(jnp.bfloat16).astype(dtype)

    names = list(GLOBAL_KEYS) + list(BLOCK_KEYS)
    keys = dict(zip(names, jax.random.split(key, len(names))))
    out = {n: leaf(n, shapes[n], keys[n]) for n in GLOBAL_KEYS}
    out["blocks"] = {n: leaf(n, shapes["blocks"][n], keys[n]) for n in BLOCK_KEYS}
    return out


def init_params(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """All weights in one jitted call on the default device: N(0, 0.02), the
    two residual projections scaled by 1/sqrt(2 layers), LayerNorm gains about
    1, biases small and not zero so that every leaf matters.  ``seed`` is any
    whole number up to 2**63; both 32-bit words of it are used."""
    seed = int(seed)
    words = jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], jnp.uint32)
    return _init(words, tuple(int(cfg[k]) for k in _SIZE_KEYS), jnp.dtype(dtype))


# the fused qkv projection is three leaves to the comparison: the key's bias
# has no gradient under softmax, and must not hide in a leaf that has one
SPLIT = {"qkv_w": ("q_w", "k_w", "v_w"), "qkv_b": ("q_b", "k_b", "v_b")}


def split_leaf(name: str, x, axis: int = 0) -> list:
    """``[(leaf name, array)]``: a fused qkv array cut in its three parts along
    ``axis`` (its output dimension), any other array as it is."""
    key = name.rsplit(".", 1)[-1]
    if key not in SPLIT:
        return [(name, x)]
    prefix = name[: len(name) - len(key)]
    return [(prefix + part, piece) for part, piece in zip(SPLIT[key], jnp.split(x, 3, axis=axis))]


def leaf_norms(tree: dict) -> dict:
    """``{leaf name: 2-norm}`` of a parameter-shaped tree: the globals, then
    ``h.<layer>.<key>`` per layer of the stacked block arrays."""
    out = {k: float(jnp.linalg.norm(tree[k].astype(jnp.float32))) for k in GLOBAL_KEYS}
    for k in BLOCK_KEYS:
        for name, x in split_leaf(k, tree["blocks"][k].astype(jnp.float32), axis=1):
            per_layer = jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, axis=1))
            for i, v in enumerate(jax.device_get(per_layer)):
                out[f"h.{i}.{name}"] = float(v)
    return out


# ---------------------------------------------------------------------------
# matrix products at a stated precision
# ---------------------------------------------------------------------------
def _quant(x, dtype):
    """Per-tensor scaled round trip through a float8 type, or through int8."""
    amax = jnp.max(jnp.abs(x))
    if dtype == jnp.int8:
        scale = jnp.where(amax > 0, 127.0 / amax, 1.0)
        return jnp.clip(jnp.round(x * scale), -127, 127) / scale
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _make_product(spec_fwd, spec_da, spec_db, precision):
    """``product(a, b)`` for one einsum and its two transposes."""
    if precision == "float32":
        return lambda a, b: _mm(a, b, spec_fwd)
    if precision == "bfloat16":
        def product(a, b):
            return jnp.einsum(spec_fwd, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return product
    if precision not in ("fp8", "int8"):
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
    operand, gradient = (
        (jnp.float8_e4m3fn, jnp.float8_e5m2) if precision == "fp8" else (jnp.int8, jnp.int8)
    )

    @jax.custom_vjp
    def product(a, b):
        return _mm(_quant(a, operand), _quant(b, operand), spec_fwd)

    def fwd(a, b):
        return product(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        g8 = _quant(g, gradient)
        return (
            _mm(g8, _quant(b, operand), spec_da),
            _mm(g8, _quant(a, operand), spec_db),
        )

    product.defvjp(fwd, bwd)
    return product


@functools.lru_cache(maxsize=None)
def _products(precision):
    attn = precision
    return {
        # x (..., in) @ w (out, in).T
        "linear": _make_product("bsi,oi->bso", "bso,oi->bsi", "bso,bsi->oi", precision),
        "scores": _make_product("bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd", "bhqk,bhqd->bhkd", attn),
        "mix": _make_product("bhqk,bhkd->bhqd", "bhqd,bhkd->bhqk", "bhqd,bhqk->bhkd", attn),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _layernorm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _block(x, p, n_head, prod):
    b, s, e = x.shape
    hd = e // n_head
    h = _layernorm(x, p["ln1_w"], p["ln1_b"])
    qkv = prod["linear"](h, p["qkv_w"]) + p["qkv_b"]
    qkv = qkv.reshape(b, s, 3, n_head, hd).transpose(2, 0, 3, 1, 4)
    q, k, v = qkv[0], qkv[1], qkv[2]
    scores = prod["scores"](q, k) * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = prod["mix"](jax.nn.softmax(scores, axis=-1), v)
    att = att.transpose(0, 2, 1, 3).reshape(b, s, e)
    x = x + prod["linear"](att, p["proj_w"]) + p["proj_b"]
    h = _layernorm(x, p["ln2_w"], p["ln2_b"])
    ff = jax.nn.gelu(prod["linear"](h, p["fc_w"]) + p["fc_b"], approximate=True)
    return x + prod["linear"](ff, p["fcproj_w"]) + p["fcproj_b"]


def hidden(params, ids, n_head, precision="float32"):
    """Final-LayerNorm output, (rows, seq, n_embd) float32."""
    prod = _products(precision)
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    s = ids.shape[1]
    x = p32["wte"][ids] + p32["wpe"][:s][None]

    @jax.checkpoint
    def layer(x, p):
        return _block(x, p, n_head, prod), None

    x, _ = jax.lax.scan(layer, x, p32["blocks"])
    return _layernorm(x, p32["ln_f_w"], p32["ln_f_b"])


def logits(params, ids, n_head, precision="float32"):
    prod = _products(precision)
    x = hidden(params, ids, n_head, precision)
    return prod["linear"](x, params["wte"].astype(jnp.float32))


def nll_sum(params, ids, n_head, precision="float32"):
    """Sum over rows and positions 0..S-2 of the next-token negative log
    likelihood, and the count of terms."""
    lg = logits(params, ids, n_head, precision)[:, :-1]
    targets = ids[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked), targets.size


# ---------------------------------------------------------------------------
# training: loss, gradient, AdamW — in blocks of rows so it fits
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_head", "precision", "total"))
def _grad_block(params, ids, n_head, precision, total):
    def f(p):
        s, _ = nll_sum(p, ids, n_head, precision)
        return s / total

    return jax.value_and_grad(f)(params)


_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=(0,))


def loss_and_grad(params, ids, n_head, precision="float32", block_rows=2):
    """Mean next-token loss of the whole batch and its gradient, accumulated
    over blocks of ``block_rows`` rows."""
    rows, seq = ids.shape
    total = rows * (seq - 1)
    loss, grad = 0.0, None
    for r in range(0, rows, block_rows):
        part, g = _grad_block(params, ids[r:r + block_rows], n_head, precision, total)
        loss = loss + part
        grad = g if grad is None else _add(grad, g)
    return loss, grad


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grad, mu, nu, step, lr, b1, b2, eps, wd):
    """optax.adamw: decoupled weight decay on every leaf."""
    t = step.astype(jnp.float32)

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        update = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p
        return p - lr * update, m, v

    flat = jax.tree_util.tree_map(one, params, grad, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x[i], flat, is_leaf=lambda x: isinstance(x, tuple)
    )
    return pick(0), pick(1), pick(2)


def train_steps(params, batches, n_head, optimizer: dict, precision="float32",
                block_rows=2, drop_rows=0):
    """Run ``len(batches)`` AdamW steps from ``params`` (float32 tree).

    Returns ``{"losses": [...], "grad_norms": {leaf: norm of step 1's gradient},
    "update_norms": {leaf: norm of (params after the steps - params before)}}``.
    ``drop_rows`` leaves that many rows out of every batch, the mean taken over
    the rest: a planted fault, for reading what the comparison does with it.
    """
    start = jax.tree_util.tree_map(jnp.copy, params)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    mu, nu = zeros(), zeros()
    losses, grad_norms = [], None
    hp = {k: jnp.float32(optimizer[k]) for k in ("lr", "b1", "b2", "eps", "weight_decay")}
    for i, ids in enumerate(batches):
        ids = jnp.asarray(ids)
        if drop_rows:
            ids = ids[: ids.shape[0] - drop_rows]
        loss, grad = loss_and_grad(params, ids, n_head, precision, block_rows)
        losses.append(float(loss))
        if i == 0:
            grad_norms = leaf_norms(grad)
        params, mu, nu = _adamw(
            params, grad, mu, nu, jnp.int32(i + 1),
            hp["lr"], hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"],
        )
    delta = jax.tree_util.tree_map(jnp.subtract, params, start)
    return {"losses": losses, "grad_norms": grad_norms, "update_norms": leaf_norms(delta)}


# ---------------------------------------------------------------------------
# serving: one full forward over prompt + served tokens, no cache
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_head", "precision"))
def _token_gaps(params, ids, n_valid, served_from, n_head, precision):
    lg = logits(params, ids[None], n_head, "float32")[0]  # (S, V)
    best = jnp.max(lg, axis=-1)
    pos = jnp.arange(ids.shape[0])
    # position t predicts token t+1; served tokens sit at served_from..n_valid-1
    predicts_served = (pos + 1 >= served_from) & (pos + 1 < n_valid)
    nxt = jnp.roll(ids, -1)
    if precision == "float32":
        chosen = nxt
    else:
        chosen = jnp.argmax(logits(params, ids[None], n_head, precision)[0], axis=-1)
    gap = best - jnp.take_along_axis(lg, chosen[:, None], axis=-1)[:, 0]
    return jnp.where(predicts_served, gap, 0.0)


def served_token_gaps(params, ids, prompt_len: int, n_head: int, pad_to: int,
                      precision="float32"):
    """For one request (``ids`` = prompt then served tokens): at each position
    that produced a served token, how far the reference's logit of the chosen
    token lies below the reference's best.  With ``precision="float32"`` the
    chosen token is the served one; with a lower precision it is the token that
    precision puts first, at the same prompts and tokens (the control).
    Returns a float32 vector over the served positions."""
    import numpy as np

    n = len(ids)
    padded = np.zeros(pad_to, np.int32)
    padded[:n] = ids
    gaps = _token_gaps(params, jnp.asarray(padded), jnp.int32(n), jnp.int32(prompt_len),
                       n_head, precision)
    return np.asarray(gaps)[prompt_len - 1:n - 1]
