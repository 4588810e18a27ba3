"""Generic KV-cache autoregressive decoding engine.

The reference framework delegates generation to transformers' ``generate``
(its big-model-inference benchmark, reference
benchmarks/big_model_inference/README.md, times exactly load + per-token
decode); here decode is a first-class TPU program: prefill and every decode
step run inside ONE jitted function, the layer stack is a ``lax.scan`` over
stacked per-layer parameters (no Python loop in the trace), and the KV cache
is a preallocated static-shape buffer updated with
``lax.dynamic_update_slice`` — no retracing, no dynamic shapes, one device
launch per ``generate`` call.

Model-family math lives next to each model (models/gpt.py, models/llama.py,
models/opt.py) as pure per-layer functions — the same functions the
pipelined/stacked training paths use — so decode cannot drift from the
module definition (round-2 verdict: this file used to hold a third private
copy of the GPT block math).  This module owns only the engine: cache
allocation and update, masking, grouped-query attention against the cache,
the layer scan, sampling, and the one-jitted-program contract.

Inference-only by design: it reads the module's parameter arrays directly
(no tape), so it composes with ``shard_for_inference`` — cache entries and
activations inherit the params' GSPMD layouts.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..logging import get_logger

logger = get_logger(__name__)

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


ATTENTION, RECURRENT, EXPERTS = "attention", "recurrent", "experts"


@dataclasses.dataclass(frozen=True)
class DecoderFamily:
    """Pure-math hooks one model family exports for cached decoding.

    Every function takes raw arrays (never Tensors) plus the family's static
    config.  ``l`` is one layer's params (leading layer axis already scanned
    away), ``g`` the non-layer params (embeddings, final norm, head).

    - ``embed(g, ids, positions, cfg) -> (b, s, c)``
    - ``attn_in(l, x, positions, cfg) -> (q, k, v)`` with
      ``q: (b, n_head, s, d)`` and ``k, v: (b, n_kv_head, s, d)`` — any
      norm + projection + positional rotation the family applies pre-attention
    - ``attn_out(l, x, att, cfg) -> (b, s, c)`` — output projection,
      residuals and the MLP half of the block (``att: (b, n_head, s, d)``)
    - ``finalize(g, x, cfg) -> (b, V)`` — final norm + LM head on the LAST
      position of ``x: (b, s, c)``

    **The layer plan.**  ``plan(cfg)`` gives the kind of every layer in
    order (``ATTENTION``, ``RECURRENT``, ``EXPERTS``); ``None`` is the plan
    "attention × L", which is every family that has only the four hooks
    above.  A layer of kind ``ATTENTION`` is ``attn_in`` / cached attention /
    ``attn_out``; the other kinds bring their own hooks, each a whole layer
    (norms, residuals and any MLP of its own included):

    - ``recurrent_prefill(l, x, true_len, cfg) -> (x, state, tail)`` — a layer
      with a per-slot state and a convolution tail (Mamba-2, the gated delta
      rule): one bucket-padded sequence ``x: (1, s, c)`` from a zero state;
      positions at or past ``true_len`` must not move the state, and ``tail``
      is what the next token's convolution reads (the last true rows).  The
      state's shape is the family's own: the service sizes its pool from what
      this returns
    - ``recurrent_step(l, x, pools, i, live, cfg, mesh) -> (x, state pool,
      tail)`` — one token for every slot, ``x: (slots, 1, c)``; slots never
      mix.  ``pools`` is the whole carried cache (``{"ssm", "conv"}``), ``i``
      the layer's rank in it, ``live: (slots, 1)`` bool, ``mesh`` the pools'
      mesh where it has several devices (else ``None``).  The hook hands back
      the WHOLE state pool with layer ``i``'s rows updated where they lie (a
      kernel that takes the pool in place: Mamba-2's and the delta rule's, over
      the live slots), and
      layer ``i``'s new tail, which the engine writes
    - ``recurrent_scopes`` — the two ``jax.named_scope`` names under which the
      engine writes a layer's rows of the state pool, in prefill and in decode:
      the family's own scan and step scopes, so that a trace counts the write
      with the recurrence it belongs to
    - ``ffn(l, x, valid, cfg) -> (x, load)`` — a token-wise layer (sparse
      experts); ``valid: (b, s)`` marks the tokens that count, ``load`` is a
      small int vector the engine sums over the layers and hands the host

    With a mixed plan the layers are a tuple of dicts.  One dict a layer in
    plan order, each layer's weights arrays of their own: the plan is
    unrolled (a static slice of a stack that feeds a kernel would be a copy).
    Or, where the plan is a whole number of repeats of one period
    (``plan_period``), one dict a POSITION IN THE PERIOD, every leaf a stack
    over the repeats: the engine scans the repeats (serving/engine.py).

    Declared frozen so the whole family object is a stable static argument
    to ``jax.jit`` (module-level singletons hash by function identity).
    """

    embed: Callable
    attn_in: Callable
    attn_out: Callable
    finalize: Callable
    plan: Optional[Callable] = None
    recurrent_prefill: Optional[Callable] = None
    recurrent_step: Optional[Callable] = None
    recurrent_scopes: tuple = ()
    ffn: Optional[Callable] = None


def layer_plan(family: DecoderFamily, cfg) -> Optional[tuple]:
    """The kinds of a MIXED plan in layer order, or ``None`` where every layer
    is attention: one ``lax.scan`` over the one stack serves that, and the
    engines keep it."""
    kinds = tuple(family.plan(cfg)) if family.plan is not None else None
    if kinds is None or set(kinds) == {ATTENTION}:
        return None
    return kinds


def plan_period(kinds: Optional[tuple], n_held: int) -> Optional[int]:
    """How a mixed plan's layers are walked, from the plan and from how its
    weights are held: ``p`` where the family holds ``p < L`` stacks and the
    plan is ``L / p`` repeats of its first ``p`` kinds (scanned by the
    period), ``None`` where it holds a dict a layer (unrolled)."""
    if kinds is None or n_held == len(kinds):
        return None
    if len(kinds) % n_held or kinds != kinds[:n_held] * (len(kinds) // n_held):
        raise ValueError(
            f"a plan of {len(kinds)} layers held as {n_held} stacks must be "
            f"repeats of its first {n_held} kinds; got {kinds}"
        )
    return n_held


@dataclasses.dataclass
class DecoderSpec:
    """What ``model._decoder_spec()`` hands the engine."""

    family: DecoderFamily
    cfg: Any  # static, hashable; must expose n_head / n_kv_head / head_dim
    max_len: int  # positional capacity (cache may not exceed it)
    stack: Callable[[], tuple[dict, dict]]  # () -> (globals, stacked layers)


def cached_attention(q, k, v, q_pos, cfg):
    """Grouped-query attention of ``q`` against a (padded) KV cache.

    ``q: (b, H, s, d)``; ``k, v: (b, Hkv, S, d)`` where ``S >= s``;
    ``q_pos: (s,)`` global positions of the query tokens.  Key position
    ``T`` is visible to query ``s`` iff ``T <= q_pos[s]`` — causal prefill
    (``q_pos = arange(P)``) and single-token decode (``q_pos = [t]``) are
    the same formula, so there is exactly one attention implementation.
    A family config with ``sliding_window > 0`` (Mistral-style) narrows
    visibility to the band ``q_pos - window < T`` — keeping decode logits
    identical to the training forward for windowed configs.  Softmax
    accumulates in fp32.
    """
    b, n_head, s, d = q.shape
    n_kv = k.shape[1]
    group = n_head // n_kv
    qg = q.reshape(b, n_kv, group, s, d)
    scores = jnp.einsum(
        "bkgsd,bkTd->bkgsT", qg, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    t_pos = jnp.arange(k.shape[2])
    mask = t_pos[None, :] <= q_pos[:, None]  # (s, T)
    window = getattr(cfg, "sliding_window", 0) or 0
    if window > 0:
        mask = jnp.logical_and(mask, q_pos[:, None] - t_pos[None, :] < window)
    scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    att = jnp.einsum("bkgsT,bkTd->bkgsd", probs, v)
    return att.reshape(b, n_head, s, d)


def _quantize_stacked_layers(layers: dict, bits: int) -> tuple[dict, dict, dict]:
    """Split stacked per-layer params into (plain, int8 q, scales).

    Matmul weights — ndim-3 ``(L, out, in)`` stacks — quantize per
    (layer, out-channel) symmetric int8 (int4 packs two per byte along
    ``in``); norm weights/biases (ndim <= 2) stay as-is.  Decode is
    memory-bound, so streaming weights at 1 (or 0.5) byte/param is the
    whole win (reference counterpart: the bnb int8 big-model-inference
    benchmark, /root/reference/benchmarks/big_model_inference).

    Quantization runs ON DEVICE with jnp ops, never gathering to host:
    eager ops on committed sharded arrays compute where the data lives, so
    GSPMD layouts from ``shard_for_inference`` survive into q/scales (the
    module's composition contract, and a host gather of a sharded 30B
    model would OOM the host).  The stacked-3-D math intentionally differs
    from utils/quantization.quantize_weight (numpy, 2-D, load-time); the
    per-step DEQUANT below reuses that module's exact kernel.
    """
    if isinstance(layers, tuple):
        raise NotImplementedError(
            "quantize_weights is not implemented for a mixed layer plan "
            "(per-layer weights, 3-D expert stacks among them); serve it unquantized"
        )
    plain, qd, sd = {}, {}, {}
    qmax = 127.0 if bits == 8 else 7.0
    for key, arr in layers.items():
        if arr.ndim != 3:
            plain[key] = arr
            continue
        if bits == 4 and arr.shape[-1] % 2:
            logger.warning(
                "quantize_weights=4: %s inner dim %d is odd — kept in full "
                "precision", key, arr.shape[-1],
            )
            plain[key] = arr
            continue
        amax = jnp.maximum(jnp.max(jnp.abs(arr), axis=-1, keepdims=True), 1e-12)
        scale = (amax / qmax).astype(jnp.float32)
        q = jnp.clip(jnp.round(arr / scale), -qmax - 1, qmax).astype(jnp.int8)
        if bits == 4:
            nib = (q + 8).astype(jnp.uint8)
            q = (nib[..., 0::2] << 4 | nib[..., 1::2]).astype(jnp.uint8)
        qd[key] = q
        sd[key] = scale[..., 0]  # (L, out)
    return plain, qd, sd


def stacked_params_for_mode(model, qbits: int, stack) -> tuple[dict, tuple]:
    """Per-mode memoized stacked decode params: ``(g, (plain, q, scales))``.

    One cache contract for every family's decode engine (the causal LMs here
    and T5's encoder-decoder loop).  Restacking is a full param-set copy per
    call (≈1.5 GB for GPT-2-large) and would pollute per-token latency, so
    the stack is memoized per parameter identity: the cache holds STRONG
    references to the source arrays and compares with ``is`` — an
    id()-tuple key can silently match recycled object ids after training
    rebinds p.data, serving stale weights.

    Retention policy: a mode's stack lives as long as the params do, so
    alternating full/quantized generates (the A/B comparison benchmarks do)
    never restack — but the full-precision stack is cached only when mode 0
    was itself requested.  A quantized-only deployment therefore holds
    module params + int8 stacks, NOT a third full-width copy (which at
    T0pp geometry would be the difference between fitting and OOM); the
    transient full stack built as quantizer input is dropped.
    """
    current = [p.data for _, p in model.named_parameters()]
    cached = getattr(model, "_generation_param_cache", None)
    if not (
        cached is not None
        and len(cached[0]) == len(current)
        and all(a is b for a, b in zip(cached[0], current))
    ):
        cached = (current, {})  # params changed: drop every mode
        model._generation_param_cache = cached
    by_mode: dict = cached[1]
    if qbits not in by_mode:
        if 0 in by_mode:
            g, (layers, _, _) = by_mode[0]
        else:
            g, layers = stack()
            if qbits == 0:
                by_mode[0] = (g, (layers, {}, {}))
        if qbits:
            by_mode[qbits] = (g, _quantize_stacked_layers(layers, qbits))
    return by_mode[qbits]


def _dequant_layer(plain_l: dict, q_l: dict, s_l: dict, bits: int, dtype) -> dict:
    """Rebuild one scan step's layer dict, widening int8/int4 entries to the
    activation dtype INSIDE the step — only one layer's weights are ever
    resident at full width.  The widening is utils/quantization's
    dequantize_weight (one shared bit-packing implementation)."""
    from ..utils.quantization import dequantize_weight

    l = dict(plain_l)
    for key, q in q_l.items():
        l[key] = dequantize_weight(q, s_l[key], bits, dtype)
    return l


@partial(
    jax.jit,
    static_argnames=("family", "cfg", "max_new", "cache_len", "temperature",
                     "qbits", "has_eos"),
)
def _generate_jit(
    g,
    layers,
    ids,  # (b, bucketed_prompt_len) int32, padded with pad_token_id
    prompt_len,  # () int32 TRUE prompt length — traced, NOT a cache key
    rng,
    eos_id,  # () int32 — traced so distinct stop tokens share one program
    pad_id,  # () int32
    *,
    family: DecoderFamily,
    cfg,
    max_new: int,
    cache_len: int,
    temperature: float,
    qbits: int = 0,
    has_eos: bool = False,
):
    b, padded_len = ids.shape
    plain_layers, q_layers, s_layers = layers

    # ---- prefill: full (bucketed) prompt through a scan over stacked layers.
    # The TRUE length rides as a traced scalar, so every prompt in a bucket
    # replays ONE program; pad positions are invisible — the causal mask
    # (`t <= q_pos`) hides their keys from every real query, and the decode
    # loop overwrites their cache entries before they ever unmask ----------
    positions = jnp.arange(padded_len)

    def prefill_layer(x, layer_in):
        l = _dequant_layer(*layer_in, qbits, x.dtype)
        q, k, v = family.attn_in(l, x, positions, cfg)
        # attend over the bucketed prompt keys (no wasted MXU work on the
        # not-yet-written cache region), then pad out to the decode length
        att = cached_attention(q, k, v, positions, cfg)
        pad = [(0, 0), (0, 0), (0, cache_len - padded_len), (0, 0)]
        return family.attn_out(l, x, att, cfg), (jnp.pad(k, pad), jnp.pad(v, pad))

    x = family.embed(g, ids, positions, cfg)
    x, (k_cache, v_cache) = jax.lax.scan(
        prefill_layer, x, (plain_layers, q_layers, s_layers)
    )
    # logits at the TRUE last prompt position (finalize reads x[:, -1], so
    # hand it the one dynamically gathered position)
    x_last = jax.lax.dynamic_slice_in_dim(x, prompt_len - 1, 1, axis=1)
    logits = family.finalize(g, x_last, cfg)

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(
            jnp.int32
        )

    rng, key = jax.random.split(rng)
    next_tok = sample(logits, key)
    done = next_tok == eos_id if has_eos else jnp.zeros_like(next_tok, bool)

    # ---- decode: one token per scan step, cache updated in place ----------
    def decode_step(carry, _):
        k_cache, v_cache, tok, position, rng, done = carry
        q_pos = position[None]
        x = family.embed(g, tok[:, None], q_pos, cfg)

        def layer(x, layer_in):
            l_parts, kc, vc = layer_in
            l = _dequant_layer(*l_parts, qbits, x.dtype)
            q, k, v = family.attn_in(l, x, q_pos, cfg)
            kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, position, 0))
            vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, position, 0))
            att = cached_attention(q, kc, vc, q_pos, cfg)
            return family.attn_out(l, x, att, cfg), (kc, vc)

        x, (k_cache, v_cache) = jax.lax.scan(
            layer, x, ((plain_layers, q_layers, s_layers), k_cache, v_cache)
        )
        logits = family.finalize(g, x, cfg)
        rng, key = jax.random.split(rng)
        nxt = sample(logits, key)
        if has_eos:
            # per-sequence stop: a finished row emits (and feeds) pad from
            # the step AFTER its eos.  Rows are computationally independent
            # and the rng split count is unchanged, so unfinished rows'
            # outputs are bitwise identical to the eos-free program
            nxt = jnp.where(done, pad_id, nxt)
            done = done | (nxt == eos_id)
        return (k_cache, v_cache, nxt, position + 1, rng, done), nxt

    (_, _, _, _, _, _), toks = jax.lax.scan(
        decode_step,
        (k_cache, v_cache, next_tok, prompt_len.astype(jnp.int32), rng, done),
        None,
        length=max_new - 1,
    )
    return jnp.concatenate([next_tok[None], toks], axis=0).T  # (b, max_new)


def bucket_up(n: int, multiple: int, cap: Optional[int] = None) -> int:
    """Round ``n`` up to a multiple (clamped to ``cap`` when given, never
    below ``n``) — the ONE shape-bucketing implementation every captured
    decode entry sits behind (``serving.bucket_length`` delegates here)."""
    if multiple < 1:
        raise ValueError(f"bucket multiple must be >= 1, got {multiple}")
    b = ((n + multiple - 1) // multiple) * multiple
    if cap is not None:
        b = min(b, cap)
    return max(b, n)


def generate(
    model,
    input_ids,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    quantize_weights: Optional[int] = None,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    prompt_bucket: Optional[int] = None,
    new_tokens_bucket: Optional[int] = None,
):
    """Greedy (``temperature=0``) or sampled decode with a KV cache.

    One jitted program per **bucketed** (prompt_len, max_new_tokens) pair:
    both lengths round up to configurable multiples (``prompt_bucket`` /
    ``new_tokens_bucket``, env ``ACCELERATE_GENERATE_PROMPT_BUCKET`` /
    ``ACCELERATE_GENERATE_NEW_BUCKET``, default 32; 1 disables), so repeated
    calls with nearby lengths replay ONE program instead of compiling per
    shape.  Pad prompt tokens are masked out of attention via ``q_pos`` and
    the extra decode steps are sliced off the result — outputs (and, for
    sampling, the per-step rng split sequence of the returned tokens) are
    identical to the unbucketed program.  Buckets degrade gracefully near
    the model's positional capacity; a genuinely over-long request still
    raises.

    ``eos_token_id`` enables per-sequence stopping: a row that sampled eos
    emits ``pad_token_id`` from the next step on, while unfinished rows'
    greedy outputs stay bitwise identical (rows are independent and rng
    consumption is shared per step, not per row).  The cache is sized
    ``bucketed_prompt + bucketed_new`` (must fit the model's positional
    capacity).  Works for any model exposing ``_decoder_spec()``.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    spec: DecoderSpec = model._decoder_spec()
    if layer_plan(spec.family, spec.cfg) is not None:
        raise NotImplementedError(
            "generate() runs plans of attention layers only; serve a mixed "
            "layer plan (state-space or expert layers) through DecodeService"
        )
    ids = jnp.asarray(
        input_ids.data if hasattr(input_ids, "data") else input_ids, jnp.int32
    )
    if ids.ndim == 1:
        ids = ids[None]
    prompt_len = ids.shape[1]
    if prompt_len + max_new_tokens > spec.max_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds the model's positional capacity ({spec.max_len})"
        )
    if quantize_weights not in (None, 4, 8):
        raise ValueError(
            f"quantize_weights={quantize_weights!r}: use None, 8 or 4"
        )
    from ..utils.environment import get_int_from_env

    if prompt_bucket is None:
        prompt_bucket = get_int_from_env(["ACCELERATE_GENERATE_PROMPT_BUCKET"], 32)
    if new_tokens_bucket is None:
        new_tokens_bucket = get_int_from_env(["ACCELERATE_GENERATE_NEW_BUCKET"], 32)
    padded_len = bucket_up(prompt_len, prompt_bucket, spec.max_len - max_new_tokens)
    bucket_new = bucket_up(max_new_tokens, new_tokens_bucket, spec.max_len - padded_len)
    if padded_len > prompt_len:
        ids_in = jnp.pad(
            ids, ((0, 0), (0, padded_len - prompt_len)),
            constant_values=pad_token_id,
        )
    else:
        ids_in = ids
    qbits = quantize_weights or 0
    g, layer_parts = stacked_params_for_mode(model, qbits, spec.stack)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    new_tokens = _generate_jit(
        g,
        layer_parts,
        ids_in,
        jnp.asarray(prompt_len, jnp.int32),
        rng,
        # traced scalars: distinct stop/pad ids replay ONE program; only
        # the presence of a stop token is a (boolean) cache-key component
        jnp.asarray(eos_token_id if eos_token_id is not None else 0, jnp.int32),
        jnp.asarray(pad_token_id, jnp.int32),
        family=spec.family,
        cfg=spec.cfg,
        max_new=bucket_new,
        cache_len=padded_len + bucket_new,
        temperature=float(temperature),
        qbits=qbits,
        has_eos=eos_token_id is not None,
    )
    return jnp.concatenate([ids, new_tokens[:, :max_new_tokens]], axis=1)
