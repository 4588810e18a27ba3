"""GPT-2-family causal LM on accelerate_tpu.nn — the throughput flagship.

Decoder-only transformer with pre-norm blocks, learned positions, weight-tied
LM head, causal SDPA routed to the Pallas flash kernel.  Carries the TP plan
(qkv/ffn column-parallel, proj row-parallel) so pjit lays it out on any mesh.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import F, Tensor


def shift_labels_for_lm(labels) -> jnp.ndarray:
    """Next-token targets as a flat (B*S,) id array with the final position
    masked to ignore_index (-100) — shared by the dense and chunked loss
    paths so their masking cannot drift."""
    lab = jnp.asarray(labels.data if isinstance(labels, Tensor) else labels)
    return jnp.concatenate(
        [lab[:, 1:], jnp.full((lab.shape[0], 1), -100, lab.dtype)], axis=1
    ).reshape(-1)


def lm_head_loss(x, head, labels, vocab_size: int):
    """ONE dispatch for every unrolled causal family's loss tail: dense
    head+CE, or the fused chunked path when ``ACCELERATE_TPU_CE_CHUNK`` is
    set (nn.functional.chunked_lm_head_ce — logits never materialize).
    The pipelined trunk computes its loss inside the last pp stage and is
    NOT covered (it warns when the knob is set).

    ``head`` is the family's output ``nn.Linear`` (biased for GPT-J).
    Returns ``(loss, logits_or_None)`` — None under the fused path, which
    is the documented contract for label-bearing calls with the knob on.
    """
    chunk = F.ce_chunk_size()
    # HLO metadata only: a device trace reads the head + cross-entropy's
    # forward under this scope; its backward falls under atpu_backward
    # (docs/telemetry.md §spans and scopes)
    with jax.named_scope("atpu_head_loss"):
        if chunk > 0:
            loss = F.chunked_lm_head_ce(
                x, head.weight, shift_labels_for_lm(labels), vocab_size, chunk,
                bias=getattr(head, "bias", None),
            )
            return loss, None
        logits = head(x)
        return lm_shift_loss(logits, labels, vocab_size), logits


def lm_shift_loss(logits, labels, vocab_size: int):
    """Next-token cross entropy without slicing logits to an odd length.

    Keeps the full seq-aligned logits and masks the final position with
    ignore_index (-100) instead of a ``[:, :-1]`` shift: slicing re-tiles the
    (B*S, vocab) tensor (8-sublane padding) — a measured ~4 ms, 786 MB
    physical copy per step on GPT-2-small/v5e, where the masked form is a
    free bitcast.
    """
    return F.cross_entropy(logits.reshape(-1, vocab_size), shift_labels_for_lm(labels))


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304  # padded to a 128 multiple for the MXU
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    # MoE (Switch-style): every `moe_every`-th block swaps its MLP for a
    # MixtureOfExperts over the `ep` mesh axis; 0 experts = dense model
    n_experts: int = 0
    moe_top_k: int = 1
    moe_every: int = 2
    moe_aux_weight: float = 0.01

    @classmethod
    def small(cls) -> "GPTConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "GPTConfig":
        return cls(vocab_size=1024, n_positions=256, n_embd=128, n_layer=2, n_head=4)

    @classmethod
    def tiny_moe(cls) -> "GPTConfig":
        return cls(
            vocab_size=1024, n_positions=256, n_embd=128, n_layer=2, n_head=4,
            n_experts=4, moe_every=2,
        )

    @classmethod
    def medium(cls) -> "GPTConfig":
        return cls(n_embd=1024, n_layer=24, n_head=16)

    @classmethod
    def large(cls) -> "GPTConfig":
        return cls(n_embd=1280, n_layer=36, n_head=20)


def _gpt2_init(model: nn.Module, config: GPTConfig) -> None:
    """GPT-2 init: N(0, 0.02) weights, zero biases, residual-proj scaling."""
    import jax

    from ..nn import random as nn_random

    scale = 0.02
    resid_scale = scale / math.sqrt(2 * config.n_layer)
    from ..nn.meta import is_meta

    for name, p in model.named_parameters():
        if is_meta(p.data):
            continue  # init_empty_weights: nothing to initialise
        if (
            name.endswith(".bias")
            or name.endswith(("b_in", "b_out"))  # MoE bias stacks are 2-D
            or ".ln" in name
            or "ln_" in name
        ):
            if p.ndim == 1 and name.endswith("weight"):
                continue  # LN weight stays ones
            if name.endswith("bias") or name.endswith(("b_in", "b_out")):
                p.data = jnp.zeros_like(p.data)
            continue
        if p.ndim >= 2:
            # MoE w_out plays the same residual-projection role as c_proj
            std = (
                resid_scale
                if ("c_proj" in name or name.endswith("w_out"))
                else scale
            )
            p.data = std * jax.random.normal(
                nn_random.next_key(), p.shape, dtype=p.dtype
            )


class CausalSelfAttention(nn.Module):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.n_head = config.n_head
        self.head_dim = config.n_embd // config.n_head
        self.c_attn = nn.Linear(config.n_embd, 3 * config.n_embd)
        self.c_proj = nn.Linear(config.n_embd, config.n_embd)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x):
        b, s, c = x.shape
        qkv = self.c_attn(x).reshape(b, s, 3, self.n_head, self.head_dim)
        qkv = qkv.transpose(2, 0, 3, 1, 4)  # (3, b, h, s, d)
        q, k, v = qkv[0], qkv[1], qkv[2]
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, c)
        return self.dropout(self.c_proj(out))


class MLP(nn.Module):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.c_fc = nn.Linear(config.n_embd, 4 * config.n_embd)
        self.c_proj = nn.Linear(4 * config.n_embd, config.n_embd)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x):
        return self.dropout(self.c_proj(F.gelu(self.c_fc(x))))


class Block(nn.Module):
    def __init__(self, config: GPTConfig, layer_idx: int = 0):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.n_embd, eps=config.layer_norm_eps)
        self.attn = CausalSelfAttention(config)
        self.ln_2 = nn.LayerNorm(config.n_embd, eps=config.layer_norm_eps)
        # Switch convention: every moe_every-th block routes its FFN through
        # experts (sharded over the `ep` mesh axis); the rest stay dense
        if config.n_experts > 0 and layer_idx % config.moe_every == config.moe_every - 1:
            self.mlp = nn.MixtureOfExperts(
                config.n_embd, 4 * config.n_embd, config.n_experts,
                top_k=config.moe_top_k, dropout=config.dropout,
            )
        else:
            self.mlp = MLP(config)

    def forward(self, x):
        x = x + self.attn(self.ln_1(x))
        return x + self.mlp(self.ln_2(x))


class GPTLMHeadModel(nn.Module):
    _no_split_modules = ["Block"]  # device_map units must keep residual adds intact
    tp_plan = {
        r".*\.c_attn\.weight": ("tp", None),
        r".*\.c_attn\.bias": ("tp",),
        r".*\.c_fc\.weight": ("tp", None),
        r".*\.c_fc\.bias": ("tp",),
        r".*\.c_proj\.weight": (None, "tp"),
        r"wte\.weight": ("tp", None),
        # MoE expert stacks: leading expert axis over ep (router replicated)
        r".*\.mlp\.w_in": ("ep", None, None),
        r".*\.mlp\.b_in": ("ep", None),
        r".*\.mlp\.w_out": ("ep", None, None),
        r".*\.mlp\.b_out": ("ep", None),
    }

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = nn.Embedding(config.vocab_size, config.n_embd)
        self.wpe = nn.Embedding(config.n_positions, config.n_embd)
        self.drop = nn.Dropout(config.dropout)
        self.h = nn.ModuleList(
            [Block(config, layer_idx=i) for i in range(config.n_layer)]
        )
        self.ln_f = nn.LayerNorm(config.n_embd, eps=config.layer_norm_eps)
        # LM head weight-tied to wte by Parameter-object sharing (reference
        # find_tied_parameters semantics, utils/modeling.py:559); a real
        # module (not an inline matmul) so device_map hooks cover it; built
        # under meta so the discarded weight never allocates or consumes RNG
        from ..nn.meta import meta_init

        with meta_init():
            self.lm_head = nn.Linear(config.n_embd, config.vocab_size, bias=False)
        self.lm_head.weight = self.wte.weight
        _gpt2_init(self, config)

    def forward(self, input_ids, labels=None):
        from ..parallel.sharding import constrain_activation

        ids = jnp.asarray(input_ids.data if isinstance(input_ids, Tensor) else input_ids)
        b, s = ids.shape
        pos = jnp.arange(s)[None, :]
        x = self.drop(self.wte(ids) + self.wpe(pos))
        # pin the activation layout at every layer boundary: batch stays on
        # (dp, fsdp) exactly as the loader placed it, so GSPMD never reshards
        # the residual stream (round-1 dryrun hit involuntary full remats)
        x = constrain_activation(x)
        for block in self.h:
            x = constrain_activation(block(x))
        x = self.ln_f(x)
        if labels is not None:
            loss, logits = lm_head_loss(
                x, self.lm_head, labels, self.config.vocab_size
            )
            if self.config.n_experts > 0:
                for block in self.h:
                    aux = getattr(block.mlp, "last_aux_loss", None)
                    if aux is not None:
                        loss = loss + self.config.moe_aux_weight * aux
            return {"loss": loss, "logits": logits}
        return {"logits": self.lm_head(x)}

    def generate(self, input_ids, max_new_tokens: int, temperature: float = 0.0,
                 rng=None, quantize_weights=None, **kwargs):
        """KV-cache greedy/sampled decode — see models/generation.py."""
        from .generation import generate

        return generate(self, input_ids, max_new_tokens, temperature, rng,
                        quantize_weights=quantize_weights, **kwargs)

    def _decoder_spec(self):
        """Hooks for the generic KV-cache engine (models/generation.py) —
        the math is gpt_attn_in/gpt_attn_out, the same functions the
        pipelined trunk trains with."""
        from .generation import DecoderSpec

        cfg = self.config
        if cfg.n_experts > 0:
            raise NotImplementedError(
                "generate() supports dense GPT trunks; MoE routing does not stack"
            )
        return DecoderSpec(
            family=GPT_DECODER,
            cfg=_GPTDecodeCfg(
                n_head=cfg.n_head,
                n_kv_head=cfg.n_head,
                head_dim=cfg.n_embd // cfg.n_head,
                eps=cfg.layer_norm_eps,
            ),
            max_len=cfg.n_positions,
            stack=self._stack_decoder_params,
        )

    def _stack_decoder_params(self) -> tuple[dict, dict]:
        """(globals, per-layer stacks) raw-array pytrees for cached decode,
        keyed like _StackedBlocks._ORDER so the pure block math reads both."""
        blocks = list(self.h)

        def stk(get):
            return jnp.stack([get(b).data for b in blocks])

        layers = {
            "ln1_w": stk(lambda b: b.ln_1.weight),
            "ln1_b": stk(lambda b: b.ln_1.bias),
            "qkv_w": stk(lambda b: b.attn.c_attn.weight),
            "qkv_b": stk(lambda b: b.attn.c_attn.bias),
            "proj_w": stk(lambda b: b.attn.c_proj.weight),
            "proj_b": stk(lambda b: b.attn.c_proj.bias),
            "ln2_w": stk(lambda b: b.ln_2.weight),
            "ln2_b": stk(lambda b: b.ln_2.bias),
            "fc_w": stk(lambda b: b.mlp.c_fc.weight),
            "fc_b": stk(lambda b: b.mlp.c_fc.bias),
            "fcproj_w": stk(lambda b: b.mlp.c_proj.weight),
            "fcproj_b": stk(lambda b: b.mlp.c_proj.bias),
        }
        g = {
            "wte": self.wte.weight.data,
            "wpe": self.wpe.weight.data,
            "ln_f_w": self.ln_f.weight.data,
            "ln_f_b": self.ln_f.bias.data,
        }
        return g, layers

    @property
    def num_flops_per_token(self) -> float:
        """Approximate training FLOPs/token (6N + attention term)."""
        n = self.num_parameters
        c = self.config
        attn = 12 * c.n_layer * c.n_embd * c.n_positions
        return 6 * n + attn


# ---------------------------------------------------------------------------
# Pure per-layer block math — the SINGLE source of truth shared by the
# pipelined trunk (shard_map training) and KV-cache decode (generation.py).
# Parameter keys follow _StackedBlocks._ORDER; weights are (out, in) like
# nn.Linear, applied as ``x @ w.T``.
# ---------------------------------------------------------------------------

def maybe_remat(fn):
    """Per-layer activation checkpointing (``ACCELERATE_TPU_REMAT=1`` or
    ``FullyShardedDataParallelPlugin(activation_checkpointing=True)`` /
    ``FSDP_ACTIVATION_CHECKPOINTING`` from the launcher protocol).

    Wraps a pure block function in ``jax.checkpoint``: the backward
    recomputes the layer forward instead of keeping its activations alive —
    ~33% more FLOPs for an O(layers) → O(1) activation footprint per layer,
    which buys a larger per-chip batch (usually a net MFU win on HBM-bound
    workloads; on the chip not measured).  Used by every pure-fn decoder family
    (Llama/OPT/GPT-J/NeoX); numerics are exactly unchanged (tested).

    The knobs are read at TRACE time: captured steps bake the value at
    first compile, eager steps read it per layer call (a cheap dict get).
    """
    import os

    if os.environ.get("ACCELERATE_TPU_REMAT", "0").lower() in ("1", "true", "yes"):
        return jax.checkpoint(fn)
    from ..state import AcceleratorState

    # read the Borg dict directly: constructing AcceleratorState() here
    # could silently re-run a full default init if a prior Accelerator
    # construction failed partway, and this runs per layer call
    plugin = AcceleratorState._shared_state.get("fsdp_plugin")
    if plugin is not None and getattr(plugin, "activation_checkpointing", False):
        return jax.checkpoint(fn)
    return fn


def _pure_layernorm(x, w, b, eps):
    # fp32 statistics regardless of activation dtype (bf16-safe), output
    # cast back so the residual stream keeps its dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return (((x32 - mu) * jax.lax.rsqrt(var + eps)) * w + b).astype(x.dtype)


def gpt_attn_in(p, x, *, n_head: int, eps: float):
    """LN1 + fused qkv projection, heads split: (b,s,c) → 3×(b,h,s,d)."""
    b, s, c = x.shape
    hd = c // n_head
    h = _pure_layernorm(x, p["ln1_w"], p["ln1_b"], eps)
    qkv = h @ p["qkv_w"].T + p["qkv_b"]
    qkv = qkv.reshape(b, s, 3, n_head, hd).transpose(2, 0, 3, 1, 4)
    return qkv[0], qkv[1], qkv[2]


def gpt_attn_out(p, x, att, *, eps: float):
    """Output projection + residual, then LN2 + gelu-MLP + residual.

    ``att`` arrives in (b, h, s, d) head layout straight from whichever
    attention engine ran (flash, ring, ulysses, or cached decode).
    """
    b, s, c = x.shape
    att = att.transpose(0, 2, 1, 3).reshape(b, s, c)
    h = x + att @ p["proj_w"].T + p["proj_b"]
    h2 = _pure_layernorm(h, p["ln2_w"], p["ln2_b"], eps)
    ff = jax.nn.gelu(h2 @ p["fc_w"].T + p["fc_b"], approximate=True)
    return h + ff @ p["fcproj_w"].T + p["fcproj_b"]


@dataclasses.dataclass(frozen=True)
class _GPTDecodeCfg:
    n_head: int
    n_kv_head: int
    head_dim: int
    eps: float


# The decode family reads every weight once, by the product that uses it, in
# the layout in which it lies on the device (docs/serving.md §weights are read
# where they lie).  A TPU lays an array out compactly: a matrix whose last
# dimension is off the 128 lanes (GPT-2-XL's 1600) lies with its OTHER
# dimension on the lanes — the layout ``h @ W.T`` wants.  Two things make the
# compiler re-lay a weight every step all the same, and both are stated
# otherwise here (held by tests/test_tpu_compile.py).

def _dec_embed(g, ids, positions, cfg):
    wte = g["wte"]
    if wte.shape[1] % 128 == 0:
        tok = wte[ids]  # rows lie contiguous: a gather takes them as they are
    else:
        # the table lies with the vocabulary on the lanes, as the tied head's
        # product reads it, and a gather of rows makes the compiler copy the
        # whole table to row-major first, every step (161 MB at GPT-2-XL).  A
        # one-hot product reads it where it lies, and is exact: one term of
        # each sum is not zero (HIGHEST keeps a float32 table's rows whole)
        hot = jax.nn.one_hot(ids, wte.shape[0], dtype=wte.dtype)
        tok = jnp.einsum("bsv,vc->bsc", hot, wte, precision=jax.lax.Precision.HIGHEST)
    return tok + g["wpe"][positions][None]


def _dec_attn_in(l, x, positions, cfg):
    """``gpt_attn_in``'s mathematics, the fused product's ``(b, s, 3c)``
    RESULT held two-dimensional behind a barrier before it is split into
    heads.  Without it the compiler folds the split into the product, which
    then wants the weight as ``(3, h, d, c)`` with ``c`` on the lanes: a slice
    and a copy of the layer's whole qkv weight, every layer, every step (3.1 of
    an 8.5 ms GPT-2-XL step); the split of a 16 x 4800 activation is free.
    Cutting q, k and v out of the result with no barrier cures one chip's
    program too, but on a mesh GSPMD then shards the k/v rows and all-gathers
    the replicated pools after every write (tests/test_tpu_compile.py holds
    both)."""
    b, s, c = x.shape
    h = _pure_layernorm(x, l["ln1_w"], l["ln1_b"], cfg.eps)
    qkv = jax.lax.optimization_barrier(h @ l["qkv_w"].T + l["qkv_b"])
    qkv = qkv.reshape(b, s, 3, cfg.n_head, cfg.head_dim).transpose(2, 0, 3, 1, 4)
    return qkv[0], qkv[1], qkv[2]


def _dec_attn_out(l, x, att, cfg):
    return gpt_attn_out(l, x, att, eps=cfg.eps)


def _dec_finalize(g, x, cfg):
    x = _pure_layernorm(x[:, -1], g["ln_f_w"], g["ln_f_b"], cfg.eps)
    return x @ g["wte"].T  # weight-tied head


def _make_gpt_decoder():
    from .generation import DecoderFamily

    return DecoderFamily(
        embed=_dec_embed,
        attn_in=_dec_attn_in,
        attn_out=_dec_attn_out,
        finalize=_dec_finalize,
    )


GPT_DECODER = _make_gpt_decoder()


def _pure_lm_head_loss(h, labels, extra, *, eps: float):
    """Final LN + (tied) head + shifted causal CE as pure jnp — the loss a
    1F1B pipeline computes INSIDE its last stage per microbatch.

    Returns ``(nll_sum, valid_count)`` — UN-normalised, so the pipeline can
    divide by the GLOBAL valid-token count after accumulating over
    microbatches and shards.  A per-microbatch mean would over-weight
    microbatches with more -100 padding; sum-and-count reproduces
    F.cross_entropy's global token mean exactly (the gpipe path's
    semantics).  -100 labels (HF padding convention) drop out of numerator
    AND denominator; gather on a clipped index so -100 never wraps into the
    vocab.  fp32 logsumexp.
    """
    ln_w, ln_b, head_w = extra
    h = _pure_layernorm(h, ln_w, ln_b, eps)
    logits = (h @ head_w.T).astype(jnp.float32)  # (b, s, V)
    lse = jax.nn.logsumexp(logits, axis=-1)  # (b, s)
    b, s = labels.shape
    shifted = jnp.concatenate(
        [labels[:, 1:], jnp.zeros((b, 1), labels.dtype)], axis=1
    )
    valid = shifted >= 0
    safe = jnp.where(valid, shifted, 0)
    picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = lse - picked
    mask = valid.astype(jnp.float32) * jnp.concatenate(
        [jnp.ones((b, s - 1), jnp.float32), jnp.zeros((b, 1), jnp.float32)], axis=1
    )
    return jnp.sum(nll * mask), jnp.sum(mask)


def _pipelined_block(p, h, *, n_head: int, eps: float, seq_axis: str, sp_mode: str = "ring"):
    """One pre-norm GPT block as pure jnp, runnable inside shard_map.

    Attention goes through the selected sequence-parallel per-device body
    over ``seq_axis`` (``SequenceParallelPlugin.mode``: "ring" streams k/v
    chunks via ppermute, "all_to_all" re-partitions heads Ulysses-style) —
    with sp=1 the ring has one hop and reduces to plain causal SDPA, so
    pp-only and pp×sp use the same code.
    """
    from ..ops.ring_attention import _ring_attention_local, _ulysses_attention_local

    local_attn = (
        _ulysses_attention_local if sp_mode == "all_to_all" else _ring_attention_local
    )
    hd = h.shape[-1] // n_head
    q, k, v = gpt_attn_in(p, h, n_head=n_head, eps=eps)
    att = local_attn(q, k, v, axis_name=seq_axis, is_causal=True, scale=hd**-0.5)
    return gpt_attn_out(p, h, att, eps=eps)


class _StackedBlocks(nn.Module):
    """Per-layer GPT block weights stacked on a leading layer axis.

    The layer axis is sharded over ``pp`` (see tp_plan on the parent): each
    pipeline stage holds a contiguous span of layers, the TPU-native reading
    of the reference's PiPPy split-at-layer-boundaries
    (reference inference.py:124).
    """

    def __init__(self, config: GPTConfig):
        super().__init__()
        import jax as _jax

        from ..nn import random as nn_random

        L, E = config.n_layer, config.n_embd
        scale = 0.02
        resid = scale / math.sqrt(2 * L)

        def norm(shape, std):
            return nn.Parameter(
                std * _jax.random.normal(nn_random.next_key(), shape, jnp.float32)
            )

        self.ln1_w = nn.Parameter(jnp.ones((L, E)))
        self.ln1_b = nn.Parameter(jnp.zeros((L, E)))
        self.qkv_w = norm((L, 3 * E, E), scale)
        self.qkv_b = nn.Parameter(jnp.zeros((L, 3 * E)))
        self.proj_w = norm((L, E, E), resid)
        self.proj_b = nn.Parameter(jnp.zeros((L, E)))
        self.ln2_w = nn.Parameter(jnp.ones((L, E)))
        self.ln2_b = nn.Parameter(jnp.zeros((L, E)))
        self.fc_w = norm((L, 4 * E, E), scale)
        self.fc_b = nn.Parameter(jnp.zeros((L, 4 * E)))
        self.fcproj_w = norm((L, E, 4 * E), resid)
        self.fcproj_b = nn.Parameter(jnp.zeros((L, E)))

    _ORDER = (
        "ln1_w", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
        "ln2_w", "ln2_b", "fc_w", "fc_b", "fcproj_w", "fcproj_b",
    )

    def param_tensors(self):
        return [getattr(self, n) for n in self._ORDER]


class PipelinedGPTLMHeadModel(nn.Module):
    """GPT-2 whose trunk runs as a GPipe pipeline over the ``pp`` mesh axis
    with ring attention over ``sp`` — pp × sp × dp/fsdp in ONE shard_map.

    Embeddings and the (tied) head stay outside the pipeline (GPipe classic:
    every pipelined layer must be shape-preserving).  TP inside the pipeline
    body is intentionally out of scope — on-slice, GSPMD tp on the unrolled
    ``GPTLMHeadModel`` is the faster layout; pp/sp earn their keep across
    slices and long sequences (SURVEY.md §2.2 rows PP/SP).
    """

    tp_plan = {
        r"blocks\..*": ("pp",),  # leading layer axis → pipeline stages
        r"wte\.weight": ("tp", None),
    }

    def __init__(self, config: GPTConfig, num_microbatches: int = 2):
        super().__init__()
        self.config = config
        self.num_microbatches = num_microbatches
        self.wte = nn.Embedding(config.vocab_size, config.n_embd)
        self.wpe = nn.Embedding(config.n_positions, config.n_embd)
        self.blocks = _StackedBlocks(config)
        self.ln_f = nn.LayerNorm(config.n_embd, eps=config.layer_norm_eps)
        from ..nn.meta import is_meta, meta_init

        with meta_init():
            self.lm_head = nn.Linear(config.n_embd, config.vocab_size, bias=False)
        self.lm_head.weight = self.wte.weight
        # GPT-2 embedding init (the stacked blocks init themselves)
        for emb in (self.wte, self.wpe):
            if not is_meta(emb.weight.data):
                emb.weight.data = emb.weight.data * 0.02

    def forward(self, input_ids, labels=None):
        from ..parallel.pipeline import gpipe
        from ..parallel.plan import current_plan
        from ..parallel.sharding import constrain_activation
        from ..state import AcceleratorState

        mesh = AcceleratorState().mesh if AcceleratorState._shared_state else None
        # the resolved ParallelPlan owns schedule / stage layout / sp mode
        # (docs/parallel_plan.md) — this model never pokes plugins or the
        # mesh dict for axis sizes (graftlint stage-boundary-vs-plan)
        plan = current_plan()

        ids = jnp.asarray(input_ids.data if isinstance(input_ids, Tensor) else input_ids)
        b, s = ids.shape
        pos = jnp.arange(s)[None, :]
        x = self.wte(ids) + self.wpe(pos)
        x = constrain_activation(x)

        cfg = self.config
        names = _StackedBlocks._ORDER
        # the plan's sp mode selects the attention engine; the ulysses body
        # needs heads divisible across the sp axis, else ring
        sp_mode = "ring"
        sp_size = plan.sp if plan is not None else 1
        if plan is not None and plan.sp_mode == "all_to_all" and sp_size > 1:
            if cfg.n_head % sp_size == 0:
                sp_mode = "all_to_all"
            else:
                # captured steps keep whatever mode the first trace chose, so
                # a silent fallback would be invisible for the whole run
                warnings.warn(
                    f"SequenceParallelPlugin(mode='all_to_all') ignored: "
                    f"n_head={cfg.n_head} is not divisible by the sp axis "
                    f"size {sp_size}; falling back to ring "
                    "attention for this (and, under capture, every) step.",
                    stacklevel=2,
                )

        def stage_fn(layer_params, h):
            return _pipelined_block(
                layer_params, h,
                n_head=cfg.n_head, eps=cfg.layer_norm_eps, seq_axis="sp",
                sp_mode=sp_mode,
            )

        # -- fused/interleaved 1F1B training path (plan.stage.schedule) ------
        stage = plan.stage if plan is not None else None
        schedule = stage.schedule if stage is not None else "gpipe"
        pp_size = plan.pp if plan is not None else 1
        # Layer layout of record (docs/parallel_plan.md §layout contract):
        # the prepare-time commit stamps the stacked params, so the RUNTIME
        # source of truth is the marker, not the plan alone — an unprepared
        # model (plain stack) under a committed plan still runs correctly
        # through the in-program-gather fallback.
        committed = bool(
            getattr(self.blocks.qkv_w, "_layer_layout_committed", False)
        )
        trunk_virtual = stage.virtual if stage is not None else 1
        if labels is not None and schedule in ("1f1b", "interleaved") and pp_size > 1:
            if sp_size > 1:
                raise NotImplementedError(
                    f"schedule={schedule!r} computes the loss inside the "
                    "pipeline and does not yet compose with sequence "
                    "parallelism (the shifted CE crosses seq-chunk "
                    "boundaries); use schedule='gpipe' with sp>1"
                )
            from ..parallel.pipeline import pipeline_loss_1f1b

            lbl = jnp.asarray(labels.data if isinstance(labels, Tensor) else labels)
            n_names = len(names)
            virtual = stage.virtual

            def fused(xv, *flat):
                stacked = dict(zip(names, flat[:n_names]))
                extra = tuple(flat[n_names:])  # (ln_f w, ln_f b, head w)

                def loss_fn(out, lbl_mb, ep):
                    return _pure_lm_head_loss(out, lbl_mb, ep, eps=cfg.layer_norm_eps)

                f = pipeline_loss_1f1b(
                    stage_fn, loss_fn, lbl, self.num_microbatches, mesh=mesh,
                    virtual=virtual,
                    layout="committed" if committed else None,
                )
                return f(stacked, xv, extra)

            loss = nn.tape_op(
                fused, x, *self.blocks.param_tensors(),
                self.ln_f.weight, self.ln_f.bias, self.lm_head.weight,
            )
            # logits never materialise in the fused schedule — that is the
            # memory point; callers needing logits use schedule='gpipe'
            return {"loss": loss, "logits": None}

        def trunk(xv, *flat_params):
            stacked = dict(zip(names, flat_params))
            if committed:
                # cold/inference path only: view the committed stack in
                # plain model order for the sequential gpipe trunk (the
                # captured 1F1B training step above never runs this)
                from ..parallel.pipeline import uncommit_layer_layout

                stacked = uncommit_layer_layout(stacked, trunk_virtual, mesh=mesh)
            return gpipe(
                stage_fn,
                stacked,
                xv,
                num_microbatches=self.num_microbatches,
                mesh=mesh,
                seq_axis="sp",
            )

        x = nn.tape_op(trunk, x, *self.blocks.param_tensors())
        x = self.ln_f(x)
        logits = self.lm_head(x)
        if labels is not None:
            if F.ce_chunk_size() > 0 and not getattr(self, "_ce_chunk_warned", False):
                self._ce_chunk_warned = True
                warnings.warn(
                    "ACCELERATE_TPU_CE_CHUNK has no effect on "
                    "PipelinedGPTLMHeadModel: the pipelined loss runs inside "
                    "the last pp stage (1F1B computes it per microbatch) and "
                    "materializes dense logits; the fused chunked head+CE "
                    "covers the unrolled families only.",
                    stacklevel=2,
                )
            loss = lm_shift_loss(logits, labels, cfg.vocab_size)
            return {"loss": loss, "logits": logits}
        return {"logits": logits}
