"""Olmo-Hybrid (``model_type olmo_hybrid``): a decoder whose layers alternate
by ``layer_types`` between gated delta-rule (linear-attention) mixers and full
attention, every layer with a dense SwiGLU MLP of its own, in Olmo 2/3's
reordered-norm block::

    h  = x + RMSNorm(mixer(x))
    x' = h + RMSNorm(W_down(silu(W_gate h) * W_up h))

then a final RMSNorm and an untied head.  ``u`` is a layer's input.

* ``linear_attention`` — Gated DeltaNet (``ops/delta_rule.py``): ``q, k, v, z,
  a, b = u W_q, u W_k, u W_v, u W_g, u W_a, u W_b``; a causal depthwise
  convolution (``linear_conv_kernel_dim`` taps, no bias) and SiLU over ``[q | k
  | v]``; per head ``q <- l2norm(q) / sqrt(d_k)``, ``k <- l2norm(k)``, ``beta =
  2 sigmoid(b)`` (the 2 is ``linear_allow_neg_eigval``), ``g = -exp(A_log)
  softplus(a + dt_bias)``; the delta-rule recurrence over a state of ``(d_k,
  d_v)`` a head; ``y_h = RMSNorm(o_h; w) * silu(z_h)`` (norm first, then the
  gate, over each head's ``d_v``); ``y W_o``.
* ``full_attention`` — ``q = RMSNorm(u W_q)``, ``k = RMSNorm(u W_k)`` (each norm
  over the whole projection), ``v = u W_v``; causal softmax attention, no bias
  and **no rotary or other positional term** (the published ``rope_theta`` is
  null; order comes from the recurrent layers and their convolutions).

Serving only: the model is a holder of weights and a ``DecoderFamily`` with a
mixed layer plan for ``DecodeService`` (docs/serving.md §layer plan).  Where
``layer_types`` is a whole number (two or more) of repeats of one period the
weights are held as ONE STACK PER POSITION IN THE PERIOD, ``(repeats, ...)``
leaves, and the engine scans the repeats; otherwise a dict a layer, unrolled.
Either way ``_decoder_spec().stack`` hands over the parameters' own arrays:
the weights are held once.
RMSNorm statistics, the l2 norms, ``beta``, the decays and the state are
float32 whatever the parameters' dtype; the projections run in the parameters'
dtype with float32 accumulation.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import nn
from ..ops import delta_rule, ssm
from .generation import ATTENTION, RECURRENT
from .nemotron_h import _initial as _nemotron_initial
from .nemotron_h import rmsnorm

KINDS = {"linear_attention": RECURRENT, "full_attention": ATTENTION}
_PERIOD = ("linear_attention",) * 3 + ("full_attention",)


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    layer_types: tuple = _PERIOD * 8
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    chunk_size: int = 64
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536

    def __post_init__(self):
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise NotImplementedError("linear layers with fewer key heads than value heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must be a multiple of num_attention_heads")

    # what the serving engine asks of every family's static config
    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_head(self) -> int:
        return self.num_key_value_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_width(self) -> int:
        return 2 * self.key_width + self.value_width

    @property
    def kinds(self) -> tuple:
        return tuple(KINDS[t] for t in self.layer_types)

    @property
    def period(self) -> int:
        """Layers in one period where the plan is two or more repeats of it
        (the shortest such), else the number of layers."""
        kinds, n = self.kinds, len(self.layer_types)
        for p in range(1, n // 2 + 1):
            if n % p == 0 and kinds == kinds[:p] * (n // p):
                return p
        return n

    @classmethod
    def tiny(cls, **over) -> "OlmoHybridConfig":
        """Both kinds of layer at test size, two periods (chunks of 8, so a
        16-token bucket holds two)."""
        base = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48, layer_types=_PERIOD * 2,
            num_attention_heads=4, num_key_value_heads=4,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=16,
            chunk_size=8, max_position_embeddings=128,
        )
        base.update(over)
        return cls(**base)


def layer_shapes(cfg: OlmoHybridConfig, kind: str) -> dict:
    """``{name: shape}`` of one layer of ``kind`` (``"globals"``: the table,
    the final norm and the head).  Matrices are (in, out): the products are
    ``x @ W``."""
    d, f, h = cfg.hidden_size, cfg.intermediate_size, cfg.linear_num_value_heads
    kw, vw = cfg.key_width, cfg.value_width
    mlp = {"mixer_norm": (d,), "gate_w": (d, f), "up_w": (d, f), "down_w": (f, d), "mlp_norm": (d,)}
    return {
        "globals": {"embed": (cfg.vocab_size, d), "norm_f": (d,), "head": (cfg.vocab_size, d)},
        RECURRENT: {
            "q_w": (d, kw), "k_w": (d, kw), "v_w": (d, vw), "g_w": (d, vw), "a_w": (d, h), "b_w": (d, h),
            "conv_w": (cfg.conv_width, cfg.linear_conv_kernel_dim), "dt_bias": (h,), "a_log": (h,),
            "gate_norm": (cfg.linear_value_head_dim,), "o_w": (vw, d), **mlp,
        },
        ATTENTION: {
            "q_w": (d, d), "k_w": (d, d), "v_w": (d, d), "q_norm": (d,), "k_norm": (d,), "o_w": (d, d), **mlp,
        },
    }[kind]


class OlmoHybridForCausalLM(nn.Module):
    """The weights of an Olmo-Hybrid decoder: ``globals_`` and ``layers``, one
    holder per position in the period whose leaves are stacks over the repeats
    (``config.period < len(layer_types)``), or one holder a layer."""

    def __init__(self, config: OlmoHybridConfig, dtype=jnp.float32):
        super().__init__()
        self.config = config
        key = jax.random.PRNGKey(0)
        period, n = config.period, len(config.layer_types)
        lead = (n // period,) if period < n else ()

        def holder(kind, lead=()):
            nonlocal key
            out = nn.Module()
            for name, shape in layer_shapes(config, kind).items():
                key, sub = jax.random.split(key)
                setattr(out, name, nn.Parameter(_initial(name, (*lead, *shape), sub, dtype)))
            return out

        self.globals_ = holder("globals")
        self.layers = nn.ModuleList([holder(kind, lead) for kind in config.kinds[:period]])

    def forward(self, *args, **kwargs):
        raise NotImplementedError(
            "OlmoHybridForCausalLM is served through DecodeService; training it "
            "(the chunked delta rule's backward) is not implemented"
        )

    def generate(self, *args, **kwargs):
        from .generation import generate

        return generate(self, *args, **kwargs)  # refuses a mixed plan in one line

    def _decoder_spec(self):
        from .generation import DecoderSpec

        return DecoderSpec(
            family=OLMO_HYBRID_DECODER, cfg=self.config,
            max_len=self.config.max_position_embeddings, stack=self._layer_arrays,
        )

    def _layer_arrays(self) -> tuple:
        """``(globals, one dict per holder)``: the parameters' own arrays, no
        copy — the weights are held once."""
        def arrays(holder):
            return {name: p.data for name, p in holder._parameters.items()}

        return arrays(self.globals_), tuple(arrays(layer) for layer in self.layers)


def _initial(name: str, shape, key, dtype):
    from ..nn.meta import MetaArray, meta_mode_active

    if meta_mode_active():
        return MetaArray(shape, jnp.dtype(dtype))
    if name.endswith("_norm") or name == "norm_f":
        return jnp.ones(shape, dtype)
    if name == "a_log":  # log U(0, 16), floored away from log 0
        return jnp.log(jnp.maximum(jax.random.uniform(key, shape, jnp.float32, 0.0, 16.0), 1e-2)).astype(dtype)
    if name == "dt_bias":
        return _nemotron_initial(name, shape, key, dtype, None)
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# the family's pure functions
# ---------------------------------------------------------------------------
def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _after_mixer(l, x, mixed, cfg):
    """``h = x + RMSNorm(mixer(x))``, then the MLP's half of the block."""
    h = x + rmsnorm(mixed.astype(x.dtype), l["mixer_norm"], cfg.rms_norm_eps)
    with jax.named_scope("atpu_serve_mlp"):
        inner = (jax.nn.silu(_dot(h, l["gate_w"])) * _dot(h, l["up_w"])).astype(x.dtype)
        return h + rmsnorm(_dot(inner, l["down_w"]).astype(x.dtype), l["mlp_norm"], cfg.rms_norm_eps)


def _gdn_in(l, u):
    """The six projections of ``u: (rows, c)``: the pre-convolution ``[q | k |
    v]``, the gate ``z``, and ``a, b`` in float32.  The gate's RESULT is held
    two-dimensional behind a barrier before it is split into heads: without it
    the chip's compiler folds the split into the product, which then wants
    ``W_g`` as ``(heads, d_v, c)`` — a copy of the layer's whole 44 MB weight,
    every layer, every step (as ``models/gpt.py::_dec_attn_in`` found for its
    fused qkv; held by tests/test_tpu_compile.py)."""
    qkv = jnp.concatenate([_dot(u, l["q_w"]), _dot(u, l["k_w"]), _dot(u, l["v_w"])], axis=-1)
    return qkv, jax.lax.optimization_barrier(_dot(u, l["g_w"])), _dot(u, l["a_w"]), _dot(u, l["b_w"])


def _split_qkv(qkv, cfg):
    h, kw = cfg.linear_num_key_heads, cfg.key_width
    lead = qkv.shape[:-1]
    return (
        qkv[..., :kw].reshape(*lead, h, cfg.linear_key_head_dim),
        qkv[..., kw:2 * kw].reshape(*lead, h, cfg.linear_key_head_dim),
        qkv[..., 2 * kw:].reshape(*lead, h, cfg.linear_value_head_dim),
    )


def _decay_and_beta(a, b, l, cfg):
    f32 = jnp.float32
    g = -jnp.exp(l["a_log"].astype(f32)) * jax.nn.softplus(a + l["dt_bias"].astype(f32))
    return g, (2.0 if cfg.linear_allow_neg_eigval else 1.0) * jax.nn.sigmoid(b)


def _gate_norm_out(o, z, l, x, cfg):
    """``(RMSNorm(o_h; w) * silu(z_h)) W_o`` for ``o: (rows, H, d_v)``."""
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.rms_norm_eps)
    y = o * l["gate_norm"].astype(jnp.float32) * jax.nn.silu(z.reshape(o.shape))
    return _dot(y.reshape(o.shape[0], -1).astype(x.dtype), l["o_w"])


def gdn_prefill(l, x, true_len, cfg):
    """One bucket-padded sequence ``x: (1, T, c)`` from a zero state.  The
    state comes back packed (``delta_rule.pack_state``)."""
    with jax.named_scope("atpu_serve_gdn_in"):
        qkv, z, a, b = _gdn_in(l, x[0])
    with jax.named_scope("atpu_serve_gdn_conv"):
        tail = ssm.conv_tail(qkv, true_len, cfg.linear_conv_kernel_dim)
        q, k, v = _split_qkv(jax.nn.silu(ssm.causal_conv(qkv, l["conv_w"])), cfg)
    with jax.named_scope("atpu_serve_gdn_scan"):
        g, beta = _decay_and_beta(a, b, l, cfg)
        # a recurrence has no mask: padding must not move the state
        true = (jnp.arange(x.shape[1]) < true_len)[:, None]
        o, state = delta_rule.delta_rule_chunked(
            q, k, v, jnp.where(true, g, 0.0), jnp.where(true, beta, 0.0), cfg.chunk_size
        )
        state = delta_rule.pack_state(state)
    with jax.named_scope("atpu_serve_gdn_out"):
        mixed = _gate_norm_out(o, z, l, x, cfg)
    return _after_mixer(l, x[0], mixed, cfg)[None], state, tail


def gdn_step(l, x, pools, i, live, cfg, mesh=None):
    """One token for every slot: ``x: (slots, 1, c)``; ``pools`` the state
    pools (``{"ssm", "conv"}``), ``i`` this layer's rank in them, ``live:
    (slots, 1)``.  The recurrence runs over the live slots alone, in place in
    the whole state pool, which comes back beside the new tail
    (``native/kernels/gdn_step.py``; imported here, at trace time).  A dead
    slot's state is left as it is and its ``o`` is zeros."""
    from ..native.kernels import gdn_step as kernel

    tail = pools["conv"][i]
    with jax.named_scope("atpu_serve_gdn_in"):
        qkv, z, a, b = _gdn_in(l, x[:, 0])
    with jax.named_scope("atpu_serve_gdn_conv"):
        conv, tail = ssm.conv_step(tail, qkv, l["conv_w"])
        q, k, v = _split_qkv(jax.nn.silu(conv), cfg)
    with jax.named_scope("atpu_serve_gdn_step"):
        g, beta = _decay_and_beta(a, b, l, cfg)
        o, state = kernel.gdn_step_live(pools["ssm"], i, live[:, 0], q, k, v, g, beta, mesh=mesh)
    with jax.named_scope("atpu_serve_gdn_out"):
        mixed = _gate_norm_out(o, z, l, x, cfg)
    return _after_mixer(l, x[:, 0], mixed, cfg)[:, None], state, tail


def _embed(g, ids, positions, cfg):
    return g["embed"][ids]  # no positional term anywhere in this family


def _attn_in(l, x, positions, cfg):
    b, s, _ = x.shape
    eps = cfg.rms_norm_eps
    q = rmsnorm(_dot(x, l["q_w"]).astype(x.dtype), l["q_norm"], eps)
    k = rmsnorm(_dot(x, l["k_w"]).astype(x.dtype), l["k_norm"], eps)
    v = _dot(x, l["v_w"]).astype(x.dtype)
    heads = lambda t, n: t.reshape(b, s, n, cfg.head_dim).transpose(0, 2, 1, 3)  # noqa: E731
    return heads(q, cfg.n_head), heads(k, cfg.n_kv_head), heads(v, cfg.n_kv_head)


def _attn_out(l, x, att, cfg):
    b, s, _ = x.shape
    att = att.transpose(0, 2, 1, 3).reshape(b, s, -1).astype(x.dtype)
    return _after_mixer(l, x, _dot(att, l["o_w"]), cfg)


def _finalize(g, x, cfg):
    h = rmsnorm(x[:, -1], g["norm_f"], cfg.rms_norm_eps)
    return jnp.dot(h, g["head"].T, preferred_element_type=jnp.float32)


def _make_decoder():
    from .generation import DecoderFamily

    return DecoderFamily(
        embed=_embed, attn_in=_attn_in, attn_out=_attn_out, finalize=_finalize,
        plan=lambda cfg: cfg.kinds,
        recurrent_prefill=gdn_prefill, recurrent_step=gdn_step,
        recurrent_scopes=("atpu_serve_gdn_scan", "atpu_serve_gdn_step"),
    )


OLMO_HYBRID_DECODER = _make_decoder()
