"""Nemotron-H (``model_type nemotron_h``): a hybrid decoder whose every layer is
ONE mixer behind a pre-norm, ``x <- x + mixer_i(RMSNorm_i(x))``, the kind of
layer ``i`` being character ``i`` of ``pattern``:

* ``M`` Mamba-2 (``ops/ssm.py``): ``[z | xBC | dt] = u W_in``; a causal
  depthwise convolution (kernel 4) and SiLU over ``xBC``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space recurrence;
  ``y <- RMSNorm_groups(y * silu(z))`` (gate first, then the norm over each of
  ``n_groups`` groups of channels); ``y W_out``.
* ``*`` attention: grouped-query causal softmax attention, no bias and **no
  rotary or other positional term** (order comes from the Mamba layers).
* ``E`` experts (``nn/moe.py``): a sigmoid router over all ``n_routed_experts``
  with a correction bias, top-``k``, normalised, scaled; the experts are not
  gated (``W_down relu(W_up u)^2``); one shared expert of the same form.  The
  model holds ``experts_held`` of the routed experts from ``expert_offset`` on
  — one chip's share of a layer that several chips divide — and computes
  their part of the result without drops.

Serving only: the model is a holder of weights and a ``DecoderFamily`` with
a mixed layer plan for ``DecodeService`` (docs/serving.md §layer plan).
``_decoder_spec().stack`` hands the engine the parameters' own arrays, a dict
a layer: the weights are held once.
RMSNorm statistics, the router, ``dt``, the decays and the state are float32
whatever the parameters' dtype; the projections run in the parameters' dtype
with float32 accumulation.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.moe import held_experts_apply, route_sigmoid_topk, shared_expert_ffn
from ..ops import ssm
from .generation import ATTENTION, EXPERTS, RECURRENT

KINDS = {"M": RECURRENT, "*": ATTENTION, "E": EXPERTS}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # experts
    n_routed_experts: int = 128  # the router's width
    experts_held: int = 128  # how many of them this model holds ...
    expert_offset: int = 0  # ... from this one on
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
    max_position_embeddings: int = 262144

    # what the serving engine asks of every family's static config
    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_head(self) -> int:
        return self.num_key_value_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def expert_width_stored(self) -> int:
        """The routed experts' width as stored: the published one zero-padded
        to whole 128-lane tiles (1856 is 14.5 of them, 1920 is 15), which is
        what the chip's tiling makes of the minor-most axis anyway.  A zero
        column gives relu(0)^2 = 0 and meets a zero row."""
        return -(-self.moe_intermediate_size // 128) * 128

    @property
    def kinds(self) -> tuple:
        return tuple(KINDS[ch] for ch in self.pattern)

    @classmethod
    def tiny(cls, **over) -> "NemotronHConfig":
        """Every kind of layer at test size (chunks of 8, so a 16-token bucket
        holds two)."""
        base = dict(
            vocab_size=96, hidden_size=32, pattern="ME*EM",
            mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=8,
            chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            n_routed_experts=8, experts_held=8, num_experts_per_tok=3,
            moe_intermediate_size=16, moe_shared_expert_intermediate_size=24,
            max_position_embeddings=128,
        )
        base.update(over)
        return cls(**base)


GLOBAL_SHAPES = ("embed", "norm_f", "head")


def layer_shapes(cfg: NemotronHConfig, kind: str) -> dict:
    """``{name: shape}`` of one layer of ``kind`` (``"globals"``: the table,
    the final norm and the head).  Matrices are (in, out): the products are
    ``x @ W``, and an expert stack is ``(experts held, in, out)``."""
    d, di, cw = cfg.hidden_size, cfg.d_inner, cfg.conv_width
    h, fs = cfg.mamba_num_heads, cfg.moe_shared_expert_intermediate_size
    f = cfg.expert_width_stored
    qd, kvd = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    return {
        "globals": {"embed": (cfg.vocab_size, d), "norm_f": (d,), "head": (cfg.vocab_size, d)},
        RECURRENT: {
            "norm": (d,), "in_w": (d, di + cw + h), "conv_w": (cw, cfg.conv_kernel), "conv_b": (cw,),
            "dt_bias": (h,), "a_log": (h,), "d": (h,), "gate_norm": (di,), "out_w": (di, d),
        },
        ATTENTION: {"norm": (d,), "qkv_w": (d, qd + 2 * kvd), "o_w": (qd, d)},
        EXPERTS: {
            "norm": (d,), "router_w": (cfg.n_routed_experts, d), "router_bias": (cfg.n_routed_experts,),
            "up_w": (cfg.experts_held, d, f), "down_w": (cfg.experts_held, f, d),
            "shared_up_w": (d, fs), "shared_down_w": (fs, d),
        },
    }[kind]


class NemotronHForCausalLM(nn.Module):
    """The weights of a Nemotron-H decoder: ``globals_`` and one holder a
    layer, in the pattern's order.  Every layer's weights are arrays of their
    own and not rows of a stack: the chip's compiler copied each layer's
    experts out of an ``(L, E, d, f)`` stack, 640 MB a layer at the published
    widths (PERF.md, PR 31)."""

    def __init__(self, config: NemotronHConfig, dtype=jnp.float32):
        super().__init__()
        self.config = config
        key = jax.random.PRNGKey(0)

        def holder(kind):
            nonlocal key
            out = nn.Module()
            for name, shape in layer_shapes(config, kind).items():
                key, sub = jax.random.split(key)
                setattr(out, name, nn.Parameter(_initial(name, shape, sub, dtype, config)))
            return out

        self.globals_ = holder("globals")
        self.layers = nn.ModuleList([holder(kind) for kind in config.kinds])

    def forward(self, *args, **kwargs):
        raise NotImplementedError(
            "NemotronHForCausalLM is served through DecodeService; training it "
            "(the chunked scan's backward, the expert layer's) is not implemented"
        )

    def generate(self, *args, **kwargs):
        from .generation import generate

        return generate(self, *args, **kwargs)  # refuses a mixed plan in one line

    def _decoder_spec(self):
        from .generation import DecoderSpec

        return DecoderSpec(
            family=NEMOTRON_H_DECODER, cfg=self.config,
            max_len=self.config.max_position_embeddings, stack=self._layer_arrays,
        )

    def _layer_arrays(self) -> tuple:
        """``(globals, per-layer dicts in plan order)``: the parameters' own
        arrays, no copy — the weights are held once."""
        def arrays(holder):
            return {name: p.data for name, p in holder._parameters.items()}

        return arrays(self.globals_), tuple(arrays(layer) for layer in self.layers)


def _initial(name: str, shape, key, dtype, cfg: NemotronHConfig):
    from ..nn.meta import MetaArray, meta_mode_active

    if meta_mode_active():
        return MetaArray(shape, jnp.dtype(dtype))
    if name in ("norm", "gate_norm", "norm_f", "d"):
        return jnp.ones(shape, dtype)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1
    if name in ("conv_b", "router_bias"):
        return jnp.zeros(shape, dtype)
    w = (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name in ("up_w", "down_w"):  # the padding is zeros
        keep = jnp.arange(cfg.expert_width_stored) < cfg.moe_intermediate_size
        w = jnp.where(keep[None, None, :] if name == "up_w" else keep[None, :, None], w, 0)
    return w


# ---------------------------------------------------------------------------
# the family's pure functions
# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps: float):
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (x32 * w.astype(jnp.float32)).astype(x.dtype)


def _split_in_proj(zxd, cfg):
    di, cw = cfg.d_inner, cfg.conv_width
    return zxd[..., :di], zxd[..., di:di + cw], zxd[..., di + cw:]


def _split_xbc(xbc, cfg):
    di, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state_size
    lead = xbc.shape[:-1]
    return (
        xbc[..., :di].reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim),
        xbc[..., di:di + gn].reshape(*lead, cfg.n_groups, cfg.ssm_state_size),
        xbc[..., di + gn:].reshape(*lead, cfg.n_groups, cfg.ssm_state_size),
    )


def _dt_and_a(dt_raw, l):
    f32 = jnp.float32
    dt = jax.nn.softplus(dt_raw.astype(f32) + l["dt_bias"].astype(f32))
    return dt, -jnp.exp(l["a_log"].astype(f32))


def _gate_norm_out(y, z, l, x, cfg):
    """``RMSNorm_groups(y * silu(z)) W_out`` added to the residual ``x``."""
    lead = y.shape[:-2]
    y = y.reshape(*lead, cfg.d_inner) * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(*lead, cfg.n_groups, cfg.d_inner // cfg.n_groups)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + cfg.norm_eps)
    y = (grouped.reshape(*lead, cfg.d_inner) * l["gate_norm"].astype(jnp.float32)).astype(x.dtype)
    return x + jnp.dot(y, l["out_w"], preferred_element_type=jnp.float32).astype(x.dtype)


def mamba_prefill(l, x, true_len, cfg):
    """One bucket-padded sequence ``x: (1, T, c)`` from a zero state."""
    with jax.named_scope("atpu_serve_ssm_in"):
        u = rmsnorm(x[0], l["norm"], cfg.norm_eps)
        z, xbc, dt_raw = _split_in_proj(jnp.dot(u, l["in_w"], preferred_element_type=jnp.float32), cfg)
    with jax.named_scope("atpu_serve_ssm_conv"):
        tail = ssm.conv_tail(xbc, true_len, cfg.conv_kernel)
        xs, b, c = _split_xbc(jax.nn.silu(ssm.causal_conv(xbc, l["conv_w"], l["conv_b"])), cfg)
    with jax.named_scope("atpu_serve_ssm_scan"):
        dt, a = _dt_and_a(dt_raw, l)
        # a recurrence has no mask: padding must not move the state
        dt = jnp.where((jnp.arange(x.shape[1]) < true_len)[:, None], dt, 0.0)
        y, state = ssm.ssd_chunked(xs, dt, a, b, c, l["d"], cfg.chunk_size)
    with jax.named_scope("atpu_serve_ssm_out"):
        return _gate_norm_out(y, z, l, x[0], cfg)[None], state, tail


def mamba_step(l, x, pools, i, live, cfg, mesh=None):
    """One token for every slot: ``x: (slots, 1, c)``; ``pools`` the state
    pools (``{"ssm", "conv"}``), ``i`` this layer's rank in them, ``live:
    (slots, 1)``.  The recurrence runs over the live slots alone, in place in
    the whole state pool, which comes back beside the new tail
    (``native/kernels/ssm_step.py``; imported here, at trace time).  A dead
    slot's state is left as it is and its ``y`` is zeros."""
    from ..native.kernels import ssm_step as kernel

    tail = pools["conv"][i]
    with jax.named_scope("atpu_serve_ssm_in"):
        u = rmsnorm(x[:, 0], l["norm"], cfg.norm_eps)
        z, xbc, dt_raw = _split_in_proj(jnp.dot(u, l["in_w"], preferred_element_type=jnp.float32), cfg)
    with jax.named_scope("atpu_serve_ssm_conv"):
        conv, tail = ssm.conv_step(tail, xbc, l["conv_w"], l["conv_b"])
        xs, b, c = _split_xbc(jax.nn.silu(conv), cfg)
    with jax.named_scope("atpu_serve_ssm_step"):
        dt, a = _dt_and_a(dt_raw, l)
        y, state = kernel.ssm_step_live(pools["ssm"], i, live[:, 0], xs, dt, a, b, c, l["d"], mesh=mesh)
    with jax.named_scope("atpu_serve_ssm_out"):
        return _gate_norm_out(y, z, l, x[:, 0], cfg)[:, None], state, tail


def experts_ffn(l, x, valid, cfg):
    """``x + held experts' part + shared expert``; ``x: (b, s, c)``.  The load
    is the tokens each held expert got, then how many of them got any."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    with jax.named_scope("atpu_serve_moe_route"):
        u = rmsnorm(flat, l["norm"], cfg.norm_eps)
        chosen, weights = route_sigmoid_topk(
            u, l["router_w"], l["router_bias"],
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        )
    with jax.named_scope("atpu_serve_moe_experts"):
        routed, sizes = held_experts_apply(
            u, chosen, weights, l["up_w"], l["down_w"],
            expert_offset=cfg.expert_offset, valid=valid.reshape(-1),
        )
    with jax.named_scope("atpu_serve_moe_shared"):
        y = routed + shared_expert_ffn(u, l["shared_up_w"], l["shared_down_w"])
        load = jnp.concatenate([sizes, jnp.sum(sizes > 0, dtype=jnp.int32)[None]])
        return (flat + y.astype(x.dtype)).reshape(shape), load


def _embed(g, ids, positions, cfg):
    return g["embed"][ids]  # no positional term anywhere in this family


def _attn_in(l, x, positions, cfg):
    b, s, _ = x.shape
    nq, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    qkv = jnp.dot(rmsnorm(x, l["norm"], cfg.norm_eps), l["qkv_w"],
                  preferred_element_type=jnp.float32).astype(x.dtype)
    q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
    heads = lambda t, n: t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
    return heads(q, nq), heads(k, nkv), heads(v, nkv)


def _attn_out(l, x, att, cfg):
    b, s, _ = x.shape
    att = att.transpose(0, 2, 1, 3).reshape(b, s, -1).astype(x.dtype)
    return x + jnp.dot(att, l["o_w"], preferred_element_type=jnp.float32).astype(x.dtype)


def _finalize(g, x, cfg):
    h = rmsnorm(x[:, -1], g["norm_f"], cfg.norm_eps)
    return jnp.dot(h, g["head"].T, preferred_element_type=jnp.float32)


def _make_decoder():
    from .generation import DecoderFamily

    return DecoderFamily(
        embed=_embed, attn_in=_attn_in, attn_out=_attn_out, finalize=_finalize,
        plan=lambda cfg: cfg.kinds,
        recurrent_prefill=mamba_prefill, recurrent_step=mamba_step,
        recurrent_scopes=("atpu_serve_ssm_scan", "atpu_serve_ssm_step"), ffn=experts_ffn,
    )


NEMOTRON_H_DECODER = _make_decoder()
