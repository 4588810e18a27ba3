"""Mixture-of-Experts feed-forward with expert parallelism over ``ep``.

The reference has no MoE layer at all — only a DeepSpeed leaf-module hint
(reference accelerator.py:1881, SURVEY.md §2.2 row EP) — so this is new
capability, built the TPU way (GShard/Switch formulation):

* routing, dispatch and combine are DENSE one-hot einsums over static shapes
  (tokens × experts × capacity) — no gathers, no dynamic shapes, everything
  tiles onto the MXU and ``jit`` sees one fixed program;
* the stacked expert weights carry a leading expert axis that the sharding
  planner lays on the ``ep`` mesh axis (see ``tp_plan`` entries in models
  using the layer); GSPMD then inserts the all_to_all pair around the expert
  computation — the manual NCCL alltoall of GPU MoE stacks is compiled in;
* tokens beyond an expert's capacity are dropped (their combine weight is
  zero and the residual stream carries them unchanged) — Switch semantics;
* the load-balancing auxiliary loss (Switch eq. 4: E · Σ_e f_e · P_e) is
  stashed on the module as ``last_aux_loss`` after every forward; training
  loops (e.g. models/gpt.py) add it into the objective with a small weight.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import init
from .module import Module, Parameter
from .tape import Tensor, tape_op


def _switch_moe_forward(
    x,  # (tokens, d_model)
    router_w,  # (E, d_model)
    router_b,  # (E,)
    w_in,  # (E, d_ff, d_model)
    b_in,  # (E, d_ff)
    w_out,  # (E, d_model, d_ff)
    b_out,  # (E, d_model)
    *,
    capacity: int,
    top_k: int,
):
    """Dense Switch/top-k MoE over flattened tokens. Returns y."""
    g, d = x.shape
    E = router_w.shape[0]

    logits = x @ router_w.T + router_b  # (g, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    combine = jnp.zeros((g, E, capacity), dtype=jnp.float32)
    remaining = probs
    # per-expert slot counters evolve as each top-k choice claims capacity
    fill = jnp.zeros((E,), dtype=jnp.int32)
    for _ in range(top_k):
        choice = jnp.argmax(remaining, axis=-1)  # (g,)
        gate = jnp.take_along_axis(remaining, choice[:, None], axis=-1)[:, 0]
        onehot = jax.nn.one_hot(choice, E, dtype=jnp.float32)  # (g, E)
        # position of each token within its chosen expert's buffer
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (g, E)
        pos = jnp.sum(pos_in_expert, axis=-1) + jnp.take(fill, choice)  # (g,)
        keep = pos < capacity
        slot = jax.nn.one_hot(
            jnp.where(keep, pos, capacity).astype(jnp.int32),
            capacity + 1,
            dtype=jnp.float32,
        )[:, :capacity]  # (g, capacity); dropped tokens hit the phantom slot
        combine = combine + (gate * keep)[:, None, None] * (
            onehot[:, :, None] * slot[:, None, :]
        )
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)  # next choice excludes this one

    dispatch = (combine > 0.0).astype(x.dtype)  # (g, E, capacity)

    # all_to_all pair happens here under GSPMD when w_in/w_out are ep-sharded
    expert_in = jnp.einsum("gec,gd->ecd", dispatch, x)  # (E, capacity, d)
    h = jnp.einsum("ecd,efd->ecf", expert_in, w_in) + b_in[:, None, :]
    h = jax.nn.gelu(h, approximate=True)
    expert_out = jnp.einsum("ecf,edf->ecd", h, w_out) + b_out[:, None, :]
    return jnp.einsum("gec,ecd->gd", combine.astype(x.dtype), expert_out)


def _switch_aux_loss(x, router_w, router_b):
    """Switch load-balancing loss (eq. 4): E · Σ_e f_e · P_e.

    Recomputes the (cheap) router probs so it can live in its own tape op —
    grads w.r.t. the router flow from both the gates (main path) and here.
    """
    E = router_w.shape[0]
    logits = x.reshape(-1, x.shape[-1]) @ router_w.T + router_b
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1), E, dtype=jnp.float32)
    f = top1.mean(axis=0)
    p = probs.mean(axis=0)
    return E * jnp.sum(f * p)


class MixtureOfExperts(Module):
    """Drop-in MoE replacement for an MLP block (Switch top-1 / top-2).

    Stacked expert weights ``w_in/w_out`` carry the leading expert axis —
    shard it over ``ep`` via the owning model's ``tp_plan`` (e.g.
    ``r".*moe\\.w_in": ("ep", None, None)``).
    """

    def __init__(
        self,
        d_model: int,
        d_ff: int,
        num_experts: int,
        top_k: int = 1,
        capacity_factor: float = 1.25,
        dropout: float = 0.0,
        dtype=jnp.float32,
    ):
        super().__init__()
        if top_k not in (1, 2):
            raise ValueError(f"top_k must be 1 or 2, got {top_k}")
        from .layers import Dropout

        self.dropout = Dropout(dropout)
        self.d_model = d_model
        self.d_ff = d_ff
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        bound_in = 1.0 / math.sqrt(d_model)
        bound_out = 1.0 / math.sqrt(d_ff)
        self.router = Parameter(init.uniform((num_experts, d_model), bound_in, dtype))
        self.router_bias = Parameter(init.zeros((num_experts,), dtype))
        self.w_in = Parameter(init.uniform((num_experts, d_ff, d_model), bound_in, dtype))
        self.b_in = Parameter(init.zeros((num_experts, d_ff), dtype))
        self.w_out = Parameter(init.uniform((num_experts, d_model, d_ff), bound_out, dtype))
        self.b_out = Parameter(init.zeros((num_experts, d_model), dtype))
        self.last_aux_loss: Optional[Tensor] = None

    def capacity(self, tokens: int) -> int:
        cap = int(math.ceil(tokens * self.top_k / self.num_experts * self.capacity_factor))
        return max(cap, self.top_k)

    def forward(self, x):
        xv = x.data if isinstance(x, Tensor) else jnp.asarray(x)
        # GShard-style routing groups: route independently per leading-axis
        # group (sequence row) so capacity — and with it the (tokens, E,
        # capacity) dispatch tensors — stays CONSTANT per group instead of
        # scaling with the global batch (O(tokens) total memory, not O(g²))
        group_tokens = xv.shape[-2] if xv.ndim >= 3 else xv.shape[0]
        cap = self.capacity(int(group_tokens))

        def _moe(v, rw, rb, wi, bi, wo, bo):
            def one_group(t):
                return _switch_moe_forward(
                    t, rw, rb, wi, bi, wo, bo, capacity=cap, top_k=self.top_k
                )

            if v.ndim == 2:
                return one_group(v)
            groups = v.reshape(-1, v.shape[-2], v.shape[-1])
            return jax.vmap(one_group)(groups).reshape(v.shape)

        y = tape_op(
            _moe, x, self.router, self.router_bias,
            self.w_in, self.b_in, self.w_out, self.b_out,
        )
        self.last_aux_loss = tape_op(
            _switch_aux_loss, x, self.router, self.router_bias
        )
        return self.dropout(y)

    def __repr__(self):
        return (
            f"MixtureOfExperts(d_model={self.d_model}, d_ff={self.d_ff}, "
            f"experts={self.num_experts}, top_k={self.top_k})"
        )


# ---------------------------------------------------------------------------
# the share of an expert layer that one chip holds (dropless, sigmoid router)
# ---------------------------------------------------------------------------
def route_sigmoid_topk(x, router_w, correction_bias, *, top_k: int, scale: float):
    """DeepSeek-V3 / Nemotron-H routing over ALL experts, in float32.

    ``s = sigmoid(x W_r^T)``; the ``top_k`` experts of ``s + correction_bias``
    are chosen; their weights are ``s`` (without the bias) divided by their sum
    and multiplied by ``scale``.  ``x: (T, d)``, ``router_w: (E, d)``.
    Returns ``(chosen (T, k) int32, weights (T, k) float32)``."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", x.astype(f32), router_w.astype(f32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, chosen = jax.lax.top_k(s + correction_bias.astype(f32)[None], top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True) * scale


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def held_experts_apply(x, chosen, weights, w_up, w_down, *, expert_offset: int = 0, valid=None):
    """The weighted outputs of the experts held HERE (``expert_offset ..
    expert_offset + E_held - 1``; ``w_up: (E_held, d, f)``, ``w_down:
    (E_held, f, d)``; an expert is not gated: ``w_down relu(w_up u)^2``),
    summed per token.  A pick of an expert held elsewhere, or of a token that
    ``valid: (T,)`` leaves out (padding, a dead slot), adds nothing.  No
    capacity and no drop, whatever the router does: every held expert
    multiplies every token in one batched product, and the picks' weights
    choose among the results.

    A chip reads an expert's weights in the time it multiplies some 500 rows
    by them (TPU v5e: 197 TFLOP/s over 819 GB/s is 240 FLOP a byte), so for a
    decode step and for a prefill bucket the rows no pick asked for cost no
    more than the weights' bytes; the compiler's grouped product over the
    sorted picks (``jax.lax.ragged_dot``) was 4-5x off those bytes on the
    chip (PERF.md, PR 31).  The temporaries are ``T * E_held * (f + d)``
    float32 numbers: split longer inputs before the call.

    Returns ``(y (T, d) float32, tokens each held expert got (E_held,) int32)``."""
    f32 = jnp.float32
    held = w_up.shape[0]
    local = chosen - expert_offset
    here = (local >= 0) & (local < held)
    if valid is not None:
        here = here & valid[:, None]
    group = jnp.where(here, local, held)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group.reshape(-1)].add(1)[:held]
    combine = jnp.sum(
        jnp.where(here, weights, 0.0)[..., None] * jax.nn.one_hot(group, held, dtype=f32), axis=1
    )  # (T, E_held)
    h = relu2(jnp.einsum("td,edf->etf", x, w_up, preferred_element_type=f32)).astype(x.dtype)
    out = jnp.einsum("etf,efd->etd", h, w_down, preferred_element_type=f32)
    return jnp.sum(out * combine.T[:, :, None], axis=0), sizes


def held_experts_ffn(x, router_w, correction_bias, w_up, w_down, *, top_k: int,
                     scale: float, expert_offset: int = 0, valid=None):
    """The routed part of an expert layer that THIS chip's experts give.

    The router is as wide as the model has experts; ``w_up`` and ``w_down``
    are the experts held here.  A pick of an expert held elsewhere adds
    nothing here — that chip adds it, and the partial sums are added across
    the chips that share the layer.  Returns ``held_experts_apply``'s pair."""
    chosen, weights = route_sigmoid_topk(
        x, router_w, correction_bias, top_k=top_k, scale=scale
    )
    return held_experts_apply(x, chosen, weights, w_up, w_down, expert_offset=expert_offset, valid=valid)


def shared_expert_ffn(x, w_up, w_down):
    """The expert every token meets, whole on every chip: ``w_up: (d, f)``,
    ``w_down: (f, d)``, ``relu^2`` between; float32 out."""
    h = relu2(jnp.dot(x, w_up, preferred_element_type=jnp.float32)).astype(x.dtype)
    return jnp.dot(h, w_down, preferred_element_type=jnp.float32)
