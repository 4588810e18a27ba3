"""Operations and bytes the Olmo-Hybrid algorithm needs, from shapes and counts
alone.  Kept with the benchmark so that no later change to the program can move
the yardstick.

Counting rules (``flops.py``'s): one multiply-add is 2 operations; attention
over the cache is left out of the per-token FLOPs (a share of peak is counted
low, never high); recomputation, padding and the passes a float32 product takes
on the matrix unit are never counted.
"""

from __future__ import annotations

import math

BF16 = 2
F32 = 4
LINEAR, FULL = "linear_attention", "full_attention"


def counts(cfg: dict) -> dict:
    """How many layers of each kind the configuration runs."""
    return {kind: list(cfg["layer_types"]).count(kind) for kind in (LINEAR, FULL)}


def _sizes(cfg: dict) -> dict:
    h, dk, dv = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {
        "d": cfg["hidden_size"], "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
        "h": h, "dk": dk, "dv": dv, "kw": h * dk, "vw": h * dv, "cw": 2 * h * dk + h * dv,
        "taps": cfg["linear_conv_kernel_dim"],
    }


def layer_matmul_params(cfg: dict) -> dict:
    """Parameters in matrix products that every token meets, per layer of each
    kind (its MLP among them), and the head."""
    s = _sizes(cfg)
    mlp = 3 * s["d"] * s["f"]
    return {
        LINEAR: s["d"] * (2 * s["kw"] + 2 * s["vw"] + 2 * s["h"]) + s["vw"] * s["d"] + mlp,
        FULL: 4 * s["d"] * s["d"] + mlp,
        "head": s["v"] * s["d"],
    }


def param_count(cfg: dict) -> int:
    """Every stored parameter: the products' matrices, the table, the
    convolution's taps, the norms' gains, ``A_log`` and ``dt_bias``."""
    s, n, per = _sizes(cfg), counts(cfg), layer_matmul_params(cfg)
    small = {
        LINEAR: s["cw"] * s["taps"] + 2 * s["h"] + s["dv"] + 2 * s["d"],
        FULL: 4 * s["d"],
    }
    return (sum((per[k] + small[k]) * n[k] for k in n)
            + 2 * per["head"] + s["d"])


def state_elements(cfg: dict) -> int:
    """One layer's recurrent state of one slot: heads x d_k x d_v."""
    s = _sizes(cfg)
    return s["h"] * s["dk"] * s["dv"]


def token_flops(cfg: dict) -> float:
    """Forward FLOPs of one token through every layer, without the head: 2 per
    parameter it meets, the convolution's taps, and the delta rule's one-token
    recurrence (per state element the decay and three multiply-adds: S^T k, the
    write, S^T q)."""
    s, n, per = _sizes(cfg), counts(cfg), layer_matmul_params(cfg)
    recurrence = 2.0 * s["cw"] * s["taps"] + 7.0 * state_elements(cfg)
    return sum(2.0 * per[k] * n[k] for k in n) + recurrence * n[LINEAR]


def head_flops(cfg: dict) -> float:
    return 2.0 * layer_matmul_params(cfg)["head"]


def state_bytes_per_slot(cfg: dict) -> float:
    """One slot's recurrent state over all linear layers: S in float32 and the
    convolution tail in bfloat16."""
    s = _sizes(cfg)
    tail = (s["taps"] - 1) * s["cw"] * BF16
    return counts(cfg)[LINEAR] * (state_elements(cfg) * F32 + tail)


def kv_bytes_per_token(cfg: dict) -> float:
    return 2.0 * counts(cfg)[FULL] * cfg["num_key_value_heads"] * (
        cfg["hidden_size"] // cfg["num_attention_heads"]) * BF16


def weight_bytes(cfg: dict) -> float:
    """Weights every decode step reads: every layer's matrices and the head,
    bfloat16 (the table's unread rows, the norms and the taps are left out)."""
    n, per = counts(cfg), layer_matmul_params(cfg)
    return BF16 * (sum(per[k] * n[k] for k in n) + per["head"])


def decode_step_bytes(cfg: dict, live_slots: float, live_kv_tokens: float) -> float:
    """Bytes one decode step must move: the weights once, the live slots' state
    read and written once, and the live keys and values."""
    return (weight_bytes(cfg) + 2.0 * live_slots * state_bytes_per_slot(cfg)
            + live_kv_tokens * kv_bytes_per_token(cfg))


def gdn_step_cost(cfg: dict, live_slots: float) -> tuple:
    """(operations, bytes) of the one-token recurrence over the live slots in
    all linear layers: the state read and written once; q, k, v, g, beta read
    and o written, float32."""
    s, layers = _sizes(cfg), counts(cfg)[LINEAR]
    ops = 7.0 * state_elements(cfg)
    nbytes = 2.0 * state_elements(cfg) * F32 + (2 * s["kw"] + 2 * s["vw"] + 2 * s["h"]) * F32
    return layers * live_slots * ops, layers * live_slots * nbytes


def gdn_prefill_cost(cfg: dict, bucket_len: int, chunk: int) -> tuple:
    """(operations, bytes) of the chunked scan over one bucket in all linear
    layers.  Per chunk of Q positions and head: K K^T and Q K^T (2 Q^2 d_k
    each), the forward substitution of the unit-lower-triangular system against
    d_v + d_k columns (Q^2 each), the writes' and the queries' products with
    the incoming state and the state's update (2 Q d_k d_v each), the chunk's
    own part of the outputs (2 Q^2 d_v).  q, k, v, g, beta read and o written
    in float32, the final state written."""
    s, layers = _sizes(cfg), counts(cfg)[LINEAR]
    q, dk, dv = chunk, s["dk"], s["dv"]
    per_chunk_head = 4.0 * q * q * dk + q * q * (dk + dv) + 6.0 * q * dk * dv + 2.0 * q * q * dv
    ops = math.ceil(bucket_len / chunk) * s["h"] * per_chunk_head
    nbytes = bucket_len * (2 * s["kw"] + 2 * s["vw"] + 2 * s["h"]) * F32 + state_elements(cfg) * F32
    return layers * ops, layers * nbytes
