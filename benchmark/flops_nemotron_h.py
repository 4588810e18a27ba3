"""Operations and bytes the Nemotron-H algorithm needs, from shapes and counts
alone.  Kept with the benchmark so that no later change to the program can move
the yardstick.

Counting rules (``flops.py``'s): one multiply-add is 2 operations; attention
over the cache is left out of the per-token FLOPs (a share of peak is counted
low, never high); recomputation and padding are never counted — the routed
experts are counted at the published width (1856), not the stored 1920, and a
product that runs in several bfloat16 passes counts once.
"""

from __future__ import annotations

BF16 = 2
F32 = 4
KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}


def counts(cfg: dict) -> dict:
    """How many layers of each kind the configuration runs."""
    return {kind: cfg["hybrid_override_pattern"].count(ch) for ch, kind in KINDS.items()}


def _sizes(cfg: dict) -> dict:
    h, p, g, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "d": cfg["hidden_size"], "h": h, "p": p, "g": g, "n": n, "di": h * p, "cw": h * p + 2 * g * n,
        "qd": cfg["num_attention_heads"] * cfg["head_dim"],
        "kvd": cfg["num_key_value_heads"] * cfg["head_dim"],
        "f": cfg["moe_intermediate_size"], "fs": cfg["moe_shared_expert_intermediate_size"],
        "wide": cfg.get("router_width", cfg["n_routed_experts"]), "v": cfg["vocab_size"],
    }


def layer_matmul_params(cfg: dict) -> dict:
    """Parameters in matrix products that EVERY token meets, per layer of each
    kind (the routed experts are not among them) and the head."""
    s = _sizes(cfg)
    return {
        "mamba2": s["d"] * (s["di"] + s["cw"] + s["h"]) + s["di"] * s["d"],
        "attention": s["d"] * (s["qd"] + 2 * s["kvd"]) + s["qd"] * s["d"],
        "experts": s["wide"] * s["d"] + 2 * s["d"] * s["fs"],  # router and shared expert
        "head": s["v"] * s["d"],
    }


def expert_params(cfg: dict) -> int:
    """One routed expert: up and down at the published width."""
    s = _sizes(cfg)
    return 2 * s["d"] * s["f"]


def token_flops(cfg: dict) -> float:
    """Forward FLOPs of one token through every layer, without the routed
    experts and the head: 2 per parameter it meets, the convolution's taps, and
    the state's update and read-out (2 + 2 per state element)."""
    s, n, per = _sizes(cfg), counts(cfg), layer_matmul_params(cfg)
    recurrence = 2.0 * s["cw"] * cfg["conv_kernel"] + 4.0 * s["h"] * s["p"] * s["n"]
    return sum(2.0 * per[k] * n[k] for k in n) + recurrence * n["mamba2"]


def head_flops(cfg: dict) -> float:
    return 2.0 * layer_matmul_params(cfg)["head"]


def expert_token_flops(cfg: dict) -> float:
    """One token through one routed expert."""
    return 2.0 * expert_params(cfg)


def state_bytes_per_slot(cfg: dict) -> float:
    """One slot's recurrent state over all Mamba layers: S in float32 and the
    convolution tail in bfloat16."""
    s = _sizes(cfg)
    tail = (cfg["conv_kernel"] - 1) * s["cw"] * BF16
    return counts(cfg)["mamba2"] * (s["h"] * s["p"] * s["n"] * F32 + tail)


def kv_bytes_per_token(cfg: dict) -> float:
    return 2.0 * counts(cfg)["attention"] * _sizes(cfg)["kvd"] * BF16


def fixed_weight_bytes(cfg: dict) -> float:
    """Weights every decode step reads whatever the router does: the mixers,
    the routers, the shared experts and the head, bfloat16 (norms, biases and
    the token table's used rows are negligible and left out)."""
    n, per = counts(cfg), layer_matmul_params(cfg)
    return BF16 * (sum(per[k] * n[k] for k in n) + per["head"])


def decode_step_bytes(cfg: dict, live_slots: float, experts_touched: float,
                      live_kv_tokens: float) -> float:
    """Bytes one decode step must move: the fixed weights once, the routed
    experts that got a token (``experts_touched``: (layer, expert) pairs), the
    live slots' state read and written, and the live keys and values."""
    return (
        fixed_weight_bytes(cfg) + experts_touched * expert_params(cfg) * BF16
        + 2.0 * live_slots * state_bytes_per_slot(cfg) + live_kv_tokens * kv_bytes_per_token(cfg)
    )


def ssm_step_cost(cfg: dict, live_slots: float) -> tuple:
    """(operations, bytes) of the one-token recurrence over the live slots in
    all Mamba layers: the state read and written, x, B and C read, y written."""
    s, layers = _sizes(cfg), counts(cfg)["mamba2"]
    state = s["h"] * s["p"] * s["n"]
    ops = 4.0 * state
    nbytes = 2.0 * state * F32 + (2 * s["di"] + 2 * s["g"] * s["n"]) * F32
    return layers * live_slots * ops, layers * live_slots * nbytes


def moe_experts_cost(cfg: dict, expert_tokens: float, experts_touched: float) -> tuple:
    """(operations, bytes) of the routed experts' grouped products in one
    execution: every touched expert's weights once; the rows in and out."""
    s = _sizes(cfg)
    ops = expert_tokens * expert_token_flops(cfg)
    nbytes = experts_touched * expert_params(cfg) * BF16 + expert_tokens * 2 * s["d"] * BF16
    return ops, nbytes


def ssd_prefill_cost(cfg: dict, bucket_len: int) -> tuple:
    """(operations, bytes) of the chunked scan over one bucket in all Mamba
    layers: per chunk C B^T, its masked product with x, what the chunk adds to
    the state and what the incoming state adds to its outputs; x, B, C, dt
    read and y written in float32, the final state written."""
    s, layers, q = _sizes(cfg), counts(cfg)["mamba2"], cfg["chunk_size"]
    chunks = bucket_len / q
    state = s["h"] * s["p"] * s["n"]
    ops = chunks * (2.0 * s["g"] * q * q * s["n"] + 2.0 * s["h"] * q * q * s["p"] + 4.0 * q * state)
    nbytes = bucket_len * (2 * s["di"] + 2 * s["g"] * s["n"] + s["h"]) * F32 + state * F32
    return layers * ops, layers * nbytes
