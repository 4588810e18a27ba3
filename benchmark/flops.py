"""Operations and bytes the algorithm needs, from shapes alone.  Kept with the
benchmark so that no later change to the program can move the yardstick.

Counting rules: one multiply-add is 2 operations; causal attention is counted
at half of the full square; recomputation (rematerialised layers, the flash
backward's second look at the scores) is never counted; the vocabulary is the
published one, not the padded table.
"""

from __future__ import annotations

import json
import os

BF16 = 2
F32 = 4


def load_peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``.  A kind that is not in the table is
    an error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(k for k in table if not k.startswith('_'))})"
        )
    return table[device_kind]


# -- GPT-2 family -------------------------------------------------------------
def param_count(cfg: dict) -> int:
    """Every stored parameter (padded vocabulary table, positions, biases,
    LayerNorms); the tied head counts once."""
    e, n_layer = cfg["n_embd"], cfg["n_layer"]
    per_layer = 12 * e * e + 13 * e  # 4 matrices, their 4 biases, 2 LayerNorms
    return cfg["vocab_rows"] * e + cfg["n_positions"] * e + n_layer * per_layer + 2 * e


def matmul_params(cfg: dict) -> int:
    """Parameters that sit in matrix products: the blocks' four matrices and
    the tied head once, at the published vocabulary.  No position table, bias
    or LayerNorm."""
    e = cfg["n_embd"]
    return cfg["n_layer"] * 12 * e * e + cfg["vocab_size"] * e


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 N for the products with weights (forward 2, backward 4) plus causal
    attention's 6 L S d."""
    return 6.0 * matmul_params(cfg) + 6.0 * cfg["n_layer"] * seq_len * cfg["n_embd"]


def forward_flops_per_token(cfg: dict) -> float:
    """2 N: serving's products with weights (attention over the cache is left
    out, so the share of peak is counted low, never high)."""
    return 2.0 * matmul_params(cfg)


def flash_fwd_cost(rows: int, n_head: int, seq_len: int, head_dim: int) -> tuple:
    """(operations, bytes) of one causal flash-attention forward call: QK^T and
    PV over the lower triangle; q, k, v read and o written once in bfloat16,
    the row log-sum-exp written in float32."""
    ops = 2.0 * rows * n_head * seq_len * seq_len * head_dim
    nbytes = 4.0 * rows * n_head * seq_len * head_dim * BF16 + rows * n_head * seq_len * F32
    return ops, nbytes


def flash_bwd_cost(rows: int, n_head: int, seq_len: int, head_dim: int) -> tuple:
    """(operations, bytes) of one causal flash-attention backward call: dV, dP,
    dQ and dK over the lower triangle (the recomputed scores are not counted);
    q, k, v, o, do read and dq, dk, dv written once in bfloat16."""
    ops = 4.0 * rows * n_head * seq_len * seq_len * head_dim
    nbytes = 8.0 * rows * n_head * seq_len * head_dim * BF16 + rows * n_head * seq_len * F32
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """Least time the chip could take, and which of the two bounds it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one token over all layers, bfloat16."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * BF16


def decode_step_bytes(cfg: dict, live_kv_tokens: float) -> float:
    """Bytes one decode step must read: every weight once in bfloat16 (the
    position table's used rows are negligible and left out) and the keys and
    values of the tokens the active slots hold."""
    weights = (param_count(cfg) - cfg["n_positions"] * cfg["n_embd"]) * BF16
    return weights + live_kv_tokens * kv_bytes_per_token(cfg)
