"""Operations and bytes that each phase and kernel requires, from shapes.

Every count is of the work the algorithm needs at the precision the
configuration states (bf16 operands and activations): no tile padding,
no recomputation, and no auxiliary operand such as an all-zero entropy
array.  ``m`` is a configuration's ``model`` block (bench/configs/*.json).
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(m: dict) -> int:
    """Weights of one layer's matmuls: fused q/k/v, output, gated FFN."""
    d, hd = m["d_model"], m["head_dim"]
    qkv = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd
    o = m["n_heads"] * hd * d
    ffn = d * 2 * m["d_ff"] + m["d_ff"] * d
    return qkv + o + ffn


def matmul_params(m: dict) -> int:
    """N of the PaLM count: every matmul weight, the LM head once (tied or
    not), the embedding lookup not at all."""
    return m["n_layers"] * layer_matmul_params(m) + m["vocab_size"] * m["d_model"]


def small_params(m: dict) -> int:
    """Parameters outside the matmuls that every token reads: norm scales
    and q/k/v biases (the embedding rows a token looks up are not
    counted)."""
    d = m["d_model"]
    per_layer = 0
    if m["norm"] != "nonparametric_ln":
        per_layer += 2 * d
    if m["qkv_bias"]:
        per_layer += (m["n_heads"] + 2 * m["n_kv_heads"]) * m["head_dim"]
    final = d if m["norm"] != "nonparametric_ln" else 0
    return m["n_layers"] * per_layer + final


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """PaLM (arXiv:2204.02311, App. B): 6 N + 12 L H Q T per token."""
    attn = 12 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seq_len
    return 6.0 * matmul_params(m) + attn


def kv_bytes_per_token(m: dict) -> int:
    """bf16 K and V of one position over all layers."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * BF16


def prefill_chunk_flops(m: dict, pos0: int, n_tokens: int) -> float:
    """Forward FLOPs of one prompt chunk of `n_tokens` starting at
    position `pos0`: the weight matmuls plus causal attention over the
    live positions only (QK^T and PV), and the head on the chunk's last
    position, which is all the engine reads."""
    n_layer = m["n_layers"] * layer_matmul_params(m)
    live = n_tokens * pos0 + n_tokens * (n_tokens + 1) // 2
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * live
    head = 2 * m["vocab_size"] * m["d_model"]
    return 2.0 * n_layer * n_tokens + attn + head


def decode_step_bytes(m: dict, live_positions: int, active_rows: int) -> float:
    """Least HBM bytes of one masked decode step: every bf16 weight read
    once, the K/V of the live positions of the active rows read, and one
    new K/V position written per active row."""
    weights = (matmul_params(m) + small_params(m)) * BF16
    kv = kv_bytes_per_token(m)
    return float(weights + kv * live_positions + kv * active_rows)


def matmul_cost(mm: int, kk: int, nn: int) -> tuple:
    """(FLOPs, bytes) of (mm x kk) @ (kk x nn) with bf16 operands and a
    bf16 result: the FF and BP passes of ``sr_matmul``."""
    return 2.0 * mm * kk * nn, float(BF16 * (mm * kk + kk * nn + mm * nn))


def outer_cost(tt: int, dd: int, ff: int) -> tuple:
    """(FLOPs, bytes) of dW = X^T dY, X (tt x dd), dY (tt x ff), bf16 in
    and out: the UP pass of ``outer_accum``."""
    return 2.0 * tt * dd * ff, float(BF16 * (tt * dd + tt * ff + dd * ff))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least time, which bound) of work on a chip with `peaks`."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
