"""Bytes and operations the algorithm needs, from the seven header sizes.
Counted from shapes, never measured: a share of a peak divides these by a
time taken from the device trace."""

from __future__ import annotations

Q40_BLOCK_BYTES = 18   # 16 bytes of nibbles + an f16 delta per 32 values
Q40_BLOCK = 32


def matmul_params(sizes: dict) -> dict:
    """Matmul weight elements: per layer, and the classifier."""
    d, h = sizes["dim"], sizes["hidden_dim"]
    kv = d * sizes["n_kv_heads"] // sizes["n_heads"]
    return {"layer": 2 * d * d + 2 * kv * d + 3 * h * d,
            "wcls": sizes["vocab_size"] * d}


def q40_weight_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of every matmul weight: what one decode step must
    read from HBM whatever its batch (each weight once)."""
    p = matmul_params(sizes)
    n = sizes["n_layers"] * p["layer"] + p["wcls"]
    return n // Q40_BLOCK * Q40_BLOCK_BYTES


def kv_bytes_per_position(sizes: dict, kv_bytes: int = 4) -> int:
    kv = sizes["dim"] * sizes["n_kv_heads"] // sizes["n_heads"]
    return 2 * kv * kv_bytes * sizes["n_layers"]


def decode_step_bytes(sizes: dict, context: int = 0, rows: int = 1,
                      chips: int = 1) -> float:
    """HBM bytes one decode step must move on ONE chip: its share of the
    weights, plus each row's keys and values up to ``context``."""
    return (q40_weight_bytes(sizes)
            + rows * context * kv_bytes_per_position(sizes)) / chips


def flops_per_token(sizes: dict, context: int = 0) -> int:
    """Multiply-adds counted as two: every matmul weight once, plus scores
    and the weighted sum over ``context`` positions."""
    p = matmul_params(sizes)
    mm = 2 * (sizes["n_layers"] * p["layer"] + p["wcls"])
    attn = 4 * context * sizes["dim"] * sizes["n_layers"]
    return mm + attn
