"""Operations and bytes the algorithm needs, from the configuration's shapes.

The per-layer metrics divide these by measured device time. Bytes count
what a kernel must move at the least (each operand read once, each
result written once); operations count multiply-adds as two.
"""
from __future__ import annotations

CODE_BYTES = 2          # a 16-bit code lives in a uint16 container
ACT_BYTES = 2           # bfloat16 activations
F32 = 4


def matmul_shapes(cfg: dict) -> list[tuple[int, int, int]]:
    """``(K, N, x_bytes)`` of every dense product one token passes
    through, in one decode or prefill step: seven per layer (q, k, v,
    o, gate, up, down) and the tied unembedding, whose input is float32."""
    d, F, V = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    hq, hkv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv"] * cfg["head_dim"]
    layer = [(d, hq), (d, hkv), (d, hkv), (hq, d), (d, F), (d, F), (F, d)]
    return ([(k, n, ACT_BYTES) for k, n in layer] * cfg["n_layers"]
            + [(d, V, F32)])


def dequant_matmul_cost(M: int, K: int, N: int, x_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one fused dequant-matmul call: codes K x N,
    input M x K, float32 output M x N."""
    return 2.0 * M * K * N, float(K * N * CODE_BYTES + M * K * x_bytes + M * N * F32)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position in every layer, bfloat16."""
    return 2 * cfg["n_layers"] * cfg["n_kv"] * cfg["head_dim"] * ACT_BYTES


def decode_attention_bytes(cfg: dict, q_pos: int) -> int:
    """Live cache bytes one decode query at position ``q_pos`` must read:
    positions 0..q_pos of K and V in every layer."""
    return (q_pos + 1) * kv_bytes_per_position(cfg)


def plane_or_bytes(n_elements: int) -> int:
    """One eq.-(4) OR over the flat accumulator: read it and the plane,
    write it back (uint16 each)."""
    return 3 * n_elements * CODE_BYTES


def token_flops(cfg: dict, position: int) -> float:
    """Model operations to process one token at ``position`` (it attends
    to ``position + 1`` keys): every dense product, the unembedding,
    and the score and value products of attention."""
    dense = sum(2.0 * k * n for k, n, _ in matmul_shapes(cfg))
    attn = 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * (position + 1)
    return dense + attn


def sequence_flops(cfg: dict, start: int, stop: int) -> float:
    """``token_flops`` summed over positions ``start .. stop - 1``."""
    n = max(0, stop - start)
    if not n:
        return 0.0
    dense = sum(2.0 * k * n_ for k, n_, _ in matmul_shapes(cfg))
    pos_sum = (start + stop - 1) * n / 2.0 + n   # sum of (p + 1)
    return n * dense + 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * pos_sum
