"""Operations and bytes the algorithm needs, from the configuration's shapes.

The per-layer metrics divide these by measured device time. Bytes count
what a kernel must move at the least (each operand read once, each
result written once); operations count multiply-adds as two. What
depends on the block (its products, its cache, a token's operations)
is the configuration's architecture file's (``arch/``).
"""
from __future__ import annotations

from bench import arch

CODE_BYTES = 2          # a 16-bit code lives in a uint16 container
ACT_BYTES = 2           # bfloat16 activations
F32 = 4


def dequant_matmul_cost(M: int, K: int, N: int, x_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one fused dequant-matmul call: codes K x N,
    input M x K, float32 output M x N."""
    return 2.0 * M * K * N, float(K * N * CODE_BYTES + M * K * x_bytes + M * N * F32)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def kv_bytes_per_position(cfg: dict) -> int:
    """The cache bytes one position holds across the layers."""
    return arch.load(cfg).kv_bytes_per_position(cfg)


def decode_attention_bytes(cfg: dict, q_pos: int) -> int:
    """Live cache bytes one decode query at position ``q_pos`` must read:
    those of positions 0..q_pos."""
    return (q_pos + 1) * kv_bytes_per_position(cfg)


def plane_or_bytes(n_elements: int) -> int:
    """One eq.-(4) OR over the flat accumulator: read it and the plane,
    write it back (uint16 each)."""
    return 3 * n_elements * CODE_BYTES


def sequence_flops(cfg: dict, start: int, stop: int) -> float:
    """Model operations to process positions ``start .. stop - 1``."""
    return arch.load(cfg).sequence_flops(cfg, start, stop)
