"""Fused dequantize-matmul Pallas TPU kernel.

The TPU-native form of the paper's steps 3+4: weights stay in HBM as
k-bit unsigned integers (the receiver's plane accumulator), and eq. (5)
is applied *in VMEM, per tile, on the way into the MXU*:

    y = x @ (span * q / 2^k + lo + span / 2^{m+1})
      = x @ (scale * q + offset)

So the model is never materialized in floating point in HBM: resident
weight bytes are ``k/16``x smaller than bf16 and a precision upgrade
(another plane OR-ed into ``q``) changes *values only* — same buffer,
same executable. ``scale``/``offset`` are *traced* (1, 1) operands
(computed outside by :func:`repro.core.quantize.dequant_affine` from
(lo, hi, bits, received_bits)); nothing about the received precision is
baked into the executable, so a consumer jitted around this call keeps
exactly one compilation across every precision upgrade.

Tiling: grid (M/bm, N/bn, K/bk) with K innermost; a fp32 accumulator
tile lives in VMEM scratch across the K sweep. Block shapes default to
MXU-aligned (128, 128) tiles (512 in K for bandwidth); the uint16 weight
tile (bk x bn) is dequantized in-register (VPU) then fed to the MXU.

Sharding: the kernel itself is single-device; multi-device serving
shards ``q`` on N only (never K — the fp32 accumulation order across
the K sweep is part of the bit-exactness contract, and a sharded K
would turn it into partial sums + an all-reduce). Per-shard launches go
through :func:`repro.kernels.ops.sharded_dequant_matmul` (shard_map,
one launch per shard on its own (K, N/n) columns) or the engines'
jit-with-shardings path; each shard's call is exactly this kernel on
its local columns, so per-stage outputs match single-device bit for
bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, q_ref, scale_ref, off_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output tile; K swept by the innermost grid dim."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    scale = scale_ref[0, 0]
    off = off_ref[0, 0]
    # eq. (5) on the weight tile, in-register: uint -> fp32 affine.
    # Mosaic has no unsigned -> float cast, so widen to int32 first:
    # exact for the uint8/uint16 containers (k <= 16); uint32 codes at
    # or above 2^31 would wrap negative.
    w = q_ref[...].astype(jnp.int32).astype(jnp.float32) * scale + off
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret", "out_dtype"),
)
def dequant_matmul(
    x: jax.Array,            # (M, K) float
    q: jax.Array,            # (K, N) uint8/uint16/uint32
    scale: jax.Array,        # traced eq.-(5) slope; scalar or (1, 1) f32
    offset: jax.Array,       # traced eq.-(5) intercept; scalar or (1, 1) f32
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """y = x @ (scale * q + offset) without materializing the fp weight.

    ``scale``/``offset`` come from
    :func:`repro.core.quantize.dequant_affine` — they are plain traced
    operands, NOT static arguments, so a precision upgrade (new
    received_bits -> new affine values) re-runs the same executable.
    """
    M, K = x.shape
    K2, N = q.shape
    assert K == K2, (x.shape, q.shape)
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    off = jnp.asarray(offset, jnp.float32).reshape(1, 1)

    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    # pad to tile multiples (host-side; cheap relative to the matmul)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        q = jnp.pad(q, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    n_k = Kp // bk

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        # fp32 accumulator tile persists across the K sweep
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scale, off)
    return out[:M, :N]
