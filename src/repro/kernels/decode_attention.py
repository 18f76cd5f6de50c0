"""Ragged batched flash decode-attention Pallas TPU kernel (one new
token per slot vs a long KV cache).

Decode at 32k–500k context is memory-bound: the whole KV cache crosses
HBM once per token while the MXU does a rank-1 sliver of work. The
kernel therefore optimizes for exactly one pass over K and V:

  grid = (B, Kh, S/bs); for each slot, KV-head and cache chunk, compute
  the (G, bs) score tile (G = query heads per KV head, padded to the
  8-row sublane), run the online-softmax update against VMEM scratch
  carries (m, l, acc), and emit the normalized (G, hd) output on the
  last chunk.

The batch is *ragged*: every slot carries its own query position
(``q_pos`` is ``(B,)``) and its own per-slot cache position vector
(``k_pos`` is ``(B, S)``; ring buffers pass their slot positions,
negative marks an empty/unwritten slot, and a fully negative row marks
a free slot of a continuous-batching pool). Full caches,
partially-filled caches, sliding-window ring caches and empty pool
slots all use the same kernel — which is what lets a slot-pool serving
engine run requests at wildly different positions in ONE launch.

K and V arrive in the kernel's native ``(B, Kh, S, hd)`` layout — the
same layout the model's KV caches are stored in — so the wrapper
performs no transpose and, for any reasonably-sized cache, no
sequence-axis padding: the hot decode loop touches each cache byte
exactly once. (When S doesn't divide by the block size the block
shrinks to a divisor; only a divisor-hostile S — prime-ish lengths —
falls back to padding the tail block with masked keys. Keep cache
lengths multiples of the block size — 512 by default — for peak TPU
efficiency.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pick_block(S: int, bs: int) -> int:
    """Choose a sequence block size for a cache of length S: S itself
    when it fits in one block, else the largest *sublane-aligned*
    (multiple-of-8) divisor of S that is <= bs. Returns 0 when no
    aligned divisor of useful size exists (caller pads instead)."""
    if S <= bs:
        return S
    for d in range(bs - bs % 8, 7, -8):
        if S % d == 0:
            return d if d >= bs // 2 else 0
    return 0


def _kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_s: int, window: int, softcap: float):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, hd), pre-scaled
    k = k_ref[0, 0].astype(jnp.float32)          # (bs, hd)
    v = v_ref[0, 0].astype(jnp.float32)          # (bs, hd)
    kpos = pos_ref[0, 0]                         # (1, bs) int32, this slot
    qpos = qpos_ref[0]                           # (1, 1) int32, this slot

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    valid = (kpos >= 0) & (kpos <= qpos)
    if window:
        valid = valid & (kpos > qpos - window)
    s = jnp.where(valid, s, NEG_INF)          # broadcast (1,bs) over (G,bs)

    m_prev = m_ref[...]                        # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(si == n_s - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "bs", "interpret")
)
def flash_decode(
    q: jax.Array,        # (B, H, hd) one new token's queries per slot
    k: jax.Array,        # (B, Kh, S, hd) cache, native layout
    v: jax.Array,        # (B, Kh, S, hd)
    k_pos: jax.Array,    # (B, S) int32; negative = empty slot
    q_pos: jax.Array,    # (B,) int32; negative = free pool slot
    *,
    window: int = 0,
    softcap: float = 0.0,
    bs: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    Kh, S = k.shape[1], k.shape[2]
    G = H // Kh

    # prefer shrinking the block to a sublane-aligned divisor of S (no
    # padding, no copies); if S is divisor-hostile (prime-ish, or only
    # misaligned/tiny divisors) fall back to padding the tail block —
    # keys padded with k_pos = -1 are masked exactly like empty slots
    d = _pick_block(S, bs)
    if d:
        bs = d
    else:
        pad_s = (-S) % bs
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_s)), constant_values=-1)
        S = S + pad_s
    n_s = S // bs

    # pad G to the 8-row sublane so the score tile is vreg-aligned
    Gp = max(8, G)
    qg = q.reshape(B, Kh, G, hd) * (hd ** -0.5)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    # Mosaic wants a block's last two dims to be (8k, 128m) or the whole
    # array dims: per-slot position rows get unit axes so each block
    # spans its array's last two dims in full, for any B and bs.
    pos2 = k_pos.astype(jnp.int32).reshape(B, n_s, 1, bs)
    qpos2 = q_pos.astype(jnp.int32).reshape(B, 1, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, n_s=n_s, window=window, softcap=softcap),
        grid=(B, Kh, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda b, h, s: (b, 0, 0)),
            pl.BlockSpec((1, 1, Gp, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda b, h, s: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Gp, hd), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kh, Gp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qpos2, qg, k, v, pos2)
    return out[:, :, :G, :].reshape(B, H, hd)
