"""Ragged batched flash verify-attention Pallas TPU kernel: a block of
T = k+1 draft tokens per slot against a long KV cache, in ONE pass.

Speculative decoding's verify step scores a whole draft block — the
last accepted token plus k drafted continuations — through the target
model at once. Attention-wise that is the flash-decode problem with a
(T, ...) query *block* per slot instead of a single token: the whole KV
cache still crosses HBM exactly once, but it is amortized over T
queries, which is where the verify step's throughput multiplier comes
from on a memory-bound decode.

  grid = (B, Kh, S/bs); for each slot, KV-head and cache chunk the
  kernel computes the (T*G, bs) score tile (T draft rows x G query
  heads per KV head, padded to the 8-row sublane), runs the online
  softmax against VMEM scratch carries (m, l, acc), and emits the
  normalized (T*G, hd) output on the last chunk.

Raggedness is *per query row*: ``q_pos`` is ``(B, T)`` — every draft
token carries its own position, so one launch serves slots whose drafts
start at wildly different depths (a continuous-batching pool
mid-speculation), slots whose draft is shorter than T (padding rows are
marked ``q_pos = -1`` and fully masked), and free slots (whole row
negative). ``k_pos`` is the same ``(B, S)`` per-slot cache position
vector flash-decode uses — full caches, partially filled caches and
sliding-window ring caches (where ring slots beyond the attention
window are excluded by the window mask, not by layout) all work
unchanged. Masked rows produce finite garbage (uniform attention over
nothing is avoided by the same NEG_INF + 1e-30 guard as flash_decode)
and are discarded host-side.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one block-size policy for both decode-family kernels: a tuning change
# there must not desynchronize the verify kernel's padding behavior
from repro.kernels.decode_attention import NEG_INF, stacked_kv


def _kernel(layer_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_s: int, window: int, softcap: float):
    del layer_ref  # consumed by the K/V index maps
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (R, hd), pre-scaled
    k = k_ref[0, 0].astype(jnp.float32)          # (bs, hd)
    v = v_ref[0, 0].astype(jnp.float32)          # (bs, hd)
    kpos = pos_ref[0, 0]                         # (1, bs) int32, this slot
    qp = qpos_ref[0]                             # (R, 1) int32 per-row pos

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (R, bs)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    # per-row causality: row r is the query at position qp[r]; a
    # negative qp (draft padding / free slot) masks the entire row
    valid = (kpos >= 0) & (kpos <= qp) & (qp >= 0)
    if window:
        valid = valid & (kpos > qp - window)
    s = jnp.where(valid, s, NEG_INF)             # (R, bs)

    m_prev = m_ref[...]                          # (R, 1)
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
def flash_verify(
    q: jax.Array,        # (B, T, H, hd) draft-block queries per slot
    k: jax.Array,        # (B, Kh, S, hd) cache, native layout, or the
    v: jax.Array,        #   stacked (L, B, Kh, S, hd) cache with `layer`
    k_pos: jax.Array,    # (B, S) int32; negative = empty slot
    q_pos: jax.Array,    # (B, T) int32 per-token; negative = masked row
    layer=None,          # () int32 index into a stacked cache
    *,
    window: int = 0,
    softcap: float = 0.0,
    bs: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, T, H, hd = q.shape
    k, v, k_pos, layer, bs = stacked_kv(k, v, k_pos, layer, bs)
    Kh, S = k.shape[2], k.shape[3]
    G = H // Kh
    n_s = S // bs

    # rows: draft-token-major, query-group-minor — (t, g) -> t * G + g;
    # padded to the 8-row sublane, padding rows masked via q_pos = -1
    R = T * G
    Rp = -(-max(R, 8) // 8) * 8
    qg = (q.reshape(B, T, Kh, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, Kh, R, hd)) * (hd ** -0.5)
    qpos_rows = jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)  # (B, R)
    if Rp != R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
        qpos_rows = jnp.pad(qpos_rows, ((0, 0), (0, Rp - R)),
                            constant_values=-1)
    # unit axes make every position block span its array's last two
    # dims in full, which Mosaic accepts for any B, Rp and bs
    pos2 = k_pos.astype(jnp.int32).reshape(B, n_s, 1, bs)
    qpos_rows = qpos_rows[:, :, None]                           # (B, Rp, 1)

    kv = pl.BlockSpec((None, 1, 1, bs, hd),
                      lambda b, h, s, layer: (layer[0], b, h, s, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, n_s=n_s, window=window, softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Kh, n_s),
            in_specs=[
                pl.BlockSpec((1, Rp, 1), lambda b, h, s, _: (b, 0, 0)),
                pl.BlockSpec((1, 1, Rp, hd), lambda b, h, s, _: (b, h, 0, 0)),
                kv,
                kv,
                pl.BlockSpec((1, 1, 1, bs), lambda b, h, s, _: (b, s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, Rp, hd),
                                   lambda b, h, s, _: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Rp, 1), jnp.float32),
                pltpu.VMEM((Rp, 1), jnp.float32),
                pltpu.VMEM((Rp, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Kh, Rp, hd), q.dtype),
        interpret=interpret,
    )(layer, qpos_rows, qg, k, v, pos2)
    out = out[:, :, :R, :].reshape(B, Kh, T, G, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, T, H, hd)
