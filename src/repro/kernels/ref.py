"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the mathematical definition of its kernel, written with
plain jnp ops (no pallas imports). Kernel tests sweep shapes/dtypes and
assert_allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import dequant_affine

NEG_INF = -1e30


def dequant_ref(q: jax.Array, lo: jax.Array, hi: jax.Array, bits: int,
                received_bits: int | None = None) -> jax.Array:
    """Eq. (5) via the one shared affine helper — the ε-widened span is
    defined in ``repro.core.quantize.dequant_affine`` and nowhere else,
    so kernel, oracle and materialization cannot drift."""
    scale, offset = dequant_affine(lo, hi, bits, received_bits)
    return q.astype(jnp.float32) * scale + offset


def dequant_matmul_ref(x: jax.Array, q: jax.Array, scale: jax.Array,
                       offset: jax.Array) -> jax.Array:
    """y = x @ (scale * q + offset).  x: (M, K) float; q: (K, N) uint.
    Mirrors the kernel's operands: the affine comes precomputed (from
    ``dequant_affine``), exactly like the traced (1, 1) kernel inputs."""
    w = q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32) \
        + jnp.asarray(offset, jnp.float32)
    return x.astype(jnp.float32) @ w


def plane_or_ref(acc: jax.Array, plane: jax.Array, shift: int) -> jax.Array:
    """Eq. (4) single-plane accumulate: acc | (plane << shift)."""
    return (acc.astype(jnp.uint32) | (plane.astype(jnp.uint32) << shift)).astype(acc.dtype)


def plane_unpack_ref(packed: jax.Array, width: int, dtype) -> jax.Array:
    """Packed ``width``-bit values (big-endian within each byte) -> one
    ``dtype`` element per value. Forms the (bytes, 8 / width) array the
    kernel avoids."""
    v = 8 // width
    shifts = 8 - width * (1 + jnp.arange(v, dtype=jnp.uint8))
    vals = (packed[:, None] >> shifts) & jnp.uint8((1 << width) - 1)
    return vals.reshape(-1).astype(dtype)


def kv_write_ref(k: jax.Array, v: jax.Array, k_new: jax.Array,
                 v_new: jax.Array, rows: jax.Array, layer: jax.Array):
    """Write new K/V rows into one layer of a stacked cache.

    k/v: (L, B, Kh, S, hd); k_new/v_new: (B, Kh, T, hd); rows: (B, T)
    int32 position of each new row (negative or >= S = masked: the
    row writes nothing); layer: () int32. Returns the updated (k, v);
    every byte outside the live rows is unchanged."""
    S = k.shape[3]
    r = jnp.where((rows >= 0) & (rows < S), rows, S)   # S: dropped
    b = jnp.arange(rows.shape[0])[:, None]

    def put(c, new):
        # advanced indices around a slice: the (B, T) index dims lead
        return c.at[layer, b, :, r, :].set(
            jnp.swapaxes(new, 1, 2).astype(c.dtype), mode="drop")

    return put(k, k_new), put(v, v_new)


def _layer_of(k: jax.Array, v: jax.Array, layer):
    """The (B, Kh, S, hd) cache an attention oracle reads: k/v as given,
    or layer ``layer`` of a stacked (L, B, Kh, S, hd) cache."""
    if layer is None:
        return k, v
    return k[layer], v[layer]


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_pos: jax.Array, q_pos: jax.Array, layer=None,
                     *, window: int = 0, softcap: float = 0.0) -> jax.Array:
    """Ragged batched single-token GQA decode attention.

    q: (B, H, hd); k/v: (B, Kh, S, hd) native cache layout, or the
    stacked (L, B, Kh, S, hd) cache with ``layer`` its () int32 index;
    k_pos: (B, S) int32 per-slot cache positions (negative = empty
    slot); q_pos: (B,) int32 per-slot query position (negative = free
    pool slot: every key is masked and the output row is meaningless).
    Returns (B, H, hd).
    """
    k, v = _layer_of(k, v, layer)
    B, H, hd = q.shape
    Kh, S = k.shape[1], k.shape[2]
    G = H // Kh
    qf = q.reshape(B, Kh, G, hd).astype(jnp.float32) * (hd ** -0.5)
    s = jnp.einsum("bkgd,bksd->bkgs", qf, k.astype(jnp.float32))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qp = q_pos.reshape(B, 1)
    valid = (k_pos >= 0) & (k_pos <= qp)          # (B, S)
    if window:
        valid = valid & (k_pos > qp - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd)


def flash_verify_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_pos: jax.Array, q_pos: jax.Array, layer=None,
                     *, window: int = 0, softcap: float = 0.0) -> jax.Array:
    """Ragged batched draft-block verify attention (T = k+1 queries per
    slot against one native-layout cache).

    q: (B, T, H, hd); k/v: (B, Kh, S, hd), or stacked with ``layer``
    as in :func:`flash_decode_ref`; k_pos: (B, S) per-slot cache
    positions; q_pos: (B, T) int32 *per-token* query positions (negative
    = masked row — draft padding or a free pool slot). Returns
    (B, T, H, hd).

    Implemented as a sequential ``lax.map`` of :func:`flash_decode_ref`
    over the T draft rows ON PURPOSE: each row then runs the *exact*
    computation a plain decode step would, so the verify pass is
    bit-identical to sequential decode on this backend — which is what
    makes lossless speculative token-identity testable at equality
    rather than tolerance. T is small (k+1), so the sequential map costs
    nothing here; the TPU kernel amortizes the cache pass instead.
    """
    k, v = _layer_of(k, v, layer)
    qt = jnp.swapaxes(q, 0, 1)        # (T, B, H, hd)
    qpt = jnp.swapaxes(q_pos, 0, 1)   # (T, B)

    def row(args):
        qr, qp = args
        return flash_decode_ref(qr, k, v, k_pos, qp,
                                window=window, softcap=softcap)

    out = jax.lax.map(row, (qt, qpt))  # (T, B, H, hd)
    return jnp.swapaxes(out, 0, 1)


def flash_prefill_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      k_pos: jax.Array, q_pos: jax.Array, layer=None,
                      *, window: int = 0, softcap: float = 0.0) -> jax.Array:
    """Ragged chunked-prefill attention oracle: a (B, chunk) block of
    prompt queries per slot against one native-layout cache.

    Operand contract is :func:`flash_verify_ref`'s — q: (B, T, H, hd);
    k/v: (B, Kh, S, hd); k_pos: (B, S); q_pos: (B, T) per-token
    positions, negative = masked row — but the rows carry per-slot
    CHUNK OFFSETS (slot b's row t is prompt position off_b + t, with -1
    padding past a short final chunk and for slots that are free or
    decoding). The computation is identical, and deliberately shared:
    each chunk row runs the exact computation a decode step at that
    position would, so chunked prefill is bit-identical per row to
    sequential decode of the prompt — the property the parity suite
    pins. Kept as a separate entry point so call sites (and
    LAUNCH_COUNTS) distinguish prefill chunks from verify blocks, and
    so a TPU prefill kernel can diverge from the verify kernel without
    touching callers.
    """
    return flash_verify_ref(q, k, v, k_pos, q_pos, layer,
                            window=window, softcap=softcap)
