"""Attention: RoPE, chunked online-softmax (flash-style) attention, and
the attention-family block (full / sliding-window / cross / enc-dec).

The chunked attention is the load-bearing piece for prefill/training:
it scans over KV chunks with a running (max, denominator, accumulator)
triple, so neither the 32k-prefill compile nor the 500k-decode compile
ever materializes a (Tq, Tk) score matrix.

The per-token decode path is different: KV caches are stored in the
flash kernel's **native** ``(B, Kh, S, hd)`` layout from prefill
onwards, each decode step writes one token per slot at its own
position (``pos`` may be a ``(B,)`` vector — ragged continuous
batching), and attention runs through
:func:`repro.kernels.ops.decode_attention` (the Pallas flash kernel on
TPU, its vectorized jnp oracle elsewhere). No transpose and no
sequence-axis padding of the cache ever happens inside the hot loop —
each cache byte crosses HBM exactly once per token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.models.common import (ArchConfig, apply_norm, norm_init,
                                 activation, dense, dense_init, launch)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, pos: jax.Array, theta: float,
         rotary_dim: int | None = None) -> jax.Array:
    """x: (B, T, H, hd); pos: (T,) shared or (B, T) per-slot int32
    positions (ragged decode batches rotate every slot at its own
    position). ``rotary_dim`` rotates only each head's first
    ``rotary_dim`` dimensions (partial rotary) and passes the rest
    through."""
    hd = x.shape[-1]
    if rotary_dim is not None and rotary_dim < hd:
        rot = rope(x[..., :rotary_dim], pos, theta)
        return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = pos.astype(jnp.float32)[..., None] * freqs  # (..., T, half)
    if angles.ndim == 2:
        angles = angles[None]                            # (1|B, T, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention
# ---------------------------------------------------------------------------

def chunked_attention(
    q: jax.Array,  # (B, Tq, H, hd)
    k: jax.Array,  # (B, Tk, K, hd)
    v: jax.Array,  # (B, Tk, K, hd)
    q_pos: jax.Array,  # (Tq,) int32
    k_pos: jax.Array,  # (Tk,) int32; negative = invalid slot
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
    softcap: float = 0.0,
    unroll: bool = False,
) -> jax.Array:
    B, Tq, H, hd = q.shape
    Tk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = hd**-0.5

    chunk = min(chunk, Tk) if Tk else 1
    if unroll:
        # costing mode: cap the unrolled trip count at 16 by enlarging the
        # chunk (FLOPs/bytes are chunk-size-invariant; only tiling changes)
        chunk = max(chunk, -(-Tk // 16))
    pad = (-Tk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)
    n_chunks = k.shape[1] // chunk

    qg = q.reshape(B, Tq, K, G, hd).astype(jnp.float32) * scale
    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, K, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, K, hd), 1, 0)
    pc = k_pos.reshape(n_chunks, chunk)

    def body(carry, xs):
        m, l, acc = carry  # (B,K,G,Tq), (B,K,G,Tq), (B,K,G,Tq,hd)
        kk, vv, pp = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kk.astype(jnp.float32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        valid = pp[None, :] >= 0  # (1, chunk)
        if causal:
            valid = valid & (pp[None, :] <= q_pos[:, None])
        if window:
            valid = valid & (pp[None, :] > q_pos[:, None] - window)
        s = jnp.where(valid[None, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vv.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Tq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Tq, hd), jnp.float32)
    if unroll:
        carry = (m0, l0, a0)
        for c in range(n_chunks):
            carry, _ = body(carry, (kc[c], vc[c], pc[c]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,K,G,Tq,hd)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Tq, H, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache helpers (native (B, K, S, hd) layout)
# ---------------------------------------------------------------------------

def make_ring_cache(k: jax.Array, v: jax.Array, window: int):
    """Prefill -> ring cache holding the last `window` positions at slot
    p % window. k/v: (B, S, K, hd) in; caches come out in the native
    (B, K, window, hd) layout. Speculative decoding over-allocates the
    ring AFTER prefill (see :func:`grow_ring_cache`) so speculative
    writes past the head never clobber entries still inside a live
    window."""
    B, S, K, hd = k.shape
    W = min(window, S)
    slots = jnp.arange(S - W, S) % window
    kn = jnp.swapaxes(k, 1, 2)  # one transpose at prefill, never per step
    vn = jnp.swapaxes(v, 1, 2)
    ring_k = jnp.zeros((B, K, window, hd), k.dtype).at[:, :, slots].set(kn[:, :, S - W :])
    ring_v = jnp.zeros((B, K, window, hd), v.dtype).at[:, :, slots].set(vn[:, :, S - W :])
    return ring_k, ring_v


def grow_ring_cache(cache: dict, new_size: int, pos: int) -> dict:
    """Repack a prefill-produced ring cache (ring size = its S axis)
    into a larger ring, preserving every stored position. ``pos`` is the
    next write position (= tokens consumed so far) — a concrete host
    int, so this is plain indexing, done once per request at admission.

    Why: a ring of size W is only safe when positions are written in
    strict sequence (writing p clobbers p - W exactly when no future
    query can attend p - W). A speculative round writes k + 1 positions
    ahead and may then *rewind* to the first rejection, after which
    still-live window entries would have been clobbered. Over-allocating
    the ring to W + k + 1 restores the invariant: the attention window
    mask is still ``window`` (positions), only the slot layout widens.
    """
    R = cache["k"].shape[-2]  # slot axis is -2 (stacked or not)
    if new_size <= R:
        return cache
    import numpy as np

    held = np.asarray(ring_positions(R, pos - 1)) if pos > 0 else \
        np.full((R,), -1, np.int64)
    src = np.nonzero(held >= 0)[0]
    dst = held[src] % new_size

    def regrow(a):
        shp = a.shape[:-2] + (new_size,) + a.shape[-1:]
        out = jnp.zeros(shp, a.dtype)
        return out.at[..., dst, :].set(a[..., src, :])

    return {"k": regrow(cache["k"]), "v": regrow(cache["v"])}


def ring_positions(window: int, pos: jax.Array) -> jax.Array:
    """Position stored at each ring slot after a write at ``pos``;
    negative for not-yet-filled slots. ``pos`` scalar -> (window,);
    ``pos`` (B,) -> (B, window) per-slot position maps."""
    i = jnp.arange(window)
    p = jnp.asarray(pos)[..., None]   # () -> (1,); (B,) -> (B, 1)
    return p - ((p - i) % window)     # (window,) or (B, window)


def write_kv_slot(cache: jax.Array, new: jax.Array, pos: jax.Array,
                  active: jax.Array) -> jax.Array:
    """Write one row of K or V per slot into a native ring cache.
    cache: (B, K, W, hd); new: (B, K, 1, hd); pos: (B,) int32 ring slot;
    ``active`` (B,) bool makes the write a per-slot no-op instead, so a
    masked slot's cache row stays byte-identical (which is what lets the
    rollback invariant be tested at equality). Positional caches take
    ``kernels.ops.kv_write`` instead, in place."""
    def upd(c, u, p, a):
        u = u.astype(c.dtype)
        old = lax.dynamic_slice(c, (0, p, 0), u.shape)
        return lax.dynamic_update_slice(c, jnp.where(a, u, old), (0, p, 0))

    return jax.vmap(upd)(cache, new, pos, active)


# ---------------------------------------------------------------------------
# Attention-family blocks
# ---------------------------------------------------------------------------

def mlp_init(cfg: ArchConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "wi_up": dense_init(k2, cfg.d_model, cfg.d_ff),
        "wo": dense_init(k3, cfg.d_ff, cfg.d_model),
    }
    if cfg.mlp_gated:
        p["wi_gate"] = dense_init(k1, cfg.d_model, cfg.d_ff)
    return p


def mlp_apply(cfg: ArchConfig, p, x: jax.Array) -> jax.Array:
    """Gated: ``wo(act(wi_gate x) * wi_up x)``; ungated:
    ``wo(act(wi_up x))``."""
    dt = cfg.dtype
    with jax.named_scope("mlp"):
        if cfg.mlp_gated:
            h = activation(cfg, dense(x, p["wi_gate"], dtype=dt)) \
                * dense(x, p["wi_up"], dtype=dt)
        else:
            h = activation(cfg, dense(x, p["wi_up"], dtype=dt))
        return dense(h, p["wo"], dtype=dt)


def attn_init(cfg: ArchConfig, key, *, cross: bool = False):
    ks = jax.random.split(key, 5)
    kv_in = cfg.d_model  # enc states are projected to d_model upstream
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * cfg.hd),
        "wk": dense_init(ks[1], kv_in, cfg.n_kv * cfg.hd),
        "wv": dense_init(ks[2], kv_in, cfg.n_kv * cfg.hd),
        "wo": dense_init(ks[3], cfg.n_heads * cfg.hd, cfg.d_model),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.hd,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.hd,), jnp.float32)
    return p


def _qk_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (y * scale).astype(x.dtype)


def project_qkv(cfg: ArchConfig, p, x: jax.Array, kv_src: jax.Array):
    dt = cfg.dtype
    B, Tq, _ = x.shape
    Tk = kv_src.shape[1]
    q = dense(x, p["wq"], dtype=dt).reshape(B, Tq, cfg.n_heads, cfg.hd)
    k = dense(kv_src, p["wk"], dtype=dt).reshape(B, Tk, cfg.n_kv, cfg.hd)
    v = dense(kv_src, p["wv"], dtype=dt).reshape(B, Tk, cfg.n_kv, cfg.hd)
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"])
        k = _qk_norm(k, p["k_norm"])
    return q, k, v


def decode_pos_vector(pos, batch: int) -> jax.Array:
    """Normalize a decode position argument — scalar (lock-stepped
    stream) or (B,) vector (ragged slot pool) — to a (B,) int32."""
    p = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(p, (batch,)) if p.ndim == 0 else p


def self_attention(
    cfg: ArchConfig,
    p,
    x: jax.Array,
    *,
    mode: str,  # full | prefill | prefill_chunk | verify | decode
    window: int,
    cache,  # {"k","v"} native (B, K, S|W, hd), stacked (L, B, K, S, hd)
            # with `layer`, or None
    pos,  # decode/verify: scalar or (B,) int32 per-slot positions;
          # prefill_chunk: (B, T) per-token positions (negative = masked);
          # prefill: optional (B,) valid lengths for bucket-padded prompts
    rope_theta: float | None = None,
    layer=None,  # () int32: this layer's index into a stacked cache
):
    """Returns (attn_out, new_cache); with ``layer``, new_cache is the
    whole stacked cache with this layer's new rows written."""
    theta = rope_theta if rope_theta is not None else cfg.rope_theta
    rd = cfg.rotary_dim
    B, Tq, _ = x.shape
    if mode in ("full", "prefill"):
        q, k, v = project_qkv(cfg, p, x, x)
        q_pos = jnp.arange(Tq, dtype=jnp.int32)
        if pos is not None:
            # bucket-padded prefill: positions at/after the valid length
            # are masked out (-1). All batch rows share one valid length
            # (the pool prefills at batch 1); keys at masked positions
            # are invisible to every query, and their garbage cache rows
            # sit beyond the prompt, overwritten by decode before any
            # query can attend them.
            if window:
                raise NotImplementedError(
                    "bucketed prefill is not supported for sliding-window "
                    "attention (the ring layout has no masked slots)")
            nv = jnp.asarray(pos, jnp.int32).reshape(-1)[0]
            q_pos = jnp.where(q_pos < nv, q_pos, jnp.int32(-1))
        q = rope(q, q_pos, theta, rd)
        k = rope(k, q_pos, theta, rd)
        out = chunked_attention(
            q, k, v, q_pos, q_pos, causal=True, window=window,
            chunk=cfg.attn_chunk, unroll=cfg.costing,
        )
        new_cache = None
        if mode == "prefill":
            if window:
                rk, rv = make_ring_cache(k, v, window)
                new_cache = {"k": rk, "v": rv}
            else:
                # one transpose at prefill; decode never transposes
                new_cache = {"k": jnp.swapaxes(k, 1, 2),
                             "v": jnp.swapaxes(v, 1, 2)}
    else:
        # decode (T = 1), verify (T = k + 1 draft tokens) or
        # prefill_chunk (a (B, chunk) block of ragged prompt positions,
        # written straight into the pooled cache): write the new rows,
        # then attend, in one multi-row pass per slot.
        q, k_new, v_new = project_qkv(cfg, p, x, x)
        if mode == "prefill_chunk":
            # per-token positions arrive precomputed: row t of slot b
            # holds prompt position off_b + t, or -1 for masked rows
            # (free/decoding slots riding the batched launch, ragged
            # padding past a short final chunk)
            tok_pos = jnp.asarray(pos, jnp.int32)              # (B, T)
        else:
            pos_vec = decode_pos_vector(pos, B)                # (B,) base
            # per-token positions; a negative base (free pool slot, or a
            # mid-prefill slot riding a decode step) keeps every row
            # masked instead of walking into valid range
            tok_pos = jnp.where(pos_vec[:, None] >= 0,
                                pos_vec[:, None]
                                + jnp.arange(Tq, dtype=jnp.int32)[None, :],
                                jnp.int32(-1))                 # (B, T)
        q = rope(q, tok_pos, theta, rd)
        k_new = rope(k_new, tok_pos, theta, rd)
        kn = jnp.swapaxes(k_new, 1, 2)                         # (B, K, T, hd)
        vn = jnp.swapaxes(v_new, 1, 2)
        # write the whole block FIRST, then attend: rejected verify rows
        # are never rolled back — the next round simply overwrites them,
        # and the per-row causal mask (k_pos <= q_pos) keeps any not-yet
        # -overwritten row invisible to every live query. Masked rows
        # (free/finished slots, ragged padding) write NOTHING — their
        # cache rows stay byte-identical.
        if window:
            # ring size comes from the cache (it may be over-allocated
            # beyond the attention window for speculative rounds and
            # prefill chunks); the window mask itself is positional
            ring = cache["k"].shape[2]
            if Tq > 1 and ring < window + Tq:
                raise ValueError(
                    f"multi-row writes over a ring cache need ring >= "
                    f"window + T ({window} + {Tq}), got {ring}: the block "
                    f"would clobber live window entries (grow the cache "
                    f"with ring_margin >= the block length)")
            k_cache, v_cache = cache["k"], cache["v"]
            for t in range(Tq):
                wp = jnp.maximum(tok_pos[:, t], 0) % ring
                live = tok_pos[:, t] >= 0
                k_cache = write_kv_slot(k_cache, kn[:, :, t:t + 1], wp, live)
                v_cache = write_kv_slot(v_cache, vn[:, :, t:t + 1], wp, live)
            # last written position per slot (-1 if fully masked): for a
            # verify block this is pos + T - 1; for a chunk, off + n - 1;
            # ring_positions(-1) is all-negative, so a masked slot's
            # whole ring stays invisible
            k_pos = ring_positions(ring, jnp.max(tok_pos, axis=1))
        else:
            # positional cache: the new rows go into the buffer that
            # came in — layer ``layer`` of the stack the layer loop
            # carries, or a cache of its own (layer None) — and nothing
            # else of it moves. Live rows sit below max_len (the engines
            # check prompt + budget, and the speculative pool's headroom
            # covers a verify block); the kernel reads every position
            # and masks k_pos > q_pos per row, so stale entries beyond
            # each slot's position never contribute.
            k_cache, v_cache = launch(ops.kv_write, cache["k"], cache["v"],
                                      kn, vn, tok_pos, layer)
            S = k_cache.shape[-2]
            k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        k_pos = k_pos.astype(jnp.int32)
        if mode == "decode":
            out = launch(ops.decode_attention, q[:, 0], k_cache, v_cache,
                         k_pos, tok_pos[:, 0], layer, window=window)[:, None]
        else:
            attend = (ops.prefill_attention if mode == "prefill_chunk"
                      else ops.verify_attention)
            out = launch(attend, q, k_cache, v_cache, k_pos, tok_pos, layer,
                         window=window)                        # (B, T, H, hd)
        new_cache = {"k": k_cache, "v": v_cache}
    return dense(out.reshape(B, Tq, -1), p["wo"], dtype=cfg.dtype), new_cache


def cross_attention(cfg: ArchConfig, p, x: jax.Array, enc_kv, *,
                    native: bool = False):
    """enc_kv: precomputed {"k","v"} from the encoder or vision
    projector — computed once at prefill, static afterwards. With
    ``native=False`` (prefill/full) the memory is (B, Tv, K, hd) and
    attention runs chunked; with ``native=True`` (decode Tq == 1, or a
    verify block Tq == k+1) the memory is the cached native
    (B, K, Tv, hd) layout and attention runs through the ragged
    decode/verify kernel with every memory slot valid — no per-step
    transpose of the cross cache."""
    dt = cfg.dtype
    B, Tq, _ = x.shape
    q = dense(x, p["wq"], dtype=dt).reshape(B, Tq, cfg.n_heads, cfg.hd)
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"])
    if native:
        Tv = enc_kv["k"].shape[2]
        k_pos = jnp.broadcast_to(jnp.arange(Tv, dtype=jnp.int32), (B, Tv))
        # non-causal: q_pos = Tv admits every memory slot for every slot
        if Tq == 1:
            q_pos = jnp.full((B,), Tv, jnp.int32)
            out = launch(
                ops.decode_attention, q[:, 0], enc_kv["k"], enc_kv["v"],
                k_pos, q_pos, window=0,
            )[:, None]
        else:
            q_pos = jnp.full((B, Tq), Tv, jnp.int32)
            out = launch(
                ops.verify_attention, q, enc_kv["k"], enc_kv["v"], k_pos,
                q_pos, window=0,
            )
    else:
        Tv = enc_kv["k"].shape[1]
        k_pos = jnp.arange(Tv, dtype=jnp.int32)
        q_pos = jnp.zeros((Tq,), jnp.int32)  # no causality vs. memory tokens
        out = chunked_attention(
            q, enc_kv["k"], enc_kv["v"], q_pos, k_pos, causal=False, window=0,
            chunk=cfg.attn_chunk, unroll=cfg.costing,
        )
    return dense(out.reshape(B, Tq, -1), p["wo"], dtype=dt)


def cross_kv(cfg: ArchConfig, p, enc_out: jax.Array):
    """Project encoder/vision states to this block's K/V once.
    Returns the sequence-major (B, Tv, K, hd) layout used by the
    chunked prefill path; cache it with :func:`to_native_kv`."""
    dt = cfg.dtype
    B, Tv, _ = enc_out.shape
    k = dense(enc_out, p["wk"], dtype=dt).reshape(B, Tv, cfg.n_kv, cfg.hd)
    v = dense(enc_out, p["wv"], dtype=dt).reshape(B, Tv, cfg.n_kv, cfg.hd)
    if cfg.qk_norm:
        k = _qk_norm(k, p["k_norm"])
    return {"k": k, "v": v}


def to_native_kv(kv):
    """(B, Tv, K, hd) -> native (B, K, Tv, hd); one transpose at
    prefill so decode steps read the cache as-is."""
    return {"k": jnp.swapaxes(kv["k"], 1, 2), "v": jnp.swapaxes(kv["v"], 1, 2)}
