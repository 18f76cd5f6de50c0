"""Public jit'd wrappers for the Pallas kernels.

On a TPU the calls compile to Mosaic; on any other backend (the CPU
test suite) they run with ``interpret=True`` (the kernel body executes
in Python, bit-exact with the TPU lowering's semantics). The switch is
automatic via the default backend — callers never pass ``interpret``.

``LAUNCH_COUNTS`` tallies kernel dispatches at the *call site* (outside
jit), which is what the upgrade-latency benchmark uses to prove a
full-model stage upgrade issues O(1) launches through the PlaneStore
instead of O(n_tensors) through the old per-tensor loop.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.kernels import dequant_matmul as _dqm
from repro.kernels import bitplane as _bp
from repro.kernels import decode_attention as _da
from repro.kernels import kv_write as _kvw
from repro.kernels import verify_attention as _va

# Dispatch counts per public kernel entry point. Reset freely; purely
# diagnostic (benchmarks, tests) — never read on a hot path.
LAUNCH_COUNTS: collections.Counter = collections.Counter()


def reset_launch_counts() -> None:
    LAUNCH_COUNTS.clear()


def _count(name: str) -> None:
    """Tally one call of a public kernel wrapper. Under ``jax.jit`` the
    wrapper runs once per trace, not once per launch: a profiler trace
    counts real launches, by kernel name."""
    LAUNCH_COUNTS[name] += 1


def _interpret_default() -> bool:
    """The one backend switch: Mosaic kernels on a TPU, interpret mode
    (or, for the model's attention entry points, the jnp oracles)
    everywhere else."""
    return jax.default_backend() != "tpu"


def dequant_matmul(x, q, scale, offset, **kw):
    """y = x @ (scale * q + offset); the eq.-(5) affine rides in as
    traced (1, 1) operands (see ``repro.core.quantize.dequant_affine``),
    so precision upgrades never recompile a jitted consumer."""
    _count("dequant_matmul")
    kw.setdefault("interpret", _interpret_default())
    return _dqm.dequant_matmul(x, q, scale, offset, **kw)


@functools.lru_cache(maxsize=None)
def _sharded_dqm(mesh, axis: str, interpret: bool):
    from jax.sharding import PartitionSpec as P

    # check_vma=False is required: pallas_call has no rule for varying
    # manual axes, and the kernel computes no cross-shard reductions
    # anyway (K stays whole per shard).
    return jax.jit(jax.shard_map(
        functools.partial(_dqm.dequant_matmul, interpret=interpret),
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(), P()),
        out_specs=P(None, axis),
        check_vma=False))


def sharded_dequant_matmul(x, q, scale, offset, *, mesh, axis: str = "model"):
    """Explicit tensor-parallel dequant-matmul: ``q`` (K, N) sharded on
    N over ``mesh``'s ``axis``; x/scale/offset replicated. One kernel
    launch *per shard* under ``shard_map`` — each shard dequantizes and
    multiplies its own (K, N/n) accumulator columns, and the output
    comes back (M, N) sharded on N. Bit-identical to the single-device
    kernel: the K contraction is never sharded, so no partial-sum
    all-reduce ever reorders float adds. The model's dense dispatch
    (``models.common.dense``) routes here whenever a serving mesh is
    active: GSPMD cannot partition a Mosaic kernel, so a sharded
    program must place each launch itself."""
    _count("sharded_dequant_matmul")
    return _sharded_dqm(mesh, axis, _interpret_default())(
        x, q, scale, offset)


def plane_or(acc, plane, *, shift, **kw):
    _count("plane_or")
    kw.setdefault("interpret", _interpret_default())
    return _bp.plane_or(acc, plane, shift=shift, **kw)


def plane_or_segments(acc, plane, seg_starts, seg_shifts, **kw):
    _count("plane_or_segments")
    kw.setdefault("interpret", _interpret_default())
    return _bp.plane_or_segments(acc, plane, seg_starts, seg_shifts, **kw)


def plane_unpack(packed, *, width, dtype, **kw):
    _count("plane_unpack")
    kw.setdefault("interpret", _interpret_default())
    return _bp.plane_unpack(packed, width=width, dtype=dtype, **kw)


def plane_extract(q, *, bits, before, width, **kw):
    _count("plane_extract")
    kw.setdefault("interpret", _interpret_default())
    return _bp.plane_extract(q, bits=bits, before=before, width=width, **kw)


def kv_write(k, v, k_new, v_new, rows, layer=None):
    """The model's KV row write: new rows k_new/v_new (B, Kh, T, hd)
    into the cache at per-row positions ``rows`` (B, T) int32 (negative
    = masked: the row writes nothing). k/v are the stacked
    (L, B, Kh, S, hd) cache with ``layer`` its () int32 index, or one
    (B, Kh, S, hd) cache with ``layer`` None. Returns the updated
    (k, v), the buffers that came in (updated in place when the caller
    owns them). On TPU this is the Pallas kernel, for any cache length
    its DMA windows can tile; elsewhere the jnp oracle."""
    _count("kv_write")
    one = layer is None
    if one:
        k, v, layer = k[None], v[None], 0
    if not _interpret_default() and k.shape[3] % _kvw.ALIGN == 0:
        k, v = _kvw.kv_write(k, v, k_new, v_new, rows, layer,
                             interpret=False)
    else:
        from repro.kernels import ref as _ref

        k, v = _ref.kv_write_ref(k, v, k_new, v_new, rows, layer)
    return (k[0], v[0]) if one else (k, v)


def flash_decode(q, k, v, k_pos, q_pos, layer=None, *, window=0,
                 softcap=0.0, **kw):
    """Ragged batched decode attention: q (B, H, hd); k/v in the native
    (B, Kh, S, hd) cache layout, or stacked (L, B, Kh, S, hd) with
    ``layer`` the () int32 index of the layer to read; k_pos (B, S);
    q_pos (B,)."""
    _count("flash_decode")
    kw.setdefault("interpret", _interpret_default())
    return _da.flash_decode(
        q, k, v, k_pos, q_pos, layer, window=window, softcap=softcap, **kw
    )


def decode_attention(q, k, v, k_pos, q_pos, layer=None, *, window=0,
                     softcap=0.0):
    """The model's per-step decode-attention entry point (same ragged
    operands as :func:`flash_decode`, stacked caches and ``layer``
    included). On TPU this is the Pallas flash kernel; elsewhere it is
    the vectorized jnp oracle — interpret-mode
    Pallas unrolls the (B, Kh, S/bs) grid into the jaxpr, which turns a
    batched decode step into O(B) staged kernel bodies and defeats the
    whole point of continuous batching on CPU CI. Both consume the
    native (B, Kh, S, hd) cache layout with no transpose; parity is
    pinned by tests/test_kernels.py. (No pass-through kwargs: kernel
    tuning knobs like ``bs`` belong to :func:`flash_decode` callers,
    and the two backends must accept identical calls.)"""
    _count("decode_attention")
    if not _interpret_default():
        return _da.flash_decode(
            q, k, v, k_pos, q_pos, layer, window=window, softcap=softcap,
            interpret=False
        )
    from repro.kernels import ref as _ref

    return _ref.flash_decode_ref(
        q, k, v, k_pos, q_pos, layer, window=window, softcap=softcap
    ).astype(q.dtype)


def flash_verify(q, k, v, k_pos, q_pos, layer=None, *, window=0,
                 softcap=0.0, **kw):
    """Ragged draft-block verify attention: q (B, T, H, hd); k/v in the
    native (B, Kh, S, hd) cache layout, or stacked with ``layer`` as in
    :func:`flash_decode`; k_pos (B, S); q_pos (B, T) per-token
    positions (negative = masked row)."""
    _count("flash_verify")
    kw.setdefault("interpret", _interpret_default())
    return _va.flash_verify(
        q, k, v, k_pos, q_pos, layer, window=window, softcap=softcap, **kw
    )


def verify_attention(q, k, v, k_pos, q_pos, layer=None, *, window=0,
                     softcap=0.0):
    """The model's verify-step attention entry point: T = k+1 draft
    queries per slot against the same native cache, one pass. On TPU
    this is the Pallas flash_verify kernel; elsewhere it is the jnp
    oracle, whose per-row computation is *exactly* a decode step's (see
    ``kernels/ref.flash_verify_ref``) — the bit-identity that makes
    lossless speculative decoding token-identical to plain greedy on
    this backend. Same no-pass-through-kwargs rule as
    :func:`decode_attention`."""
    _count("verify_attention")
    if not _interpret_default():
        return _va.flash_verify(
            q, k, v, k_pos, q_pos, layer, window=window, softcap=softcap,
            interpret=False
        )
    from repro.kernels import ref as _ref

    return _ref.flash_verify_ref(
        q, k, v, k_pos, q_pos, layer, window=window, softcap=softcap
    ).astype(q.dtype)


def prefill_attention(q, k, v, k_pos, q_pos, layer=None, *, window=0,
                      softcap=0.0):
    """The model's chunked-prefill attention entry point: a (B, chunk)
    block of ragged prompt queries per slot against the same native
    (B, Kh, S, hd) cache (or stacked, with ``layer``), one pass.
    Operand-wise this is
    :func:`verify_attention` — q (B, T, H, hd), k_pos (B, S), q_pos
    (B, T) per-token positions with negative = masked row — the
    difference is what the rows MEAN: q_pos rows carry per-slot chunk
    offsets (slot b's row t is prompt position off_b + t), so slots at
    different prompt depths prefill in the same launch while free and
    decoding slots ride fully masked. On TPU this is the Pallas
    flash_verify kernel (multi-query-position causal attention is the
    same program either way); elsewhere the jnp oracle
    ``kernels/ref.flash_prefill_ref``. Same no-pass-through-kwargs rule
    as :func:`decode_attention`."""
    _count("prefill_attention")
    if not _interpret_default():
        return _va.flash_verify(
            q, k, v, k_pos, q_pos, layer, window=window, softcap=softcap,
            interpret=False
        )
    from repro.kernels import ref as _ref

    return _ref.flash_prefill_ref(
        q, k, v, k_pos, q_pos, layer, window=window, softcap=softcap
    ).astype(q.dtype)


# The old pytree-level ``receiver_or`` convenience (one plane_or per
# leaf) is gone: shipments now flow through the PlaneStore
# (``repro/core/plane_store.py``), which batches a whole shipment into
# one plane_or_segments launch per container dtype.
