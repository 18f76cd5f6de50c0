"""Bit-plane accumulate (eq. 4) as a Pallas TPU kernel.

A precision upgrade on a serving pod is, per weight shard:

    acc <- acc | (plane << shift)

pure integer VPU work, elementwise, embarrassingly tiled. On a real pod
the plane shard arrives over ICI/DCN into HBM and this kernel streams
(acc, plane) HBM->VMEM, ORs, and writes back — memory-bound at
~3 bytes/element moved, i.e. a 27B-param upgrade costs ~`3*27e9/819e9`
≈ 100 ms of HBM time per chip. The serving engine calls this between
decode steps; it never blocks the MXU for long.

The same kernel also implements eq. (3) extraction (split) via shift
masks, so divide/concat are one code path.

``plane_unpack`` expands a stage's packed wire bytes into the plane
the OR reads, so the host uploads ``w`` bits per element instead of a
container element (2-bit planes into uint16: 8x fewer bytes) and never
unpacks a value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _or_kernel(acc_ref, plane_ref, o_ref, *, shift: int):
    a = acc_ref[...].astype(jnp.uint32)
    p = plane_ref[...].astype(jnp.uint32)
    o_ref[...] = (a | (p << shift)).astype(o_ref.dtype)


def _or_segments_kernel(starts_ref, shifts_ref, acc_ref, plane_ref, o_ref,
                        *, search_steps: int):
    # starts_ref/shifts_ref are the scalar-prefetch segment table (SMEM):
    # segment j covers blocks [starts[j], starts[j+1]) at shift shifts[j].
    # A binary search finds the last segment starting at or before this
    # block: ceil(log2(n_segments)) scalar steps per grid step.
    i = pl.program_id(0)
    lo = jnp.int32(0)
    hi = jnp.int32(starts_ref.shape[0])
    for _ in range(search_steps):
        mid = (lo + hi) // 2
        right = starts_ref[mid] <= i
        lo = jnp.where(right, mid, lo)
        hi = jnp.where(right, hi, mid)
    sh = shifts_ref[lo].astype(jnp.uint32)
    a = acc_ref[...].astype(jnp.uint32)
    p = plane_ref[...].astype(jnp.uint32)
    o_ref[...] = (a | (p << sh)).astype(o_ref.dtype)


def _extract_kernel(q_ref, o_ref, *, bits: int, before: int, width: int):
    q = q_ref[...].astype(jnp.uint32)
    mask = jnp.uint32(2 ** bits - 1)
    o_ref[...] = (((q << before) & mask) >> (bits - width)).astype(o_ref.dtype)


def _tile_1d(n: int, block: int) -> tuple[int, int]:
    pad = (-n) % block
    return n + pad, pad


@functools.partial(jax.jit, static_argnames=("shift", "block", "interpret"))
def plane_or(acc: jax.Array, plane: jax.Array, *, shift: int,
             block: int = 1024, interpret: bool = False) -> jax.Array:
    """acc | (plane << shift), elementwise over arbitrary-shape arrays."""
    shape = acc.shape
    a = acc.ravel()
    p = plane.ravel()
    n = a.shape[0]
    block = min(block, max(n, 8))
    npad, pad = _tile_1d(n, block)
    if pad:
        a = jnp.pad(a, (0, pad))
        p = jnp.pad(p, (0, pad))
    # 2-D tiles: TPU vregs want (8, 128); flatten into rows of `block`.
    a2 = a.reshape(-1, block)
    p2 = p.reshape(-1, block)
    rows = a2.shape[0]
    brows = min(rows, 8)
    rpad = (-rows) % brows
    if rpad:
        a2 = jnp.pad(a2, ((0, rpad), (0, 0)))
        p2 = jnp.pad(p2, ((0, rpad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_or_kernel, shift=shift),
        grid=(a2.shape[0] // brows,),
        in_specs=[
            pl.BlockSpec((brows, block), lambda i: (i, 0)),
            pl.BlockSpec((brows, block), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((brows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(a2.shape, acc.dtype),
        interpret=interpret,
    )(a2, p2)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def plane_or_segments(acc: jax.Array, plane: jax.Array, seg_starts: jax.Array,
                      seg_shifts: jax.Array, *, block: int = 1024,
                      interpret: bool = False) -> jax.Array:
    """Batched eq. (4) over a *flat concatenated* accumulator buffer.

    One launch upgrades every tensor of a model at once: ``acc`` and
    ``plane`` are 1-D buffers in which each tensor occupies a
    block-aligned segment (see ``core/plane_store.py``). The segment
    table gives, per run of blocks sharing one left shift, its first
    block (``seg_starts``, int32, ascending, starting at 0) and that
    shift (``seg_shifts``, int32). The table rides in as scalar-prefetch
    operands (SMEM), so each block's shift is known before its DMA
    issues — the grid stays a single dense 1-D sweep and the whole
    upgrade is ONE ``pallas_call`` instead of one per tensor. The table
    grows with the number of segments, not with the buffer: a model has
    a handful of tensors but up to millions of blocks, and SMEM holds
    1 MiB.

    Blocks with nothing arriving carry a zero plane segment: OR with 0
    is the identity at any shift, so no masking is needed.

    ``block`` must be a multiple of 128 (lane width); both buffers must
    be a multiple of ``block`` long.
    """
    if acc.ndim != 1 or plane.ndim != 1:
        raise ValueError("plane_or_segments operates on flat 1-D buffers")
    if block % 128:
        raise ValueError(f"block must be a multiple of 128, got {block}")
    n = acc.shape[0]
    if n % block:
        raise ValueError(f"buffer length {n} not a multiple of block {block}")
    if plane.shape[0] != n:
        raise ValueError(
            f"plane length {plane.shape[0]} != acc length {n}")
    if seg_starts.ndim != 1 or seg_starts.shape != seg_shifts.shape \
            or seg_starts.shape[0] < 1:
        raise ValueError(
            f"segment table needs matching non-empty 1-D starts/shifts, "
            f"got {seg_starts.shape} and {seg_shifts.shape}")
    rows = block // 128
    a2 = acc.reshape(-1, 128)
    p2 = plane.reshape(-1, 128)
    n_blocks = n // block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((rows, 128), lambda i, st, sh: (i, 0)),
            pl.BlockSpec((rows, 128), lambda i, st, sh: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, 128), lambda i, st, sh: (i, 0)),
    )
    n_segs = seg_starts.shape[0]
    out = pl.pallas_call(
        functools.partial(_or_segments_kernel,
                          search_steps=(n_segs - 1).bit_length()),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(a2.shape, acc.dtype),
        interpret=interpret,
    )(seg_starts.astype(jnp.int32), seg_shifts.astype(jnp.int32), a2, p2)
    return out.reshape(-1)


# Input rows per step of plane_unpack's inner loop: one uint8 tile.
_UNPACK_SUB = 32


def _unpack_consts(width: int) -> tuple[np.ndarray, np.ndarray]:
    """The two 0/1 matrices of :func:`plane_unpack` for ``v = 8 //
    width`` values per byte. ``expand`` (128, 128 v) copies byte
    ``(128 / v) k + l // v`` of a row to column ``128 k + l``: column
    block k holds the bytes of output row ``v j + k``, each repeated
    once per value it carries. ``interleave`` (32 v, 32 v) moves row j
    of column block k, stacked as row ``32 k + j``, to output row
    ``v j + k``. Each output column has a single 1, so the products are
    exact in f32."""
    v = 8 // width
    b = np.arange(128)[:, None]
    c = np.arange(128 * v)[None, :]
    expand = b == (128 // v) * (c // 128) + (c % 128) // v
    r = np.arange(_UNPACK_SUB * v)
    interleave = np.zeros((r.size, r.size), bool)
    interleave[r, (r % v) * _UNPACK_SUB + r // v] = True
    return expand, interleave


def _unpack_kernel(x_ref, *refs, width: int):
    o_ref = refs[-1]
    v = 8 // width
    if v == 1:
        o_ref[...] = x_ref[...].astype(jnp.int32).astype(o_ref.dtype)
        return
    e_ref, s_ref = refs[:2]
    rows = _UNPACK_SUB * v
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    shift = 8 - width * (1 + lane % v)   # big-endian: value 0 on top

    def body(i, carry):
        # bytes 0..255 are exact in bf16; Mosaic has no unsigned ->
        # float cast, so widen to int32 first
        x = x_ref[pl.ds(pl.multiple_of(i * _UNPACK_SUB, _UNPACK_SUB),
                        _UNPACK_SUB), :]
        x = x.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
        y = jnp.dot(x, e_ref[...], preferred_element_type=jnp.float32)
        y = jnp.concatenate([y[:, k * 128:(k + 1) * 128] for k in range(v)],
                            axis=0).astype(jnp.bfloat16)
        z = jnp.dot(s_ref[...], y, preferred_element_type=jnp.float32)
        q = (z.astype(jnp.int32) >> shift) & ((1 << width) - 1)
        o_ref[pl.ds(pl.multiple_of(i * rows, rows), rows), :] = (
            q.astype(jnp.uint32).astype(o_ref.dtype))
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0] // _UNPACK_SUB, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("width", "dtype", "block_rows",
                                    "interpret"))
def plane_unpack(packed: jax.Array, *, width: int, dtype,
                 block_rows: int = 2048,
                 interpret: bool = False) -> jax.Array:
    """Expand a flat buffer of packed ``width``-bit values (big-endian
    within each byte, as :func:`repro.core.bitplanes.pack_bits` writes
    them) into one ``dtype`` element per value, on the device.

    ``packed`` is uint8 of length ``n * width / 8`` with ``n`` a
    multiple of 128; the result has ``n`` elements, so a zero byte
    gives zero elements (the PlaneStore's padding). ``width`` divides
    8. Lane-dense throughout: a (32, 128) byte tile becomes the
    (32 v, 128) output rows it covers, ``v = 8 / width``, by two 0/1
    matmuls on the MXU (``_unpack_consts``: expand bytes along lanes,
    then interleave rows), a per-lane shift and a mask. No
    (n, v)-shaped array is ever formed: a TPU pads a minor dimension of
    v to 128 lanes. Each grid step writes ``block_rows`` rows of 128
    elements; the last step may be partial."""
    if 8 % width:
        raise ValueError(f"width must divide 8, got {width}")
    if packed.ndim != 1 or packed.dtype != jnp.uint8:
        raise ValueError(
            f"plane_unpack wants flat uint8 bytes, got {packed.dtype} "
            f"{packed.shape}")
    v = 8 // width
    n = packed.shape[0] * v
    if n % 128:
        raise ValueError(f"{n} values is not a multiple of 128")
    if block_rows % (_UNPACK_SUB * v):
        raise ValueError(
            f"block_rows {block_rows} not a multiple of {_UNPACK_SUB * v}")
    x = packed.reshape(-1, 128)
    in_rows = block_rows // v
    operands = [x]
    in_specs = [pl.BlockSpec((in_rows, 128), lambda i: (i, 0))]
    if v > 1:
        for c in _unpack_consts(width):
            operands.append(jnp.asarray(c, jnp.bfloat16))
            in_specs.append(pl.BlockSpec(c.shape, lambda i: (0, 0)))
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, width=width),
        grid=(pl.cdiv(n // 128, block_rows),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // 128, 128), dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(-1)


@functools.partial(
    jax.jit, static_argnames=("bits", "before", "width", "block", "interpret")
)
def plane_extract(q: jax.Array, *, bits: int, before: int, width: int,
                  block: int = 1024, interpret: bool = False) -> jax.Array:
    """Eq. (3): extract the plane at cumulative offset ``before`` of
    ``width`` bits from k-bit values (server-side divide)."""
    shape = q.shape
    a = q.ravel()
    n = a.shape[0]
    block = min(block, max(n, 8))
    npad, pad = _tile_1d(n, block)
    if pad:
        a = jnp.pad(a, (0, pad))
    a2 = a.reshape(-1, block)
    rows = a2.shape[0]
    brows = min(rows, 8)
    rpad = (-rows) % brows
    if rpad:
        a2 = jnp.pad(a2, ((0, rpad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_extract_kernel, bits=bits, before=before, width=width),
        grid=(a2.shape[0] // brows,),
        in_specs=[pl.BlockSpec((brows, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((brows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(a2.shape, q.dtype),
        interpret=interpret,
    )(a2)
    return out.reshape(-1)[:n].reshape(shape)
