"""Bit division and bit concatenation (paper eqs. 3 and 4).

Eq. (3) fetches the m-th fraction ("plane") of widths ``b`` from a k-bit
quantized integer:

    p<k, m> = (q<k> << b_{m-1}) >> (k - b_m + b_{m-1}),   b_0 = 0

where ``b_{m-1}`` here is the *cumulative* width of the planes before m
(the paper indexes cumulative widths; we make that explicit). Eq. (4)
reassembles whatever prefix of planes has been received:

    q'<k> = OR_m ( p<k, m> << (k - c_m) ),   c_m = b_1 + ... + b_m

Shifts are unsigned; everything is vectorized jnp and jit-safe, and the
same arithmetic is mirrored by the Pallas kernel in
``repro/kernels/bitplane.py`` (this module is its oracle's oracle).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import QuantizedTensor, container_dtype


def validate_widths(bits: int, widths: Sequence[int]) -> tuple[int, ...]:
    widths = tuple(int(w) for w in widths)
    if any(w < 1 for w in widths):
        raise ValueError(f"plane widths must be >= 1, got {widths}")
    if sum(widths) != bits:
        raise ValueError(f"plane widths {widths} must sum to bits={bits}")
    return widths


def cumulative(widths: Sequence[int]) -> tuple[int, ...]:
    out, acc = [], 0
    for w in widths:
        acc += w
        out.append(acc)
    return tuple(out)


def split_plane(q: jax.Array, bits: int, widths: Sequence[int], m: int) -> jax.Array:
    """Eq. (3): extract plane m (1-indexed, MSB planes first)."""
    widths = validate_widths(bits, widths)
    if not (1 <= m <= len(widths)):
        raise ValueError(f"m={m} outside [1, {len(widths)}]")
    cum = (0,) + cumulative(widths)
    before = cum[m - 1]
    w = widths[m - 1]
    # Work in a container wide enough that `<< before` cannot overflow.
    wide = q.astype(jnp.uint32)
    mask = jnp.uint32(2**bits - 1)
    shifted = (wide << before) & mask          # unsigned left shift within k bits
    plane = shifted >> (bits - w)              # keep w top bits
    return plane.astype(container_dtype(w))


def split(qt: QuantizedTensor, widths: Sequence[int]) -> list[jax.Array]:
    """All planes of a quantized tensor, MSB-first."""
    widths = validate_widths(qt.bits, widths)
    return [split_plane(qt.q, qt.bits, widths, m + 1) for m in range(len(widths))]


def concat(planes: Sequence[jax.Array], bits: int, widths: Sequence[int]) -> jax.Array:
    """Eq. (4): OR together the received prefix of planes.

    ``planes`` may be any prefix (1..n planes); the result is the k-bit
    integer with the unreceived low bits zero.
    """
    widths = validate_widths(bits, widths)
    if not (1 <= len(planes) <= len(widths)):
        raise ValueError(f"got {len(planes)} planes for {len(widths)} widths")
    cum = cumulative(widths)
    acc = jnp.zeros(planes[0].shape, dtype=jnp.uint32)
    for m, p in enumerate(planes, start=1):
        acc = acc | (p.astype(jnp.uint32) << (bits - cum[m - 1]))
    return acc.astype(container_dtype(bits))


@dataclasses.dataclass(frozen=True)
class PlaneSchedule:
    """Static description of a bit-division: k bits into widths b."""

    bits: int
    widths: tuple[int, ...]

    def __post_init__(self):
        object.__setattr__(self, "widths", validate_widths(self.bits, self.widths))

    @property
    def n_planes(self) -> int:
        return len(self.widths)

    @property
    def cumulative_bits(self) -> tuple[int, ...]:
        return cumulative(self.widths)

    def payload_bytes(self, n_elements: int, upto: int | None = None) -> int:
        """Dense-packed payload size of planes [1..upto]."""
        import math

        upto = self.n_planes if upto is None else upto
        return sum(math.ceil(n_elements * w / 8) for w in self.widths[:upto])


# The paper's default: 16-bit model sent as eight 2-bit planes
# (2 -> 4 -> 6 -> ... -> 16).
PAPER_DEFAULT = PlaneSchedule(bits=16, widths=(2,) * 8)


# ---------------------------------------------------------------------------
# Dense bit-packing: planes are transmitted packed (w bits per element),
# not one container-int per element — this is what keeps "no size
# increase" true on the wire. Packing is host work in NumPy: wire bytes
# live in host memory at both ends, and the group layout below — an
# (n, values-per-group) array — would be padded to (n, 128) lanes by a
# TPU's tiled layout. The receiver's unpack of a whole stage runs on
# the device instead (``kernels/bitplane.plane_unpack``, lane-dense);
# ``unpack_bits`` is its oracle and the host fallback.
# ---------------------------------------------------------------------------

def _bit_group(width: int) -> tuple[int, int]:
    """Smallest group of values whose packed bits land on a byte
    boundary: lcm(width, 8) bits = (values per group, bytes per group)."""
    import math

    L = width * 8 // math.gcd(width, 8)
    return L // width, L // 8


def _value_dtype(width: int) -> np.dtype:
    """Narrowest unsigned dtype holding a width-bit value."""
    return np.dtype(np.uint8 if width <= 8 else
                    np.uint16 if width <= 16 else np.uint32)


def _field(src: np.ndarray, right: int, nbits: int, dtype,
           left: int) -> np.ndarray:
    """``((src >> right) & (2^nbits - 1)) << left`` as ``dtype``, in
    place after the first shift, so one temporary is live at a time."""
    piece = src >> right
    piece &= 2**nbits - 1
    piece = piece.astype(dtype, copy=False)
    piece <<= left
    return piece


def pack_bits(plane, width: int) -> np.ndarray:
    """Pack a width-bit plane into a dense uint8 byte stream (big-endian
    bit order). Used by the wire format.

    Works at byte granularity: values are grouped so a group's bits fill
    whole bytes (lcm(width, 8) bits), and each output byte is assembled
    from the <= 2 + 8//width values overlapping it. Peak intermediate is
    O(n) — never an (n, width) bit matrix, which at width=16 would be a
    32x blowup over the packed payload.
    """
    flat = np.asarray(plane).ravel().astype(_value_dtype(width), copy=False)
    n = flat.shape[0]
    gv, gb = _bit_group(width)
    pad = (-n) % gv
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
    vals = flat.reshape(-1, gv)
    out = np.zeros((vals.shape[0], gb), np.uint8)
    for b in range(gb):
        lo_bit, hi_bit = 8 * b, 8 * b + 8
        for i in range(gv):
            v_lo, v_hi = i * width, (i + 1) * width
            o_lo, o_hi = max(lo_bit, v_lo), min(hi_bit, v_hi)
            if o_lo >= o_hi:
                continue
            nbits = o_hi - o_lo
            out[:, b] |= _field(vals[:, i], v_hi - o_hi, nbits, np.uint8,
                                hi_bit - o_hi)
    return out.reshape(-1)[: -(-n * width // 8)]


def unpack_bits(packed, width: int, n_elements: int) -> np.ndarray:
    """Inverse of :func:`pack_bits`; returns uint32 values in [0, 2^w).
    Byte-granular like :func:`pack_bits`: O(n) peak intermediates.
    A payload too short for ``n_elements`` values raises (a truncated
    wire payload must never decode to silent zeros); extra trailing
    bytes are ignored."""
    packed = np.asarray(packed, np.uint8).ravel()
    need = -(-n_elements * width // 8)
    if packed.shape[0] < need:
        raise ValueError(
            f"packed payload has {packed.shape[0]} bytes, need {need} "
            f"for {n_elements} width-{width} values")
    gv, gb = _bit_group(width)
    groups = -(-n_elements // gv)
    by = packed[:need]
    pad = groups * gb - need
    if pad:
        by = np.concatenate([by, np.zeros(pad, np.uint8)])
    bys = by.reshape(groups, gb)
    vdt = _value_dtype(width)
    out = np.zeros((groups, gv), vdt)
    for i in range(gv):
        v_lo, v_hi = i * width, (i + 1) * width
        for b in range(gb):
            lo_bit, hi_bit = 8 * b, 8 * b + 8
            o_lo, o_hi = max(lo_bit, v_lo), min(hi_bit, v_hi)
            if o_lo >= o_hi:
                continue
            nbits = o_hi - o_lo
            out[:, i] |= _field(bys[:, b], hi_bit - o_hi, nbits, vdt,
                                v_hi - o_hi)
    return out.reshape(-1)[:n_elements].astype(np.uint32, copy=False)


@dataclasses.dataclass(frozen=True)
class PackedPlane:
    """One plane as the wire carries it: ``n_elements`` values of
    ``width`` bits, packed by :func:`pack_bits` into ``data``. The
    PlaneStore takes these as they are and unpacks them on the device
    where it can (``kernels/bitplane.plane_unpack``), on the host with
    :meth:`unpack` where it cannot."""

    data: bytes
    width: int
    n_elements: int

    def __post_init__(self):
        need = -(-self.n_elements * self.width // 8)
        if len(self.data) != need:
            raise ValueError(
                f"packed plane has {len(self.data)} bytes, need {need} for "
                f"{self.n_elements} width-{self.width} values")

    def unpack(self) -> np.ndarray:
        return unpack_bits(np.frombuffer(self.data, np.uint8), self.width,
                           self.n_elements)
