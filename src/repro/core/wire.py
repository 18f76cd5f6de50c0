"""Wire format for progressive model transmission.

v1 layout (all little-endian):

    [HEADER]   json (length-prefixed): per-tensor path/shape/dtype/lo/hi,
               plane schedule, stage order. Shipped before stage 1.
    [STAGE 1]  concat of dense bit-packed planes, in policy priority order
    [STAGE 2]  ...
    ...
    [STAGE n]

``total wire bytes == header + singleton quantized payload`` — the
paper's "no size increase" claim, verified by tests. Stages can be cut at
arbitrary byte offsets by the transport; the client state machine in
``transmission/client.py`` resumes mid-plane.

v2 layout (``encode(model, schedule=..., entropy_coded=...)``) keeps the
12-byte prefix — the first byte after MAGIC is the explicit version —
but replaces the fixed stage-major plane order with an explicit
(tensor, plane) *unit* list carried in the header:

    [HEADER]   v1 meta + "units" [[t,p],...] + "checkpoints" (prefix
               unit counts standing in for stage ends) + "unit_bytes"
               (on-wire size of each unit incl. frame) + "entropy" flag
    [UNIT 0]   <mode u8><reserved u8> + payload
    [UNIT 1]   ...

Units are MSB-first *within* each tensor (the eq.-(5) contiguous-prefix
invariant ``PlaneStore.ingest`` enforces) but interleave freely *across*
tensors — see :mod:`repro.core.calibrate`. Each unit body is either the
raw packed plane (``MODE_RAW``) or its entropy-coded form
(:mod:`repro.core.entropy`), chosen per-plane so a coded unit is never
larger than raw + the 2-byte frame. ``packed_plane`` undoes the framing
and hands on the same packed bytes a v1 stream carries, so everything
downstream of the client — PlaneStore ingest, OR-reassembly, the eq.-(5)
affine — is untouched and the fully-received model is bit-identical to
the v1 stream's.

v3 layout (``encode(model, integrity=True)``) is the fault-tolerant
wire: the same unit stream as v2, but every unit is preceded by an
8-byte integrity frame ``<seq u32><crc u32>`` (seq = unit index in the
schedule; crc = CRC32 over seq+mode+reserved+payload) and the header
carries a trailing whole-header CRC32. Lengths still come exclusively
from the header (``unit_bytes``), so framing is length-safe: a flipped
bit anywhere in a unit is caught by the unit CRC, a flipped bit in the
header by the header CRC, and the client can quarantine + re-request
individual units without losing stream sync. Framing overhead is
bounded and reported (:func:`framing_overhead`): 4 header bytes +
``FRAME_BYTES_V3`` per unit.

``encode(model)`` with no schedule still emits byte-identical v1
streams; ``decode_header`` accepts all three versions.
"""
from __future__ import annotations

import dataclasses
import json
import struct
import zlib

import numpy as np

from repro.core import bitplanes, entropy
from repro.core.progressive import ProgressiveModel

MAGIC = b"PGNJ"
VERSION = 1            # legacy stage-major stream (the default)
VERSION_SCHEDULED = 2  # scheduled/entropy-coded unit stream
VERSION_INTEGRITY = 3  # integrity-framed unit stream (CRC + seq)
SUPPORTED_VERSIONS = (VERSION, VERSION_SCHEDULED, VERSION_INTEGRITY)
FRAME_BYTES = 2        # v2 per-unit frame: <mode u8><reserved u8>
HEADER_CRC_BYTES = 4   # v3: CRC32 of the full header, appended to it
FRAME_BYTES_V3 = 10    # v3 per-unit frame: <seq u32><crc u32><mode u8><u8>
# Plausibility cap on the header's declared JSON length: a corrupted
# length field must not make a client wait forever for bytes that will
# never come. Real headers are a few KB per thousand tensors.
MAX_HEADER_BYTES = 1 << 28


class WireFormatError(ValueError):
    """Malformed wire bytes (truncation, garbage, bad lengths). Raised
    with offset context instead of letting struct/json/index errors
    escape. Subclasses ValueError so legacy callers keep working."""


class WireIntegrityError(WireFormatError):
    """v3 integrity violation: CRC mismatch or unexpected sequence
    number. Distinct from plain format errors so receivers can route it
    to quarantine/re-request instead of treating the stream as
    unparseable."""


def _path_key(path: tuple) -> str:
    return path_str(path)


def path_str(path: tuple) -> str:
    """Render a jax tree path as 'a/b/0/c' regardless of key kind."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _tensor_meta(model: ProgressiveModel) -> list[dict]:
    return [
        {
            "path": _path_key(t.path),
            "shape": list(t.shape),
            "dtype": np.dtype(t.orig_dtype).name,
            "lo": float(t.lo),
            "hi": float(t.hi),
            "bits": t.plan.schedule.bits,
            "widths": list(t.plan.schedule.widths),
            "priority": t.plan.priority,
            "slice_axis": t.slice_axis,
            "slice_idx": t.slice_idx,
            "n_slices": t.n_slices,
        }
        for t in model.tensors
    ]


def encode_header(model: ProgressiveModel) -> bytes:
    meta = {
        "version": VERSION,
        "n_stages": model.n_stages,
        "tensors": _tensor_meta(model),
    }
    body = json.dumps(meta).encode()
    return MAGIC + struct.pack("<II", VERSION, len(body)) + body


def decode_header(buf: bytes):
    """Parse the stream header. Returns ``(meta, header_bytes)``.

    Malformed input raises :class:`WireFormatError` with offset
    context (never a bare struct/json/index error); a v3 header whose
    trailing CRC32 does not cover its bytes raises
    :class:`WireIntegrityError`."""
    if len(buf) < 12:
        raise WireFormatError(
            f"truncated header: need 12 prefix bytes, have {len(buf)}")
    if buf[:4] != MAGIC:
        raise WireFormatError(
            f"bad magic at offset 0: {bytes(buf[:4])!r} != {MAGIC!r}")
    version, n = struct.unpack("<II", buf[4:12])
    if version not in SUPPORTED_VERSIONS:
        raise WireFormatError(f"unsupported version {version} at offset 4")
    if n > MAX_HEADER_BYTES:
        raise WireFormatError(
            f"header declares {n} body bytes at offset 8 "
            f"(cap {MAX_HEADER_BYTES}) — length field is corrupt")
    end = 12 + n
    if len(buf) < end:
        raise WireFormatError(
            f"truncated header: body ends at offset {end}, have {len(buf)}")
    if version == VERSION_INTEGRITY:
        if len(buf) < end + HEADER_CRC_BYTES:
            raise WireFormatError(
                f"truncated header: v3 CRC ends at offset "
                f"{end + HEADER_CRC_BYTES}, have {len(buf)}")
        (crc,) = struct.unpack("<I", buf[end:end + HEADER_CRC_BYTES])
        got = zlib.crc32(bytes(buf[:end])) & 0xFFFFFFFF
        if got != crc:
            raise WireIntegrityError(
                f"header CRC mismatch over [0, {end}): "
                f"computed {got:#010x}, stored {crc:#010x}")
        end += HEADER_CRC_BYTES
    try:
        meta = json.loads(bytes(buf[12:12 + n]).decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise WireFormatError(
            f"unparseable header body at offsets [12, {12 + n}): {e}"
        ) from None
    if not isinstance(meta, dict) or "tensors" not in meta:
        raise WireFormatError(
            f"header body at offsets [12, {12 + n}) is valid JSON but "
            f"not a wire header (missing 'tensors')")
    if meta.get("version", version) != version:
        # the prefix version is outside the v3 CRC's reach by necessity
        # (it selects whether a CRC exists at all) — cross-checking it
        # against the JSON body closes the gap where a flipped prefix
        # byte demotes a v3 stream to an unchecked v2 parse
        raise WireFormatError(
            f"version mismatch: prefix says {version} at offset 4, "
            f"header body says {meta['version']}")
    return meta, end


def encode_stage(model: ProgressiveModel, s: int) -> bytes:
    """Dense bit-packed payload of one stage (no per-plane framing needed:
    sizes are derivable from the header)."""
    chunks = []
    for idx, plane in model.stage(s):
        t = model.tensors[idx]
        w = t.plan.schedule.widths[s - 1]
        chunks.append(bitplanes.pack_bits(plane, w).tobytes())
    return b"".join(chunks)


def encode_unit(model: ProgressiveModel, t_idx: int, p: int,
                *, entropy_coded: bool = False) -> bytes:
    """One v2 shipment unit: 2-byte frame + (raw | entropy-coded) packed
    plane ``p`` of tensor ``t_idx``. Coded only when it wins, so the
    unit is never larger than the raw packed plane + FRAME_BYTES."""
    t = model.tensors[t_idx]
    w = t.plan.schedule.widths[p]
    packed = bitplanes.pack_bits(t.planes[p], w).tobytes()
    if entropy_coded:
        mode, body = entropy.encode(packed)
    else:
        mode, body = entropy.MODE_RAW, packed
    return struct.pack("<BB", mode, 0) + body


def encode_v2(model: ProgressiveModel, schedule=None,
              *, entropy_coded: bool = True) -> bytes:
    """Scheduled/entropy-coded stream. ``schedule`` is a
    :class:`~repro.core.calibrate.TransmissionSchedule` (anything with
    ``units``/``checkpoints``); ``None`` falls back to the v1
    stage-major order (entropy coding alone still applies). Unit sizes
    are data-dependent, so payloads are encoded first and their on-wire
    sizes recorded in the header."""
    if schedule is None:
        from repro.core.calibrate import uniform_schedule
        schedule = uniform_schedule(model)
    payloads = [encode_unit(model, t, p, entropy_coded=entropy_coded)
                for t, p in schedule.units]
    meta = {
        "version": VERSION_SCHEDULED,
        "n_stages": len(schedule.checkpoints),
        "tensors": _tensor_meta(model),
        "units": [[int(t), int(p)] for t, p in schedule.units],
        "checkpoints": [int(c) for c in schedule.checkpoints],
        "unit_bytes": [len(u) for u in payloads],
        "entropy": bool(entropy_coded),
    }
    body = json.dumps(meta).encode()
    header = MAGIC + struct.pack("<II", VERSION_SCHEDULED, len(body)) + body
    return header + b"".join(payloads)


def frame_unit(seq: int, unit: bytes) -> bytes:
    """Wrap a v2-framed unit body (``<mode u8><reserved u8>`` +
    payload) in the v3 integrity frame. The CRC covers the sequence
    number AND the body, so any flipped bit in the on-wire unit —
    including its seq — fails verification."""
    seq_b = struct.pack("<I", seq)
    crc = zlib.crc32(seq_b + unit) & 0xFFFFFFFF
    return seq_b + struct.pack("<I", crc) + unit


def verify_unit(payload: bytes) -> tuple[int, bytes]:
    """Check a v3 unit's integrity frame. Returns ``(seq, body)`` where
    ``body`` is the v2-framed unit (feed it to ``packed_plane(...,
    framed=True)``). Raises :class:`WireIntegrityError` on CRC mismatch
    and :class:`WireFormatError` on truncation."""
    if len(payload) < FRAME_BYTES_V3:
        raise WireFormatError(
            f"v3 unit shorter than its {FRAME_BYTES_V3}-byte frame: "
            f"{len(payload)} bytes")
    seq, crc = struct.unpack("<II", payload[:8])
    body = payload[8:]
    got = zlib.crc32(payload[:4] + body) & 0xFFFFFFFF
    if got != crc:
        raise WireIntegrityError(
            f"unit CRC mismatch (frame claims seq {seq}): "
            f"computed {got:#010x}, stored {crc:#010x}")
    return seq, body


def encode_v3(model: ProgressiveModel, schedule=None,
              *, entropy_coded: bool = False) -> bytes:
    """Integrity-framed stream: v2's unit layout with a per-unit
    ``<seq u32><crc u32>`` frame and a whole-header CRC32. The payload
    bytes inside each frame are exactly the v2 unit encoding, so a
    fully-received v3 stream reconstructs bit-identically to the v1/v2
    streams of the same model."""
    if schedule is None:
        from repro.core.calibrate import uniform_schedule
        schedule = uniform_schedule(model)
    payloads = [
        frame_unit(seq, encode_unit(model, t, p, entropy_coded=entropy_coded))
        for seq, (t, p) in enumerate(schedule.units)
    ]
    meta = {
        "version": VERSION_INTEGRITY,
        "n_stages": len(schedule.checkpoints),
        "tensors": _tensor_meta(model),
        "units": [[int(t), int(p)] for t, p in schedule.units],
        "checkpoints": [int(c) for c in schedule.checkpoints],
        "unit_bytes": [len(u) for u in payloads],
        "entropy": bool(entropy_coded),
    }
    body = json.dumps(meta).encode()
    header = MAGIC + struct.pack("<II", VERSION_INTEGRITY, len(body)) + body
    header += struct.pack("<I", zlib.crc32(header) & 0xFFFFFFFF)
    return header + b"".join(payloads)


def framing_overhead(meta: dict) -> dict:
    """v3 integrity-framing overhead, from a decoded header: absolute
    bytes and the fraction of the total stream they cost. Zero for
    v1/v2. The bound is structural — HEADER_CRC_BYTES plus
    FRAME_BYTES_V3 - FRAME_BYTES per unit — so it is derivable (and
    asserted) without ever shipping the stream."""
    version = meta.get("version", VERSION)
    if version != VERSION_INTEGRITY:
        return {"version": version, "overhead_bytes": 0, "overhead_frac": 0.0}
    n_units = len(meta["units"])
    overhead = HEADER_CRC_BYTES + n_units * (FRAME_BYTES_V3 - FRAME_BYTES)
    total = sum(meta["unit_bytes"])
    return {
        "version": version,
        "n_units": n_units,
        "overhead_bytes": overhead,
        "overhead_frac": overhead / max(total, 1),
        "per_unit_bytes": FRAME_BYTES_V3 - FRAME_BYTES,
    }


def encode(model: ProgressiveModel, *, schedule=None,
           entropy_coded: bool = False, integrity: bool = False) -> bytes:
    """Default call emits byte-identical v1 streams; requesting a
    schedule and/or entropy coding switches to v2; ``integrity=True``
    selects the fault-tolerant v3 framing (composable with both)."""
    if integrity:
        return encode_v3(model, schedule, entropy_coded=entropy_coded)
    if schedule is None and not entropy_coded:
        return encode_header(model) + b"".join(
            encode_stage(model, s) for s in range(1, model.n_stages + 1)
        )
    return encode_v2(model, schedule, entropy_coded=entropy_coded)


@dataclasses.dataclass
class StageLayout:
    """Byte layout derived purely from the header — what a client needs
    to slice an incoming byte stream into (tensor, plane) payloads.

    v1: one stage per plane rank, entries dense-packed. v2
    (``framed=True``): "stages" are checkpoint groups of schedule
    units; each entry's ``payload_bytes`` INCLUDES the 2-byte frame,
    and payloads must pass through :func:`packed_plane` with
    ``framed=True`` to strip the frame / undo entropy coding."""

    header_bytes: int
    # per stage: list of (tensor_idx, width, payload_bytes, n_elements)
    stages: list[list[tuple[int, int, int, int]]]
    framed: bool = False
    # v3: payloads additionally carry the <seq u32><crc u32> integrity
    # frame and MUST pass wire.verify_unit before packed_plane
    integrity: bool = False

    def unit_offsets(self) -> list[int]:
        """Absolute wire offset of each unit's first byte, flattened
        across stages (what a resume cursor / re-request indexes)."""
        offs, off = [], self.header_bytes
        for st in self.stages:
            for e in st:
                offs.append(off)
                off += e[2]
        return offs

    @property
    def stage_bytes(self) -> list[int]:
        return [sum(e[2] for e in st) for st in self.stages]

    @property
    def total_bytes(self) -> int:
        return self.header_bytes + sum(self.stage_bytes)


def layout_from_header(meta: dict, header_bytes: int) -> StageLayout:
    version = meta.get("version", VERSION)
    if version in (VERSION_SCHEDULED, VERSION_INTEGRITY):
        return _layout_v2(meta, header_bytes,
                          integrity=version == VERSION_INTEGRITY)
    n_stages = meta["n_stages"]
    order = sorted(
        range(len(meta["tensors"])),
        key=lambda i: (meta["tensors"][i]["priority"], i),
    )
    stages = []
    for s in range(1, n_stages + 1):
        entries = []
        for i in order:
            t = meta["tensors"][i]
            if s <= len(t["widths"]):
                w = t["widths"][s - 1]
                n_el = int(np.prod(t["shape"])) if t["shape"] else 1
                nbytes = -(-n_el * w // 8)
                entries.append((i, w, nbytes, n_el))
        stages.append(entries)
    return StageLayout(header_bytes=header_bytes, stages=stages)


def _layout_v2(meta: dict, header_bytes: int,
               *, integrity: bool = False) -> StageLayout:
    units = meta["units"]
    unit_bytes = meta["unit_bytes"]
    if len(unit_bytes) != len(units):
        raise ValueError("unit_bytes length mismatch")
    entries = []
    for (t_idx, p), nbytes in zip(units, unit_bytes):
        t = meta["tensors"][t_idx]
        w = t["widths"][p]
        n_el = int(np.prod(t["shape"])) if t["shape"] else 1
        entries.append((int(t_idx), int(w), int(nbytes), n_el))
    stages, lo = [], 0
    for cp in meta["checkpoints"]:
        stages.append(entries[lo:cp])
        lo = cp
    if lo != len(entries):
        raise ValueError("checkpoints do not cover all units")
    return StageLayout(header_bytes=header_bytes, stages=stages,
                       framed=True, integrity=integrity)


def packed_plane(payload: bytes, width: int, n_elements: int,
                 *, framed: bool = False) -> bitplanes.PackedPlane:
    """One plane payload as packed bytes, checked but not unpacked.
    ``framed=True`` (v2/v3 body) strips the 2-byte mode frame and undoes
    entropy coding first; the recovered packed bytes are identical to
    the raw path, so reconstruction downstream is bit-exact either way.
    Malformed input raises :class:`WireFormatError` with length
    context. v3 callers strip/verify the integrity frame via
    :func:`verify_unit` first."""
    raw_len = -(-n_elements * width // 8)
    if framed:
        if len(payload) < FRAME_BYTES:
            raise WireFormatError(
                f"framed payload shorter than its {FRAME_BYTES}-byte "
                f"frame: {len(payload)} bytes")
        mode = payload[0]
        try:
            payload = entropy.decode(mode, memoryview(payload)[FRAME_BYTES:],
                                     raw_len)
        except Exception as e:
            raise WireFormatError(
                f"undecodable unit body (mode {mode}, "
                f"{len(payload) - FRAME_BYTES} coded bytes for "
                f"{raw_len} raw): {e}") from None
    if len(payload) != raw_len:
        raise WireFormatError(
            f"plane payload is {len(payload)} bytes, expected {raw_len} "
            f"({n_elements} elements x {width} bits)")
    return bitplanes.PackedPlane(payload, width, n_elements)


def decode_plane(payload: bytes, width: int, n_elements: int,
                 *, framed: bool = False) -> np.ndarray:
    """:func:`packed_plane`, unpacked on the host into uint32 values."""
    return packed_plane(payload, width, n_elements, framed=framed).unpack()
