"""Progressive client: byte stream -> device-resident PlaneStore.

Consumes the wire format produced by :mod:`repro.core.wire` incrementally
(arbitrary chunk boundaries — a transport delivers bytes, not planes).
Completed planes are checked and handed, still packed
(:class:`~repro.core.bitplanes.PackedPlane`), to a shared
:class:`~repro.core.plane_store.PlaneStore`: they are buffered and
flushed as one *batched* OR launch per stage completion (eq. 4), the
store unpacking them on the device, and ``materialize()`` is the
store's incremental eq. (5) — tensors untouched since the last call
are served from cache.

Fault tolerance (wire v3)
-------------------------
The PlaneStore OR is irreversible: one corrupt plane poisons its
accumulator for the rest of the session. On a v3 (integrity-framed)
stream the client therefore *verifies before it ingests*:

* every unit's CRC32 + sequence number is checked the moment its bytes
  are complete — BEFORE its body is decoded or reaches the store;
* a unit that fails verification is **quarantined**: its bytes are
  consumed (lengths come from the header, so stream sync survives) but
  nothing reaches the store, and a NACK entry is recorded for the
  transport to re-request (:meth:`ProgressiveClient.feed_repair`);
* verified units are OR-ed strictly in sequence order — a verified
  unit behind an unrepaired gap is *held* (never OR-ed early), which
  preserves both the per-tensor MSB-first prefix invariant and
  bit-identity with the clean stream at every checkpoint;
* the client exposes a durable resume cursor ``(unit_seq,
  byte_offset)``: everything before it has arrived (good or NACKed), so
  a dropped connection resumes there without re-shipping verified
  units; quarantined units behind the cursor are repaired per-unit.

v1/v2 streams have no integrity frames and keep their original
byte-identical decode path.

This is the framework's equivalent of the paper's browser client; the
serving engine drives the same store with its pytree receiver.
"""
from __future__ import annotations

import struct
from typing import Callable

from repro import obs as _obs
from repro.core import wire
from repro.core.bitplanes import PackedPlane
from repro.core.plane_store import PlaneStore


class ProgressiveClient:
    """Incremental decoder of the progressive wire format."""

    def __init__(self, on_stage_complete: Callable[[int], None] | None = None,
                 *, mesh=None):
        # mesh=None: single-device flat-buffer store. With a serving
        # mesh, decoded planes route shard-local into a
        # ShardedPlaneStore (each model shard ORs only its own segment
        # of the plane — no host gather, no replicated OR).
        self._mesh = mesh
        self._buf = bytearray()
        self._meta = None
        self._layout: wire.StageLayout | None = None
        self.store: PlaneStore | None = None
        self._pending: list[tuple[int, PackedPlane]] = []  # un-OR-ed
        self._cursor = 0          # absolute offset of next undecoded byte
        self._stage = 0           # completed stages
        self._entry = 0           # next entry within current stage
        self._on_stage_complete = on_stage_complete
        # -- v3 integrity state (inert for v1/v2 streams) ------------------
        self.header_failed = False      # header CRC mismatch: resend from 0
        self._units: list[tuple[int, int, int, int]] = []  # flat entries
        self._unit_offsets: list[int] = []
        self._checkpoints: list[int] = []
        self._next_unit = 0             # stream position, in units
        self._ready: dict[int, tuple[int, PackedPlane]] = {}  # seq -> (t, plane)
        self._verified: set[int] = set()
        self._nacks: dict[int, str] = {}          # seq -> quarantine reason
        self._contig = 0                # all seq < _contig verified
        self._ingested_upto = 0         # all seq < this OR-ed (or queued)
        self.quarantine_log: list[dict] = []
        self.duplicate_units = 0

    # -- feeding -----------------------------------------------------------
    def feed(self, chunk: bytes) -> None:
        if self.header_failed:
            # the transport is expected to restart the stream from byte
            # 0 (see resume_cursor); accept the fresh bytes
            self.header_failed = False
        with _obs.get_tracer().span("client_append"):
            self._buf.extend(chunk)
        self._advance()
        if _obs.enabled():
            reg = _obs.get_registry()
            reg.counter("client_bytes_fed_total",
                        "bytes fed to the progressive client").inc(
                            len(chunk))
            seq, off = self.resume_cursor
            reg.gauge("client_resume_cursor_unit",
                      "first unit not fully arrived").set(seq)
            reg.gauge("client_resume_cursor_byte",
                      "wire offset of the resume cursor").set(off)

    @property
    def stages_complete(self) -> int:
        return self._stage

    @property
    def bytes_fed(self) -> int:
        return len(self._buf)

    @property
    def complete(self) -> bool:
        if self._layout is None:
            return False
        if self.integrity:
            return self._stage == len(self._checkpoints)
        return self._stage == len(self._layout.stages)

    @property
    def header_ready(self) -> bool:
        return self._meta is not None

    @property
    def expected_total_bytes(self) -> int | None:
        return self._layout.total_bytes if self._layout else None

    @property
    def integrity(self) -> bool:
        """True once a v3 (integrity-framed) header has been decoded."""
        return bool(self._layout is not None and self._layout.integrity)

    # -- v3 transport interface --------------------------------------------
    @property
    def nacks(self) -> dict[int, str]:
        """Quarantined units awaiting re-request: ``{seq: reason}``."""
        return dict(self._nacks)

    @property
    def resume_cursor(self) -> tuple[int, int]:
        """Durable resume point ``(unit_seq, byte_offset)``: the first
        unit whose bytes have not fully arrived on the stream, and its
        absolute wire offset. Everything before it arrived (verified or
        NACKed — NACKs are repaired per-unit, not by replay), so a
        reconnect replays from here without re-shipping verified
        units. ``(0, 0)`` until the header verifies."""
        if not self.integrity:
            if self._layout is None:
                return (0, 0)
            done = sum(len(s) for s in self._layout.stages[:self._stage])
            return (done + self._entry, self._cursor)
        if self._next_unit >= len(self._units):
            return (len(self._units), self._layout.total_bytes)
        return (self._next_unit, self._unit_offsets[self._next_unit])

    @property
    def verified_units(self) -> int:
        return len(self._verified)

    def drop_unconsumed(self) -> int:
        """Discard buffered bytes past the last complete unit (a
        partial frame cut off by a disconnect). The transport replays
        from :attr:`resume_cursor` after this; returns the number of
        bytes dropped."""
        dropped = len(self._buf) - self._cursor
        if dropped > 0:
            del self._buf[self._cursor:]
        return dropped

    def rewind_to_gap(self) -> tuple[int, int]:
        """Connection-level resync after the transport detects a
        desynchronized stream (length-changing faults: truncation,
        duplication, reordering). Drops unconsumed buffered bytes,
        rewinds the stream position to the first *unverified* unit and
        clears quarantine entries at/after it (they re-arrive
        in-stream); already-verified units past the gap are kept and
        simply skipped as duplicates on replay. Returns the new
        ``(unit_seq, byte_offset)`` cursor the transport must replay
        from."""
        if not self.integrity:
            raise RuntimeError("rewind_to_gap requires a v3 integrity stream")
        self.drop_unconsumed()
        gap = self._contig
        for seq in [s for s in self._nacks if s >= gap]:
            del self._nacks[seq]
        self._next_unit = gap
        if gap >= len(self._units):
            return (gap, self._layout.total_bytes)
        return (gap, self._unit_offsets[gap])

    def feed_repair(self, seq: int, payload: bytes) -> bool:
        """Deliver a re-requested unit out of band. ``payload`` is the
        unit's full on-wire bytes (integrity frame included) and is
        verified exactly like stream bytes — a corrupt repair stays
        quarantined (returns False) and the NACK entry survives for the
        next retry. Repairing an already-verified unit is a duplicate:
        dropped, counted, returns True."""
        if not self.integrity:
            raise RuntimeError("feed_repair requires a v3 integrity stream")
        if seq < 0 or seq >= len(self._units):
            raise ValueError(f"repair seq {seq} out of range")
        if seq in self._verified:
            self.duplicate_units += 1
            _obs.get_registry().counter(
                "client_duplicate_units_total",
                "duplicate unit deliveries dropped").inc()
            return True
        ok = self._verify_and_stash(seq, bytes(payload), origin="repair")
        if ok:
            self._nacks.pop(seq, None)
            self._advance_contig()
        _obs.get_registry().counter(
            "client_repairs_total",
            "out-of-band unit repairs").inc(ok=ok)
        return ok

    # -- internal machinery --------------------------------------------------
    def _advance(self) -> None:
        if self._meta is None:
            if not self._try_header():
                return
        if self._layout.integrity:
            self._advance_v3()
        else:
            self._advance_stream()

    def _try_header(self) -> bool:
        if len(self._buf) < 12:
            return False
        version, n = struct.unpack("<II", bytes(self._buf[4:12]))
        if version == wire.VERSION_INTEGRITY and n > wire.MAX_HEADER_BYTES:
            # corrupted length field would stall the stream forever;
            # flag it so the transport restarts from byte 0
            self._quarantine_header(
                f"header declares {n} body bytes (cap "
                f"{wire.MAX_HEADER_BYTES})")
            return False
        hdr_len = 12 + n
        if version == wire.VERSION_INTEGRITY:
            hdr_len += wire.HEADER_CRC_BYTES
        if len(self._buf) < hdr_len:
            return False
        try:
            self._meta, hdr = wire.decode_header(bytes(self._buf))
        except wire.WireFormatError as e:
            # only a v3 stream can *recover* from a bad header (the
            # caller knows to restart); v1/v2 keeps the old hard error
            if version == wire.VERSION_INTEGRITY:
                self._quarantine_header(str(e))
                return False
            raise
        self._layout = wire.layout_from_header(self._meta, hdr)
        self._cursor = hdr
        if self._mesh is not None:
            from repro.core.plane_store import ShardedPlaneStore
            self.store = ShardedPlaneStore.from_wire_meta(
                self._meta, self._mesh)
        else:
            self.store = PlaneStore.from_wire_meta(self._meta)
        if self._layout.integrity:
            self._units = [e for st in self._layout.stages for e in st]
            self._unit_offsets = self._layout.unit_offsets()
            cps, acc = [], 0
            for st in self._layout.stages:
                acc += len(st)
                cps.append(acc)
            self._checkpoints = cps
        return True

    def _quarantine_header(self, reason: str) -> None:
        self.header_failed = True
        self._meta = None
        self._buf.clear()
        self._cursor = 0
        self.quarantine_log.append({"seq": None, "target": "header",
                                    "reason": reason})

    def _payload(self, nbytes: int) -> bytes:
        """The ``nbytes`` at the cursor, copied out of the buffer once.
        The memoryview is a temporary: a live export of ``_buf`` would
        make the next ``feed``'s extend raise ``BufferError``."""
        a = self._cursor
        return bytes(memoryview(self._buf)[a:a + nbytes])

    # -- v1/v2: trusted in-order stream -------------------------------------
    def _advance_stream(self) -> None:
        # Queue completed planes; the eq. (4) OR happens in batched
        # flushes, not per plane.
        assert self._layout is not None
        while self._stage < len(self._layout.stages):
            entries = self._layout.stages[self._stage]
            with _obs.get_tracer().span("client_decode") as sp:
                n = self._decode_arrived(entries)
                if sp is not None:
                    sp["planes"] = n
            if self._entry < len(entries):
                return
            self._stage += 1
            self._entry = 0
            self._flush()
            if self._on_stage_complete:
                self._on_stage_complete(self._stage)

    def _decode_arrived(self, entries) -> int:
        """Check the current stage's planes whose bytes have all
        arrived, in order, and queue them packed; returns how many."""
        n = 0
        while self._entry < len(entries):
            idx, w, nbytes, n_el = entries[self._entry]
            if len(self._buf) - self._cursor < nbytes:
                break
            self._pending.append((idx, wire.packed_plane(
                self._payload(nbytes), w, n_el, framed=self._layout.framed)))
            self._cursor += nbytes
            self._entry += 1
            n += 1
        return n

    # -- v3: verify-before-ingest --------------------------------------------
    def _advance_v3(self) -> None:
        with _obs.get_tracer().span("client_decode") as sp:
            first = self._next_unit
            while self._next_unit < len(self._units):
                seq = self._next_unit
                nbytes = self._units[seq][2]
                if len(self._buf) - self._cursor < nbytes:
                    break
                payload = self._payload(nbytes)
                self._cursor += nbytes
                self._next_unit += 1
                if seq in self._verified:
                    # duplicated bytes on the stream (e.g. an injected
                    # repeat already repaired out of band)
                    self.duplicate_units += 1
                    continue
                if self._verify_and_stash(seq, payload, origin="stream"):
                    self._nacks.pop(seq, None)
            if sp is not None:
                sp["planes"] = self._next_unit - first
        self._advance_contig()

    def _verify_and_stash(self, seq: int, payload: bytes,
                          origin: str) -> bool:
        """CRC/seq-check one on-wire unit; decode its body to packed
        bytes and stage it for in-order ingest on success, quarantine on
        failure. Decode errors after a *passing* CRC (possible only for
        malformed repair lengths) quarantine too — nothing unverified
        can reach the store."""
        idx, w, nbytes, n_el = self._units[seq]
        reason = None
        try:
            got_seq, body = wire.verify_unit(payload)
            if got_seq != seq:
                reason = f"sequence mismatch: frame says {got_seq}, " \
                         f"stream position says {seq}"
            elif len(payload) != nbytes:
                reason = (f"unit is {len(payload)} bytes on the wire, "
                          f"header says {nbytes}")
        except wire.WireFormatError as e:
            reason = str(e)
        if reason is None:
            try:
                plane = wire.packed_plane(body, w, n_el, framed=True)
            except wire.WireFormatError as e:
                reason = f"verified frame but undecodable body: {e}"
        if reason is not None:
            self._nacks[seq] = reason
            self.quarantine_log.append({"seq": seq, "origin": origin,
                                        "reason": reason})
            _obs.get_registry().counter(
                "client_quarantined_total",
                "units quarantined before ingest").inc(origin=origin)
            return False
        self._ready[seq] = (idx, plane)
        self._verified.add(seq)
        _obs.get_registry().counter(
            "client_units_verified_total",
            "integrity-verified units").inc(origin=origin)
        return True

    def _advance_contig(self) -> None:
        """Advance the verified-prefix pointer, and OR ready units in
        strict sequence order whenever it crosses a checkpoint —
        mirroring the v1/v2 per-stage flush so the store's state at
        each stage completion is bit-identical to the clean stream."""
        while self._contig in self._verified:
            self._contig += 1
        while (self._stage < len(self._checkpoints)
               and self._checkpoints[self._stage] <= self._contig):
            cp = self._checkpoints[self._stage]
            self._ingest_ready_below(cp)
            self._flush()
            self._stage += 1
            if self._on_stage_complete:
                self._on_stage_complete(self._stage)

    def _ingest_ready_below(self, bound: int) -> None:
        """Queue verified units with seq in [_ingested_upto, bound) for
        the batched OR. Strict seq order keeps each tensor's planes
        MSB-first; callers guarantee the range is fully verified."""
        for seq in range(self._ingested_upto, bound):
            self._pending.append(self._ready.pop(seq))
        self._ingested_upto = max(self._ingested_upto, bound)

    def _flush(self) -> None:
        """Push buffered planes into the store: one batched Pallas
        launch per container dtype (per plane round)."""
        if self._pending:
            if _obs.enabled():
                reg = _obs.get_registry()
                reg.counter("client_planes_ored_total",
                            "planes OR-ed into the store").inc(
                                len(self._pending))
                reg.histogram("client_flush_planes",
                              "planes per batched flush").observe(
                                  len(self._pending))
            self.store.ingest(self._pending)
            self._pending = []
            if _obs.enabled():
                _obs.get_registry().gauge(
                    "store_resident_bytes",
                    "accumulator bytes resident on device").set(
                        self.store.resident_bytes())

    # -- inference-side view -------------------------------------------------
    def materialize(self):
        """Current approximate params as a flat {path: array} dict (eq. 5;
        sliced tensors are stacked back along their slice axis). Planes
        of a partially-received stage are flushed first, so mid-stage
        precision is never left on the floor. On a v3 stream only the
        *verified contiguous prefix* flushes — units behind a
        quarantined gap never reach the accumulators early."""
        if self.store is None:
            raise RuntimeError("header not received yet")
        if self.integrity:
            self._ingest_ready_below(self._contig)
        self._flush()
        return dict(self.store.materialize_leaves())
