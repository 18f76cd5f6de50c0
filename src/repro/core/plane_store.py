"""PlaneStore: the single device-resident receiver runtime (eqs. 4+5).

Every client of progressive transmission — the pytree receiver
(``core/progressive.ReceiverState``), the byte-stream client
(``transmission/client.ProgressiveClient``), and the quantized-resident
serving path (``serving/quantized``) — used to carry its own copy of
the OR/shift/stacking arithmetic. They now all sit on this one store.

Layout
------
All tensors sharing a container dtype live in ONE flat 1-D uint buffer;
each tensor occupies a block-aligned segment ``[offset, offset+size)``
(padding between segments is dead space, < ``block`` elements per
tensor). Per-tensor metadata (shape, plane schedule, quantization
range, slice info) lives in :class:`TensorSlot` views.

Upgrades (eq. 4)
----------------
``ingest([(tensor_idx, plane), ...])`` assembles one flat plane buffer
plus a segment table (first block and shift per run of tensors) and
issues ONE batched
``plane_or_segments`` Pallas launch per container dtype — O(1) in the
number of tensors, vs. the old one-``pallas_call``-per-tensor loop.
Block alignment is what makes the per-block shift well defined: a block
never straddles two tensors. Planes may arrive as the wire's packed
bytes (:class:`~repro.core.bitplanes.PackedPlane`); the store then
uploads those and unpacks them on the device (``plane_unpack``).

Materialization (eq. 5)
-----------------------
``materialize()`` is *incremental*: only tensors whose accumulator
changed since the last call are re-dequantized; unchanged float leaves
come out of a cache (same array objects — downstream jit sees identical
buffer donations). Sliced tensors (expert banks) are restacked along
their slice axis only when one of their slices is dirty.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.core.bitplanes import PackedPlane, PlaneSchedule
from repro.core.quantize import (QuantizedTensor, affine_span,
                                 container_dtype, dequant_affine,
                                 dequant_constants, dequantize_buffers)
from repro.kernels import ops

# One grid step of plane_or_segments: 8 sublanes x 128 lanes.
DEFAULT_BLOCK = 1024


@functools.partial(jax.jit, static_argnames=("segs",))
def _scatter_segments(buf: jax.Array, out: jax.Array,
                      segs: tuple) -> jax.Array:
    """Write compact OR results back into the flat buffer. ``segs`` is
    ``((buf_offset, compact_pos, length), ...)``. One jitted call: the
    update chain fuses into a single new buffer (one allocation per
    round, not one full copy per segment as eager .at[].set would pay).
    NOT donated: ``copy()`` stores share buffer objects, so donating
    here would invalidate a sibling store's accumulator."""
    for off, pos, length in segs:
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, jax.lax.dynamic_slice_in_dim(out, pos, length), off, axis=0)
    return buf


def _plane_size(plane) -> int:
    if isinstance(plane, PackedPlane):
        return plane.n_elements
    return int(np.prod(np.shape(plane)) or 1)


def _count_unpacked(n: int, where: str) -> None:
    if n and _obs.enabled():
        _obs.get_registry().counter(
            "store_planes_unpacked_total",
            "packed planes unpacked by the store").inc(n, where=where)


def _device_width(planes: Sequence) -> int | None:
    """The width the device can unpack a dtype's share of a round at:
    every plane packed, all at one width dividing 8 (so each tensor's
    bytes start on a byte boundary of the packed staging buffer)."""
    widths = {p.width if isinstance(p, PackedPlane) else None
              for p in planes}
    if len(widths) == 1:
        (w,) = widths
        if w is not None and 8 % w == 0:
            return w
    return None


def next_plane_shift(schedule: PlaneSchedule, received: int) -> int:
    """Eq. (4) shift for the next arriving plane: after ``received``
    planes, plane ``received+1`` lands at ``bits - c_{received+1}``.
    The ONLY place this arithmetic lives."""
    if received >= schedule.n_planes:
        raise ValueError(
            f"all {schedule.n_planes} planes already received")
    return schedule.bits - schedule.cumulative_bits[received]


def received_bits(schedule: PlaneSchedule, received: int) -> int:
    """Effective precision m = sum of the first ``received`` widths."""
    return schedule.cumulative_bits[received - 1] if received > 0 else 0


def _entries_from_model(model, indices: Sequence[int] | None = None
                        ) -> list[dict]:
    """Per-tensor descriptor dicts from a server-side ProgressiveModel
    (keys are pytree paths) — the pre-layout form both the flat
    :class:`PlaneStore` and the per-shard sub-stores of
    :class:`ShardedPlaneStore` build from."""
    tensors = (model.tensors if indices is None
               else [model.tensors[i] for i in indices])
    return [{"key": t.path, "schedule": t.plan.schedule, "lo": t.lo,
             "hi": t.hi, "shape": tuple(t.shape),
             "orig_dtype": t.orig_dtype, "slice_axis": t.slice_axis,
             "slice_idx": t.slice_idx} for t in tensors]


def _entries_from_wire_meta(meta: Mapping) -> list[dict]:
    """Per-tensor descriptor dicts from a decoded wire header (keys are
    path strings)."""
    return [{"key": t["path"],
             "schedule": PlaneSchedule(bits=t["bits"],
                                       widths=tuple(t["widths"])),
             "lo": jnp.float32(t["lo"]), "hi": jnp.float32(t["hi"]),
             "shape": tuple(t["shape"]), "orig_dtype": np.dtype(t["dtype"]),
             "slice_axis": t.get("slice_axis"),
             "slice_idx": t.get("slice_idx", 0)} for t in meta["tensors"]]


@dataclasses.dataclass(frozen=True)
class TensorSlot:
    """Static per-tensor metadata: a view descriptor into a flat buffer."""

    key: Any                  # opaque leaf key (tuple path or path string)
    schedule: PlaneSchedule
    lo: jax.Array
    hi: jax.Array
    shape: tuple
    orig_dtype: Any
    offset: int               # element offset within the dtype's buffer
    size: int                 # n elements
    padded: int               # block-aligned span (size rounded up)
    slice_axis: int | None = None
    slice_idx: int = 0

    @property
    def bits(self) -> int:
        return self.schedule.bits

    @property
    def container(self):
        return container_dtype(self.bits)


class PlaneStore:
    """Device-resident accumulators for one progressive model.

    ``device`` commits every buffer (and every ingest upload) to one
    specific device — the per-shard sub-stores of
    :class:`ShardedPlaneStore` use this so each shard's planes are
    OR-ed on the device that owns them (shard-local ingest, no
    replicated OR). ``None`` keeps jax's default placement."""

    def __init__(self, slots: list[TensorSlot], *, block: int = DEFAULT_BLOCK,
                 device=None):
        self.block = block
        self.slots = slots
        self.device = device
        self.received = [0] * len(slots)
        # dtype name -> flat uint buffer (length: multiple of block)
        self.buffers: dict[str, jax.Array] = {}
        sizes: dict[str, int] = {}
        for t in slots:
            dt = np.dtype(t.container).name
            sizes[dt] = max(sizes.get(dt, 0), t.offset + t.padded)
        for dt, n in sizes.items():
            buf = jnp.zeros((n,), dtype=np.dtype(dt))
            if device is not None:
                buf = jax.device_put(buf, device)
            self.buffers[dt] = buf
        self._dirty: set[int] = set(range(len(slots)))
        self._leaf_cache: dict[Any, jax.Array] = {}
        self._qleaf_cache: dict[Any, QuantizedTensor] = {}
        self._qtrunc_cache: dict[tuple, QuantizedTensor] = {}
        self._acc_cache: dict[int, jax.Array] = {}
        # stacked eq.-(5) constants per batch of slot indices; lo/hi/
        # bits never change after the header, so never invalidated
        self._consts_cache: dict[tuple, tuple] = {}
        # per-key quantized-view affine constants (placed lo/hi/scale +
        # host lo/span mirrors); m-independent, so — unlike
        # _qleaf_cache — survives every ingest
        self._qmeta_cache: dict[Any, dict] = {}

    # -- construction ------------------------------------------------------
    @staticmethod
    def _layout(entries, block):
        """Assign (offset, padded) per entry, grouped by container dtype."""
        cursors: dict[str, int] = {}
        out = []
        for e in entries:
            dt = np.dtype(container_dtype(e["schedule"].bits)).name
            size = int(np.prod(e["shape"])) if e["shape"] else 1
            padded = -(-size // block) * block
            off = cursors.get(dt, 0)
            cursors[dt] = off + padded
            out.append((off, size, padded))
        return out

    @classmethod
    def _from_entries(cls, entries: list[dict], *,
                      block: int = DEFAULT_BLOCK, device=None) -> "PlaneStore":
        """Build from per-tensor descriptor dicts (no layout yet):
        key/schedule/lo/hi/shape/orig_dtype[/slice_axis/slice_idx]."""
        layout = cls._layout(entries, block)
        slots = [
            TensorSlot(
                key=e["key"], schedule=e["schedule"], lo=e["lo"], hi=e["hi"],
                shape=tuple(e["shape"]), orig_dtype=e["orig_dtype"],
                offset=off, size=size, padded=padded,
                slice_axis=e.get("slice_axis"),
                slice_idx=e.get("slice_idx", 0),
            )
            for e, (off, size, padded) in zip(entries, layout)
        ]
        return cls(slots, block=block, device=device)

    @classmethod
    def from_model(cls, model, *, block: int = DEFAULT_BLOCK,
                   indices: Sequence[int] | None = None) -> "PlaneStore":
        """Build from a server-side :class:`ProgressiveModel` (keys are
        pytree paths). ``indices`` restricts the store to a subset of
        the model's tensors (slot i is then ``model.tensors[indices[i]]``
        — a single-tensor store allocates one tensor's buffer, not the
        whole model's)."""
        return cls._from_entries(_entries_from_model(model, indices),
                                 block=block)

    @classmethod
    def from_wire_meta(cls, meta: Mapping, *, block: int = DEFAULT_BLOCK
                       ) -> "PlaneStore":
        """Build from a decoded wire header (keys are path strings)."""
        return cls._from_entries(_entries_from_wire_meta(meta), block=block)

    def copy(self) -> "PlaneStore":
        """Cheap snapshot: buffers are immutable jax arrays, so sharing
        them is safe; bookkeeping is shallow-copied. Lets the functional
        ``ReceiverState.receive`` keep value semantics for free."""
        new = object.__new__(PlaneStore)
        new.block = self.block
        new.slots = self.slots
        new.device = self.device
        new.received = list(self.received)
        new.buffers = dict(self.buffers)
        new._dirty = set(self._dirty)
        new._leaf_cache = dict(self._leaf_cache)
        new._qleaf_cache = dict(self._qleaf_cache)
        new._qtrunc_cache = dict(self._qtrunc_cache)
        new._acc_cache = dict(self._acc_cache)
        new._consts_cache = dict(self._consts_cache)
        new._qmeta_cache = dict(self._qmeta_cache)
        return new

    # -- views -------------------------------------------------------------
    def _slice_acc(self, i: int) -> jax.Array:
        t = self.slots[i]
        dt = np.dtype(t.container).name
        return self.buffers[dt][t.offset:t.offset + t.size].reshape(t.shape)

    def acc(self, i: int) -> jax.Array:
        """Tensor i's accumulator: a view into the flat buffer. Cached
        until the tensor's next ingest, so eager hot paths (per-token
        ``QuantizedLinearState.matmul``) don't re-slice per call. The
        cache fills only on explicit ``acc`` access — one-shot readers
        (materialize) slice without caching, so they don't pin a second
        copy of every accumulator."""
        got = self._acc_cache.get(i)
        if got is None:
            got = self._slice_acc(i)
            self._acc_cache[i] = got
        return got

    def quantized(self, i: int) -> QuantizedTensor:
        t = self.slots[i]
        return QuantizedTensor(q=self._slice_acc(i), lo=t.lo, hi=t.hi,
                               bits=t.bits, orig_dtype=t.orig_dtype)

    def effective_bits(self, i: int) -> int:
        return received_bits(self.slots[i].schedule, self.received[i])

    @property
    def n_tensors(self) -> int:
        return len(self.slots)

    def resident_bytes(self) -> int:
        return sum(b.size * b.dtype.itemsize for b in self.buffers.values())

    def fingerprint(self) -> dict[str, int]:
        """CRC32 of each flat accumulator buffer's bytes, keyed by
        container dtype. Two stores with the same layout have equal
        fingerprints iff their accumulator state is bit-identical —
        the cheap audit the fault-tolerance tests use to prove that a
        quarantined-and-repaired stream matches the clean stream at
        every checkpoint (and that a force-ingested corrupt plane
        diverges forever). Pulls buffers to host; debugging/audit use,
        not a hot path."""
        return {dt: int(zlib.crc32(np.asarray(buf).tobytes()))
                for dt, buf in sorted(self.buffers.items())}

    # -- eq. (4): batched upgrade -----------------------------------------
    def ingest(self, items: Sequence[tuple[int, Any]]) -> None:
        """OR a shipment of planes into the store. ``items`` holds
        ``(tensor_idx, plane)`` pairs, the plane given as its values or
        as the wire's :class:`PackedPlane`; each plane is the *next*
        plane of its tensor's schedule (the wire delivers them in
        order). One ``plane_or_segments`` launch per container dtype per
        round; a shipment carrying several planes of the same tensor is
        split into rounds (distinct shifts for the same segment can't
        share one OR).

        The whole shipment is validated up front, so a bad item leaves
        the store untouched — callers (e.g. the client's ``_flush``)
        may safely retry the identical shipment after a failure."""
        with _obs.get_tracer().span("store_ingest", planes=len(items)):
            self._ingest(items)

    def _ingest(self, items: Sequence[tuple[int, Any]]) -> None:
        pending = list(items)
        counts: dict[int, int] = {}
        for idx, plane in pending:
            t = self.slots[idx]
            n = _plane_size(plane)
            if n != t.size:
                raise ValueError(
                    f"plane for tensor {idx} has {n} elements, "
                    f"expected {t.size}")
            counts[idx] = counts.get(idx, 0) + 1
        for idx, c in counts.items():
            have, total = self.received[idx], self.slots[idx].schedule.n_planes
            if have + c > total:
                raise ValueError(
                    f"tensor {idx}: {have} planes received + {c} arriving "
                    f"exceeds schedule of {total}")
        while pending:
            round_items: dict[int, Any] = {}
            rest = []
            for idx, plane in pending:
                if idx in round_items:
                    rest.append((idx, plane))
                else:
                    round_items[idx] = plane
            self._ingest_round(round_items)
            pending = rest

    def _ingest_round(self, items: dict[int, Any]) -> None:
        """One OR round: the accumulator never round-trips through the
        host. Touched segments are gathered into a *compact* buffer
        (cheap XLA slices/concat, no kernel launches), the single
        ``plane_or_segments`` launch sweeps only those blocks, and the
        results go back via one fused scatter — a sparse shipment's OR
        work and transfers are O(touched bytes); the write-back is a
        single whole-buffer update (immutable arrays), not one per
        segment.

        Where a dtype's planes all arrived packed at one width ``w``
        dividing 8, the host lays their bytes out like the blocks (a
        tensor at element ``pos`` starts at byte ``pos * w / 8``),
        uploads ``w / 8`` bytes per element and ``plane_unpack`` expands
        them on the device. Otherwise (mixed or odd widths, value
        arrays) the plane is assembled from values on the host, packed
        planes unpacked there."""
        if _obs.enabled():
            reg = _obs.get_registry()
            reg.counter("store_or_rounds_total",
                        "batched plane-OR rounds").inc()
            reg.histogram("store_or_round_planes",
                          "planes per OR round").observe(len(items))
        by_dtype: dict[str, list[int]] = {}
        for idx in items:
            dt = np.dtype(self.slots[idx].container).name
            by_dtype.setdefault(dt, []).append(idx)
        tr = _obs.get_tracer()
        for dt, idxs in by_dtype.items():
            buf = self.buffers[dt]
            idxs.sort(key=lambda i: self.slots[i].offset)
            total = sum(self.slots[i].padded for i in idxs)
            full = total == buf.shape[0]
            w = _device_width([items[i] for i in idxs])
            with tr.span("store_assemble", dtype=dt):
                # segment table: one (first block, shift) entry per run
                # of consecutive tensors sharing a shift — a uniform
                # schedule collapses a whole stage to a single entry
                starts: list[int] = []
                seg_shifts: list[int] = []
                # the staging buffer is the DMA landing zone: one memcpy
                # pass and one upload, whatever the backend
                if w is None:
                    staging = np.zeros((total,), buf.dtype)
                else:
                    staging = np.zeros((total * w // 8,), np.uint8)
                pos = 0
                for idx in idxs:
                    t = self.slots[idx]
                    sh = next_plane_shift(t.schedule, self.received[idx])
                    if not seg_shifts or seg_shifts[-1] != sh:
                        starts.append(pos // self.block)
                        seg_shifts.append(sh)
                    p = items[idx]
                    if w is not None:
                        self._stage_packed(staging, pos * w // 8, p)
                    else:
                        if isinstance(p, PackedPlane):
                            p = p.unpack()
                        staging[pos:pos + t.size] = np.asarray(p).reshape(-1)
                    pos += t.padded
                table = (np.asarray(starts, np.int32),
                         np.asarray(seg_shifts, np.int32))
            _count_unpacked(
                sum(isinstance(items[i], PackedPlane) for i in idxs),
                "host" if w is None else "device")
            with tr.span("store_upload", dtype=dt):
                if self.device is None:
                    seg_starts, shifts = (jnp.asarray(a) for a in table)
                    plane = jnp.asarray(staging)
                else:
                    seg_starts, shifts, plane = jax.device_put(
                        (*table, staging), self.device)
            if w is not None:
                with tr.span("store_unpack", dtype=dt):
                    plane = ops.plane_unpack(plane, width=w, dtype=buf.dtype)
            with tr.span("store_or", dtype=dt):
                if full:
                    # Whole buffer touched (the common full-stage
                    # upgrade): segments are dense by layout, no
                    # gather/scatter needed.
                    self.buffers[dt] = ops.plane_or_segments(
                        buf, plane, seg_starts, shifts, block=self.block)
                else:
                    # Sparse shipment: sweep only the touched blocks —
                    # O(touched bytes), not O(whole per-dtype buffer).
                    compact = (buf[self.slots[idxs[0]].offset:
                                   self.slots[idxs[0]].offset + total]
                               if len(idxs) == 1 else
                               jnp.concatenate([
                                   buf[self.slots[i].offset:
                                       self.slots[i].offset
                                       + self.slots[i].padded]
                                   for i in idxs]))
                    out = ops.plane_or_segments(
                        compact, plane, seg_starts, shifts, block=self.block)
                    segs, pos = [], 0
                    for idx in idxs:
                        t = self.slots[idx]
                        segs.append((t.offset, pos, t.padded))
                        pos += t.padded
                    self.buffers[dt] = _scatter_segments(buf, out,
                                                         tuple(segs))
            del plane
        for idx in items:
            self.received[idx] += 1
            self._dirty.add(idx)
            self._acc_cache.pop(idx, None)
            key = self.slots[idx].key
            self._leaf_cache.pop(key, None)
            self._qleaf_cache.pop(key, None)
            for tk in [t for t in self._qtrunc_cache if t[0] == key]:
                self._qtrunc_cache.pop(tk)

    @staticmethod
    def _stage_packed(staging: np.ndarray, at: int,
                      plane: PackedPlane) -> None:
        """Copy a packed plane's bytes to ``staging[at:]``, with the
        unused low bits of a ragged last byte cleared so the padding
        elements after the tensor unpack to 0."""
        data = np.frombuffer(plane.data, np.uint8)
        end = at + data.size
        staging[at:end] = data
        tail = plane.n_elements * plane.width % 8
        if tail:
            staging[end - 1] &= (0xFF << (8 - tail)) & 0xFF

    # -- eq. (5): incremental materialization ------------------------------
    def _by_key(self) -> dict[Any, list[int]]:
        by_key: dict[Any, list[int]] = {}
        for i, t in enumerate(self.slots):
            by_key.setdefault(t.key, []).append(i)
        return by_key

    def _refresh_fp_leaves(self, stale: list[tuple[Any, list[int]]]) -> None:
        """Batch-dequantize every slot of the given keys and refill the
        leaf cache. The whole set is one :func:`dequantize_batch` call —
        O(1) host dispatches however many tensors an upgrade dirtied —
        with the stacked eq.-(5) constants cached across upgrades (lo/
        hi/bits are fixed at the header). This is what keeps an
        ``resident='fp'`` upgrade's refresh an enqueue, not a stall."""
        if not stale:
            return
        jobs = [i for _, idxs in stale for i in idxs]
        if _obs.enabled():
            reg = _obs.get_registry()
            reg.counter("store_refresh_dispatches_total",
                        "batched eq.-(5) refresh dispatches").inc()
            reg.histogram("store_refresh_slots",
                          "tensor slots per refresh dispatch").observe(
                              len(jobs))
        consts = self._consts_cache.get(tuple(jobs))
        if consts is None:
            consts = dequant_constants([self.slots[i].lo for i in jobs],
                                       [self.slots[i].hi for i in jobs],
                                       [self.slots[i].bits for i in jobs])
            self._consts_cache[tuple(jobs)] = consts
        vals = iter(dequantize_buffers(
            self.buffers,
            [(np.dtype(self.slots[i].container).name, self.slots[i].offset,
              self.slots[i].size, self.slots[i].shape) for i in jobs],
            [self.slots[i].bits for i in jobs],
            [self.effective_bits(i) for i in jobs],
            [np.dtype(self.slots[i].orig_dtype).name for i in jobs],
            constants=consts))
        for key, idxs in stale:
            parts = [(self.slots[i].slice_idx, self.slots[i].slice_axis,
                      next(vals)) for i in idxs]
            if len(parts) == 1 and parts[0][1] is None:
                leaf = parts[0][2]
            else:
                axis = parts[0][1]
                parts.sort(key=lambda x: x[0])
                leaf = jnp.stack([v for _, _, v in parts], axis=axis)
            self._leaf_cache[key] = leaf

    def _fp_leaf(self, key: Any, idxs: list[int]) -> jax.Array:
        """One dequantized float leaf (sliced tensors restacked), served
        from the leaf cache when untouched since the last rebuild —
        ``ingest`` pops touched keys, so cache presence means fresh."""
        cached = self._leaf_cache.get(key)
        if cached is not None and not any(i in self._dirty for i in idxs):
            return cached
        self._refresh_fp_leaves([(key, idxs)])
        return self._leaf_cache[key]

    def materialize_leaves(self) -> dict[Any, jax.Array]:
        """Dequantize into ``{key: array}``, restacking sliced tensors
        along their slice axis. Only keys touched since the last call
        are recomputed — batched into one :func:`dequantize_batch`
        call — and the rest are served from the leaf cache."""
        by_key = self._by_key()
        self._refresh_fp_leaves(
            [(key, idxs) for key, idxs in by_key.items()
             if self._leaf_cache.get(key) is None
             or any(i in self._dirty for i in idxs)])
        out = {key: self._leaf_cache[key] for key in by_key}
        self._dirty.clear()
        return out

    # -- quantized-resident views ------------------------------------------
    def _quantized_leaf(self, key: Any, idxs: list[int]
                        ) -> QuantizedTensor | None:
        """One leaf as a live :class:`QuantizedTensor`: ``q`` is the
        accumulator (a view into the flat buffer; sliced tensors restack
        their *uint* segments — still no float copy), and the eq.-(5)
        affine rides along as traced arrays shaped
        ``q.shape[:-2] + (1, 1)`` — exactly what ``lax.scan`` slices to
        the per-layer ``(1, 1)`` kernel operands. Returns None when the
        leaf can't feed a dequant matmul (ndim < 2, or slices along one
        of the two contracting dims)."""
        slots = [self.slots[i] for i in idxs]
        if len({s.bits for s in slots}) != 1:
            return None
        if len(idxs) == 1 and slots[0].slice_axis is None:
            q = self._slice_acc(idxs[0])
            if q.ndim < 2:
                return None
            order = [(idxs[0], slots[0])]
            ax = None
        else:
            ax = slots[0].slice_axis
            if ax is None or any(s.slice_axis != ax for s in slots):
                return None
            stacked_ndim = len(slots[0].shape) + 1
            if ax >= stacked_ndim - 2:
                return None
            order = sorted(zip(idxs, slots), key=lambda p: p[1].slice_idx)
            q = jnp.stack([self._slice_acc(i) for i, _ in order], axis=ax)
        meta_shape = q.shape[:-2] + (1, 1)

        def place(vals, dtype) -> jax.Array:
            """Per-slice scalars -> broadcastable metadata: values vary
            along the slice axis, broadcast everywhere else."""
            a = jnp.asarray(vals, dtype)
            if ax is not None:
                shp = [1] * q.ndim
                shp[ax] = len(order)
                a = a.reshape(tuple(shp))
            return jnp.broadcast_to(a, meta_shape)

        # Only `offset` and `received_bits` depend on the planes
        # received so far; lo/hi/scale are fixed at the header. They are
        # built (and their host mirrors captured) exactly once per key,
        # so a precision upgrade's metadata refresh is a handful of
        # dispatches, not a per-slice eager affine recomputation — the
        # host cost that made sharded upgrades look like stalls.
        const = self._qmeta_cache.get(key)
        if const is None:
            scales = [dequant_affine(s.lo, s.hi, s.bits)[0]
                      for _, s in order]
            spans = [affine_span(s.lo, s.hi) for _, s in order]
            const = {
                "lo": place([s.lo for _, s in order], jnp.float32),
                "hi": place([s.hi for _, s in order], jnp.float32),
                "scale": place(scales, jnp.float32),
                # exact f32 bits of the jnp computation, pulled once
                "lo_np": np.asarray(jnp.stack(
                    [jnp.asarray(s.lo, jnp.float32) for _, s in order])),
                "span_np": np.asarray(jnp.stack(spans)),
            }
            self._qmeta_cache[key] = const
        ms = np.asarray([received_bits(s.schedule, self.received[i])
                         for i, s in order], np.int32)
        # offset = lo + span * 0.5**(m+1): same two f32 ops on the same
        # f32 values as dequant_affine (its m == 0 branch equals the
        # closed form at m = 0), so the recompute is bit-identical
        half_lsb = np.ldexp(np.float32(1.0), -(ms + 1)).astype(np.float32)
        off = const["lo_np"] + const["span_np"] * half_lsb

        def shape_np(a: np.ndarray) -> np.ndarray:
            """Host-side reshape/broadcast — free views, no dispatch."""
            if ax is not None:
                shp = [1] * q.ndim
                shp[ax] = len(order)
                a = a.reshape(tuple(shp))
            return np.ascontiguousarray(np.broadcast_to(a, meta_shape))

        # both per-upgrade metadata fields in ONE transfer
        off_b, ms_b = shape_np(off.astype(np.float32)), shape_np(ms)
        if self.device is None:
            off_d, ms_d = jnp.asarray(off_b), jnp.asarray(ms_b)
        else:
            off_d, ms_d = jax.device_put((off_b, ms_b), self.device)
        return QuantizedTensor(
            q=q,
            lo=const["lo"],
            hi=const["hi"],
            bits=slots[0].bits,
            orig_dtype=slots[0].orig_dtype,
            scale=const["scale"],
            offset=off_d,
            received_bits=ms_d,
        )

    def quantized_leaves(self, eligible=None, *, bits: int | None = None
                         ) -> dict[Any, Any]:
        """The param pytree's leaves with weight tensors as *live*
        :class:`QuantizedTensor` views over the flat accumulators —
        the quantized-resident serving surface. ``eligible`` is an
        optional ``key -> bool`` predicate restricting which leaves go
        quantized (e.g. matmul weights only); everything else — and any
        leaf a dequant matmul can't consume — falls back to the same
        incremental float materialization ``materialize_leaves`` uses.

        ``bits=b`` hands out the *truncated-precision* view instead: the
        same accumulators, behaving as if only ``min(b, received)`` bits
        had arrived (:meth:`QuantizedTensor.truncate` — a deferred plane
        mask plus a recomputed eq.-(5) affine; ``q`` is the *same*
        array object as the full view's, so a draft model built from
        this view adds zero resident weight bytes next to the target).
        Ineligible leaves fall back to the *shared* full-precision float
        leaf — tiny non-matmul remainders are not worth degrading.

        Like ``materialize_leaves`` this is incremental: clean keys come
        out of a cache as the *same* leaf objects, so a jitted consumer
        sees identical buffers for untouched weights. After an
        ``ingest``, only touched keys rebuild — a precision upgrade is
        the ingest plus this metadata refresh, no ``materialize()``."""
        out: dict[Any, Any] = {}
        with _obs.get_tracer().span("store_quantized_leaves"):
            for key, idxs in self._by_key().items():
                if eligible is None or eligible(key):
                    got = self._qleaf_cache.get(key)
                    if got is None:
                        got = self._quantized_leaf(key, idxs)
                        if got is not None:
                            self._qleaf_cache[key] = got
                    if got is not None:
                        if bits is not None:
                            # clamp per leaf: schedules may differ per
                            # tensor, and bits >= the leaf's own width
                            # just means "full precision, masked form" —
                            # the no-op mask keeps the draft and target
                            # views treedef-identical, so one decode
                            # executable serves both
                            b_eff = min(bits, got.bits)
                            trunc = self._qtrunc_cache.get((key, b_eff))
                            if trunc is None:
                                trunc = got.truncate(b_eff)
                                self._qtrunc_cache[(key, b_eff)] = trunc
                            got = trunc
                        out[key] = got
                        continue
                out[key] = self._fp_leaf(key, idxs)
        self._dirty.clear()
        return out

    def dirty_keys(self) -> set:
        return {self.slots[i].key for i in self._dirty}


def _key_path_str(key) -> str:
    """Leaf key as an 'a/b/c' path string (wire stores already use
    strings; pull-mode stores use jax tree-path tuples)."""
    if isinstance(key, str):
        return key
    from repro.core.wire import path_str

    return path_str(key)


class ShardedPlaneStore:
    """Multi-device PlaneStore: per-model-shard sub-stores, shard-local
    ingest, globally-sharded leaf views.

    Each model shard ``j`` owns an ordinary :class:`PlaneStore`
    committed to ``mesh`` device column ``j`` — the same flat per-dtype
    uint accumulators, block-aligned layout and batched
    ``plane_or_segments`` upgrade, just device-pinned. A tensor routes
    to the sub-stores one of three ways, along the same axes
    :func:`repro.launch.sharding.serving_spec_for_param` shards the
    param it backs:

    * **expert slices** (``slice_axis`` set, slice count divisible by
      the shard count): each per-expert slice is already its own store
      tensor, so slice ``e`` goes *whole* to shard ``e // (E/n)`` —
      expert-parallel ingest with no plane surgery;
    * **split dense** (>= 2-D, serving spec shards a dim divisibly):
      each arriving plane is split along that dim and each segment is
      uploaded to — and OR-ed on — its owning shard only;
    * **whole** (1-D, indivisible, or unshardable): round-robin to one
      sub-store; the leaf is replicated at materialization.

    Every plane row is OR-ed exactly once on exactly one device (no
    host gather of accumulators, no replicated OR); launch counts are
    the per-sub-store sums. Leaves come back as *global* jax arrays:
    sharded leaves are zero-copy-assembled from the sub-stores' buffer
    views via ``jax.make_array_from_single_device_arrays`` (plus
    per-data-row replica transfers when the mesh has a data axis > 1),
    whole-routed leaves are replicated. The eq.-(5) affine constants
    stay *shard-local*: each sub-store batches its own
    ``dequantize_buffers`` refresh with its own cached constants, so an
    upgrade stays O(1) host dispatches per shard. Everything is
    dispatch-only — ingest and refresh never block on device results,
    preserving the zero-stall upgrade property."""

    def __init__(self, entries: list[dict], mesh, *,
                 block: int = DEFAULT_BLOCK):
        if mesh.axis_names != ("data", "model"):
            raise ValueError(
                f"ShardedPlaneStore wants a ('data', 'model') mesh, got "
                f"axes {mesh.axis_names}")
        self.mesh = mesh
        self.block = block
        self._n_model = int(mesh.shape["model"])
        self._n_data = int(mesh.shape["data"])
        self._devs = np.asarray(mesh.devices).reshape(
            self._n_data, self._n_model)
        self.keys = [e["key"] for e in entries]
        self.schedules = [e["schedule"] for e in entries]
        self.shapes = [tuple(e["shape"]) for e in entries]
        self.received = [0] * len(entries)
        # key -> ordered global tensor idxs (slices group under one key)
        self._groups: dict[Any, list[int]] = {}
        for i, k in enumerate(self.keys):
            self._groups.setdefault(k, []).append(i)
        # routing (per key): ("expert", axis) | ("split", axis) |
        # ("whole", owner_shard)
        self._route: dict[Any, tuple] = {}
        # global idx -> [(shard, plane_segment_index)] in shard order
        self._placement: list[list[tuple[int, int]]] = [
            [] for _ in entries]
        per_shard: list[list[dict]] = [[] for _ in range(self._n_model)]
        # key -> shard -> local slot idxs (for per-shard leaf refresh)
        self._local_by_key: dict[Any, dict[int, list[int]]] = {}
        rr = 0  # round-robin cursor for whole-routed groups
        for key, idxs in self._groups.items():
            locs = self._local_by_key.setdefault(key, {})

            def _place(i: int, j: int, entry: dict) -> None:
                self._placement[i].append((j, len(per_shard[j])))
                locs.setdefault(j, []).append(len(per_shard[j]))
                per_shard[j].append(entry)

            e0 = entries[idxs[0]]
            ax = e0.get("slice_axis")
            if (ax is not None and len(idxs) > 1
                    and len(idxs) % self._n_model == 0
                    and all(entries[i].get("slice_axis") == ax
                            for i in idxs)):
                ordered = sorted(idxs, key=lambda i: entries[i]["slice_idx"])
                per = len(ordered) // self._n_model
                for r, i in enumerate(ordered):
                    _place(i, r // per, entries[i])
                self._route[key] = ("expert", ax)
                continue
            split_ax = (self._split_axis(e0) if len(idxs) == 1 and ax is None
                        else None)
            if split_ax is not None:
                i = idxs[0]
                shape = list(e0["shape"])
                shape[split_ax] //= self._n_model
                local = dict(e0, shape=tuple(shape))
                for j in range(self._n_model):
                    _place(i, j, local)
                self._route[key] = ("split", split_ax)
                continue
            owner = rr % self._n_model
            rr += 1
            for i in idxs:
                _place(i, owner, entries[i])
            self._route[key] = ("whole", owner)
        self.substores = [
            PlaneStore._from_entries(per_shard[j], block=block,
                                     device=self._devs[0, j])
            for j in range(self._n_model)
        ]
        self._g_dirty: set[int] = set(range(len(entries)))
        self._g_leaf_cache: dict[Any, jax.Array] = {}
        self._g_qleaf_cache: dict[Any, QuantizedTensor] = {}
        self._g_qtrunc_cache: dict[tuple, QuantizedTensor] = {}
        # globally-placed lo/hi/scale per key (m-independent — survives
        # ingest; only offset/received_bits reassemble per upgrade)
        self._g_qmeta_cache: dict[Any, dict] = {}

    def _split_axis(self, entry: dict) -> int | None:
        """Dim to split a dense tensor on, from the serving sharding
        rule (reuses launch/sharding's spec; lazy import, launch sits
        above core)."""
        from repro.launch.sharding import serving_spec_for_param

        shape = entry["shape"]
        if len(shape) < 2:
            return None
        spec = serving_spec_for_param(_key_path_str(entry["key"]), shape,
                                      self.mesh)
        for d, name in enumerate(spec):
            if name == "model":
                return d
        return None

    # -- construction ------------------------------------------------------
    @classmethod
    def from_model(cls, model, mesh, *,
                   block: int = DEFAULT_BLOCK) -> "ShardedPlaneStore":
        return cls(_entries_from_model(model), mesh, block=block)

    @classmethod
    def from_wire_meta(cls, meta: Mapping, mesh, *,
                       block: int = DEFAULT_BLOCK) -> "ShardedPlaneStore":
        return cls(_entries_from_wire_meta(meta), mesh, block=block)

    def copy(self) -> "ShardedPlaneStore":
        new = object.__new__(ShardedPlaneStore)
        for attr in ("mesh", "block", "_n_model", "_n_data", "_devs",
                     "keys", "schedules", "shapes", "_groups", "_route",
                     "_placement", "_local_by_key"):
            setattr(new, attr, getattr(self, attr))
        new.received = list(self.received)
        new.substores = [s.copy() for s in self.substores]
        new._g_dirty = set(self._g_dirty)
        new._g_leaf_cache = dict(self._g_leaf_cache)
        new._g_qleaf_cache = dict(self._g_qleaf_cache)
        new._g_qtrunc_cache = dict(self._g_qtrunc_cache)
        new._g_qmeta_cache = dict(self._g_qmeta_cache)
        return new

    # -- basic views -------------------------------------------------------
    @property
    def n_tensors(self) -> int:
        return len(self.keys)

    def effective_bits(self, i: int) -> int:
        return received_bits(self.schedules[i], self.received[i])

    def resident_bytes(self) -> int:
        return sum(s.resident_bytes() for s in self.substores)

    def fingerprint(self) -> dict[str, int]:
        """Per-shard accumulator CRCs (``shard<j>/<dtype>`` keys) — the
        sharded counterpart of :meth:`PlaneStore.fingerprint`."""
        out: dict[str, int] = {}
        for j, s in enumerate(self.substores):
            for dt, crc in s.fingerprint().items():
                out[f"shard{j}/{dt}"] = crc
        return out

    def dirty_keys(self) -> set:
        return {self.keys[i] for i in self._g_dirty}

    def acc(self, i: int) -> jax.Array:
        """Tensor i's accumulator, re-joined across shards (compat /
        debug surface; the serving path reads the sharded leaves and
        never host-gathers)."""
        kind, _ = self._route[self.keys[i]]
        if kind != "split":
            j, lidx = self._placement[i][0]
            return self.substores[j].acc(lidx)
        ax = self._route[self.keys[i]][1]
        return jnp.concatenate(
            [jnp.asarray(np.asarray(self.substores[j].acc(lidx)))
             for j, lidx in self._placement[i]], axis=ax)

    def quantized(self, i: int) -> QuantizedTensor:
        t0 = self.substores[self._placement[i][0][0]].slots[
            self._placement[i][0][1]]
        return QuantizedTensor(q=self.acc(i), lo=t0.lo, hi=t0.hi,
                               bits=t0.bits, orig_dtype=t0.orig_dtype)

    # -- eq. (4): shard-local batched upgrade ------------------------------
    def ingest(self, items: Sequence[tuple[int, Any]]) -> None:
        """Route a shipment to the owning shards and OR it there.
        Validation is global and up front (a bad item leaves every
        sub-store untouched); each sub-store then runs its own batched
        ``plane_or_segments`` rounds on its own device — launches are
        the per-shard sums, and no accumulator bytes cross devices.
        Packed planes are unpacked on the host first: a split tensor's
        rows are routed by value, and a shard's piece of a packed plane
        need not start on a byte."""
        with _obs.get_tracer().span("store_ingest", planes=len(items)):
            self._ingest(items)

    def _ingest(self, items: Sequence[tuple[int, Any]]) -> None:
        pending = list(items)
        counts: dict[int, int] = {}
        for idx, plane in pending:
            size = int(np.prod(self.shapes[idx]) or 1)
            n = _plane_size(plane)
            if n != size:
                raise ValueError(
                    f"plane for tensor {idx} has {n} elements, "
                    f"expected {size}")
            counts[idx] = counts.get(idx, 0) + 1
        for idx, c in counts.items():
            have, total = self.received[idx], self.schedules[idx].n_planes
            if have + c > total:
                raise ValueError(
                    f"tensor {idx}: {have} planes received + {c} arriving "
                    f"exceeds schedule of {total}")
        _count_unpacked(sum(isinstance(p, PackedPlane) for _, p in pending),
                        "host")
        pending = [(i, p.unpack() if isinstance(p, PackedPlane) else p)
                   for i, p in pending]
        sub_items: list[list[tuple[int, Any]]] = [
            [] for _ in range(self._n_model)]
        for idx, plane in pending:
            key = self.keys[idx]
            kind, ax = self._route[key]
            if kind == "split":
                # Host planes (the wire path) split on host — zero-copy
                # views, one direct H2D per shard. Device-resident
                # planes (pull-mode serving) split ON DEVICE: np.asarray
                # here would be a blocking D2H sync on the upgrade path.
                if isinstance(plane, jax.Array):
                    arr = jnp.reshape(plane, self.shapes[idx])
                    pieces = jnp.split(arr, self._n_model, axis=ax)
                else:
                    arr = np.asarray(plane).reshape(self.shapes[idx])
                    pieces = np.split(arr, self._n_model, axis=ax)
                for (j, lidx), piece in zip(self._placement[idx], pieces):
                    sub_items[j].append((lidx, piece))
            else:
                j, lidx = self._placement[idx][0]
                sub_items[j].append((lidx, plane))
        for j, its in enumerate(sub_items):
            if its:
                self.substores[j]._ingest(its)
        for idx, _ in pending:
            self.received[idx] += 1
            self._g_dirty.add(idx)
            key = self.keys[idx]
            self._g_leaf_cache.pop(key, None)
            self._g_qleaf_cache.pop(key, None)
            for tk in [t for t in self._g_qtrunc_cache if t[0] == key]:
                self._g_qtrunc_cache.pop(tk)

    # -- global leaf assembly ----------------------------------------------
    def _assemble(self, pieces: list, global_shape: tuple, spec) -> jax.Array:
        """Zero-copy global array from per-shard pieces: piece ``j`` is
        normally already committed to device column ``j`` (a lazy view
        of that sub-store's buffer or a shard-local dequant result), so
        the row-0 ``device_put`` is a no-op view; host-built pieces
        (per-slice metadata) get committed here, and extra data rows get
        async replica transfers."""
        from jax.sharding import NamedSharding

        sharding = NamedSharding(self.mesh, spec)
        # one batched transfer for all (data row, shard) targets — the
        # per-piece device_put loop was most of an upgrade's assembly
        # dispatch cost
        srcs = [p for _ in range(self._n_data) for p in pieces]
        devs = [self._devs[i, j] for i in range(self._n_data)
                for j in range(len(pieces))]
        arrs = jax.device_put(srcs, devs)
        return jax.make_array_from_single_device_arrays(
            tuple(global_shape), sharding, arrs)

    def _replicated(self, x):
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(x, NamedSharding(self.mesh, PartitionSpec()))

    def _spec_at(self, ndim: int, ax: int):
        from jax.sharding import PartitionSpec

        names = [None] * ndim
        names[ax] = "model"
        return PartitionSpec(*names)

    def _refresh_fp(self, keys: list) -> None:
        """Per-shard batched eq.-(5) refresh for the given keys: ONE
        ``dequantize_buffers`` dispatch per sub-store (shard-local
        affine constants via each sub-store's own consts cache), then
        global assembly of each leaf."""
        if not keys:
            return
        for j, sub in enumerate(self.substores):
            stale = [(key, self._local_by_key[key][j]) for key in keys
                     if j in self._local_by_key[key]]
            if stale:
                sub._refresh_fp_leaves(stale)
                for _, lidxs in stale:
                    sub._dirty.difference_update(lidxs)
        for key in keys:
            kind, ax = self._route[key]
            if kind == "whole":
                leaf = self._replicated(self.substores[ax]._leaf_cache[key])
            else:
                shards = sorted(self._local_by_key[key])
                pieces = [self.substores[j]._leaf_cache[key] for j in shards]
                shape = list(pieces[0].shape)
                shape[ax] *= self._n_model
                leaf = self._assemble(pieces, tuple(shape),
                                      self._spec_at(len(shape), ax))
            self._g_leaf_cache[key] = leaf

    def _fp_leaf(self, key) -> jax.Array:
        cached = self._g_leaf_cache.get(key)
        if cached is not None and not any(
                i in self._g_dirty for i in self._groups[key]):
            return cached
        self._refresh_fp([key])
        return self._g_leaf_cache[key]

    def materialize_leaves(self) -> dict[Any, jax.Array]:
        """Global ``{key: array}`` view; stale keys are re-dequantized
        in one batched dispatch per sub-store and re-assembled, clean
        keys come back as the *same* global array objects."""
        stale = [key for key, idxs in self._groups.items()
                 if self._g_leaf_cache.get(key) is None
                 or any(i in self._g_dirty for i in idxs)]
        self._refresh_fp(stale)
        out = {key: self._g_leaf_cache[key] for key in self._groups}
        self._g_dirty.clear()
        return out

    # -- quantized-resident views ------------------------------------------
    def _sub_qleaf(self, j: int, key) -> QuantizedTensor | None:
        sub = self.substores[j]
        got = sub._qleaf_cache.get(key)
        if got is None:
            got = sub._quantized_leaf(key, self._local_by_key[key][j])
            if got is not None:
                sub._qleaf_cache[key] = got
        return got

    def _quantized_leaf(self, key) -> QuantizedTensor | None:
        # lo/hi/scale are fixed at the header, so their global placement
        # (_g_qmeta_cache) happens once per key; an upgrade's refresh
        # only reassembles q + offset + received_bits — the per-upgrade
        # host dispatch count is what makes sharded upgrades enqueues.
        kind, ax = self._route[key]
        const_fields = ("lo", "hi", "scale")
        live_fields = ("offset", "received_bits")
        if kind == "whole":
            local = self._sub_qleaf(ax, key)
            if local is None:
                return None
            const = self._g_qmeta_cache.get(key)
            if const is None:
                const = {f: self._replicated(getattr(local, f))
                         for f in const_fields}
                self._g_qmeta_cache[key] = const
            q_r, off_r, rb_r = self._replicated(
                (local.q, local.offset, local.received_bits))
            return QuantizedTensor(
                q=q_r, bits=local.bits, orig_dtype=local.orig_dtype,
                offset=off_r, received_bits=rb_r, **const)
        shards = sorted(self._local_by_key[key])
        locals_ = [self._sub_qleaf(j, key) for j in shards]
        if any(l is None for l in locals_):
            return None
        l0 = locals_[0]
        gshape = list(l0.q.shape)
        gshape[ax] *= self._n_model
        q = self._assemble([l.q for l in locals_], tuple(gshape),
                           self._spec_at(len(gshape), ax))
        const = self._g_qmeta_cache.get(key)
        if ax < len(gshape) - 2:
            # the sharded dim survives into the metadata shape
            # (q.shape[:-2] + (1, 1)): shard the metadata exactly like
            # q's dim — per-expert affines vary along it, per-tensor
            # affines broadcast along it, either way the shapes align
            mshape = list(l0.scale.shape)
            mshape[ax] *= self._n_model
            mspec = self._spec_at(len(mshape), ax)
            if const is None:
                const = {f: self._assemble([getattr(l, f) for l in locals_],
                                           tuple(mshape), mspec)
                         for f in const_fields}
                self._g_qmeta_cache[key] = const
            live = {f: self._assemble([getattr(l, f) for l in locals_],
                                      tuple(mshape), mspec)
                    for f in live_fields}
        else:
            # split on a contraction-adjacent dim (last two): the
            # metadata collapses it to 1 and the per-tensor affine is
            # identical on every shard — replicate shard 0's
            if const is None:
                const = {f: self._replicated(getattr(l0, f))
                         for f in const_fields}
                self._g_qmeta_cache[key] = const
            live = {f: self._replicated(getattr(l0, f))
                    for f in live_fields}
        return QuantizedTensor(q=q, bits=l0.bits, orig_dtype=l0.orig_dtype,
                               **const, **live)

    def quantized_leaves(self, eligible=None, *, bits: int | None = None
                         ) -> dict[Any, Any]:
        """Globally-sharded mirror of
        :meth:`PlaneStore.quantized_leaves`: eligible leaves are live
        QuantizedTensor views whose ``q`` is a global sharded array over
        the sub-stores' accumulators; truncated (``bits=b``) draft views
        share those exact global buffers (zero extra weight bytes,
        sharded or not)."""
        out: dict[Any, Any] = {}
        with _obs.get_tracer().span("store_quantized_leaves"):
            for key, idxs in self._groups.items():
                if eligible is None or eligible(key):
                    got = self._g_qleaf_cache.get(key)
                    if got is None:
                        got = self._quantized_leaf(key)
                        if got is not None:
                            self._g_qleaf_cache[key] = got
                    if got is not None:
                        if bits is not None:
                            b_eff = min(bits, got.bits)
                            trunc = self._g_qtrunc_cache.get((key, b_eff))
                            if trunc is None:
                                trunc = got.truncate(b_eff)
                                self._g_qtrunc_cache[(key, b_eff)] = trunc
                            got = trunc
                        out[key] = got
                        continue
                out[key] = self._fp_leaf(key)
        self._g_dirty.clear()
        return out
