"""PlaneStore: the unified receiver runtime.

Covers the ISSUE acceptance surface: stage-prefix round-trips vs the
pytree receiver, incremental-materialize cache correctness under
partial-stage arrivals, mixed container-dtype models, the batched
segment-OR kernel vs the per-tensor kernel, and the byte-granular
wire packing (no O(n*width) intermediate blowup).
"""
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplanes
from repro.core.bitplanes import PlaneSchedule, pack_bits, unpack_bits
from repro.core.plane_store import PlaneStore, next_plane_shift
from repro.core.policy import DivisionPolicy, TensorPlan, UniformPolicy
from repro.core.progressive import ReceiverState, divide, transmit_reconstruct
from repro.core.wire import path_str
from repro.kernels import ops
from repro.kernels.bitplane import plane_or, plane_or_segments


@pytest.fixture
def params():
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 4)
    return {
        "embed": jax.random.normal(ks[0], (40, 12)),
        "layers": [
            {"w": jax.random.normal(ks[1], (16, 16)) * 3.0, "b": jnp.ones((16,))},
            {"w": jax.random.normal(ks[2], (16, 16)), "b": jnp.zeros((16,))},
        ],
        "scale": jnp.float32(2.5),
        "step": jnp.int32(3),
    }


class MixedBitsPolicy(DivisionPolicy):
    """8-bit schedule (uint8 container) for biases/scalars, 16-bit
    (uint16) for matrices — exercises multi-buffer stores."""

    def plan(self, path, shape, dtype, slice_idx=None):
        if len(shape) < 2:
            return TensorPlan(schedule=PlaneSchedule(bits=8, widths=(2, 2, 4)))
        return TensorPlan(schedule=PlaneSchedule(bits=16, widths=(2,) * 8))

    @property
    def n_stages(self):
        return 8


# ---------------------------------------------------------------------------
# round-trip vs the reference pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [None, MixedBitsPolicy()],
                         ids=["uniform16", "mixed8-16"])
def test_store_roundtrip_every_stage_prefix(params, policy):
    """divide -> store -> materialize == transmit_reconstruct at every
    prefix of stages (the eq. 4/5 contract all consumers rely on)."""
    model = divide(params, policy)
    st = ReceiverState.init(model)
    for s in range(1, model.n_stages + 1):
        st = st.receive(model.stage(s))
        got = st.materialize()
        want = transmit_reconstruct(params, policy, upto_stage=s)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mixed_dtype_buffers(params):
    model = divide(params, MixedBitsPolicy())
    store = PlaneStore.from_model(model)
    assert set(store.buffers) == {"uint8", "uint16"}
    # every slot's segment is block-aligned and inside its buffer
    for t in store.slots:
        assert t.offset % store.block == 0
        assert t.offset + t.size <= store.buffers[np.dtype(t.container).name].shape[0]


def test_acc_views_match_reference_accumulators(params):
    """Flat-buffer views equal the per-tensor accumulators the old
    ReceiverState carried (same eq. 4 integer state)."""
    model = divide(params)
    store = PlaneStore.from_model(model)
    for s in range(1, 3):
        store.ingest(model.stage(s))
    for i, t in enumerate(model.tensors):
        # reference via bitplanes.concat on the received prefix
        want = bitplanes.concat(t.planes[:2], t.bits, t.plan.schedule.widths)
        np.testing.assert_array_equal(np.asarray(store.acc(i)), np.asarray(want))


# ---------------------------------------------------------------------------
# incremental materialization
# ---------------------------------------------------------------------------

def test_incremental_materialize_reuses_clean_leaves(params):
    model = divide(params)
    store = PlaneStore.from_model(model)
    store.ingest(model.stage(1))
    first = store.materialize_leaves()
    # Partial arrival: only tensor 0 gets its next plane.
    idx0 = 0
    store.ingest([(idx0, model.tensors[idx0].planes[1])])
    second = store.materialize_leaves()
    touched = model.tensors[idx0].path
    for key, leaf in second.items():
        if key == touched:
            assert leaf is not first[key]  # recomputed
        else:
            assert leaf is first[key]      # served from cache, same object
    # and the recomputed leaf is numerically right
    ref = ReceiverState.init(model).receive(model.stage(1))
    ref = ref.receive([(idx0, model.tensors[idx0].planes[1])])
    np.testing.assert_array_equal(
        np.asarray(second[touched]),
        np.asarray(ref.store.materialize_leaves()[touched]))


def test_materialize_idempotent_when_nothing_arrives(params):
    model = divide(params)
    store = PlaneStore.from_model(model)
    store.ingest(model.stage(1))
    a = store.materialize_leaves()
    b = store.materialize_leaves()
    for k in a:
        assert a[k] is b[k]


def test_copy_isolates_dirty_state(params):
    """ReceiverState's functional receive relies on copy(): mutating the
    child store must not corrupt the parent's cache or accumulators."""
    model = divide(params)
    parent = PlaneStore.from_model(model)
    parent.ingest(model.stage(1))
    parent_leaves = parent.materialize_leaves()
    child = parent.copy()
    child.ingest(model.stage(2))
    for k, v in parent.materialize_leaves().items():
        assert v is parent_leaves[k]
    assert child.received[0] == 2 and parent.received[0] == 1


# ---------------------------------------------------------------------------
# batched segment kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes,seg_shifts", [
    ([300, 128, 1000], (14, 10, 8)),  # padded segments of 2, 1, 4 blocks
    ([700], (6,)),
    ([256, 1, 513, 90, 1024, 3, 77], (14, 12, 12, 10, 8, 6, 0)),
])
def test_plane_or_segments_matches_per_tensor_kernel(sizes, seg_shifts):
    rng = np.random.default_rng(0)
    block = 256
    offs, cur = [], 0
    for s in sizes:
        offs.append(cur)
        cur += -(-s // block) * block
    acc = jnp.asarray(rng.integers(0, 2**8, size=cur), jnp.uint16)
    plane_flat = jnp.zeros((cur,), jnp.uint16)
    per_tensor = []
    for (off, s, sh) in zip(offs, sizes, seg_shifts):
        p = jnp.asarray(rng.integers(0, 4, size=s), jnp.uint16)
        plane_flat = plane_flat.at[off:off + s].set(p)
        per_tensor.append(plane_or(acc[off:off + s], p, shift=sh,
                                   interpret=True))
    # segment table: first block of each segment and its shift
    starts = jnp.asarray([off // block for off in offs], jnp.int32)
    shifts = jnp.asarray(seg_shifts, jnp.int32)
    out = plane_or_segments(acc, plane_flat, starts, shifts,
                            block=block, interpret=True)
    for off, s, want in zip(offs, sizes, per_tensor):
        np.testing.assert_array_equal(np.asarray(out[off:off + s]),
                                      np.asarray(want))


def test_stage_upgrade_is_one_launch_per_dtype(params):
    """The acceptance criterion: a full-model stage upgrade through the
    store issues O(1) plane_or_segments launches, not O(n_tensors)."""
    model = divide(params)
    store = PlaneStore.from_model(model)
    ops.reset_launch_counts()
    store.ingest(model.stage(1))
    assert ops.LAUNCH_COUNTS["plane_or_segments"] == 1
    assert ops.LAUNCH_COUNTS["plane_or"] == 0

    mixed = divide(params, MixedBitsPolicy())
    store2 = PlaneStore.from_model(mixed)
    ops.reset_launch_counts()
    store2.ingest(mixed.stage(1))
    assert ops.LAUNCH_COUNTS["plane_or_segments"] == 2  # uint8 + uint16


def test_ingest_multiple_planes_same_tensor_rounds(params):
    """A shipment carrying several planes of one tensor splits into
    rounds but stays correct (client flushing a backlog)."""
    model = divide(params)
    store = PlaneStore.from_model(model)
    t0 = model.tensors[0]
    store.ingest([(0, t0.planes[0]), (0, t0.planes[1]), (0, t0.planes[2])])
    want = bitplanes.concat(t0.planes[:3], t0.bits, t0.plan.schedule.widths)
    np.testing.assert_array_equal(np.asarray(store.acc(0)), np.asarray(want))
    assert store.received[0] == 3 and store.received[1] == 0


# ---------------------------------------------------------------------------
# wire-header construction (client path) and shift helper
# ---------------------------------------------------------------------------

def test_from_wire_meta_matches_from_model(params):
    from repro.core import wire

    model = divide(params)
    meta, _ = wire.decode_header(wire.encode_header(model))
    sm = PlaneStore.from_model(model)
    sw = PlaneStore.from_wire_meta(meta)
    for s in range(1, 4):
        items = model.stage(s)
        sm.ingest(items)
        sw.ingest(items)
    got = sw.materialize_leaves()
    for i, t in enumerate(model.tensors):
        np.testing.assert_array_equal(np.asarray(sw.acc(i)), np.asarray(sm.acc(i)))
    for key, leaf in sm.materialize_leaves().items():
        np.testing.assert_array_equal(np.asarray(got[path_str(key)]),
                                      np.asarray(leaf))


# ---------------------------------------------------------------------------
# packed planes: the client hands the wire's bytes over, the store
# unpacks them on the device where one width covers a dtype's round
# ---------------------------------------------------------------------------

class MixedWidthPolicy(DivisionPolicy):
    """Both uint16: matrices in 2-bit planes, vectors and scalars in
    4-bit ones, so each uint16 round mixes two widths."""

    def plan(self, path, shape, dtype, slice_idx=None):
        widths = (2,) * 8 if len(shape) >= 2 else (4,) * 4
        return TensorPlan(schedule=PlaneSchedule(bits=16, widths=widths))

    @property
    def n_stages(self):
        return 8


class OddWidthPolicy(DivisionPolicy):
    """9-bit codes (uint16 container) in three 3-bit planes: 3 does not
    divide 8, so a tensor's bytes need not start on a byte."""

    def plan(self, path, shape, dtype, slice_idx=None):
        return TensorPlan(schedule=PlaneSchedule(bits=9, widths=(3, 3, 3)))

    @property
    def n_stages(self):
        return 3


def _value_fingerprints(blob: bytes) -> list[dict]:
    """Fingerprint after each stage of a store fed the stream's planes
    as values, unpacked on the host (the path packed ingest replaces)."""
    from repro.core import wire

    meta, hdr = wire.decode_header(blob)
    layout = wire.layout_from_header(meta, hdr)
    store = PlaneStore.from_wire_meta(meta)
    off, fps = hdr, []
    for stage in layout.stages:
        items = []
        for idx, w, nbytes, n_el in stage:
            payload = blob[off:off + nbytes]
            off += nbytes
            if layout.integrity:
                _, payload = wire.verify_unit(payload)
            items.append((idx, wire.decode_plane(payload, w, n_el,
                                                 framed=layout.framed)))
        store.ingest(items)
        fps.append(store.fingerprint())
    return fps


def _feed_in_odd_chunks(blob: bytes, mesh=None):
    """A client fed ``blob`` in chunks of 1, 7, 4093, 13 and 65537
    bytes in turn; returns it with its fingerprint at each stage and
    the store_planes_unpacked_total counts by ``where``."""
    from repro import obs
    from repro.transmission.client import ProgressiveClient

    fps = []
    client = ProgressiveClient(
        on_stage_complete=lambda s: fps.append(client.store.fingerprint()),
        mesh=mesh)
    sizes, off, i = (1, 7, 4093, 13, 65537), 0, 0
    with obs.telemetry(True) as reg:
        while off < len(blob):
            client.feed(blob[off:off + sizes[i % len(sizes)]])
            off += sizes[i % len(sizes)]
            i += 1
        c = reg.counter("store_planes_unpacked_total")
        counts = {w: c.value(where=w) for w in ("device", "host")}
    return client, fps, counts


@pytest.mark.parametrize("version", ["v1", "v2-entropy", "v3"])
def test_packed_ingest_fingerprint_matches_value_ingest(params, version):
    """At every stage the store a client fills from the wire's packed
    bytes (unpacked on the device) is bit-identical to one fed the same
    planes as host-unpacked values; every plane is counted once, on
    the device. Mixed containers: uint8 (widths 2, 2, 4) and uint16."""
    from repro.core import wire

    model = divide(params, MixedBitsPolicy())
    blob = wire.encode(model, entropy_coded=version == "v2-entropy",
                       integrity=version == "v3")
    ops.reset_launch_counts()
    client, fps, counts = _feed_in_odd_chunks(blob)
    assert client.complete
    assert fps == _value_fingerprints(blob)
    n_planes = sum(t.plan.schedule.n_planes for t in model.tensors)
    assert counts == {"device": n_planes, "host": 0}
    assert ops.LAUNCH_COUNTS["plane_unpack"] > 0


@pytest.mark.parametrize("policy", [MixedWidthPolicy(), OddWidthPolicy()],
                         ids=["mixed-widths", "odd-width"])
def test_packed_ingest_host_path_for_mixed_or_odd_widths(params, policy):
    """A round whose planes of one dtype differ in width, or whose width
    does not divide 8, is unpacked on the host: same fingerprints, and
    each plane counted once, under where="host" in such a round. (With
    mixed widths only stages 1-4 mix; stages 5-8 carry 2-bit planes
    alone and go to the device.)"""
    from repro.core import wire

    blob = wire.encode(divide(params, policy))
    client, fps, counts = _feed_in_odd_chunks(blob)
    assert client.complete
    assert fps == _value_fingerprints(blob)
    want = {"device": 0, "host": 0}
    for stage in wire.layout_from_header(*wire.decode_header(blob)).stages:
        ws = {w for _, w, _, _ in stage}          # all in uint16
        one = len(ws) == 1 and 8 % min(ws) == 0
        want["device" if one else "host"] += len(stage)
    assert want["host"] > 0
    assert counts == want


def test_sharded_store_unpacks_packed_planes_on_host(params):
    """ShardedPlaneStore routes planes by value rows, so a meshed client
    unpacks on the host: every plane counted where="host", and each
    tensor's accumulator equals the single-device client's."""
    from repro.core import wire
    from repro.launch.mesh import make_serving_mesh

    blob = wire.encode(divide(params))
    ops.reset_launch_counts()
    sharded, _, counts = _feed_in_odd_chunks(blob, mesh=make_serving_mesh(1))
    assert ops.LAUNCH_COUNTS["plane_unpack"] == 0
    n_planes = sum(len(t["widths"])
                   for t in wire.decode_header(blob)[0]["tensors"])
    assert counts == {"device": 0, "host": n_planes}
    single, _, _ = _feed_in_odd_chunks(blob)
    for i in range(single.store.n_tensors):
        np.testing.assert_array_equal(np.asarray(sharded.store.acc(i)),
                                      np.asarray(single.store.acc(i)))


def test_packed_plane_tail_bits_never_reach_padding():
    """Set bits past a ragged tensor's last value (which pack_bits
    never writes) are cleared on staging: the padding stays 0, so the
    fingerprint matches the value path's."""
    sched = PlaneSchedule(bits=16, widths=(2,) * 8)
    entries = [{"key": k, "schedule": sched, "lo": jnp.float32(-1),
                "hi": jnp.float32(1), "shape": (n,),
                "orig_dtype": np.float32} for k, n in (("a", 5), ("b", 11))]
    packed_store = PlaneStore._from_entries(entries)
    value_store = PlaneStore._from_entries(entries)
    rng = np.random.default_rng(0)
    packed, values = [], []
    for i, n in enumerate((5, 11)):
        vals = rng.integers(0, 4, n)
        pk = pack_bits(vals, 2)
        pk[-1] |= 0xFF >> (n * 2 % 8)     # dirty the unused low bits
        packed.append((i, bitplanes.PackedPlane(pk.tobytes(), 2, n)))
        values.append((i, vals))
    packed_store.ingest(packed)
    value_store.ingest(values)
    assert packed_store.fingerprint() == value_store.fingerprint()


def test_next_plane_shift_exhaustion():
    sched = PlaneSchedule(bits=16, widths=(2,) * 8)
    assert next_plane_shift(sched, 0) == 14
    assert next_plane_shift(sched, 7) == 0
    with pytest.raises(ValueError):
        next_plane_shift(sched, 8)


# ---------------------------------------------------------------------------
# byte-granular packing: no O(n*width) intermediates
# ---------------------------------------------------------------------------

def _peak_alloc_bytes(fn, *args):
    """Peak bytes NumPy allocates while ``fn`` runs (NumPy reports its
    buffers to tracemalloc)."""
    tracemalloc.start()
    try:
        fn(*args)
        return tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()


@pytest.mark.parametrize("width", [2, 3, 7, 16])
def test_pack_bits_large_n_no_blowup(width):
    n = 1 << 18
    vals = np.random.default_rng(width).integers(
        0, 2**width, size=n).astype(np.uint32)
    packed = pack_bits(vals, width)
    assert packed.shape[0] == -(-n * width // 8)
    np.testing.assert_array_equal(unpack_bits(packed, width, n), vals)
    # Peak stays O(n): at most two uint32 values per element, where an
    # (n, width) bit matrix plus an 8-wide byte matrix would be
    # > n * (width + 8) bytes.
    peak = _peak_alloc_bytes(pack_bits, vals, width)
    assert peak <= 2 * vals.nbytes, peak
    peak_un = _peak_alloc_bytes(unpack_bits, packed, width, n)
    assert peak_un <= 2 * vals.nbytes, peak_un
    # Truncated payloads must raise, never zero-fill; trailing extra
    # bytes are tolerated.
    with pytest.raises(ValueError):
        unpack_bits(packed[:-1], width, n)
    np.testing.assert_array_equal(
        unpack_bits(np.concatenate([packed, np.zeros(3, packed.dtype)]),
                    width, n), vals)


def test_batched_dequant_bit_identical_to_scalar():
    """The upgrade hot path (``dequantize_batch`` and the from-buffers
    variant the store's refresh uses) must be BYTE-identical to
    per-tensor ``dequantize`` — not merely close: a single jitted
    ``q*scale+offset`` executable FMA-contracts one ulp away from the
    eager oracle and the fused dequant-matmul kernel, which is exactly
    the drift the mul-only/add-only executable split prevents."""
    from repro.core.quantize import (dequant_constants, dequantize,
                                     dequantize_batch, dequantize_buffers,
                                     quantize)
    rng = np.random.default_rng(11)
    qts, ms = [], []
    for j, (shape, bits) in enumerate(
            [((7,), 3), ((5, 9), 8), ((2, 3, 4), 16), ((33,), 12)]):
        x = jnp.asarray(
            (rng.standard_normal(shape) * 10.0 ** (j - 2)).astype(np.float32))
        qts.append(quantize(x, bits))
        ms.append([None, 0, bits // 2, bits][j % 4])
    batch = dequantize_batch(qts, ms)
    for qt, m, got in zip(qts, ms, batch):
        assert np.asarray(dequantize(qt, m)).tobytes() == \
            np.asarray(got).tobytes()

    # from-buffers variant: pack the q's into one flat container buffer
    # (all uint16 here) and dequantize via in-executable slicing
    u16 = [(qt, m) for qt, m in zip(qts, ms) if qt.q.dtype == jnp.uint16]
    flat = jnp.concatenate([qt.q.reshape(-1) for qt, _ in u16])
    specs, off = [], 0
    for qt, _ in u16:
        specs.append(("uint16", off, qt.q.size, qt.q.shape))
        off += qt.q.size
    consts = dequant_constants([qt.lo for qt, _ in u16],
                               [qt.hi for qt, _ in u16],
                               [qt.bits for qt, _ in u16])
    out = dequantize_buffers({"uint16": flat}, specs,
                             [qt.bits for qt, _ in u16],
                             [m for _, m in u16],
                             ["float32"] * len(u16), constants=consts)
    for (qt, m), got in zip(u16, out):
        assert np.asarray(dequantize(qt, m)).tobytes() == \
            np.asarray(got).tobytes()


def test_store_materialize_matches_per_tensor_dequantize(params):
    """The store's batched refresh must give byte-identical leaves to
    eagerly slicing each accumulator and dequantizing it alone — at a
    partial stage (mixed received bits) and at the final stage."""
    from repro.core.quantize import dequantize
    prog = divide(params)
    state = ReceiverState.init(prog)
    for s in range(1, prog.n_stages + 1):
        state = state.receive(prog.stage(s))
        store = state.store
        leaves = store.materialize_leaves()
        for i, t in enumerate(store.slots):
            if t.slice_axis is not None:
                continue
            want = dequantize(store.quantized(i),
                              received_bits=store.effective_bits(i))
            assert np.asarray(want).tobytes() == \
                np.asarray(leaves[t.key]).tobytes()
