"""Wire v3 integrity framing: CRC+seq per unit, whole-header CRC,
typed errors on malformed input.

Pins the ISSUE-9 tentpole surface (a):

* a clean v3 stream reconstructs bit-identically to the v1 stream of
  the same model (the integrity frame wraps the v2 unit encoding, it
  never changes payload bytes);
* framing overhead is structural — exactly ``HEADER_CRC_BYTES +
  n_units * 8`` on the wire — and ``framing_overhead`` reports it;
* EVERY flipped payload byte is detected (exhaustive sweep), and every
  flipped header byte raises a typed error;
* malformed/truncated/fuzzed buffers raise :class:`WireFormatError`
  with offset context — never a bare struct/json/index error.
"""
import struct
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; deterministic ones still run
    class _StrategyStub:
        def __getattr__(self, _name):
            return lambda *a, **k: self

        def __call__(self, *a, **k):
            return self

    st = _StrategyStub()

    def settings(**_kw):
        return lambda f: f

    def given(*_a, **_kw):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed")(f)

from repro.core import wire
from repro.core.progressive import divide
from repro.transmission.client import ProgressiveClient


@pytest.fixture(scope="module")
def setup():
    k = jax.random.PRNGKey(1)
    params = {
        "w1": jax.random.normal(k, (24, 8)),
        "w2": jax.random.normal(jax.random.fold_in(k, 1), (7,)),
        "scale": jnp.float32(2.5),
    }
    model = divide(params)
    blob = wire.encode(model, integrity=True)
    meta, hdr = wire.decode_header(blob)
    layout = wire.layout_from_header(meta, hdr)
    return params, model, blob, meta, hdr, layout


def _materialized(blob):
    c = ProgressiveClient()
    c.feed(blob)
    assert c.complete
    return c.materialize()


# -- round trip & bit-identity ------------------------------------------------

def test_v3_header_roundtrip(setup):
    _, model, blob, meta, hdr, layout = setup
    assert meta["version"] == wire.VERSION_INTEGRITY
    assert layout.integrity
    assert layout.total_bytes == len(blob)
    # header end = 12-byte prefix + JSON body + 4 CRC bytes, and the
    # stored CRC actually covers everything before it
    (n,) = struct.unpack("<I", blob[8:12])
    assert hdr == 12 + n + wire.HEADER_CRC_BYTES
    (crc,) = struct.unpack("<I", blob[hdr - 4:hdr])
    assert crc == zlib.crc32(blob[:hdr - 4]) & 0xFFFFFFFF


def test_clean_v3_stream_bit_identical_to_v1(setup):
    params, model, blob, *_ = setup
    v1 = _materialized(wire.encode(model))
    v3 = _materialized(blob)
    assert v1.keys() == v3.keys()
    for key in v1:
        np.testing.assert_array_equal(np.asarray(v1[key]),
                                      np.asarray(v3[key]))


def test_unit_offsets_cover_the_stream(setup):
    _, _, blob, meta, hdr, layout = setup
    offs = layout.unit_offsets()
    sizes = [e[2] for st_ in layout.stages for e in st_]
    assert offs[0] == hdr
    for o, n, nxt in zip(offs, sizes, offs[1:] + [len(blob)]):
        assert o + n == nxt
    # every on-wire unit verifies in place
    for seq, (o, n) in enumerate(zip(offs, sizes)):
        got_seq, _ = wire.verify_unit(blob[o:o + n])
        assert got_seq == seq


# -- framing overhead ----------------------------------------------------------

def test_framing_overhead_is_structural_and_reported(setup):
    _, model, blob, meta, hdr, _ = setup
    v2 = wire.encode_v2(model, entropy_coded=False)
    v2meta, v2hdr = wire.decode_header(v2)
    rep = wire.framing_overhead(meta)
    n_units = len(meta["units"])
    expected = (wire.HEADER_CRC_BYTES
                + n_units * (wire.FRAME_BYTES_V3 - wire.FRAME_BYTES))
    assert rep["overhead_bytes"] == expected
    # the payload region costs exactly 8 bytes per unit; the header
    # costs its CRC (JSON digit counts may wobble, so compare regions)
    assert ((len(blob) - hdr) - (len(v2) - v2hdr)
            == n_units * (wire.FRAME_BYTES_V3 - wire.FRAME_BYTES))
    assert 0.0 < rep["overhead_frac"] <= 1.0
    # v1/v2 report zero
    v1meta, _ = wire.decode_header(wire.encode(model))
    assert wire.framing_overhead(v1meta)["overhead_bytes"] == 0


# -- corruption detection -------------------------------------------------------

def test_every_flipped_payload_byte_is_detected(setup):
    """Exhaustive: flipping ANY single byte of ANY unit fails that
    unit's verification."""
    _, _, blob, meta, hdr, layout = setup
    offs = layout.unit_offsets()
    sizes = [e[2] for st_ in layout.stages for e in st_]
    for o, n in zip(offs, sizes):
        unit = bytearray(blob[o:o + n])
        for i in range(n):
            unit[i] ^= 0x40
            with pytest.raises(wire.WireFormatError):
                wire.verify_unit(bytes(unit))
            unit[i] ^= 0x40


def test_every_flipped_header_byte_raises_typed_error(setup):
    _, _, blob, _, hdr, _ = setup
    for i in range(hdr):
        mut = bytearray(blob[:hdr])
        mut[i] ^= 0x01
        with pytest.raises(wire.WireFormatError):
            wire.decode_header(bytes(mut))


def test_seq_mismatch_is_detected_even_with_valid_crc(setup):
    """A unit re-framed under the wrong sequence number has a VALID
    CRC (the frame is self-consistent) — the client's positional check
    must catch it."""
    _, model, blob, meta, hdr, layout = setup
    body = wire.encode_unit(model, *meta["units"][0], entropy_coded=False)
    wrong = wire.frame_unit(5, body)
    got_seq, got_body = wire.verify_unit(wrong)  # frame itself is coherent
    assert got_seq == 5 and got_body == body
    c = ProgressiveClient()
    sizes = [e[2] for st_ in layout.stages for e in st_]
    assert len(wrong) == sizes[0]  # same payload, same on-wire size
    c.feed(blob[:hdr] + wrong + blob[hdr + sizes[0]:])
    assert 0 in c.nacks and "sequence mismatch" in c.nacks[0]


@pytest.mark.parametrize("damage", ["crc", "body"])
def test_corrupt_unit_is_quarantined_before_ingest(setup, damage):
    """The client hands the store packed bytes, and still only verified,
    decodable ones: a unit whose CRC fails ("crc"), or whose frame is
    valid around an undecodable body ("body": an unknown entropy mode),
    is quarantined and nothing reaches the store; its repair then
    completes the stream bit-identically to the clean one."""
    from repro.core.plane_store import PlaneStore

    _, _, blob, meta, _, layout = setup
    seq = 0
    o, n = layout.unit_offsets()[seq], layout.stages[0][0][2]
    unit = bytearray(blob[o:o + n])
    if damage == "crc":
        unit[-1] ^= 0x01
        reason = "CRC mismatch"
    else:  # <seq><crc> | <mode><reserved> | payload
        unit = wire.frame_unit(seq, b"\xee" + bytes(unit[9:]))
        reason = "undecodable body"
    assert len(unit) == n
    c = ProgressiveClient()
    c.feed(blob[:o] + bytes(unit) + blob[o + n:])
    assert seq in c.nacks and reason in c.nacks[seq]
    assert c.stages_complete == 0 and not any(c.store.received)
    assert c.store.fingerprint() == \
        PlaneStore.from_wire_meta(meta).fingerprint()
    assert c.feed_repair(seq, blob[o:o + n]) and c.complete
    for a, b in zip(c.materialize().values(), _materialized(blob).values()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- typed errors on malformed input --------------------------------------------

def test_decode_header_error_catalogue(setup):
    _, _, blob, *_ = setup
    with pytest.raises(wire.WireFormatError, match="truncated"):
        wire.decode_header(blob[:7])
    with pytest.raises(wire.WireFormatError, match="bad magic"):
        wire.decode_header(b"XXXX" + bytes(blob[4:]))
    bad_ver = bytearray(blob)
    bad_ver[4] = 99
    with pytest.raises(wire.WireFormatError, match="unsupported version"):
        wire.decode_header(bytes(bad_ver))
    bad_len = bytearray(blob)
    struct.pack_into("<I", bad_len, 8, wire.MAX_HEADER_BYTES + 1)
    with pytest.raises(wire.WireFormatError, match="length field is corrupt"):
        wire.decode_header(bytes(bad_len))


def test_decode_plane_typed_errors():
    with pytest.raises(wire.WireFormatError, match="frame"):
        wire.decode_plane(b"\x00", 1, 8, framed=True)
    # unknown entropy mode byte
    with pytest.raises(wire.WireFormatError):
        wire.decode_plane(b"\xee\x00" + b"\x00" * 4, 1, 8, framed=True)


def test_fuzz_truncations_and_flips_only_raise_wire_errors(setup):
    """Deterministic fuzz sweep: random truncations and byte flips of
    the whole stream must never escape as struct/json/index errors —
    ``decode_header`` raises :class:`WireFormatError`, and the v3
    client swallows damage into quarantine instead of raising."""
    _, _, blob, _, hdr, _ = setup
    rng = np.random.default_rng(0)
    for trial in range(200):
        mut = bytearray(blob)
        for _ in range(int(rng.integers(1, 4))):
            mut[int(rng.integers(0, len(mut)))] ^= int(rng.integers(1, 256))
        if rng.random() < 0.5:
            mut = mut[:int(rng.integers(0, len(mut)))]
        try:
            wire.decode_header(bytes(mut))
        except wire.WireFormatError:
            pass  # typed, with offset context — exactly the contract
        c = ProgressiveClient()
        c.feed(bytes(mut))  # must never raise: quarantine, not crash


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**32 - 1), st.binary(min_size=0, max_size=64))
def test_frame_verify_roundtrip_property(seq, body):
    framed = wire.frame_unit(seq, body)
    assert len(framed) == len(body) + 8
    got_seq, got_body = wire.verify_unit(framed)
    assert (got_seq, got_body) == (seq, body)
