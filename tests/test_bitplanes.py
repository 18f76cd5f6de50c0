"""Property tests for eq. (3)/(4): bit division + concatenation, and the
dense wire packing."""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import bitplanes
from repro.core.quantize import quantize, truncate


def widths_strategy(bits):
    """Random partition of `bits` into plane widths."""

    def build(cuts):
        cs = sorted(set(cuts) | {bits})
        prev, out = 0, []
        for c in cs:
            if c > prev:
                out.append(c - prev)
                prev = c
        return tuple(out)

    return st.lists(st.integers(1, bits - 1), min_size=0, max_size=6).map(build)


@settings(max_examples=60, deadline=None)
@given(
    st.lists(st.floats(-100, 100, allow_nan=False, width=32), min_size=1, max_size=48)
    .map(lambda xs: np.asarray(xs, np.float32)),
    st.integers(2, 16),
    st.data(),
)
def test_split_concat_roundtrip(x, bits, data):
    widths = data.draw(widths_strategy(bits))
    qt = quantize(jnp.asarray(x), bits)
    planes = bitplanes.split(qt, widths)
    q2 = bitplanes.concat(planes, bits, widths)
    assert (np.asarray(q2) == np.asarray(qt.q)).all()


@settings(max_examples=60, deadline=None)
@given(
    st.lists(st.floats(-100, 100, allow_nan=False, width=32), min_size=1, max_size=48)
    .map(lambda xs: np.asarray(xs, np.float32)),
    st.integers(2, 16),
    st.data(),
)
def test_prefix_equals_truncate(x, bits, data):
    """Receiving planes [1..j] == truncating q to the cumulative width —
    the invariant that makes intermediate models well-defined."""
    widths = data.draw(widths_strategy(bits))
    j = data.draw(st.integers(1, len(widths)))
    qt = quantize(jnp.asarray(x), bits)
    planes = bitplanes.split(qt, widths)
    got = bitplanes.concat(planes[:j], bits, widths)
    cum = bitplanes.cumulative(widths)[j - 1]
    want = truncate(qt, cum).q
    assert (np.asarray(got) == np.asarray(want)).all()


@settings(max_examples=60, deadline=None)
@given(
    st.lists(st.integers(0, 2**16 - 1), min_size=1, max_size=64),
    st.integers(1, 16),
)
def test_pack_unpack_roundtrip(vals, width):
    vals = np.asarray(vals, np.uint32) & ((1 << width) - 1)
    packed = bitplanes.pack_bits(jnp.asarray(vals), width)
    assert packed.dtype == jnp.uint8
    # dense: exactly ceil(n*w/8) bytes — the "no size increase" unit fact
    assert packed.shape[0] == -(-len(vals) * width // 8)
    out = bitplanes.unpack_bits(packed, width, len(vals))
    assert (np.asarray(out) == vals).all()


@pytest.mark.parametrize("width", [1, 2, 3, 5, 7, 8, 11, 16])
def test_pack_bits_matches_bitstream_reference(width):
    """The wire layout: each value's ``width`` bits, MSB first, end to
    end, cut into bytes MSB first (``np.packbits`` order), the last byte
    zero-padded."""
    vals = np.random.default_rng(width).integers(
        0, 2**width, size=1001).astype(np.uint32)
    bits = (vals[:, None] >> np.arange(width - 1, -1, -1)) & 1
    want = np.packbits(bits.astype(np.uint8).ravel())
    np.testing.assert_array_equal(np.asarray(bitplanes.pack_bits(vals, width)),
                                  want)
    np.testing.assert_array_equal(
        np.asarray(bitplanes.unpack_bits(want, width, vals.size)), vals)


def test_width_validation():
    with pytest.raises(ValueError):
        bitplanes.validate_widths(8, (2, 2))  # sums to 4
    with pytest.raises(ValueError):
        bitplanes.validate_widths(8, (0, 8))
    with pytest.raises(ValueError):
        bitplanes.PlaneSchedule(bits=16, widths=(8, 4))


def test_paper_default_schedule():
    s = bitplanes.PAPER_DEFAULT
    assert s.bits == 16 and s.widths == (2,) * 8
    assert s.cumulative_bits == (2, 4, 6, 8, 10, 12, 14, 16)
