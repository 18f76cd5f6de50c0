"""Pallas kernel sweeps: every kernel vs its pure-jnp oracle across
shapes / dtypes / bit-widths (interpret=True executes the kernel body on
CPU with TPU semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplanes
from repro.core.quantize import dequant_affine, quantize, container_dtype
from repro.kernels import ref
from repro.kernels.bitplane import plane_extract, plane_or
from repro.kernels.decode_attention import flash_decode
from repro.kernels.dequant_matmul import dequant_matmul


# ---------------------------------------------------------------------------
# dequant_matmul — the eq.-(5) affine rides in as traced operands from
# the one shared dequant_affine helper (never recomputed per call site)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [(8, 16, 8), (96, 200, 130), (128, 128, 128),
                                   (1, 64, 257), (33, 500, 65)])
@pytest.mark.parametrize("bits", [4, 8, 16])
def test_dequant_matmul_shapes_bits(M, K, N, bits):
    kx, kw = jax.random.split(jax.random.PRNGKey(M * 1000 + K + N + bits))
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32) * 3.0 + 0.5
    qt = quantize(w, bits)
    scale, offset = dequant_affine(qt.lo, qt.hi, bits)
    y = dequant_matmul(x, qt.q, scale, offset,
                       bm=32, bn=64, bk=64, interpret=True)
    yr = ref.dequant_matmul_ref(x, qt.q, scale, offset)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-5, atol=3e-4)


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
def test_dequant_matmul_input_dtypes(x_dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 64)).astype(x_dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 48))
    qt = quantize(w, 16)
    scale, offset = dequant_affine(qt.lo, qt.hi, 16)
    y = dequant_matmul(x, qt.q, scale, offset, bm=16, bn=16, bk=32,
                       interpret=True)
    yr = ref.dequant_matmul_ref(x.astype(jnp.float32), qt.q, scale, offset)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("received", [2, 6, 10, 16])
def test_dequant_matmul_partial_precision(received):
    """Consuming a truncated accumulator must equal the oracle at the
    received precision (the serving engine's mid-transmission matmul)."""
    from repro.core.quantize import truncate

    x = jax.random.normal(jax.random.PRNGKey(2), (16, 40))
    w = jax.random.normal(jax.random.PRNGKey(3), (40, 24))
    qt = truncate(quantize(w, 16), received)
    scale, offset = dequant_affine(qt.lo, qt.hi, 16, received_bits=received)
    y = dequant_matmul(x, qt.q, scale, offset,
                       bm=16, bn=16, bk=16, interpret=True)
    yr = ref.dequant_matmul_ref(x, qt.q, scale, offset)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-5, atol=3e-4)


def test_dequant_matmul_zero_received_uses_range_centre():
    x = jnp.ones((4, 8))
    q = jnp.zeros((8, 4), jnp.uint16)
    lo, hi = jnp.float32(-1.0), jnp.float32(3.0)
    scale, offset = dequant_affine(lo, hi, 16, received_bits=0)
    y = dequant_matmul(x, q, scale, offset,
                       bm=4, bn=4, bk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y), 8 * 1.0, rtol=1e-5)


def test_dequant_matmul_upgrade_changes_values_not_executables():
    """received_bits is NOT a static argument: sweeping it must reuse
    one compiled executable (the zero-recompile upgrade contract)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    qt = quantize(w, 16)
    before = dequant_matmul._cache_size()
    outs = []
    for m in (2, 4, 8, 16):
        scale, offset = dequant_affine(qt.lo, qt.hi, 16, received_bits=m)
        outs.append(dequant_matmul(x, qt.q, scale, offset,
                                   bm=16, bn=16, bk=32, interpret=True))
    assert dequant_matmul._cache_size() - before <= 1
    # sanity: different precisions produce different numbers
    assert not np.allclose(np.asarray(outs[0]), np.asarray(outs[-1]))


# ---------------------------------------------------------------------------
# bitplane kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(7,), (64,), (37, 53), (3, 5, 11)])
@pytest.mark.parametrize("widths", [(2,) * 8, (1, 3, 12), (8, 8), (16,)])
def test_plane_extract_or_roundtrip(shape, widths):
    x = jax.random.normal(jax.random.PRNGKey(sum(shape)), shape)
    qt = quantize(x, 16)
    cum = (0,) + bitplanes.cumulative(widths)
    acc = jnp.zeros_like(qt.q)
    for m, w in enumerate(widths, 1):
        pk = plane_extract(qt.q, bits=16, before=cum[m - 1], width=w,
                           interpret=True)
        want = bitplanes.split_plane(qt.q, 16, widths, m)
        assert (np.asarray(pk) == np.asarray(want, np.uint16)).all()
        acc = plane_or(acc, pk, shift=16 - cum[m], interpret=True)
    assert (np.asarray(acc) == np.asarray(qt.q)).all()


@pytest.mark.parametrize("bits", [4, 8, 16])
def test_plane_or_matches_ref(bits):
    k1, k2 = jax.random.split(jax.random.PRNGKey(bits))
    dt = container_dtype(bits)
    acc = jax.random.randint(k1, (129,), 0, 2 ** (bits // 2)).astype(dt)
    plane = jax.random.randint(k2, (129,), 0, 4).astype(dt)
    shift = bits - 2
    got = plane_or(acc, plane, shift=shift, interpret=True)
    want = ref.plane_or_ref(acc, plane, shift)
    assert (np.asarray(got) == np.asarray(want)).all()


# layouts as the PlaneStore stages them: tensors of these element counts
# packed back to back, each padded to ``block`` elements; block_rows
# (rows of 128 elements per grid step), so one layout spans several
# grid steps with a partial last one
UNPACK_LAYOUTS = {
    "ragged-multi-tensor": ((1000, 1, 2051, 77), 1024, 2048),
    "multi-step-partial": ((70_000, 9), 1024, 256),
}


@pytest.mark.parametrize("layout", sorted(UNPACK_LAYOUTS))
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.uint16, jnp.uint32])
@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_plane_unpack_matches_unpack_bits(width, dtype, layout):
    """plane_unpack (interpret mode) and its jnp oracle both give
    unpack_bits' values, and 0 in every padding element, whatever the
    tensors' counts (none of them need be a multiple of 8 / width)."""
    from repro.kernels.bitplane import plane_unpack

    sizes, block, block_rows = UNPACK_LAYOUTS[layout]
    rng = np.random.default_rng(width * 100 + len(sizes))
    padded = [-(-n // block) * block for n in sizes]
    packed = np.zeros(sum(padded) * width // 8, np.uint8)
    want = np.zeros(sum(padded), np.uint32)
    pos = 0
    for n, span in zip(sizes, padded):
        vals = rng.integers(0, 2 ** width, n)
        pk = bitplanes.pack_bits(vals, width)
        packed[pos * width // 8:pos * width // 8 + pk.size] = pk
        want[pos:pos + n] = bitplanes.unpack_bits(pk, width, n)
        pos += span
    got = plane_unpack(jnp.asarray(packed), width=width, dtype=dtype,
                       block_rows=block_rows, interpret=True)
    oracle = ref.plane_unpack_ref(jnp.asarray(packed), width, dtype)
    for out in (got, oracle):
        assert out.dtype == dtype and out.shape == want.shape
        np.testing.assert_array_equal(np.asarray(out), want.astype(dtype))


# ---------------------------------------------------------------------------
# flash decode attention (ragged batches, native (B, Kh, S, hd) layout)
# ---------------------------------------------------------------------------

def _ragged_inputs(key, B, H, Kh, hd, S, pos):
    """Random q/k/v in native layout + lock-stepped position operands
    (every slot at ``pos``)."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, S, hd))
    v = jax.random.normal(ks[2], (B, Kh, S, hd))
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q_pos = jnp.full((B,), pos, jnp.int32)
    return q, k, v, k_pos, q_pos


@pytest.mark.parametrize("B,H,Kh,hd,S", [
    (1, 4, 4, 32, 64),     # MHA
    (2, 8, 2, 64, 300),    # GQA, ragged S (block shrinks to a divisor)
    (2, 16, 1, 32, 128),   # MQA
    (1, 8, 8, 128, 1024),  # long-ish
])
def test_flash_decode_vs_ref(B, H, Kh, hd, S):
    q, k, v, k_pos, q_pos = _ragged_inputs(
        jax.random.PRNGKey(B + H + S), B, H, Kh, hd, S, S * 3 // 4)
    o = flash_decode(q, k, v, k_pos, q_pos, bs=128, interpret=True)
    orf = ref.flash_decode_ref(q, k, v, k_pos, q_pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_decode_window(window):
    B, H, Kh, hd, S = 2, 8, 4, 32, 200
    q, k, v, k_pos, q_pos = _ragged_inputs(
        jax.random.PRNGKey(window), B, H, Kh, hd, S, 150)
    o = flash_decode(q, k, v, k_pos, q_pos, window=window, bs=64,
                     interpret=True)
    orf = ref.flash_decode_ref(q, k, v, k_pos, q_pos, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


def test_flash_decode_softcap_and_ring_positions():
    """Ring-buffer slot positions (unordered, with overwrites, per-slot
    write depths) must work."""
    from repro.models.attention import ring_positions

    B, H, Kh, hd, W = 2, 4, 2, 32, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, W, hd))
    v = jax.random.normal(ks[2], (B, Kh, W, hd))
    q_pos = jnp.array([50, 17], jnp.int32)  # one wrapped ring, one not
    k_pos = ring_positions(W, q_pos)        # (B, W)
    o = flash_decode(q, k, v, k_pos, q_pos, window=W, softcap=20.0,
                     bs=16, interpret=True)
    orf = ref.flash_decode_ref(q, k, v, k_pos, q_pos, window=W,
                               softcap=20.0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


# -- ragged-parity sweeps: kernel (interpret) vs the chunked_attention
#    oracle, per-slot positions / GQA / window / softcap / empty slots ------

def _chunked_oracle(q, k, v, k_pos, q_pos, *, window=0, softcap=0.0):
    """Per-slot chunked_attention reference: runs each slot as its own
    B=1 sequence-major call, i.e. the PR-3 single-stream semantics."""
    from repro.models.attention import chunked_attention

    B = q.shape[0]
    outs = []
    for b in range(B):
        ob = chunked_attention(
            q[b][None, None],                      # (1, 1, H, hd)
            jnp.swapaxes(k[b], 0, 1)[None],        # (1, S, Kh, hd)
            jnp.swapaxes(v[b], 0, 1)[None],
            q_pos[b][None],
            k_pos[b],
            causal=True, window=window, softcap=softcap, chunk=32,
        )[0, 0]
        outs.append(ob)
    return jnp.stack(outs)


@pytest.mark.parametrize("Kh,window,softcap", [
    (4, 0, 0.0),    # MHA
    (2, 0, 0.0),    # GQA groups
    (2, 24, 0.0),   # sliding window
    (1, 0, 30.0),   # MQA + softcap
    (2, 16, 25.0),  # everything at once
])
def test_flash_decode_ragged_parity_vs_chunked(Kh, window, softcap):
    """Every slot at its own position (including one EMPTY slot with
    q_pos = -1 and k_pos all -1): the batched kernel must equal the
    single-stream chunked_attention oracle slot by slot — this is the
    contract that makes slot-pool decode token-identical to the
    lock-stepped path."""
    B, H, hd, S = 4, 8, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(Kh * 100 + window), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, S, hd))
    v = jax.random.normal(ks[2], (B, Kh, S, hd))
    q_pos = jnp.array([95, 40, 7, -1], jnp.int32)  # ragged + one empty
    base = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    k_pos = jnp.where(q_pos[:, None] >= 0, base, -1)

    got = flash_decode(q, k, v, k_pos, q_pos, window=window,
                       softcap=softcap, bs=32, interpret=True)
    live = [b for b in range(B) if int(q_pos[b]) >= 0]
    want_live = _chunked_oracle(
        q[jnp.array(live)], k[jnp.array(live)], v[jnp.array(live)],
        k_pos[jnp.array(live)], q_pos[jnp.array(live)],
        window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want_live),
                               rtol=2e-5, atol=2e-5)
    # the empty slot's row must be finite garbage, never NaN/Inf
    assert bool(jnp.all(jnp.isfinite(got[3])))
    # and it must equal the jnp oracle exactly on the same inputs
    orf = ref.flash_decode_ref(q, k, v, k_pos, q_pos, window=window,
                               softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_divisor_hostile_length_pads_tail():
    """A prime cache length can't shrink the block to a useful divisor;
    the wrapper must fall back to masked tail padding and stay exact."""
    B, H, Kh, hd, S = 2, 4, 2, 32, 97  # prime S
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, S, hd))
    v = jax.random.normal(ks[2], (B, Kh, S, hd))
    q_pos = jnp.array([96, 40], jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    o = flash_decode(q, k, v, k_pos, q_pos, bs=32, interpret=True)
    orf = ref.flash_decode_ref(q, k, v, k_pos, q_pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_all_slots_empty_is_finite():
    """A fully idle pool (every k_pos = -1) still runs one launch and
    produces finite output."""
    B, H, Kh, hd, S = 3, 4, 2, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, S, hd))
    v = jax.random.normal(ks[2], (B, Kh, S, hd))
    k_pos = jnp.full((B, S), -1, jnp.int32)
    q_pos = jnp.full((B,), -1, jnp.int32)
    o = flash_decode(q, k, v, k_pos, q_pos, bs=32, interpret=True)
    assert bool(jnp.all(jnp.isfinite(o)))
    orf = ref.flash_decode_ref(q, k, v, k_pos, q_pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_dispatch_matches_kernel():
    """ops.decode_attention (the model's entry point: oracle on CPU,
    Pallas on TPU) agrees with the interpret-mode kernel on identical
    ragged operands."""
    from repro.kernels import ops

    B, H, Kh, hd, S = 3, 8, 2, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, S, hd))
    v = jax.random.normal(ks[2], (B, Kh, S, hd))
    q_pos = jnp.array([63, 20, 5], jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    got = ops.decode_attention(q, k, v, k_pos, q_pos)
    want = flash_decode(q, k, v, k_pos, q_pos, bs=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash_verify — draft-block verify attention (T = k+1 ragged queries
# per slot, one cache pass)
# ---------------------------------------------------------------------------

from repro.kernels.verify_attention import flash_verify


def _verify_inputs(key, B, T, H, Kh, hd, S, bases):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, S, hd))
    v = jax.random.normal(ks[2], (B, Kh, S, hd))
    base = jnp.asarray(bases, jnp.int32)
    q_pos = jnp.where(base[:, None] >= 0,
                      base[:, None] + jnp.arange(T, dtype=jnp.int32),
                      -1)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    k_pos = jnp.where(base[:, None] >= 0, k_pos, -1)
    return q, k, v, k_pos, q_pos


@pytest.mark.parametrize("B,T,H,Kh,hd,S", [
    (1, 5, 4, 4, 32, 64),     # MHA
    (2, 3, 8, 2, 64, 300),    # GQA + divisor-shrunk block
    (2, 9, 16, 1, 32, 128),   # MQA, long draft block
    (3, 2, 4, 2, 32, 97),     # prime S: masked tail padding
])
def test_flash_verify_vs_ref(B, T, H, Kh, hd, S):
    q, k, v, k_pos, q_pos = _verify_inputs(
        jax.random.PRNGKey(B * 100 + T + S), B, T, H, Kh, hd, S,
        [S - T - 1] + [max(0, S // (b + 2) - T) for b in range(1, B)])
    o = flash_verify(q, k, v, k_pos, q_pos, bs=64, interpret=True)
    orf = ref.flash_verify_ref(q, k, v, k_pos, q_pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 48])
def test_flash_verify_window_and_ragged_rows(window):
    """Sliding-window verify with per-row positions AND ragged draft
    lengths: slot 1's last two rows are padding (q_pos = -1), slot 2 is
    a free pool slot (whole row masked). Padding/free rows must come
    out finite and live rows must match the oracle."""
    B, T, H, Kh, hd, S = 3, 4, 8, 2, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, Kh, S, hd))
    v = jax.random.normal(ks[2], (B, Kh, S, hd))
    q_pos = jnp.array([[60, 61, 62, 63],
                       [30, 31, -1, -1],
                       [-1, -1, -1, -1]], jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    o = flash_verify(q, k, v, k_pos, q_pos, window=window, bs=32,
                     interpret=True)
    orf = ref.flash_verify_ref(q, k, v, k_pos, q_pos, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)
    assert bool(jnp.all(jnp.isfinite(o)))


def test_flash_verify_row_matches_flash_decode():
    """Each live verify row must equal a single-token flash_decode call
    at the same position against the same cache — the kernel-level face
    of 'verify logits == sequential decode logits' that makes
    speculative decoding lossless."""
    B, T, H, Kh, hd, S = 2, 4, 8, 2, 32, 64
    q, k, v, k_pos, q_pos = _verify_inputs(
        jax.random.PRNGKey(3), B, T, H, Kh, hd, S, [40, 9])
    o = flash_verify(q, k, v, k_pos, q_pos, bs=32, interpret=True)
    for t in range(T):
        ot = flash_decode(q[:, t], k, v, k_pos, q_pos[:, t], bs=32,
                          interpret=True)
        np.testing.assert_allclose(np.asarray(o[:, t]), np.asarray(ot),
                                   rtol=2e-5, atol=2e-5)


def test_verify_attention_dispatch_matches_kernel():
    """ops.verify_attention (oracle on CPU, Pallas on TPU) agrees with
    the interpret-mode kernel on identical operands."""
    from repro.kernels import ops

    B, T, H, Kh, hd, S = 2, 3, 4, 2, 32, 64
    q, k, v, k_pos, q_pos = _verify_inputs(
        jax.random.PRNGKey(29), B, T, H, Kh, hd, S, [50, 12])
    got = ops.verify_attention(q, k, v, k_pos, q_pos)
    want = flash_verify(q, k, v, k_pos, q_pos, bs=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# in-place KV row write and layer-indexed attention over a stacked cache
# ---------------------------------------------------------------------------

def _old_row_writes(cache, new, rows):
    """The per-row masked ``write_kv_slot`` sequence a step used before
    the in-place write: row t of slot b lands at rows[b, t], a negative
    row writes nothing."""
    from repro.models.attention import write_kv_slot

    for t in range(rows.shape[1]):
        cache = write_kv_slot(cache, new[:, :, t:t + 1],
                              jnp.maximum(rows[:, t], 0), rows[:, t] >= 0)
    return cache


KV_WRITE_ROWS = {
    # T = 1 decode: live slots, a free slot, the last position
    "decode": [[5], [-1], [63], [16]],
    # T = 8 chunk: a full chunk, a short last chunk, a masked slot, and
    # a chunk whose last row is max_len - 1
    "chunk": [list(range(8, 16)), [21, 22, 23, -1, -1, -1, -1, -1],
              [-1] * 8, list(range(56, 64))],
    # T = k + 1 = 5 verify block: blocks straddling a 16-row tile, and a
    # masked slot
    "verify": [[13, 14, 15, 16, 17], [-1] * 5, [59, 60, 61, 62, 63],
               [0, 1, 2, 3, 4]],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(KV_WRITE_ROWS))
def test_kv_write_matches_row_writes_byte_for_byte(case, dtype):
    """The Pallas row write (interpret mode) and its oracle write into
    layer 1 of a 3-layer stack exactly the bytes the old per-row masked
    ``write_kv_slot`` writes into that layer alone, and leave every
    other layer, slot and row untouched."""
    from repro.kernels.kv_write import kv_write

    rows = jnp.asarray(KV_WRITE_ROWS[case], jnp.int32)
    (B, T), L, Kh, S, hd, layer = rows.shape, 3, 2, 64, 32, 1
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    k, v = (jax.random.normal(kk, (L, B, Kh, S, hd)).astype(dtype)
            for kk in ks[:2])
    kn, vn = (jax.random.normal(kk, (B, Kh, T, hd)).astype(dtype)
              for kk in ks[2:])
    want_k = np.array(k).copy()
    want_v = np.array(v).copy()
    want_k[layer] = np.array(_old_row_writes(k[layer], kn, rows))
    want_v[layer] = np.array(_old_row_writes(v[layer], vn, rows))
    got = {
        "kernel": kv_write(k, v, kn, vn, rows, jnp.int32(layer),
                           interpret=True),
        "oracle": ref.kv_write_ref(k, v, kn, vn, rows, jnp.int32(layer)),
    }
    for name, (gk, gv) in got.items():
        for g, w in ((gk, want_k), (gv, want_v)):
            assert g.dtype == w.dtype, name
            np.testing.assert_array_equal(
                np.asarray(g).view(np.uint8), w.view(np.uint8),
                err_msg=f"{name} {case}")


def test_kv_write_entry_point_on_one_cache():
    """``ops.kv_write`` with no layer index writes a plain (B, Kh, S, hd)
    cache (a tail block's) like the stacked path writes one layer."""
    from repro.kernels import ops

    rows = jnp.asarray(KV_WRITE_ROWS["chunk"], jnp.int32)
    B, T = rows.shape
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    k, v = (jax.random.normal(kk, (B, 2, 64, 32)) for kk in ks[:2])
    kn, vn = (jax.random.normal(kk, (B, 2, T, 32)) for kk in ks[2:])
    gk, gv = ops.kv_write(k, v, kn, vn, rows)
    np.testing.assert_array_equal(np.asarray(gk),
                                  np.asarray(_old_row_writes(k, kn, rows)))
    np.testing.assert_array_equal(np.asarray(gv),
                                  np.asarray(_old_row_writes(v, vn, rows)))


@pytest.mark.parametrize("S", [128, 97])  # aligned; prime (tail padding)
@pytest.mark.parametrize("rows", [1, 4])  # flash_decode, flash_verify
def test_layer_indexed_attention_matches_sliced_layer(rows, S):
    """The kernels and their oracles reading layer l of a stacked
    (L, B, Kh, S, hd) cache by index give exactly what they give on that
    layer sliced out beforehand."""
    from repro.kernels import ops

    L, B, H, Kh, hd, layer = 3, 2, 8, 2, 32, 2
    ks = jax.random.split(jax.random.PRNGKey(S + rows), 3)
    k = jax.random.normal(ks[0], (L, B, Kh, S, hd))
    v = jax.random.normal(ks[1], (L, B, Kh, S, hd))
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    base = jnp.array([S - rows - 1, S // 3], jnp.int32)
    if rows == 1:
        q = jax.random.normal(ks[2], (B, H, hd))
        q_pos = base
        kernel, entries = flash_decode, (ops.decode_attention,)
    else:
        q = jax.random.normal(ks[2], (B, rows, H, hd))
        q_pos = base[:, None] + jnp.arange(rows, dtype=jnp.int32)
        kernel = flash_verify
        entries = (ops.verify_attention, ops.prefill_attention)
    li = jnp.int32(layer)
    got = kernel(q, k, v, k_pos, q_pos, li, bs=32, interpret=True)
    want = kernel(q, k[layer], v[layer], k_pos, q_pos, bs=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for entry in entries:
        np.testing.assert_array_equal(
            np.asarray(entry(q, k, v, k_pos, q_pos, li)),
            np.asarray(entry(q, k[layer], v[layer], k_pos, q_pos)))
