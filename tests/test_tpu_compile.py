"""Compile-only guards for the serving path's Pallas kernels on a TPU v5e.

Each test compiles one kernel for a *described* ``v5e:2x2`` chip at
olmo-1b widths: Mosaic refuses there what interpret mode accepts
(unaligned blocks, casts it lacks, more SMEM than a chip has). Nothing
runs, so no result or time is checked here; ``chip_smoke.py`` does that
on the chip. The topology is described inside a fixture, never at
import: only one process may load the TPU library, and the others skip.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels import bitplane, decode_attention, dequant_matmul, \
    verify_attention
from repro.models.model import build_model

OLMO = get_config("olmo-1b")
S, HD = 2048, OLMO.hd
CHUNK = 8  # the slot pool's default prefill chunk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_on_chip(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("n", [OLMO.d_ff, OLMO.vocab])
@pytest.mark.parametrize("m", [4, 4 * CHUNK])  # decode, prefill chunk
@pytest.mark.parametrize("qdtype", [jnp.uint8, jnp.uint16])
def test_dequant_matmul_compiles(shape_on_chip, no_persistent_cache,
                                 qdtype, m, n):
    hlo = _compile_for_chip(
        lambda x, q, s, o: dequant_matmul.dequant_matmul(x, q, s, o,
                                                         interpret=False),
        shape_on_chip((m, OLMO.d_model), jnp.bfloat16),
        shape_on_chip((OLMO.d_model, n), qdtype),
        shape_on_chip((1, 1), jnp.float32),
        shape_on_chip((1, 1), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b", [1, 4, 8])
def test_flash_decode_compiles(shape_on_chip, no_persistent_cache, b):
    kv = shape_on_chip((b, OLMO.n_kv, S, HD), jnp.bfloat16)
    hlo = _compile_for_chip(
        lambda q, k, v, kp, qp: decode_attention.flash_decode(
            q, k, v, kp, qp, interpret=False),
        shape_on_chip((b, OLMO.n_heads, HD), jnp.bfloat16), kv, kv,
        shape_on_chip((b, S), jnp.int32), shape_on_chip((b,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b", [1, 4, 8])
def test_flash_verify_compiles(shape_on_chip, no_persistent_cache, b):
    """T = CHUNK query rows per slot: the chunked-prefill launch, which
    is the verify kernel."""
    kv = shape_on_chip((b, OLMO.n_kv, S, HD), jnp.bfloat16)
    hlo = _compile_for_chip(
        lambda q, k, v, kp, qp: verify_attention.flash_verify(
            q, k, v, kp, qp, interpret=False),
        shape_on_chip((b, CHUNK, OLMO.n_heads, HD), jnp.bfloat16), kv, kv,
        shape_on_chip((b, S), jnp.int32),
        shape_on_chip((b, CHUNK), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_plane_or_segments_compiles_at_whole_olmo_buffer(
        shape_on_chip, no_persistent_cache):
    """One launch over the whole olmo-1b uint16 accumulator (every
    tensor of the model, block-aligned): the segment table must fit in
    SMEM at this length."""
    from repro.core.plane_store import DEFAULT_BLOCK

    leaves = jax.tree.leaves(jax.eval_shape(
        build_model(OLMO).init, jax.random.PRNGKey(0)))
    n = sum(-(-int(np.prod(x.shape)) // DEFAULT_BLOCK) * DEFAULT_BLOCK
            for x in leaves)
    assert n > 1_176_000_000
    hlo = _compile_for_chip(
        lambda a, p, st, sh: bitplane.plane_or_segments(
            a, p, st, sh, block=DEFAULT_BLOCK, interpret=False),
        shape_on_chip((n,), jnp.uint16), shape_on_chip((n,), jnp.uint16),
        shape_on_chip((len(leaves),), jnp.int32),
        shape_on_chip((len(leaves),), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_plane_unpack_compiles_at_whole_olmo_buffer(
        shape_on_chip, no_persistent_cache):
    """The device unpack of a whole olmo-1b stage: 2-bit planes packed
    into uint8 bytes in, the uint16 plane the OR reads out."""
    from repro.core.plane_store import DEFAULT_BLOCK

    leaves = jax.tree.leaves(jax.eval_shape(
        build_model(OLMO).init, jax.random.PRNGKey(0)))
    n = sum(-(-int(np.prod(x.shape)) // DEFAULT_BLOCK) * DEFAULT_BLOCK
            for x in leaves)
    assert n > 1_176_000_000
    hlo = _compile_for_chip(
        lambda p: bitplane.plane_unpack(p, width=2, dtype=jnp.uint16,
                                        interpret=False),
        shape_on_chip((n * 2 // 8,), jnp.uint8))
    assert "tpu_custom_call" in hlo
