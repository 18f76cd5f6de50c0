"""Compile-only guards for the serving path's Pallas kernels on a TPU v5e.

Each test compiles one kernel for a *described* ``v5e:2x2`` chip at
olmo-1b widths: Mosaic refuses there what interpret mode accepts
(unaligned blocks, casts it lacks, more SMEM than a chip has). Nothing
runs, so no result or time is checked here; ``chip_smoke.py`` does that
on the chip. The topology is described inside a fixture, never at
import: only one process may load the TPU library, and the others skip.
"""
import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.quantize import QuantizedTensor
from repro.kernels import bitplane, decode_attention, dequant_matmul, \
    kv_write, ops, verify_attention
from repro.models.common import quantized_resident_eligible
from repro.models.model import build_model
from repro.serving.engine import _make_chunk_step

OLMO = get_config("olmo-1b")
MINITRON = get_config("minitron-4b")
S, HD = 2048, OLMO.hd
CHUNK = 8  # the slot pool's default prefill chunk
SLOTS = 64  # the minitron4b.offline cell's pool


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


@pytest.mark.parametrize("k,n", [(MINITRON.d_model, MINITRON.vocab),   # untied head
                                 (MINITRON.d_ff, MINITRON.d_model)])  # MLP down
@pytest.mark.parametrize("m", [SLOTS, SLOTS * CHUNK])  # decode, prefill chunk
def test_dequant_matmul_compiles_at_minitron(shape_on_chip, no_persistent_cache,
                                             m, k, n):
    hlo = _compile_for_chip(
        lambda x, q, s, o: dequant_matmul.dequant_matmul(x, q, s, o,
                                                         interpret=False),
        shape_on_chip((m, k), jnp.float32 if n == MINITRON.vocab else jnp.bfloat16),
        shape_on_chip((k, n), jnp.uint16),
        shape_on_chip((1, 1), jnp.float32),
        shape_on_chip((1, 1), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", [1, CHUNK])  # flash_decode, flash_verify
def test_attention_compiles_at_minitron_gqa(shape_on_chip, no_persistent_cache, rows):
    """24 query heads over 8 KV heads (a group of 3, padded to the
    8-row sublane) for 64 slots of 2048 positions."""
    kv = shape_on_chip((SLOTS, MINITRON.n_kv, S, MINITRON.hd), jnp.bfloat16)
    kp = shape_on_chip((SLOTS, S), jnp.int32)
    if rows == 1:
        fn = lambda q, k, v, kp, qp: decode_attention.flash_decode(  # noqa: E731
            q, k, v, kp, qp, interpret=False)
        q = shape_on_chip((SLOTS, MINITRON.n_heads, MINITRON.hd), jnp.bfloat16)
        qp = shape_on_chip((SLOTS,), jnp.int32)
    else:
        fn = lambda q, k, v, kp, qp: verify_attention.flash_verify(  # noqa: E731
            q, k, v, kp, qp, interpret=False)
        q = shape_on_chip((SLOTS, rows, MINITRON.n_heads, MINITRON.hd), jnp.bfloat16)
        qp = shape_on_chip((SLOTS, rows), jnp.int32)
    assert "tpu_custom_call" in _compile_for_chip(fn, q, kv, kv, kp, qp)


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


# ---------------------------------------------------------------------------
# the KV cache through a whole step: written in place, never copied
# ---------------------------------------------------------------------------

# (configuration, slots, max_len) of each benchmark cell's pool
STEP_SHAPES = {
    "minitron4b": (dataclasses.replace(MINITRON, n_layers=4), SLOTS, S),
    "olmo1b": (OLMO, 8, 1280),
}
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\{[^}]*\} "
                    r"([\w-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "scatter")


def _resident_params(model, shape_on_chip):
    """Shapes of the quantized-resident parameters the benchmark's pool
    serves: every eligible leaf a 16-bit code view with its affine."""
    def leaf(path, x):
        if not quantized_resident_eligible(path):
            return shape_on_chip(x.shape, x.dtype)
        aff = lambda dt=jnp.float32: shape_on_chip(x.shape[:-2] + (1, 1), dt)  # noqa: E731
        return QuantizedTensor(q=shape_on_chip(x.shape, jnp.uint16),
                               lo=aff(), hi=aff(), bits=16,
                               orig_dtype=x.dtype, scale=aff(),
                               offset=aff(), received_bits=aff(jnp.int32))
    return jax.tree_util.tree_map_with_path(
        leaf, jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def _cache_moves(hlo: str, dtype, layer_elems: int) -> list[str]:
    """Instructions of the cache's dtype, one layer's cache or larger,
    that copy, slice or update it (fused ones by their fusion's name).
    The weight codes (uint) and logits (f32) are another matter."""
    dt = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) != dt:
            continue
        name, _, dims, op = m.groups()
        n = int(np.prod([int(d) for d in dims.split(",") if d]))
        if n >= layer_elems and (op in _MOVES or (
                op == "fusion" and any(mv in name for mv in _MOVES))):
            out.append(f"{op} {name} {dt}[{dims}]")
    return out


@pytest.mark.parametrize("step", ["decode", "chunk"])
@pytest.mark.parametrize("cell", sorted(STEP_SHAPES))
def test_step_updates_kv_cache_in_place(shape_on_chip, no_persistent_cache,
                                        monkeypatch, cell, step):
    """A decode step and the pool's chunk step, compiled with the Pallas
    kernels at a benchmark cell's shapes with the cache donated as the
    engine donates it: no layer of the cache is copied, sliced out or
    rebuilt, and the cache parameters alias the outputs."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg, B, max_len = STEP_SHAPES[cell]
    model = build_model(cfg)
    params = _resident_params(model, shape_on_chip)
    caches = jax.tree.map(lambda a: shape_on_chip(a.shape, a.dtype),
                          jax.eval_shape(lambda: model.init_caches(B, max_len)))
    i32 = lambda *shape: shape_on_chip(shape, jnp.int32)  # noqa: E731
    if step == "decode":
        fn, rest = model.decode_step, (i32(B, 1), i32(B))
    else:
        fn = _make_chunk_step(model)
        rest = (i32(B, CHUNK), i32(B, CHUNK), i32(B), i32(B),
                shape_on_chip((B, cfg.vocab), jnp.float32), i32(B, 1), i32(B))
    compiled = jax.jit(fn, donate_argnums=1).lower(params, caches, *rest)
    hlo = compiled.compile().as_text()
    assert "tpu_custom_call" in hlo
    layer_elems = B * cfg.n_kv * max_len * cfg.hd
    assert not _cache_moves(hlo, cfg.dtype, layer_elems)
    # the entry parameters keep their argument paths as names
    cache_args = {int(n) for n in re.findall(
        r"%caches\S* = \S+ parameter\((\d+)\)", hlo)}
    assert len(cache_args) == len(jax.tree.leaves(caches))
    header = hlo.split("\n", 1)[0]
    aliased = {int(a) for a in re.findall(r"\((\d+), \{\}, (?:may|must)-alias",
                                          header)}
    assert cache_args <= aliased, header[:400]


@pytest.mark.parametrize("rows", [1, CHUNK])  # decode, prefill chunk
def test_kv_write_compiles_at_minitron(shape_on_chip, no_persistent_cache,
                                       rows):
    """The row write over minitron4b.offline's 4-layer stack of 64 slots
    x 2048, with the stack aliased to the output."""
    kv = shape_on_chip((4, SLOTS, MINITRON.n_kv, S, MINITRON.hd), jnp.bfloat16)
    new = shape_on_chip((SLOTS, MINITRON.n_kv, rows, MINITRON.hd),
                        jnp.bfloat16)
    hlo = jax.jit(lambda k, v, kn, vn, r, li: kv_write.kv_write(
        k, v, kn, vn, r, li), donate_argnums=(0, 1)).lower(
            kv, kv, new, new, shape_on_chip((SLOTS, rows), jnp.int32),
            shape_on_chip((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "input_output_alias={ {0}: (0, {}, may-alias), " \
           "{1}: (1, {}, may-alias) }" in hlo
