"""In-place KV row write Pallas TPU kernel: a step's new K/V rows go
straight into the stacked cache buffer that came in.

A decode, verify or chunked-prefill step produces ``T`` new K/V rows per
slot (T = 1, k + 1 or the chunk). The cache is the whole stack's
``(L, B, Kh, S, hd)`` buffer, aliased to the output
(``input_output_aliases``), so nothing but those rows' tiles crosses
HBM: no layer is sliced out, re-laid-out or rebuilt.

  grid = (B,); for each slot, DMA the aligned window of ``W`` positions
  that holds its live rows into VMEM, select the new rows in, and DMA
  the window back.

A DMA cannot start inside a tile of the sequence axis (the cache is
tiled 8 or 16 rows deep there), so the kernel writes whole aligned
windows: ``W`` is the block length ``T`` rounded up past one tile, and
the window starts at the tile holding the slot's first live row (pulled
back from the cache end where it would run over). Rows outside the
write keep their bytes. ``rows`` (B, T) gives each new row's position;
a negative position masks the row, and a slot with no live row moves no
bytes at all. A slot's live rows must lie within ``T`` consecutive
positions from its lowest one, which every caller's blocks do (a decode
token, a contiguous verify block, a chunk's live prefix). The layer
index and the positions arrive by scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ALIGN = 16  # sequence-axis tile depth a DMA may start on (bf16 and f32)


def window_rows(T: int, S: int) -> int:
    """Rows moved per slot for a block of T new rows: T rounded up past
    one tile, at most the whole cache."""
    return min(S, -(-(T + ALIGN - 1) // ALIGN) * ALIGN)


def _kernel(layer_ref, rows_ref, kn_ref, vn_ref, k_in, v_in, k_out, v_out,
            kw, vw, sem, *, T: int, S: int, W: int):
    del k_in, v_in  # aliased to k_out / v_out
    b = pl.program_id(0)
    layer = layer_ref[0]
    lo = jnp.int32(S)
    for t in range(T):
        p = rows_ref[b, t]
        lo = jnp.where((p >= 0) & (p < lo), p, lo)

    @pl.when(lo < S)
    def _write():
        a = pl.multiple_of(jnp.minimum(lo // ALIGN * ALIGN, S - W), ALIGN)

        def dma(src, dst, i):
            return pltpu.make_async_copy(src, dst, sem.at[i])

        k_win = k_out.at[layer, b, :, pl.ds(a, W), :]
        v_win = v_out.at[layer, b, :, pl.ds(a, W), :]
        rk, rv = dma(k_win, kw, 0), dma(v_win, vw, 1)
        rk.start()
        rv.start()
        rk.wait()
        rv.wait()
        pos = lax.broadcasted_iota(jnp.int32, (1, W, 1), 1) + a
        k, v = kw[...], vw[...]
        for t in range(T):
            hit = pos == rows_ref[b, t]
            k = jnp.where(hit, kn_ref[0, :, t:t + 1, :], k)
            v = jnp.where(hit, vn_ref[0, :, t:t + 1, :], v)
        kw[...] = k
        vw[...] = v
        wk, wv = dma(kw, k_win, 0), dma(vw, v_win, 1)
        wk.start()
        wv.start()
        wk.wait()
        wv.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_write(
    k: jax.Array,       # (L, B, Kh, S, hd) stacked cache, updated in place
    v: jax.Array,       # (L, B, Kh, S, hd)
    k_new: jax.Array,   # (B, Kh, T, hd) new rows
    v_new: jax.Array,   # (B, Kh, T, hd)
    rows: jax.Array,    # (B, T) int32 positions; negative = masked row
    layer: jax.Array,   # () int32 layer index into the stack
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    L, B, Kh, S, hd = k.shape
    T = k_new.shape[2]
    if S % ALIGN:
        raise ValueError(f"cache length {S} is not a multiple of {ALIGN}")
    W = window_rows(T, S)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    new = pl.BlockSpec((1, Kh, T, hd), lambda b, *_: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, T=T, S=S, W=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[new, new, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.VMEM((Kh, W, hd), k.dtype),
                            pltpu.VMEM((Kh, W, hd), v.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      k_new.astype(k.dtype), v_new.astype(v.dtype), k, v)
