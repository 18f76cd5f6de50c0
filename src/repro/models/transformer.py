"""Pattern-scan stack executor.

A model is a repeating cycle of block kinds (cfg.cycle) executed
``n_cycles`` times under ``lax.scan`` with parameters stacked over the
cycle dimension, plus an unrolled tail for the remainder layers. HLO size
is therefore O(len(cycle)), not O(n_layers) — a 100-layer model compiles
as fast as a 5-layer one, which is what makes 80 dry-run compiles
feasible (and is just good practice on real TPUs too).

Caches mirror the parameter layout: one stacked pytree per cycle slot
plus per-tail-block pytrees. ``shared_attn`` blocks (Zamba2) read their
weights from a single non-stacked store and only their caches are
per-occurrence.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.common import ArchConfig, apply_norm, dense, norm_init, dense_init

ZERO_AUX = lambda: {"balance_loss": jnp.float32(0.0), "dropped_frac": jnp.float32(0.0)}

ATTN_KINDS = {"attn", "swa", "global", "moe", "swa_moe", "shared_attn", "enc_attn"}


# ---------------------------------------------------------------------------
# Per-block init / apply
# ---------------------------------------------------------------------------

def block_init(cfg: ArchConfig, key, kind: str):
    ks = jax.random.split(key, 4)
    if kind in ("attn", "swa", "global", "shared_attn", "enc_attn"):
        return {
            "norm1": norm_init(cfg, cfg.d_model),
            "attn": attn.attn_init(cfg, ks[0]),
            "norm2": norm_init(cfg, cfg.d_model),
            "mlp": attn.mlp_init(cfg, ks[1]),
        }
    if kind in ("moe", "swa_moe"):
        return {
            "norm1": norm_init(cfg, cfg.d_model),
            "attn": attn.attn_init(cfg, ks[0]),
            "norm2": norm_init(cfg, cfg.d_model),
            "moe": moe_mod.moe_init(cfg, ks[1]),
        }
    if kind == "cross":
        return {
            "norm1": norm_init(cfg, cfg.d_model),
            "attn": attn.attn_init(cfg, ks[0], cross=True),
            "gate_attn": jnp.zeros((), jnp.float32),
            "norm2": norm_init(cfg, cfg.d_model),
            "mlp": attn.mlp_init(cfg, ks[1]),
            "gate_mlp": jnp.zeros((), jnp.float32),
        }
    if kind == "selfcross":
        return {
            "norm1": norm_init(cfg, cfg.d_model),
            "self_attn": attn.attn_init(cfg, ks[0]),
            "norm_x": norm_init(cfg, cfg.d_model),
            "cross_attn": attn.attn_init(cfg, ks[1], cross=True),
            "norm2": norm_init(cfg, cfg.d_model),
            "mlp": attn.mlp_init(cfg, ks[2]),
        }
    if kind == "mamba2":
        return {"norm1": norm_init(cfg, cfg.d_model), "mixer": ssm.mamba2_init(cfg, ks[0])}
    if kind == "mlstm":
        return {"norm1": norm_init(cfg, cfg.d_model), "mixer": ssm.mlstm_init(cfg, ks[0])}
    if kind == "slstm":
        return {"norm1": norm_init(cfg, cfg.d_model), "mixer": ssm.slstm_init(cfg, ks[0])}
    raise ValueError(f"unknown block kind {kind}")


def _attn_window(cfg: ArchConfig, kind: str) -> int:
    if kind in ("swa", "swa_moe"):
        return cfg.window
    return 0


def _attn_theta(cfg: ArchConfig, kind: str) -> float:
    # gemma3-style: global layers use a larger rope base
    if kind == "global":
        return getattr(cfg, "rope_theta", 1e4) * 100.0
    return cfg.rope_theta


def carries_kv(cfg: ArchConfig, kind: str) -> bool:
    """Whether a block's cache is one positional self-attention KV
    cache (window 0), which the layer loop of a decode, verify or
    chunk step carries whole and updates in place. Ring caches
    (window > 0), cross caches and recurrent states are scanned
    over instead, a layer slice in and a layer out."""
    return kind in ("attn", "global", "moe", "shared_attn") or (
        kind in ("swa", "swa_moe") and not cfg.window)


def kv_path(cfg: ArchConfig) -> str:
    """``"in_place"`` when every cache of the model is carried through
    its step's layer loop (no cache-sized copy in a decode, verify or
    chunk step), else ``"copied"``."""
    kinds = set(cfg.cycle) | set(cfg.tail)
    if not cfg.costing and all(carries_kv(cfg, k) for k in kinds):
        return "in_place"
    return "copied"


def block_apply(cfg: ArchConfig, kind: str, p, x, *, mode: str, cache, pos,
                enc_out, layer=None):
    """Returns (x, new_cache, aux). ``layer`` (a () int32) marks
    ``cache`` as the whole carried stack and indexes this layer in it."""
    aux = ZERO_AUX()
    if kind in ("attn", "swa", "global", "shared_attn", "enc_attn"):
        h = apply_norm(cfg, p["norm1"], x)
        if kind == "enc_attn":
            q, k, v = attn.project_qkv(cfg, p["attn"], h, h)
            T = h.shape[1]
            qpos = jnp.arange(T, dtype=jnp.int32)
            q = attn.rope(q, qpos, cfg.rope_theta, cfg.rotary_dim)
            k = attn.rope(k, qpos, cfg.rope_theta, cfg.rotary_dim)
            o = attn.chunked_attention(
                q, k, v, qpos, qpos, causal=False, window=0,
                chunk=cfg.attn_chunk, unroll=cfg.costing,
            )
            a_out = dense(o.reshape(*h.shape[:2], -1), p["attn"]["wo"],
                          dtype=cfg.dtype)
            new_cache = None
        else:
            with jax.named_scope("attn"):
                a_out, new_cache = attn.self_attention(
                    cfg,
                    p["attn"],
                    h,
                    mode=mode,
                    window=_attn_window(cfg, kind),
                    cache=cache,
                    pos=pos,
                    rope_theta=_attn_theta(cfg, kind),
                    layer=layer,
                )
        x = x + a_out
        h2 = apply_norm(cfg, p["norm2"], x)
        x = x + attn.mlp_apply(cfg, p["mlp"], h2)
        return x, new_cache, aux

    if kind in ("moe", "swa_moe"):
        h = apply_norm(cfg, p["norm1"], x)
        a_out, new_cache = attn.self_attention(
            cfg,
            p["attn"],
            h,
            mode=mode,
            window=_attn_window(cfg, kind),
            cache=cache,
            pos=pos,
            layer=layer,
        )
        x = x + a_out
        h2 = apply_norm(cfg, p["norm2"], x)
        m_out, moe_aux = moe_mod.moe_apply(cfg, p["moe"], h2)
        x = x + m_out
        aux = {k: aux[k] + jnp.float32(moe_aux[k]) for k in aux}
        return x, new_cache, aux

    if kind in ("cross", "selfcross") and mode == "prefill_chunk":
        # the vision/enc cross memory is produced by the admission-time
        # encoder pass, which a mid-stream chunk step doesn't have; the
        # slot pool falls back to batch-1 admission for these archs
        raise NotImplementedError(
            f"chunked prefill is not supported for {kind} blocks")

    if kind == "cross":
        h = apply_norm(cfg, p["norm1"], x)
        if mode in ("decode", "verify"):
            # cache holds the native (B, K, Tv, hd) layout, static
            a_out = attn.cross_attention(cfg, p["attn"], h, cache, native=True)
            new_cache = cache
        else:
            kv = attn.cross_kv(cfg, p["attn"], enc_out)
            new_cache = attn.to_native_kv(kv) if mode == "prefill" else None
            a_out = attn.cross_attention(cfg, p["attn"], h, kv)
        x = x + jnp.tanh(p["gate_attn"]).astype(cfg.dtype) * a_out
        h2 = apply_norm(cfg, p["norm2"], x)
        x = x + jnp.tanh(p["gate_mlp"]).astype(cfg.dtype) * attn.mlp_apply(cfg, p["mlp"], h2)
        return x, new_cache, aux

    if kind == "selfcross":
        h = apply_norm(cfg, p["norm1"], x)
        self_cache = cache["self"] if cache is not None else None
        a_out, new_self = attn.self_attention(
            cfg, p["self_attn"], h, mode=mode, window=0, cache=self_cache, pos=pos
        )
        x = x + a_out
        hx = apply_norm(cfg, p["norm_x"], x)
        if mode in ("decode", "verify"):
            new_cross = cache["cross"]  # native layout, static
            x = x + attn.cross_attention(cfg, p["cross_attn"], hx,
                                         cache["cross"], native=True)
        else:
            kv = attn.cross_kv(cfg, p["cross_attn"], enc_out)
            new_cross = attn.to_native_kv(kv) if mode == "prefill" else None
            x = x + attn.cross_attention(cfg, p["cross_attn"], hx, kv)
        h2 = apply_norm(cfg, p["norm2"], x)
        x = x + attn.mlp_apply(cfg, p["mlp"], h2)
        new_cache = None
        if mode in ("prefill", "decode", "verify"):
            new_cache = {"self": new_self, "cross": new_cross}
        return x, new_cache, aux

    if kind in ("mamba2", "mlstm", "slstm"):
        if mode == "verify":
            # an SSM/recurrent state is cumulative: a rejected draft
            # can't be "overwritten", it would need a state snapshot per
            # draft token — the opposite of the zero-copy KV story
            raise NotImplementedError(
                f"speculative verify is not supported for {kind} blocks "
                f"(recurrent state has no overwrite-only rollback)")
        if mode == "prefill_chunk":
            # Unlike KV caches there is no positional indexing to hide
            # behind: the chunk is consumed token-by-token through the
            # single-step recurrence, with a per-token live mask so
            # masked rows (free/decoding slots, ragged padding) leave
            # the slot's state byte-identical. The chunk length is
            # small and static, so the unrolled loop stays cheap and
            # the executable count stays one per chunk shape.
            step = {"mamba2": ssm.mamba2_step, "mlstm": ssm.mlstm_step,
                    "slstm": ssm.slstm_step}[kind]
            h = apply_norm(cfg, p["norm1"], x)
            c = cache
            outs = []
            for t in range(h.shape[1]):
                o_t, c_new = step(cfg, p["mixer"], h[:, t:t + 1], c)
                c = _mask_recurrent(c_new, c, pos[:, t])
                outs.append(o_t)
            return x + jnp.concatenate(outs, axis=1), c, aux

    if kind == "mamba2":
        h = apply_norm(cfg, p["norm1"], x)
        if mode == "full":
            out = ssm.mamba2_forward(cfg, p["mixer"], h)
            new_cache = None
        elif mode == "prefill":
            out, new_cache = ssm.mamba2_prefill(cfg, p["mixer"], h)
        else:
            out, new_cache = ssm.mamba2_step(cfg, p["mixer"], h, cache)
            if pos is not None:
                new_cache = _mask_recurrent(
                    new_cache, cache, attn.decode_pos_vector(pos, x.shape[0]))
        return x + out, new_cache, aux

    if kind in ("mlstm", "slstm"):
        fwd = ssm.mlstm_forward if kind == "mlstm" else ssm.slstm_forward
        step = ssm.mlstm_step if kind == "mlstm" else ssm.slstm_step
        h = apply_norm(cfg, p["norm1"], x)
        if mode == "full":
            out = fwd(cfg, p["mixer"], h)
            new_cache = None
        elif mode == "prefill":
            out, new_cache = fwd(cfg, p["mixer"], h, return_cache=True)
        else:
            out, new_cache = step(cfg, p["mixer"], h, cache)
            if pos is not None:
                new_cache = _mask_recurrent(
                    new_cache, cache, attn.decode_pos_vector(pos, x.shape[0]))
        return x + out, new_cache, aux

    raise ValueError(f"unknown block kind {kind}")


def _mask_recurrent(new_cache, cache, pos_vec):
    """Per-slot no-op for a recurrent state update: slots whose position
    is negative (free pool slots, mid-chunked-prefill slots riding a
    batched decode step, ragged chunk padding) keep their old state
    byte-identical. Every recurrent cache leaf is batch-first, so one
    broadcasted ``where`` per leaf suffices — unlike KV writes there is
    no positional clamp to make a masked write land harmlessly."""
    live = pos_vec >= 0
    return jax.tree.map(
        lambda new, old: jnp.where(
            live.reshape((-1,) + (1,) * (new.ndim - 1)),
            new, old.astype(new.dtype)),
        new_cache, cache)


# ---------------------------------------------------------------------------
# Cache construction (shape-only safe: works under jax.eval_shape)
# ---------------------------------------------------------------------------

def block_init_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                     enc_len: int, ring_margin: int = 0):
    # KV caches use the decode kernel's native (B, K, S, hd) layout so
    # the per-token hot loop never transposes or pads the cache.
    # ring_margin over-allocates sliding-window rings beyond the
    # attention window so speculative verify blocks can write k+1
    # positions ahead without clobbering live window entries.
    dt = cfg.dtype
    if kind in ("attn", "global", "moe", "shared_attn"):
        return {
            "k": jnp.zeros((batch, cfg.n_kv, max_len, cfg.hd), dt),
            "v": jnp.zeros((batch, cfg.n_kv, max_len, cfg.hd), dt),
        }
    if kind in ("swa", "swa_moe"):
        # ring buffer size; margin only matters for real windows
        W = cfg.window + ring_margin if cfg.window else max_len
        return {
            "k": jnp.zeros((batch, cfg.n_kv, W, cfg.hd), dt),
            "v": jnp.zeros((batch, cfg.n_kv, W, cfg.hd), dt),
        }
    if kind == "cross":
        return {
            "k": jnp.zeros((batch, cfg.n_kv, enc_len, cfg.hd), dt),
            "v": jnp.zeros((batch, cfg.n_kv, enc_len, cfg.hd), dt),
        }
    if kind == "selfcross":
        return {
            "self": {
                "k": jnp.zeros((batch, cfg.n_kv, max_len, cfg.hd), dt),
                "v": jnp.zeros((batch, cfg.n_kv, max_len, cfg.hd), dt),
            },
            "cross": {
                "k": jnp.zeros((batch, cfg.n_kv, enc_len, cfg.hd), dt),
                "v": jnp.zeros((batch, cfg.n_kv, enc_len, cfg.hd), dt),
            },
        }
    if kind == "mamba2":
        return ssm.mamba2_init_cache(cfg, batch, dt)
    if kind == "mlstm":
        return ssm.mlstm_init_cache(cfg, batch, dt)
    if kind == "slstm":
        return ssm.slstm_init_cache(cfg, batch, dt)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Stack init / run
# ---------------------------------------------------------------------------

def _stacked_init(cfg: ArchConfig, key, kind: str, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: block_init(cfg, k, kind))(keys)


def stack_init(cfg: ArchConfig, key) -> dict:
    """Params for the decoder stack (cycles + tail + shared)."""
    out: dict[str, Any] = {"cycles": {}, "tail": {}}
    n_slots = len(cfg.cycle)
    keys = jax.random.split(key, n_slots + len(cfg.tail) + 1)
    for j, kind in enumerate(cfg.cycle):
        if kind == "shared_attn":
            continue
        out["cycles"][f"{j}_{kind}"] = _stacked_init(cfg, keys[j], kind, cfg.n_cycles)
    for i, kind in enumerate(cfg.tail):
        if kind == "shared_attn":
            continue
        out["tail"][f"{i}_{kind}"] = block_init(cfg, keys[n_slots + i], kind)
    if "shared_attn" in cfg.cycle + cfg.tail:
        out["shared"] = block_init(cfg, keys[-1], "shared_attn")
    return out


def stack_init_caches(cfg: ArchConfig, batch: int, max_len: int,
                      enc_len: int = 0, ring_margin: int = 0):
    caches: dict[str, Any] = {"cycles": {}, "tail": {}}
    for j, kind in enumerate(cfg.cycle):
        one = block_init_cache(cfg, kind, batch, max_len, enc_len, ring_margin)
        caches["cycles"][f"{j}_{kind}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_cycles,) + a.shape), one
        )
    for i, kind in enumerate(cfg.tail):
        caches["tail"][f"{i}_{kind}"] = block_init_cache(
            cfg, kind, batch, max_len, enc_len, ring_margin)
    return caches


def run_stack(
    cfg: ArchConfig,
    params: dict,
    x: jax.Array,
    *,
    mode: str,  # full | prefill | prefill_chunk | verify | decode
    caches=None,
    pos=None,
    enc_out=None,
):
    """Returns (x, new_caches, aux).

    In a decode, verify or chunk step the positional KV caches ride the
    layer loop's carry whole (:func:`carries_kv`): each layer writes its
    new rows into the stacked buffer at its own index and the attention
    kernels read the layer by index, so no layer is ever sliced out or
    stacked back. Every other cache is scanned over, a layer slice in
    and a layer out. The unrolled costing path scans everything."""
    cycle = cfg.cycle
    aux0 = ZERO_AUX()
    shared = params.get("shared")
    carried_slots = set()
    if mode in ("decode", "prefill_chunk", "verify") and not cfg.costing \
            and caches is not None:
        carried_slots = {f"{j}_{kind}" for j, kind in enumerate(cycle)
                         if carries_kv(cfg, kind)}

    def cycle_body(carry, xs):
        x, aux, carried = carry
        cyc_params, cyc_caches, layer = xs
        carried = dict(carried)
        new_caches = {}
        for j, kind in enumerate(cycle):
            slot = f"{j}_{kind}"
            p = shared if kind == "shared_attn" else cyc_params[slot]
            if slot in carried:
                c, li = carried[slot], layer
            else:
                c = cyc_caches[slot] if cyc_caches is not None else None
                li = None
            x, nc, a = block_apply(
                cfg, kind, p, x, mode=mode, cache=c, pos=pos, enc_out=enc_out,
                layer=li,
            )
            if slot in carried:
                carried[slot] = nc
            elif nc is not None:
                new_caches[slot] = nc
            aux = {k: aux[k] + a[k] for k in aux}
        return (x, aux, carried), new_caches if new_caches else None

    if cfg.n_cycles > 0:
        cyc_caches = caches["cycles"] if caches is not None else None
        carried0 = {k: cyc_caches[k] for k in carried_slots}
        scanned = (None if cyc_caches is None else
                   {k: c for k, c in cyc_caches.items()
                    if k not in carried_slots})
        xs = (params["cycles"], scanned,
              jnp.arange(cfg.n_cycles, dtype=jnp.int32))
        body = cycle_body
        if mode == "full" and cfg.remat:
            body = jax.checkpoint(cycle_body, prevent_cse=False)
        if cfg.costing:
            # unrolled for cost_analysis fidelity (see ArchConfig.costing)
            carry = (x, aux0, carried0)
            per_cycle = []
            for r in range(cfg.n_cycles):
                xs_r = jax.tree.map(lambda a: a[r], xs)
                carry, y_r = body(carry, xs_r)
                per_cycle.append(y_r)
            (x, aux, carried) = carry
            ys = (
                jax.tree.map(lambda *zs: jnp.stack(zs), *per_cycle)
                if per_cycle[0] is not None
                else None
            )
        else:
            (x, aux, carried), ys = lax.scan(body, (x, aux0, carried0), xs)
        if carried:
            ys = {**(ys or {}), **carried}
        new_caches = {"cycles": ys, "tail": {}}
    else:
        aux = aux0
        new_caches = {"cycles": None, "tail": {}}

    for i, kind in enumerate(cfg.tail):
        slot = f"{i}_{kind}"
        p = shared if kind == "shared_attn" else params["tail"][slot]
        c = caches["tail"][slot] if caches is not None else None
        x, nc, a = block_apply(cfg, kind, p, x, mode=mode, cache=c, pos=pos, enc_out=enc_out)
        if nc is not None:
            new_caches["tail"][slot] = nc
        aux = {k: aux[k] + a[k] for k in aux}
    return x, (new_caches if mode in ("prefill", "prefill_chunk", "decode",
                                      "verify")
               else None), aux
