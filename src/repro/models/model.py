"""Public model API: build_model(cfg) -> Model.

Model exposes exactly the entry points the launcher/dry-run need:

    init(key)                      -> params
    forward(params, batch)         -> full logits (small-scale debugging)
    loss(params, batch)            -> (scalar, metrics); chunked CE
    prefill(params, batch)         -> (last_logits, caches)
    prefill_chunk(params, caches, tokens, tok_pos) -> (logits, caches)
                                      (ragged chunked prefill into the
                                       pooled caches, slot-pool path)
    decode_step(params, caches, tokens, pos) -> (logits, caches)
    init_caches(batch, max_len)    -> zeroed cache pytree (eval_shape-safe)
    grow_caches(caches, max_len)   -> pad prefill caches for decoding

`batch` is a dict: {"tokens": (B,S) i32, "labels": (B,S) i32} plus, per
family, "enc_input" (audio frames, (B,S_enc,d)) or "vision_embeds"
((B, vision_tokens, d_vision)). Modality frontends are stubs per the
brief: input_specs() hands the backbone precomputed embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import (ArchConfig, apply_norm, dense, embed_lookup,
                                 norm_init, dense_init, softcap)
from repro.models import transformer as tfm


def _embed_init(key, vocab: int, d: int) -> jax.Array:
    return 0.02 * jax.random.normal(key, (vocab, d), jnp.float32)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # -- params ------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        k_embed, k_stack, k_enc, k_extra, k_head = jax.random.split(key, 5)
        params: dict[str, Any] = {
            "embed": _embed_init(k_embed, cfg.vocab, cfg.d_model),
            "decoder": tfm.stack_init(cfg, k_stack),
            "final_norm": norm_init(cfg, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab)
        if cfg.enc_layers:
            enc_cfg = dataclasses.replace(
                cfg, cycle=("enc_attn",), n_layers=cfg.enc_layers
            )
            params["encoder"] = {
                "stack": tfm.stack_init(enc_cfg, k_enc),
                "final_norm": norm_init(cfg, cfg.d_model),
            }
        if cfg.vision_tokens:
            params["vision_proj"] = dense_init(k_extra, cfg.d_vision, cfg.d_model)
        if cfg.param_dtype != jnp.float32:
            params = jax.tree.map(
                lambda a: a.astype(cfg.param_dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                params,
            )
        return params

    # -- shared pieces -------------------------------------------------------
    def _encode(self, params, batch):
        """Run the modality encoder (or vision projector); returns the
        memory the decoder cross-attends to, or None."""
        cfg = self.cfg
        if cfg.enc_layers:
            enc_in = batch["enc_input"].astype(cfg.dtype)
            enc_cfg = dataclasses.replace(cfg, cycle=("enc_attn",), n_layers=cfg.enc_layers)
            x, _, _ = tfm.run_stack(enc_cfg, params["encoder"]["stack"], enc_in, mode="full")
            return apply_norm(cfg, params["encoder"]["final_norm"], x)
        if cfg.vision_tokens:
            v = batch["vision_embeds"].astype(cfg.dtype)
            return dense(v, params["vision_proj"], dtype=cfg.dtype)
        return None

    def _embed(self, params, tokens):
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens).astype(cfg.dtype)
        if not cfg.embed_scale:
            return x
        return x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)

    def _unembed(self, params, x):
        cfg = self.cfg
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        with jax.named_scope("unembed"):
            logits = dense(x.astype(jnp.float32), w, dtype=jnp.float32)
        return softcap(logits, cfg.logit_softcap)

    # -- entry points --------------------------------------------------------
    def forward(self, params, batch):
        cfg = self.cfg
        enc_out = self._encode(params, batch)
        x = self._embed(params, batch["tokens"])
        x, _, aux = tfm.run_stack(cfg, params["decoder"], x, mode="full", enc_out=enc_out)
        x = apply_norm(cfg, params["final_norm"], x)
        return self._unembed(params, x), aux

    def loss(self, params, batch, *, ce_chunk: int = 512):
        """Chunked cross-entropy: never materializes (B, S, V)."""
        cfg = self.cfg
        enc_out = self._encode(params, batch)
        x = self._embed(params, batch["tokens"])
        x, _, aux = tfm.run_stack(cfg, params["decoder"], x, mode="full", enc_out=enc_out)
        x = apply_norm(cfg, params["final_norm"], x)

        B, S, _ = x.shape
        labels = batch["labels"]
        C = min(ce_chunk, S)
        if cfg.costing:
            C = max(C, -(-S // 16))  # cap unrolled CE trips
        pad = (-S) % C
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        n_chunks = x.shape[1] // C
        xc = jnp.moveaxis(x.reshape(B, n_chunks, C, -1), 1, 0)
        lc = jnp.moveaxis(labels.reshape(B, n_chunks, C), 1, 0)

        w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"]).astype(
            jnp.float32
        )

        def ce_chunk_fn(carry, xs):
            xx, ll = xs
            logits = softcap(xx.astype(jnp.float32) @ w, cfg.logit_softcap)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, jnp.maximum(ll, 0)[..., None], axis=-1
            )[..., 0]
            valid = (ll >= 0).astype(jnp.float32)
            nll = (logz - gold) * valid
            return (carry[0] + nll.sum(), carry[1] + valid.sum()), None

        if cfg.costing:
            carry = (jnp.float32(0), jnp.float32(0))
            for c in range(n_chunks):
                carry, _ = ce_chunk_fn(carry, (xc[c], lc[c]))
            nll_sum, n_valid = carry
        else:
            (nll_sum, n_valid), _ = lax.scan(
                ce_chunk_fn, (jnp.float32(0), jnp.float32(0)), (xc, lc)
            )
        ce = nll_sum / jnp.maximum(n_valid, 1.0)
        total = ce + 0.01 * aux["balance_loss"]
        metrics = {"ce": ce, **aux}
        return total, metrics

    def prefill(self, params, batch, n_valid=None):
        """``n_valid=None``: every token is real, the returned logits
        are the last row's. ``n_valid`` (B,) int32: the prompt is
        bucket-padded to its static length and only the first
        ``n_valid[b]`` positions are real — padded positions are masked
        out of attention and the logits are gathered at row
        ``n_valid - 1``. All batch rows must share one valid length
        (the slot pool prefills at batch 1). Only plain-attention
        stacks support masked padding: a sliding-window ring has no
        masked slots and a recurrent state would consume the padding."""
        cfg = self.cfg
        if n_valid is not None:
            kinds = set(cfg.cycle) | set(cfg.tail)
            if kinds & {"swa", "swa_moe", "mamba2", "mlstm", "slstm"}:
                raise NotImplementedError(
                    "bucket-padded prefill needs position masking, which "
                    "sliding-window rings and recurrent states don't "
                    "support — admit at the exact prompt length instead")
        enc_out = self._encode(params, batch)
        x = self._embed(params, batch["tokens"])
        x, caches, _ = tfm.run_stack(
            cfg, params["decoder"], x, mode="prefill", enc_out=enc_out,
            pos=None if n_valid is None else jnp.asarray(n_valid, jnp.int32),
        )
        if n_valid is None:
            xl = x[:, -1:, :]
        else:
            idx = jnp.clip(jnp.asarray(n_valid, jnp.int32) - 1, 0,
                           x.shape[1] - 1)
            xl = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        xl = apply_norm(cfg, params["final_norm"], xl)
        return self._unembed(params, xl)[:, 0, :], caches

    def prefill_chunk(self, params, caches, tokens, tok_pos):
        """Ragged chunked prefill: consume a (B, C) block of prompt
        tokens straight into the POOLED caches, each slot at its own
        depth. ``tok_pos`` (B, C) int32 gives token (b, t)'s prompt
        position (slot b's chunk offset + t); negative marks a masked
        row — free/decoding slots riding the batched launch, or ragged
        padding past a short final chunk. Masked rows write nothing and
        read nothing (their cache rows stay byte-identical). Returns
        ``(logits (B, C, V), caches)``; logits[:, t] is the next-token
        distribution after consuming prompt position tok_pos[:, t] —
        the chunk holding a slot's LAST prompt token yields its first
        generated token at that row. This replaces the batch-1 prefill
        + grow_caches + per-leaf slot write of legacy admission: no
        cache-sized copy ever happens on the admit path."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        x, caches, _ = tfm.run_stack(
            cfg, params["decoder"], x, mode="prefill_chunk", caches=caches,
            pos=jnp.asarray(tok_pos, jnp.int32),
        )
        x = apply_norm(cfg, params["final_norm"], x)
        return self._unembed(params, x), caches

    def decode_step(self, params, caches, tokens, pos):
        """tokens: (B, 1) int32; pos: scalar int32 (lock-stepped write
        position) or (B,) int32 per-slot positions (ragged continuous
        batching — each slot decodes at its own depth; negative marks a
        free pool slot whose output is meaningless)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        x, caches, _ = tfm.run_stack(
            cfg, params["decoder"], x, mode="decode", caches=caches, pos=pos
        )
        x = apply_norm(cfg, params["final_norm"], x)
        return self._unembed(params, x)[:, 0, :], caches

    def verify_step(self, params, caches, tokens, pos):
        """Speculative verify: score a whole draft block in one pass.

        tokens: (B, T) int32 — the last accepted token followed by the
        k = T-1 drafted continuations, per slot; pos: (B,) int32 base
        positions (token t of slot b sits at ``pos[b] + t``; negative
        marks a free pool slot whose rows stay fully masked). Returns
        ``(logits (B, T, V), caches)``: logits[:, t] is the target
        model's next-token distribution after consuming tokens[:, :t+1],
        exactly what t+1 sequential decode_step calls would produce —
        K/V for all T positions are written into the caches (rejected
        rows are *left in place* and simply overwritten by later
        rounds; the per-row causal mask keeps them invisible)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        x, caches, _ = tfm.run_stack(
            cfg, params["decoder"], x, mode="verify", caches=caches, pos=pos
        )
        x = apply_norm(cfg, params["final_norm"], x)
        return self._unembed(params, x), caches

    # -- caches ----------------------------------------------------------------
    def init_caches(self, batch: int, max_len: int, *, ring_margin: int = 0):
        cfg = self.cfg
        enc_len = self.enc_len(max_len)
        return tfm.stack_init_caches(cfg, batch, max_len, enc_len,
                                     ring_margin)

    def enc_len(self, seq_len: int) -> int:
        cfg = self.cfg
        if cfg.enc_layers:
            return max(1, seq_len // cfg.enc_seq_divisor)
        if cfg.vision_tokens:
            return cfg.vision_tokens
        return 0

    def grow_caches(self, caches, max_len: int, *, ring_margin: int = 0,
                    pos: int = 0):
        """Pad prefill-produced full-attention caches along the sequence
        axis so decode_step can write up to max_len. With
        ``ring_margin > 0`` sliding-window ring caches are additionally
        repacked to ``window + ring_margin`` slots (``pos`` = tokens
        consumed so far, i.e. the prompt length) so speculative verify
        blocks up to ``ring_margin`` tokens long never clobber live
        window entries."""
        cfg = self.cfg
        from repro.models.attention import grow_ring_cache

        def grow_slot(kind: str, c, stacked: bool):
            if c is None:
                return None
            if kind == "selfcross":
                return {"self": _pad_kv(c["self"], max_len, stacked), "cross": c["cross"]}
            if kind in ("attn", "global", "moe", "shared_attn"):
                return _pad_kv(c, max_len, stacked)
            if kind in ("swa", "swa_moe") and ring_margin and cfg.window:
                return grow_ring_cache(c, cfg.window + ring_margin, pos)
            return c  # swa ring / ssm states / cross are already final-size

        out = {"cycles": {}, "tail": {}}
        for j, kind in enumerate(cfg.cycle):
            slot = f"{j}_{kind}"
            if caches["cycles"] is not None and slot in caches["cycles"]:
                out["cycles"][slot] = grow_slot(kind, caches["cycles"][slot], True)
        for i, kind in enumerate(cfg.tail):
            slot = f"{i}_{kind}"
            if slot in caches["tail"]:
                out["tail"][slot] = grow_slot(kind, caches["tail"][slot], False)
        return out

    # -- dry-run inputs ----------------------------------------------------------
    def input_specs(self, *, batch: int, seq_len: int, mode: str):
        """ShapeDtypeStruct stand-ins for every model input (no
        allocation). mode: train | prefill | decode."""
        cfg = self.cfg
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if mode == "train":
            specs = {
                "tokens": sds((batch, seq_len), i32),
                "labels": sds((batch, seq_len), i32),
            }
        elif mode == "prefill":
            specs = {"tokens": sds((batch, seq_len), i32)}
        elif mode == "decode":
            specs = {"tokens": sds((batch, 1), i32)}
        else:
            raise ValueError(mode)
        if mode != "decode":
            if cfg.enc_layers:
                specs["enc_input"] = sds(
                    (batch, max(1, seq_len // cfg.enc_seq_divisor), cfg.d_model),
                    cfg.dtype,
                )
            if cfg.vision_tokens:
                specs["vision_embeds"] = sds(
                    (batch, cfg.vision_tokens, cfg.d_vision), cfg.dtype
                )
        return specs


def _pad_kv(c, max_len: int, stacked: bool):
    # native layout: (B, K, S, hd) / stacked (R, B, K, S, hd)
    ax = 3 if stacked else 2
    S = c["k"].shape[ax]
    if S >= max_len:
        return c
    pad = [(0, 0)] * c["k"].ndim
    pad[ax] = (0, max_len - S)
    return {"k": jnp.pad(c["k"], pad), "v": jnp.pad(c["v"], pad)}


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
