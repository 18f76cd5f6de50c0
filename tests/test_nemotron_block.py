"""Minitron-4B's Nemotron block through the program, at a tiny size.

The block: token embedding without a scale, LayerNorm1P
(``LN(x) * (1 + w) + b``), grouped-query attention with rotary positions
on the first ``rotary_dim`` dimensions of each head, an ungated relu² MLP
``wo(relu(wi_up x)^2)`` and an untied head. Here it has 6 query heads
over 2 KV heads (a group of 3, as Minitron's 24 over 8).

``_plain_forward`` is the block written out in jnp float32 from the
equations, sharing nothing with the program. The program runs in
float32 too, so the two agree to float32 rounding, reordered by the
program's chunked softmax and fused dequant products: 1e-4 of the
logits' scale leaves that room, and a wrong norm, rotary pair, head
grouping or activation moves logits by O(1) of their scale.

The olmo pins were recorded on the program before these options
existed; with their defaults the program must compute olmo's logits bit
for bit as it did.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.progressive import divide, transmit_reconstruct
from repro.models.model import build_model
from repro.serving.engine import PoolRequest, SlotPoolEngine

TOL = 1e-4   # of the logits' largest magnitude (module docstring)


@pytest.fixture(scope="module")
def tiny():
    """Minitron's block at H 6 / Kh 2, head 16 with 8 rotated dims; its
    LayerNorm1P parameters drawn away from the zeros ``init`` gives, so
    the ``1 + w`` multiply and the bias add are exercised."""
    cfg = get_config("minitron-4b").reduced(n_layers=2, d_model=64, n_heads=6, n_kv=2,
                                            head_dim=16, d_ff=96, vocab=128)
    assert (cfg.rotary_dim, cfg.act, cfg.norm_type) == (8, "relu2", "layernorm1p")
    assert not (cfg.mlp_gated or cfg.embed_scale or cfg.tie_embeddings)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 16))

    def perturb(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a

    return cfg, model, jax.tree_util.tree_map_with_path(perturb, params)


def _plain_forward(cfg, p, tokens):
    """(T, V) logits of one sequence, from the block's equations."""
    T = tokens.shape[0]
    H, K, hd, rd = cfg.n_heads, cfg.n_kv, cfg.hd, cfg.rotary_dim
    hi = jax.lax.Precision.HIGHEST
    pos = jnp.arange(T, dtype=jnp.float32)

    def ln1p(x, n):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * (1 + n["scale"]) + n["bias"]

    def rotate(x):                                   # (T, n, hd)
        half = rd // 2
        inv = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) * 2 / rd)
        ang = pos[:, None, None] * inv               # (T, 1, half)
        a, b = x[..., :half], x[..., half:rd]
        return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                b * jnp.cos(ang) + a * jnp.sin(ang), x[..., rd:]], -1)

    x = p["embed"][tokens]
    stack = p["decoder"]["cycles"]["0_attn"]
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], stack)
        h = ln1p(x, lp["norm1"])
        q = rotate(jnp.dot(h, lp["attn"]["wq"], precision=hi).reshape(T, H, hd))
        k = rotate(jnp.dot(h, lp["attn"]["wk"], precision=hi).reshape(T, K, hd))
        v = jnp.dot(h, lp["attn"]["wv"], precision=hi).reshape(T, K, hd)
        k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=hi) / np.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=hi)
        x = x + jnp.dot(o.reshape(T, H * hd), lp["attn"]["wo"], precision=hi)
        u = jnp.maximum(jnp.dot(ln1p(x, lp["norm2"]), lp["mlp"]["wi_up"], precision=hi), 0)
        x = x + jnp.dot(u * u, lp["mlp"]["wo"], precision=hi)
    return jnp.dot(ln1p(x, p["final_norm"]), p["lm_head"], precision=hi)


def _assert_close(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


def test_tree_has_no_gate_and_an_untied_head(tiny):
    _, _, params = tiny
    layer = params["decoder"]["cycles"]["0_attn"]
    assert sorted(layer["mlp"]) == ["wi_up", "wo"]
    assert sorted(layer["norm1"]) == ["bias", "scale"]
    assert params["lm_head"].shape == params["embed"].shape[::-1]


def test_forward_matches_plain_block(tiny):
    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, 24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": tokens[None]})
    _assert_close(got[0], _plain_forward(cfg, params, tokens))


def _record_pool_logits(pool, slot_of):
    """Wrap the pool's jitted steps so every finished prefill's last-row
    logits and every decode step's logits are kept per request, in order
    (test-side instrumentation only)."""
    seen = {rid: [] for rid in slot_of}
    decode, chunk = pool._decode, pool._chunk_step

    def rec_decode(params, caches, tokens, pos):
        logits, caches = decode(params, caches, tokens, pos)
        for rid, slot in slot_of.items():
            if int(pos[slot]) >= 0:
                seen[rid].append(np.asarray(logits[slot]))
        return logits, caches

    def rec_chunk(params, caches, toks, tpos, frow, *rest):
        out = chunk(params, caches, toks, tpos, frow, *rest)
        for rid, slot in slot_of.items():
            if int(frow[slot]) >= 0:
                seen[rid].append(np.asarray(out[2][slot]))
        return out

    pool._decode, pool._chunk_step = rec_decode, rec_chunk
    return seen


@pytest.mark.parametrize("stage", [3, 8])
def test_slot_pool_prefill_and_decode_match_full_forward(tiny, stage):
    """Chunked prefill then decode through the cache, in a pool of two
    requests of different lengths at a quantized-resident stage: the
    logits of the last prompt row and of each decode step equal the
    full forward's at that position, on the weights served at that
    stage."""
    cfg, model, params = tiny
    prog = divide(params)
    pool = SlotPoolEngine(model, prog, n_slots=2, max_len=40, resident="quantized",
                          prefill_chunk=4, dispatch_window=3)
    for _ in range(stage):
        pool.receive_stage()
    prompts = {0: np.arange(11, dtype=np.int32) * 7 % cfg.vocab,
               1: np.arange(5, dtype=np.int32) * 13 % cfg.vocab}
    steps = {0: 6, 1: 9}
    seen = _record_pool_logits(pool, {0: 0, 1: 1})
    for rid, prompt in prompts.items():
        pool.submit(PoolRequest(rid=rid, prompt=prompt, max_new_tokens=steps[rid]))
    out = pool.run()
    served = transmit_reconstruct(params, upto_stage=stage)
    for rid, prompt in prompts.items():
        seq = np.concatenate([prompt, np.asarray(out[rid][:-1], np.int32)])
        with jax.default_matmul_precision("highest"):
            full, _ = model.forward(served, {"tokens": jnp.asarray(seq)[None]})
        want = np.asarray(full[0, len(prompt) - 1:])
        got = np.stack(seen[rid][:len(want)])
        assert got.shape == want.shape == (steps[rid], cfg.vocab)
        _assert_close(got, want)
        assert out[rid] == [int(t) for t in np.argmax(want, -1)]


OLMO_PINS = {
    "forward": "ff8f7c1f23bf07758327a273425b438a97930c2c3a5c5c18e9f2cab4bc5bd85e",
    "slot_pool_decode": "c122c2df2b79185f0b67de7f132718fe76d839cf1fdb4258fb106c04a91ba7a4",
}


@pytest.mark.parametrize("what", sorted(OLMO_PINS))
def test_olmo_logits_pinned(what):
    """sha256 of tiny olmo logits, recorded before the block options
    existed: the full forward, and the row the slot pool holds after
    chunked prefill and two decode steps at quantized residency."""
    cfg = get_config("olmo-1b").reduced(n_layers=2, d_model=64, d_ff=96, vocab=128,
                                        n_heads=4, n_kv=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    tokens = (jnp.arange(11, dtype=jnp.int32) * 37 + 5) % cfg.vocab
    if what == "forward":
        got, _ = model.forward(params, {"tokens": tokens[None]})
    else:
        prog = divide(params)
        pool = SlotPoolEngine(model, prog, n_slots=2, max_len=16, resident="quantized")
        for _ in range(prog.n_stages):
            pool.receive_stage()
        pool.submit(PoolRequest(rid=0, prompt=np.asarray(tokens), max_new_tokens=2))
        assert pool.run() == {0: [43, 43]}
        got = pool.last_logits[0]
    assert hashlib.sha256(np.asarray(got, np.float32).tobytes()).hexdigest() == OLMO_PINS[what]
