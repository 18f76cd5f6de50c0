"""The Nemotron decoder block (Minitron-4B's).

Token embedding (no scale), then per layer a pre-norm attention block
(grouped-query, rotary positions on the first ``rotary_dim`` dimensions
of each head, pairing ``i`` with ``i + rotary_dim / 2``, the rest passed
through; causal softmax) and a pre-norm ungated MLP ``wo(relu(wi_up x)^2)``,
a final norm and an untied head ``lm_head``. Every norm is LayerNorm1P:
``LN(x) * (1 + w) + b`` with eps 1e-5. No product has a bias. The layers
are stacked on a leading axis under ``decoder/cycles/0_attn`` in the
program's parameter tree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench import flops, reference
from bench.arch.dense import _rope

HI = reference.HI
PRE = "decoder/cycles/0_attn/"
LAYER_LEAVES = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/wi_up", "mlp/wo",
                "norm1/scale", "norm1/bias", "norm2/scale", "norm2/bias")
NORM_EPS = 1e-5

# Seeded weights (``leaf_specs``), each with its reason:
# - products and the untied head: normal, std sqrt(2 / (fan_in +
#   fan_out)), as the program's own initialiser. The head has no tie to
#   the embedding, so nothing makes one token lead for a structural
#   reason, and its std only scales the logits: the program's and the
#   control's gaps scale with it alike;
# - LayerNorm1P ``w``: normal, std 0.1 about 0, so the ``1 + w`` multiply
#   is exercised (the published initialiser's zeros would leave it out);
# - LayerNorm1P ``b``: normal, std 0.1, so the bias add is exercised at a
#   tenth of the normalised stream;
# - embedding: normal, std 1. No scale multiplies it; at std 1 it is
#   about as large as one layer's output, so the residual stream carries
#   the input token to every norm and not only into the first layer
#   (the program's own 0.02 would leave it a fiftieth of the stream,
#   held in bfloat16 beside it).
NORM_W_STD = 0.1
NORM_B_STD = 0.1
EMBED_STD = 1.0


def arch_fields(cfg: dict) -> dict:
    return {k: cfg[k] for k in ("n_layers", "d_model", "n_heads", "n_kv", "d_ff",
                                "vocab", "head_dim", "norm_type", "act", "mlp_gated",
                                "rope_theta", "rotary_dim", "embed_scale",
                                "tie_embeddings")}


def _check(cfg: dict) -> None:
    want = {"norm_type": "layernorm1p", "act": "relu2", "mlp_gated": False,
            "embed_scale": False, "tie_embeddings": False}
    bad = {k: cfg[k] for k, v in want.items() if cfg[k] != v}
    if bad:
        raise ValueError(f"the nemotron block has {want}, configuration says {bad}")


# -- weights ---------------------------------------------------------------------

def leaf_specs(cfg: dict) -> dict[str, tuple[tuple, float, float]]:
    """``path -> (shape, mean, std)`` of every parameter leaf (the stds'
    reasons are above)."""
    _check(cfg)
    L, d, F, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    hq, hkv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv"] * cfg["head_dim"]

    def dense(din, dout):
        return ((L, din, dout), 0.0, math.sqrt(2.0 / (din + dout)))

    specs = {
        "embed": ((V, d), 0.0, EMBED_STD),
        "lm_head": ((d, V), 0.0, math.sqrt(2.0 / (d + V))),
        PRE + "attn/wq": dense(d, hq),
        PRE + "attn/wk": dense(d, hkv),
        PRE + "attn/wv": dense(d, hkv),
        PRE + "attn/wo": dense(hq, d),
        PRE + "mlp/wi_up": dense(d, F),
        PRE + "mlp/wo": dense(F, d),
        "final_norm/scale": ((d,), 0.0, NORM_W_STD),
        "final_norm/bias": ((d,), 0.0, NORM_B_STD),
    }
    for n in ("norm1", "norm2"):
        specs[PRE + n + "/scale"] = ((L, d), 0.0, NORM_W_STD)
        specs[PRE + n + "/bias"] = ((L, d), 0.0, NORM_B_STD)
    return specs


def to_program_tree(cfg: dict, flat: dict[str, jax.Array]) -> dict:
    """Nest ``{path: array}`` into the program's parameter tree."""

    def group(prefix, names):
        return {k: flat[prefix + k] for k in names}

    layer = {
        "norm1": group(PRE + "norm1/", ("scale", "bias")),
        "attn": group(PRE + "attn/", ("wq", "wk", "wv", "wo")),
        "norm2": group(PRE + "norm2/", ("scale", "bias")),
        "mlp": group(PRE + "mlp/", ("wi_up", "wo")),
    }
    return {"embed": flat["embed"], "lm_head": flat["lm_head"],
            "decoder": {"cycles": {"0_attn": layer}, "tail": {}},
            "final_norm": group("final_norm/", ("scale", "bias"))}


# -- the reference ---------------------------------------------------------------

def _ln1p(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + NORM_EPS) * (1.0 + w) + b


def _partial_rope(x, pos, theta, rd):
    """Rotate the first ``rd`` dimensions of each head; pass the rest."""
    return jnp.concatenate([_rope(x[..., :rd], pos, theta), x[..., rd:]], axis=-1)


def forward(cfg: dict, raw: dict, lohi: dict, m, tokens, *, low: bool = False,
            stage_of=None):
    """Logits (T, V) of one sequence (the contract in ``bench/arch``).

    Each layer's weights are quantized, truncated and dequantized inside
    the layer loop, so only one layer's float copies exist at a time."""
    _check(cfg)
    T = tokens.shape[0]
    H, K, hd, rd = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"], cfg["rotary_dim"]
    G = H // K
    pos = jnp.arange(T, dtype=jnp.int32)
    st = reference.Stages(cfg, lohi, m, T, stage_of, low)
    per_stage, w, mm = st.per_stage, st.w, st.mm

    x = per_stage(lambda mb: w("embed", raw["embed"], mb)[tokens])
    xs = {n: raw[PRE + n] for n in LAYER_LEAVES}

    def norm(x, key, scale, bias):
        return per_stage(lambda mb: _ln1p(x, w(key + "/scale", scale, mb),
                                          w(key + "/bias", bias, mb)))

    def layer(x, p):
        h = norm(x, PRE + "norm1", p["norm1/scale"], p["norm1/bias"])
        q = mm(h, PRE + "attn/wq", p["attn/wq"]).reshape(T, H, hd)
        k = mm(h, PRE + "attn/wk", p["attn/wk"]).reshape(T, K, hd)
        v = mm(h, PRE + "attn/wv", p["attn/wv"]).reshape(T, K, hd)
        q = _partial_rope(q, pos, cfg["rope_theta"], rd)
        k = _partial_rope(k, pos, cfg["rope_theta"], rd)
        k = jnp.repeat(k, G, axis=1)                     # head h reads kv h // G
        v = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(hd)
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", a, v, precision=HI).reshape(T, H * hd)
        x = x + mm(o, PRE + "attn/wo", p["attn/wo"])
        h = norm(x, PRE + "norm2", p["norm2/scale"], p["norm2/bias"])
        u = jnp.maximum(mm(h, PRE + "mlp/wi_up", p["mlp/wi_up"]), 0.0)
        return x + mm(u * u, PRE + "mlp/wo", p["mlp/wo"]), None

    x, _ = lax.scan(layer, x, xs)
    x = norm(x, "final_norm", raw["final_norm/scale"], raw["final_norm/bias"])
    return mm(x, "lm_head", raw["lm_head"])


# -- operations and bytes ----------------------------------------------------------

def _matmul_shapes(cfg: dict) -> list[tuple[int, int]]:
    """``(K, N)`` of every dense product one token passes through: six
    per layer (q, k, v, o, up, down) and the untied head."""
    d, F, V = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    hq, hkv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv"] * cfg["head_dim"]
    layer = [(d, hq), (d, hkv), (d, hkv), (hq, d), (d, F), (F, d)]
    return layer * cfg["n_layers"] + [(d, V)]


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position in every layer, bfloat16."""
    return 2 * cfg["n_layers"] * cfg["n_kv"] * cfg["head_dim"] * flops.ACT_BYTES


def sequence_flops(cfg: dict, start: int, stop: int) -> float:
    """Model operations to process positions ``start .. stop - 1``: every
    dense product and the head, and the score and value products of
    attention (a token at position p attends to p + 1 keys in each of
    the query heads)."""
    n = max(0, stop - start)
    if not n:
        return 0.0
    dense = sum(2.0 * k * n_ for k, n_ in _matmul_shapes(cfg))
    pos_sum = (start + stop - 1) * n / 2.0 + n   # sum of (p + 1)
    return n * dense + 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * pos_sum
