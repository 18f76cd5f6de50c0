"""The dense decoder block with tied embeddings (OLMo's).

Token embedding (times sqrt(d_model)), then per layer a pre-norm
attention block (grouped-query, rotary positions on the two halves of
each head, causal softmax) and a pre-norm gated MLP, a final norm and
the tied unembedding. The layers are stacked on a leading axis under
``decoder/cycles/0_attn`` in the program's parameter tree. Norm
(``nonparam_ln`` or ``rmsnorm``), activation (``silu`` or ``gelu``) and
head counts come from the configuration.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench import flops, reference

HI = reference.HI
PRE = "decoder/cycles/0_attn/"
LAYER_LEAVES = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
                "mlp/wi_gate", "mlp/wi_up", "mlp/wo",
                "norm1/scale", "norm2/scale")


def arch_fields(cfg: dict) -> dict:
    return {k: cfg[k] for k in ("n_layers", "d_model", "n_heads", "n_kv",
                                "d_ff", "vocab", "head_dim", "norm_type",
                                "act", "rope_theta", "tie_embeddings")}


# -- weights ---------------------------------------------------------------------

def leaf_specs(cfg: dict) -> dict[str, tuple[tuple, float, float]]:
    """``path -> (shape, mean, std)`` of every parameter leaf.

    Matmul weights are normal with std sqrt(2 / (fan_in + fan_out)), the
    embedding normal with std 0.002. RMSNorm scales are 1 + N(0, 0.1), so
    the reference's scale multiply is exercised (LayerNorm without
    parameters has no leaf).

    The embedding's std decides whether the check can see anything: the
    tied unembedding scores the input token by its own embedding, which
    the residual stream carries (times sqrt(d_model)). At std 0.02 that
    copy dominates every other contribution (top-1 minus top-2 logit
    about 24 at olmo-1b widths), every greedy token repeats the one
    before, and neither a wrong layer nor fp8 arithmetic changes a
    served token. At 0.002 the input token wins 6% of positions and
    the argmax depends on every layer."""
    L, d, F, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    hq, hkv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv"] * cfg["head_dim"]

    def dense(din, dout):
        return ((L, din, dout), 0.0, math.sqrt(2.0 / (din + dout)))

    specs = {
        "embed": ((V, d), 0.0, 0.002),
        PRE + "attn/wq": dense(d, hq),
        PRE + "attn/wk": dense(d, hkv),
        PRE + "attn/wv": dense(d, hkv),
        PRE + "attn/wo": dense(hq, d),
        PRE + "mlp/wi_gate": dense(d, F),
        PRE + "mlp/wi_up": dense(d, F),
        PRE + "mlp/wo": dense(F, d),
    }
    if cfg["norm_type"] == "rmsnorm":
        specs[PRE + "norm1/scale"] = ((L, d), 1.0, 0.1)
        specs[PRE + "norm2/scale"] = ((L, d), 1.0, 0.1)
        specs["final_norm/scale"] = ((d,), 1.0, 0.1)
    elif cfg["norm_type"] != "nonparam_ln":
        raise ValueError(f"unsupported norm_type {cfg['norm_type']!r}")
    if not cfg["tie_embeddings"]:
        raise ValueError("only tied embeddings are supported")
    return specs


def to_program_tree(cfg: dict, flat: dict[str, jax.Array]) -> dict:
    """Nest ``{path: array}`` into the program's parameter tree (empty
    dicts where a LayerNorm has no parameters)."""

    def norm(path):
        return {"scale": flat[path]} if path in flat else {}

    layer = {
        "norm1": norm(PRE + "norm1/scale"),
        "attn": {k: flat[PRE + "attn/" + k] for k in ("wq", "wk", "wv", "wo")},
        "norm2": norm(PRE + "norm2/scale"),
        "mlp": {k: flat[PRE + "mlp/" + k] for k in ("wi_gate", "wi_up", "wo")},
    }
    return {"embed": flat["embed"],
            "decoder": {"cycles": {"0_attn": layer}, "tail": {}},
            "final_norm": norm("final_norm/scale")}


# -- the reference ---------------------------------------------------------------

def _norm(cfg, x, scale):
    if cfg["norm_type"] == "rmsnorm":
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + 1e-5)


def _act(cfg, x):
    if cfg["act"] == "silu":
        return x / (1.0 + jnp.exp(-x))
    if cfg["act"] == "gelu":  # the tanh approximation
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                         * (x + 0.044715 * x ** 3)))
    raise ValueError(cfg["act"])


def _rope(x, pos, theta):
    """x: (T, n, hd). Rotates the pair (i, i + hd/2) by pos * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (T, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def forward(cfg: dict, raw: dict, lohi: dict, m, tokens, *, low: bool = False,
            stage_of=None):
    """Logits (T, V) of one sequence (the contract in ``bench/arch``).

    Each layer's weights are quantized, truncated and dequantized inside
    the layer loop, so only one layer's float copies exist at a time."""
    T = tokens.shape[0]
    H, K, hd = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    G = H // K
    pos = jnp.arange(T, dtype=jnp.int32)
    st = reference.Stages(cfg, lohi, m, T, stage_of, low)
    per_stage, w, mm = st.per_stage, st.w, st.mm

    x = per_stage(lambda mb: w("embed", raw["embed"], mb)[tokens])
    x = x * jnp.float32(math.sqrt(cfg["d_model"]))
    names = [n for n in LAYER_LEAVES if PRE + n in raw]
    xs = {n: raw[PRE + n] for n in names}

    def norm(x, p, key):
        if key not in p:
            return _norm(cfg, x, None)
        return per_stage(lambda mb: _norm(cfg, x, w(PRE + key, p[key], mb)))

    def layer(x, p):
        h = norm(x, p, "norm1/scale")
        q = mm(h, PRE + "attn/wq", p["attn/wq"]).reshape(T, H, hd)
        k = mm(h, PRE + "attn/wk", p["attn/wk"]).reshape(T, K, hd)
        v = mm(h, PRE + "attn/wv", p["attn/wv"]).reshape(T, K, hd)
        q = _rope(q, pos, cfg["rope_theta"])
        k = _rope(k, pos, cfg["rope_theta"])
        k = jnp.repeat(k, G, axis=1)                     # head h reads kv h // G
        v = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(hd)
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", a, v, precision=HI).reshape(T, H * hd)
        x = x + mm(o, PRE + "attn/wo", p["attn/wo"])
        h = norm(x, p, "norm2/scale")
        g = (_act(cfg, mm(h, PRE + "mlp/wi_gate", p["mlp/wi_gate"]))
             * mm(h, PRE + "mlp/wi_up", p["mlp/wi_up"]))
        return x + mm(g, PRE + "mlp/wo", p["mlp/wo"]), None

    x, _ = lax.scan(layer, x, xs)
    fn = raw.get("final_norm/scale")
    if fn is None:
        x = _norm(cfg, x, None)
    else:
        x = per_stage(lambda mb: _norm(cfg, x, w("final_norm/scale", fn, mb)))
    return per_stage(lambda mb: st.dot(x, w("embed", raw["embed"], mb).T))


# -- operations and bytes ----------------------------------------------------------

def _matmul_shapes(cfg: dict) -> list[tuple[int, int, int]]:
    """``(K, N, x_bytes)`` of every dense product one token passes
    through, in one decode or prefill step: seven per layer (q, k, v,
    o, gate, up, down) and the tied unembedding, whose input is float32."""
    d, F, V = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    hq, hkv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv"] * cfg["head_dim"]
    layer = [(d, hq), (d, hkv), (d, hkv), (hq, d), (d, F), (d, F), (F, d)]
    return ([(k, n, flops.ACT_BYTES) for k, n in layer] * cfg["n_layers"]
            + [(d, V, flops.F32)])


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position in every layer, bfloat16."""
    return 2 * cfg["n_layers"] * cfg["n_kv"] * cfg["head_dim"] * flops.ACT_BYTES


def _token_flops(cfg: dict, position: int) -> float:
    """Model operations to process one token at ``position`` (it attends
    to ``position + 1`` keys): every dense product, the unembedding,
    and the score and value products of attention."""
    dense = sum(2.0 * k * n for k, n, _ in _matmul_shapes(cfg))
    attn = 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * (position + 1)
    return dense + attn


def sequence_flops(cfg: dict, start: int, stop: int) -> float:
    """``_token_flops`` summed over positions ``start .. stop - 1``."""
    n = max(0, stop - start)
    if not n:
        return 0.0
    dense = sum(2.0 * k * n_ for k, n_, _ in _matmul_shapes(cfg))
    pos_sum = (start + stop - 1) * n / 2.0 + n   # sum of (p + 1)
    return n * dense + 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * pos_sum
