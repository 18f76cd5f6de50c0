"""Plain float32 reference: the paper's quantizer and the dense decoder block.

Nothing here imports the program. The reference follows the papers'
equations and the configuration file, in ``jax.numpy`` at float32 with
every matrix product at ``Precision.HIGHEST``:

- eq. (2), floor quantization of a whole leaf to ``bits`` bits:
  ``q = floor(2^bits * (x - lo) / span)``, ``span = hi - lo + eps``
  with ``eps = 1e-6 * (hi - lo) + 1e-12``;
- eqs. (3)-(4): after ``m`` received bits the receiver holds the top
  ``m`` bits of ``q``;
- eq. (5): ``w = span * q / 2^bits + lo + span / 2^(m + 1)``;
- the decoder: token embedding (times sqrt(d_model)), then per layer a
  pre-norm attention block (grouped-query, rotary positions on the two
  halves of each head, causal softmax) and a pre-norm gated MLP, a final
  norm and the tied unembedding.

Departures from the published models are the configuration's, listed in
each file under ``configs/`` and in PERF.md; the reference computes what
the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
PRE = "decoder/cycles/0_attn/"
LAYER_LEAVES = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
                "mlp/wi_gate", "mlp/wi_up", "mlp/wo",
                "norm1/scale", "norm2/scale")


# -- eqs. (2)-(5) ------------------------------------------------------------

def leaf_range(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    x = x.astype(jnp.float32)
    return jnp.min(x), jnp.max(x)


def span_of(lo, hi):
    return (hi - lo) + ((hi - lo) * 1e-6 + 1e-12)


def codes(x, lo, hi, bits: int) -> jax.Array:
    """Eq. (2): floor quantization of the float32 value, uint32."""
    x = x.astype(jnp.float32)
    q = jnp.floor(((x - lo) / span_of(lo, hi)) * (2.0 ** bits))
    return jnp.clip(q, 0, 2.0 ** bits - 1).astype(jnp.uint32)


def truncate(q, bits: int, m) -> jax.Array:
    """Eqs. (3)-(4): the top ``m`` of ``bits`` bits (``m`` may be traced)."""
    shift = (jnp.uint32(bits) - jnp.asarray(m, jnp.uint32))
    return (q >> shift) << shift


def dequant(q, lo, hi, bits: int, m) -> jax.Array:
    """Eq. (5) at ``m`` received bits (``m`` >= 1, may be traced)."""
    span = span_of(lo, hi)
    half_lsb = jnp.ldexp(jnp.float32(1.0), -(jnp.asarray(m, jnp.int32) + 1))
    return q.astype(jnp.float32) * (span * 2.0 ** -bits) + (lo + span * half_lsb)


def stage_weight(x, lo, hi, bits: int, m) -> jax.Array:
    """What a receiver serves after ``m`` bits of leaf ``x``."""
    return dequant(truncate(codes(x, lo, hi, bits), bits, m), lo, hi, bits, m)


def checksum(q: jax.Array) -> jax.Array:
    """Order-sensitive uint32 checksum of a flat code array: the sum of
    ``q[i] * (i mod 65521 + 1)`` wrapping at 2^32 (a one-bit change at
    any position changes it), beside the plain sum."""
    q = q.reshape(-1).astype(jnp.uint32)
    w = (jnp.arange(q.shape[0], dtype=jnp.uint32) % jnp.uint32(65521)) + 1
    return jnp.stack([jnp.sum(q * w, dtype=jnp.uint32),
                      jnp.sum(q, dtype=jnp.uint32)])


# -- the decoder ---------------------------------------------------------------

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


def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale (amax -> 448)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, low: bool):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.dot(a, b, precision=HI)


def forward(cfg: dict, raw: dict, lohi: dict, m, tokens, *, low: bool = False,
            stage_of=None):
    """Logits (T, V) of one sequence.

    ``raw`` maps leaf paths to the weights as made from the seed;
    ``lohi`` maps them to their (min, max). Each layer's weights are
    quantized, truncated and dequantized inside the layer loop, so only
    one layer's float copies exist at a time. ``low`` computes every
    matrix product from fp8-rounded operands: the control.

    ``m`` is the number of received bits, or an (S,) array of them with
    ``stage_of`` a (T,) index into it: position ``t`` (its key, value,
    activations and logits) is computed at ``m[stage_of[t]]`` bits, as the
    server computed each position at the stage it held then."""
    bits = cfg["bits"]
    T = tokens.shape[0]
    H, K, hd = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    G = H // K
    pos = jnp.arange(T, dtype=jnp.int32)
    ms = jnp.atleast_1d(jnp.asarray(m, jnp.int32))
    if stage_of is None:
        stage_of = jnp.zeros((T,), jnp.int32)
    at = [stage_of[:, None] == i for i in range(ms.shape[0])]

    def per_stage(f):
        """f(bits) at each position's own stage."""
        out = f(ms[0])
        for i in range(1, len(at)):
            out = jnp.where(at[i], f(ms[i]), out)
        return out

    def w(name, x, mb):
        lo, hi = lohi[name]
        return stage_weight(x, lo, hi, bits, mb)

    def mm(a, name, x):
        return per_stage(lambda mb: _mm(a, w(name, x, mb), low))

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
    return per_stage(lambda mb: _mm(x, w("embed", raw["embed"], mb).T, low))


def make_gap_fn(cfg: dict, with_control: bool):
    """Jitted ``(raw, lohi, ms, stage_of, tokens, served) -> gaps``.

    ``served[t]`` is the token the program served after position ``t``
    (-1 where nothing was served); position ``t`` was served at
    ``ms[stage_of[t]]`` bits. Returns ``(T,)`` gaps by which the served
    token's reference logit lies below the reference's best, and with
    ``with_control`` the same for the token the fp8 control puts first.
    Compiles once for each number of stages a sequence spans."""

    @jax.jit
    def gaps(raw, lohi, ms, stage_of, tokens, served):
        ref = forward(cfg, raw, lohi, ms, tokens, stage_of=stage_of)
        best = jnp.max(ref, axis=-1)
        live = served >= 0
        at = jnp.take_along_axis(ref, jnp.maximum(served, 0)[:, None], 1)[:, 0]
        out = [jnp.where(live, best - at, 0.0)]
        if with_control:
            low = forward(cfg, raw, lohi, ms, tokens, low=True, stage_of=stage_of)
            pick = jnp.argmax(low, axis=-1)
            at_low = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
            out.append(jnp.where(live, best - at_low, 0.0))
        return out

    return gaps


def served_positions(prompt: np.ndarray, tokens: list[int], length: int):
    """The sequence the reference reads (prompt then served tokens, padded
    to ``length``) and ``served[t]``: token j was served after position
    ``len(prompt) - 1 + j``."""
    P, n = len(prompt), len(tokens)
    seq = np.zeros(length, np.int32)
    seq[:P] = prompt
    seq[P:P + n] = tokens[:length - P]
    served = np.full(length, -1, np.int32)
    served[P - 1:P - 1 + n] = tokens
    return seq, served


def position_stages(prompt_len: int, prefill_stage: int, token_stages: list[int],
                    length: int) -> np.ndarray:
    """The stage the server held when it computed each position: the
    prompt at its prefill stage, position ``prompt_len + i`` at the stage
    of the decode step that fed served token ``i`` (later positions, never
    computed, repeat the last)."""
    st = np.full(length, token_stages[-1] if token_stages else prefill_stage, np.int32)
    st[:prompt_len] = prefill_stage
    n = min(len(token_stages), length - prompt_len)
    st[prompt_len:prompt_len + n] = token_stages[:n]
    return st
