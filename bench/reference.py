"""Plain float32 reference: the paper's quantizer, and the selection of
each position's stage that every architecture's decoder shares.

Nothing here imports the program. The reference follows the papers'
equations and the configuration file, in ``jax.numpy`` at float32 with
every matrix product at ``Precision.HIGHEST``:

- eq. (2), floor quantization of a whole leaf to ``bits`` bits:
  ``q = floor(2^bits * (x - lo) / span)``, ``span = hi - lo + eps``
  with ``eps = 1e-6 * (hi - lo) + 1e-12``;
- eqs. (3)-(4): after ``m`` received bits the receiver holds the top
  ``m`` bits of ``q``;
- eq. (5): ``w = span * q / 2^bits + lo + span / 2^(m + 1)``;
- the decoder: the configuration's architecture file under ``arch/``
  (``forward``), which reads its weights through ``Stages``.

Departures from the published models are the configuration's, listed in
each file under ``configs/`` and in PERF.md; the reference computes what
the configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import arch

HI = lax.Precision.HIGHEST


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


# -- what every decoder shares ------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale (amax -> 448)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, low: bool):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.dot(a, b, precision=HI)


class Stages:
    """Each position's own stage, for one sequence of ``T`` positions:
    the selection every architecture file's ``forward`` shares.

    ``m`` is the number of received bits, or an (S,) array of them with
    ``stage_of`` a (T,) index into it: position ``t`` (its key, value,
    activations and logits) is computed at ``m[stage_of[t]]`` bits, as the
    server computed each position at the stage it held then. ``lohi``
    maps leaf paths to their (min, max); ``low`` computes every matrix
    product from fp8-rounded operands: the control."""

    def __init__(self, cfg: dict, lohi: dict, m, T: int, stage_of=None, low: bool = False):
        self.bits, self.lohi, self.low = cfg["bits"], lohi, low
        self.ms = jnp.atleast_1d(jnp.asarray(m, jnp.int32))
        if stage_of is None:
            stage_of = jnp.zeros((T,), jnp.int32)
        self.at = [stage_of[:, None] == i for i in range(self.ms.shape[0])]

    def per_stage(self, f):
        """``f(bits)`` at each position's own stage."""
        out = f(self.ms[0])
        for i in range(1, len(self.at)):
            out = jnp.where(self.at[i], f(self.ms[i]), out)
        return out

    def w(self, name: str, x, mb):
        """Leaf ``name`` (made from the seed as ``x``) as served after
        ``mb`` received bits."""
        lo, hi = self.lohi[name]
        return stage_weight(x, lo, hi, self.bits, mb)

    def mm(self, a, name: str, x):
        """``a`` times leaf ``name``, at each position's own stage."""
        return self.per_stage(lambda mb: self.dot(a, self.w(name, x, mb)))

    def dot(self, a, b):
        """``a @ b`` at HIGHEST precision, from fp8-rounded operands under ``low``."""
        return _mm(a, b, self.low)


def forward(cfg: dict, raw: dict, lohi: dict, m, tokens, *, low: bool = False,
            stage_of=None):
    """Logits (T, V) of one sequence, by the configuration's architecture
    file (``bench/arch``): ``raw`` maps leaf paths to the weights as made
    from the seed, ``lohi`` to their (min, max); ``m``, ``stage_of`` and
    ``low`` as ``Stages`` takes them."""
    return arch.load(cfg).forward(cfg, raw, lohi, m, tokens, low=low, stage_of=stage_of)


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
