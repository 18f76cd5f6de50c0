"""Seeded weights, made by the benchmark and not by the program.

One jitted call builds every leaf on the device, in the dtype the
configuration's checkpoint is published in (what the server side
divides), in the parameter tree the program serves: the
decoder's layers stacked on a leading axis under
``decoder/cycles/0_attn``. Both the program and the plain reference get
these arrays; neither takes weights from the other.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (JAX keys take 32 bits)."""
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


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
    pre = "decoder/cycles/0_attn/"

    def dense(din, dout):
        return ((L, din, dout), 0.0, math.sqrt(2.0 / (din + dout)))

    specs = {
        "embed": ((V, d), 0.0, 0.002),
        pre + "attn/wq": dense(d, hq),
        pre + "attn/wk": dense(d, hkv),
        pre + "attn/wv": dense(d, hkv),
        pre + "attn/wo": dense(hq, d),
        pre + "mlp/wi_gate": dense(d, F),
        pre + "mlp/wi_up": dense(d, F),
        pre + "mlp/wo": dense(F, d),
    }
    if cfg["norm_type"] == "rmsnorm":
        specs[pre + "norm1/scale"] = ((L, d), 1.0, 0.1)
        specs[pre + "norm2/scale"] = ((L, d), 1.0, 0.1)
        specs["final_norm/scale"] = ((d,), 1.0, 0.1)
    elif cfg["norm_type"] != "nonparam_ln":
        raise ValueError(f"unsupported norm_type {cfg['norm_type']!r}")
    if not cfg["tie_embeddings"]:
        raise ValueError("only tied embeddings are supported")
    return specs


def make_flat(cfg: dict, seed: int) -> dict[str, jax.Array]:
    """``{path: array}`` on the default device, one executable, in the
    configuration's ``param_dtype`` (the dtype its checkpoint is
    published in): drawn in float32, then rounded."""
    specs = leaf_specs(cfg)
    names = sorted(specs)
    dtype = jnp.dtype(cfg["param_dtype"])

    @jax.jit
    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, mean, std = specs[name]
            k = jax.random.fold_in(key, i)
            x = mean + std * jax.random.normal(k, shape, jnp.float32)
            out[name] = x.astype(dtype)
        return out

    return build(seed_key(seed))


def to_program_tree(cfg: dict, flat: dict[str, jax.Array]) -> dict:
    """Nest ``{path: array}`` into the program's parameter tree (empty
    dicts where a LayerNorm has no parameters)."""
    pre = "decoder/cycles/0_attn/"

    def norm(path):
        return {"scale": flat[path]} if path in flat else {}

    layer = {
        "norm1": norm(pre + "norm1/scale"),
        "attn": {k: flat[pre + "attn/" + k] for k in ("wq", "wk", "wv", "wo")},
        "norm2": norm(pre + "norm2/scale"),
        "mlp": {k: flat[pre + "mlp/" + k] for k in ("wi_gate", "wi_up", "wo")},
    }
    return {"embed": flat["embed"],
            "decoder": {"cycles": {"0_attn": layer}, "tail": {}},
            "final_norm": norm("final_norm/scale")}
