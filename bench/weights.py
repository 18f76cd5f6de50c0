"""Seeded weights, made by the benchmark and not by the program.

One jitted call builds every leaf on the device, in the dtype the
configuration's checkpoint is published in (what the server side
divides). The configuration's architecture file (``arch/``) names the
leaves, their shapes and distributions, and nests them into the
parameter tree the program serves. Both the program and the plain
reference get these arrays; neither takes weights from the other.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (JAX keys take 32 bits)."""
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple, float, float]]:
    """``path -> (shape, mean, std)`` of every parameter leaf, by the
    configuration's architecture file."""
    return arch.load(cfg).leaf_specs(cfg)


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
    """Nest ``{path: array}`` into the program's parameter tree, by the
    configuration's architecture file."""
    return arch.load(cfg).to_program_tree(cfg, flat)
