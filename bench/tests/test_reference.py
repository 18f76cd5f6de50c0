"""The plain reference against the program, at a tiny size on the CPU.

Both read the same seeded weights. Tolerances:

- codes: exact. The reference's eq. (2) is the same float32 arithmetic
  (``(x - lo) / span`` scaled by a power of two, floored), so every code
  must agree.
- logits: the program, configured in float32 here, runs the same
  mathematics in another order (chunked online softmax, fused dequant
  products, a different reduction order), so agreement is to float32
  rounding amplified by two layers: 2e-4 of the logits' scale. A wrong
  norm, rotary pair, head grouping or mask moves logits by O(1) of
  their scale.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, weights

CFG = Path(__file__).resolve().parents[1] / "configs"


# The reference's other block options (grouped-query heads, RMSNorm with
# a scale, GELU), on the program's minitron-4b block, so they stay tested.
BLOCKS = {"olmo1b": {},
          "gqa_rmsnorm_gelu": {"program_config": "minitron-4b", "norm_type": "rmsnorm",
                               "act": "gelu"}}


def tiny(name, **kw):
    cfg = json.loads((CFG / "olmo1b.json").read_text())
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16, d_ff=96,
               vocab=128, dtype="float32", param_dtype="float32", **BLOCKS[name], **kw)
    return cfg


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_program_tree_matches_model_init(name):
    cfg = tiny(name)
    from repro.models.model import build_model

    model = build_model(harness.arch_config(cfg))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.to_program_tree(cfg, weights.make_flat(cfg, 3)))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [a.shape for a in jax.tree.leaves(want)] == [a.shape for a in jax.tree.leaves(got)]


def test_codes_match_program_quantizer():
    from repro.core.quantize import quantize

    x = weights.make_flat(tiny("olmo1b"), 5)["decoder/cycles/0_attn/mlp/wi_up"]
    lo, hi = reference.leaf_range(x)
    got = np.asarray(reference.codes(x, lo, hi, 16))
    want = np.asarray(quantize(x, 16).q)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", sorted(BLOCKS))
@pytest.mark.parametrize("m", [2, 8, 16])
def test_forward_matches_program(name, m):
    """Reference logits at m received bits against Model.forward on the
    program's own dequantized weights (eq. 5 materialized)."""
    from repro.core.progressive import transmit_reconstruct
    from repro.models.model import build_model

    cfg = tiny(name)
    flat = weights.make_flat(cfg, 11)
    params = weights.to_program_tree(cfg, flat)
    model = build_model(harness.arch_config(cfg))
    stage = m // 2
    served = transmit_reconstruct(params, harness.policy(cfg), upto_stage=stage)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg["vocab"], 24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = model.forward(served, {"tokens": tokens[None]})
        lohi = {k: reference.leaf_range(v) for k, v in flat.items()}
        ref = reference.forward(cfg, flat, lohi, m, tokens)
    prog, ref = np.asarray(prog[0], np.float64), np.asarray(ref, np.float64)
    scale = np.abs(ref).max()
    assert np.abs(prog - ref).max() <= 2e-4 * scale


def test_forward_is_causal_and_served_positions():
    cfg = tiny("olmo1b")
    flat = weights.make_flat(cfg, 2)
    lohi = {k: reference.leaf_range(v) for k, v in flat.items()}
    t = jnp.arange(10, dtype=jnp.int32) % cfg["vocab"]
    full = np.asarray(reference.forward(cfg, flat, lohi, 16, t))
    part = np.asarray(reference.forward(cfg, flat, lohi, 16, t[:6]))
    np.testing.assert_allclose(full[:6], part, rtol=1e-5, atol=1e-5)
    seq, served = reference.served_positions(np.array([5, 6, 7]), [9, 8], 8)
    assert seq.tolist() == [5, 6, 7, 9, 8, 0, 0, 0]
    assert served.tolist() == [-1, -1, 9, 8, -1, -1, -1, -1]


def test_checksum_sees_one_bit_anywhere():
    q = jnp.zeros(70000, jnp.uint32)
    base = np.asarray(reference.checksum(q))
    for i in (0, 65520, 65521, 69999):
        assert not np.array_equal(np.asarray(reference.checksum(q.at[i].set(1 << 15))), base)


def test_positions_at_their_own_stage():
    """A sequence served across upgrades: each position reads its own
    stage, and earlier positions do not see later weights."""
    cfg = tiny("gqa_rmsnorm_gelu")
    flat = weights.make_flat(cfg, 4)
    lohi = {k: reference.leaf_range(v) for k, v in flat.items()}
    t = jnp.arange(12, dtype=jnp.int32) * 5 % cfg["vocab"]
    early = np.asarray(reference.forward(cfg, flat, lohi, 4, t))
    one = np.asarray(reference.forward(cfg, flat, lohi, np.array([10]), t,
                                       stage_of=jnp.zeros(12, jnp.int32)))
    np.testing.assert_array_equal(one, np.asarray(reference.forward(cfg, flat, lohi, 10, t)))
    idx = jnp.asarray([0] * 7 + [1] * 3 + [2] * 2, jnp.int32)
    mixed = np.asarray(reference.forward(cfg, flat, lohi, np.array([4, 10, 14]), t,
                                         stage_of=idx))
    np.testing.assert_array_equal(mixed[:7], early[:7])
    assert not np.allclose(mixed[7:], early[7:])
    late = np.asarray(reference.forward(cfg, flat, lohi, np.array([4, 10]), t,
                                        stage_of=jnp.asarray([0] * 7 + [1] * 5, jnp.int32)))
    np.testing.assert_array_equal(mixed[:10], late[:10])
    assert not np.allclose(mixed[10:], late[10:])
    st = reference.position_stages(3, 1, [1, 2, 3], 8)
    assert st.tolist() == [1, 1, 1, 1, 2, 3, 3, 3]
