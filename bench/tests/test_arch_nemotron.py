"""The Nemotron architecture file (``bench/arch/nemotron.py``), the
minitron4b configuration and the ``unembed_roofline`` reader.

Tolerance of the reference against the program: the program, configured
in float32 here, runs the same mathematics in another order (chunked
online softmax, a different reduction order), so agreement is to float32
rounding amplified by two layers, 2e-4 of the logits' scale. A wrong
norm, rotary pair, head grouping or activation moves logits by O(1) of
their scale.
"""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.arch
from bench import flops, harness, reference, weights
from bench import trace as tr

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "minitron4b.json").read_text())
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def tiny():
    """The block at H 6 / Kh 2 (Minitron's group of 3), 8 of 16 dims rotated."""
    cfg = dict(CFG)
    cfg.update(n_layers=2, d_model=64, n_heads=6, n_kv=2, head_dim=16, rotary_dim=8,
               d_ff=96, vocab=128, dtype="float32", param_dtype="float32")
    return cfg


def test_found_by_name_and_tree_matches_model_init():
    assert Path(bench.arch.load(CFG).__file__).name == "nemotron.py"
    cfg = tiny()
    from repro.models.model import build_model

    model = build_model(harness.arch_config(cfg))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.to_program_tree(cfg, weights.make_flat(cfg, 3)))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [a.shape for a in jax.tree.leaves(want)] == [a.shape for a in jax.tree.leaves(got)]


@pytest.mark.parametrize("m", [2, 8, 16])
def test_forward_matches_program(m):
    """Reference logits at m received bits against Model.forward on the
    program's own dequantized weights; the fp8 control lies far off."""
    from repro.core.progressive import transmit_reconstruct
    from repro.models.model import build_model

    cfg = tiny()
    flat = weights.make_flat(cfg, 11)
    model = build_model(harness.arch_config(cfg))
    served = transmit_reconstruct(weights.to_program_tree(cfg, flat), harness.policy(cfg),
                                  upto_stage=m // 2)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg["vocab"], 24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = model.forward(served, {"tokens": tokens[None]})
        lohi = {k: reference.leaf_range(v) for k, v in flat.items()}
        ref = reference.forward(cfg, flat, lohi, m, tokens)
        low = reference.forward(cfg, flat, lohi, m, tokens, low=True)
    prog, ref, low = (np.asarray(a, np.float64) for a in (prog[0], ref, low))
    scale = np.abs(ref).max()
    assert np.abs(prog - ref).max() <= 2e-4 * scale
    assert np.abs(low - ref).max() >= 1e-2 * scale


def test_parameters_and_cache_bytes_at_minitron4b():
    specs = weights.leaf_specs(CFG)
    assert sum(int(np.prod(shape)) for shape, _, _ in specs.values()) == 1_900_075_008
    assert CFG["parameters"] == 1_900_075_008
    # K and V: 4 layers x 8 KV heads x 128 dims, bfloat16
    assert flops.kv_bytes_per_position(CFG) == 2 * 4 * 8 * 128 * 2 == 16_384


def test_sequence_flops_by_hand():
    d, hq, hkv, F, V, L = 3072, 24 * 128, 8 * 128, 9216, 256_000, 4
    layer = d * hq + 2 * d * hkv + hq * d + d * F + F * d   # q, k, v, o, up, down
    dense = 2 * (L * layer + d * V)                          # and the untied head
    attn = 4 * L * 24 * 128                                  # per key attended
    assert dense == 2_227_175_424
    assert flops.sequence_flops(CFG, 0, 1) == dense + attn
    assert flops.sequence_flops(CFG, 10, 13) == 3 * dense + attn * (11 + 12 + 13)
    assert flops.sequence_flops(CFG, 5, 5) == 0.0


def _call(M, K, N, xdt="f32", dur_ns=1000.0):
    name = (f"%dequant_matmul.7 = f32[{M},{N}]{{1,0}} custom-call({xdt}[{M},{K}]{{1,0}} %a, "
            f"u16[{K},{N}]{{1,0}} %b, f32[1,1]{{1,0}} %c, f32[1,1]{{1,0}} %d), "
            'custom_call_target="tpu_custom_call"')
    return {"device": 0, "name": name, "kernel": tr.kernel_of(name), "start_ns": 0.0,
            "dur_ns": dur_ns}


def test_unembed_roofline_counts_only_the_head():
    read = harness.load_reader("unembed_roofline")
    head = _call(64, 3072, 256_000, dur_ns=4e6)
    ops = [head, _call(64, 3072, 3072, "bf16", 1e5), _call(64, 9216, 3072, "bf16", 2e5),
           _call(512, 3072, 256_000, dur_ns=9e6)]
    assert [e["kernel"] for e in ops] == ["dequant_matmul"] * 4
    run = SimpleNamespace(cfg=CFG, peaks=PEAKS, trace={"ops": ops})
    ideal = (flops.roofline_s(*flops.dequant_matmul_cost(64, 3072, 256_000, 4), PEAKS)
             + flops.roofline_s(*flops.dequant_matmul_cost(512, 3072, 256_000, 4), PEAKS))
    assert read(run) == pytest.approx(100.0 * ideal / 13e-3)
    # the head's codes over HBM bandwidth bound the decode call
    assert flops.dequant_matmul_cost(64, 3072, 256_000, 4)[1] > 3072 * 256_000 * 2
    assert read(SimpleNamespace(cfg=CFG, peaks=PEAKS, trace={"ops": ops[1:3]})) is None
    assert read(SimpleNamespace(cfg=CFG, peaks=PEAKS, trace=None)) is None
