"""Architecture files under ``bench/arch/`` and the loader that finds them.

The pinned values were recorded from the dense block's weights,
reference logits and operation counts before they moved into
``bench/arch/dense.py``; the move must keep every one of them exact
(``==`` on a sha256 of the bytes, or on the number). They hold on the
CPU backend this suite runs on.

The stub architecture is a one-matrix model written into a temporary
directory: a configuration that names it must reach each of the six
functions through the benchmark's own entry points.
"""
import hashlib
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import bench.arch
from bench import flops, harness, reference, weights

CFG = Path(__file__).resolve().parents[1] / "configs" / "olmo1b.json"
BLOCKS = {"olmo1b": {},
          "gqa_rmsnorm_gelu": {"program_config": "minitron-4b", "norm_type": "rmsnorm",
                               "act": "gelu"}}

LAYER = "decoder/cycles/0_attn/"
SHARED_LEAVES = {
    LAYER + "attn/wk": "294834e1fc9f805dcaa6a15f0e5606c99b3c5e9aa7ce354c03135b1c94b32983",
    LAYER + "attn/wo": "4279c61e1bbed2c7c44b9a262da9b4e872b9380582fae72fa799f57f3b867201",
    LAYER + "attn/wq": "88504058849a13ea12e9d1e1b48f30a45590b7a4e5a1d6909374b78fcd7ab586",
    LAYER + "attn/wv": "d97674ab2a10fefbe4ee29d197d0b21db0f3ce68210e329f96e39ffeeacf6a9a",
    LAYER + "mlp/wi_gate": "f0a362a584555ab7addcd58d40c079f42496a0d60761b0defc4e57e0899d06cb",
    LAYER + "mlp/wi_up": "96845c4bc1c86cee92f70c9e55e0fecf4d0e3f941573dbd084d84d5e8ca6cb56",
    LAYER + "mlp/wo": "55e864cf36a80f3fd0fe58412d4ab9b721fbc9e88b966056754eaa06ca1edd19",
}
LEAVES = {
    "olmo1b": {
        **SHARED_LEAVES,
        "embed": "bc4c4c1e25a6902d5f1d7af0ce72a6c9afa59cf738147e6cbafb9ec06b00f07a",
    },
    "gqa_rmsnorm_gelu": {
        **SHARED_LEAVES,
        LAYER + "norm1/scale": "24245c44517005123cc3be220d210db3e6b2bc81ec272552d3e8c8220e5a4caf",
        LAYER + "norm2/scale": "396823141572570459d797feb3671f9df5ee147000d5bd9b938fdc33196bf710",
        "embed": "d5b7edfed1b8f9da82e27c1f4a778c2f72aeb12b5342a825c1ef15b5e3a53659",
        "final_norm/scale": "15e8066eeb4af7ab9098282e7251d7371f2224a74f90c5926bd70a9678163bd7",
    },
}
# (T, V) = (12, 128) float32 logits at m = [4, 10] bits, positions 0-6 at
# the first stage and 7-11 at the second
LOGITS = {
    ("olmo1b", False): "a48e834303ec3df9927c1a0fd93a07e54a18a0217b170d131e56d7f798f19755",
    ("olmo1b", True): "ecd2a314508e3ec8af313614dc5032fe7941ab287f3efff909aaa8a4863d8a98",
    ("gqa_rmsnorm_gelu", False): "0598d5e547ea44b29bbd5a2dcbd63b7153b746bab6de66584004f8fda04a6bc4",
    ("gqa_rmsnorm_gelu", True): "2f5a1bb90115019bb499dae255cde9d5c84000ab448a0a0aff52724c2aa4d145",
}
SEQUENCE_FLOPS = {(0, 1): 2353659904.0, (10, 13): 7065305088.0,
                  (384, 1280): 2206531059712.0}
DECODE_ATTENTION_BYTES = {0: 131072, 383: 50331648, 1279: 167772160}


def tiny(name):
    cfg = json.loads(CFG.read_text())
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16, d_ff=96,
               vocab=128, dtype="float32", param_dtype="float32", **BLOCKS[name])
    return cfg


def digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_pinned_weights(name):
    flat = weights.make_flat(tiny(name), 5)
    assert {k: (v.dtype, v.shape) for k, v in flat.items()} == \
        {k: (v.dtype, v.shape) for k, v in weights.make_flat(tiny(name), 6).items()}
    assert {k: digest(v) for k, v in flat.items()} == LEAVES[name]


@pytest.mark.parametrize("low", [False, True])
@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_pinned_reference_logits(name, low):
    cfg = tiny(name)
    flat = weights.make_flat(cfg, 5)
    lohi = {k: reference.leaf_range(v) for k, v in flat.items()}
    t = jnp.arange(12, dtype=jnp.int32) * 5 % cfg["vocab"]
    stage_of = jnp.asarray([0] * 7 + [1] * 5, jnp.int32)
    out = reference.forward(cfg, flat, lohi, np.array([4, 10]), t, low=low, stage_of=stage_of)
    assert (out.dtype, out.shape) == (jnp.float32, (12, 128))
    assert digest(out) == LOGITS[(name, low)]


def test_pinned_counts_at_olmo1b():
    cfg = json.loads(CFG.read_text())
    assert {k: flops.sequence_flops(cfg, *k) for k in SEQUENCE_FLOPS} == SEQUENCE_FLOPS
    assert {p: flops.decode_attention_bytes(cfg, p) for p in DECODE_ATTENTION_BYTES} == \
        DECODE_ATTENTION_BYTES


# -- the loader, with a second architecture ----------------------------------------

STUB = '''
"""A one-matrix model: each token's embedding times a head matrix."""
from bench import reference

WIDTH = 8


def arch_fields(cfg):
    return {"n_layers": 3}


def leaf_specs(cfg):
    return {"embed": ((cfg["vocab"], WIDTH), 0.0, 0.5),
            "head": ((WIDTH, cfg["vocab"]), 0.0, 0.5)}


def to_program_tree(cfg, flat):
    return {"stub": dict(flat)}


def forward(cfg, raw, lohi, m, tokens, *, low=False, stage_of=None):
    st = reference.Stages(cfg, lohi, m, tokens.shape[0], stage_of, low)
    x = st.per_stage(lambda mb: st.w("embed", raw["embed"], mb)[tokens])
    return st.mm(x, "head", raw["head"])


def kv_bytes_per_position(cfg):
    return 7


def sequence_flops(cfg, start, stop):
    return float(sum(1000.0 + p for p in range(start, stop)))
'''


@pytest.fixture
def stub(tmp_path, monkeypatch):
    """A config naming ``stub``, whose file lies in a directory of its own
    that the benchmark's entry points load from."""
    (tmp_path / "stub.py").write_text(STUB)
    monkeypatch.setattr(bench.arch, "ROOT", tmp_path)
    cfg = tiny("olmo1b")
    cfg["bench_arch"] = "stub"
    return cfg


def test_stub_arch_through_the_entry_points(stub):
    cfg = stub
    mod = bench.arch.load(cfg)
    assert Path(mod.__file__).name == "stub.py"
    assert len(bench.arch.FUNCTIONS) == 6

    from repro.configs import get_config
    arch = harness.arch_config(cfg)
    assert arch.n_layers == 3 and arch.d_model == get_config("olmo-1b").d_model
    assert arch.dtype == jnp.float32

    flat = weights.make_flat(cfg, 9)
    assert {k: v.shape for k, v in flat.items()} == {"embed": (128, 8), "head": (8, 128)}
    assert weights.to_program_tree(cfg, flat) == {"stub": flat}

    lohi = {k: reference.leaf_range(v) for k, v in flat.items()}
    tokens = jnp.arange(6, dtype=jnp.int32) * 11 % 128
    ms, stage_of = np.array([4, 16], np.int32), np.array([0, 0, 0, 1, 1, 1], np.int32)
    want = np.concatenate([
        np.asarray(reference.stage_weight(flat["embed"], *lohi["embed"], 16, m)[tokens[a:b]]
                   @ reference.stage_weight(flat["head"], *lohi["head"], 16, m))
        for m, (a, b) in ((4, (0, 3)), (16, (3, 6)))])
    got = np.asarray(reference.forward(cfg, flat, lohi, ms, tokens, stage_of=stage_of))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    served = np.argmax(got, axis=-1).astype(np.int32)
    served[-1] = -1
    gaps = reference.make_gap_fn(cfg, with_control=True)(flat, lohi, ms, stage_of,
                                                         tokens, served)
    assert np.asarray(gaps[0]).max() == 0.0 and len(gaps) == 2

    assert flops.kv_bytes_per_position(cfg) == 7
    assert flops.decode_attention_bytes(cfg, 4) == 35
    assert flops.sequence_flops(cfg, 2, 5) == 3009.0


def test_dense_is_found_by_name():
    cfg = json.loads(CFG.read_text())
    assert cfg["bench_arch"] == "dense"
    mod = bench.arch.load(cfg)
    assert Path(mod.__file__).parent == Path(bench.arch.__file__).parent
    assert mod is bench.arch.load(tiny("olmo1b"))


@pytest.mark.parametrize("value", [None, "nope", "../configs/olmo1b"])
def test_config_without_a_known_arch_is_refused(value, tmp_path, monkeypatch):
    monkeypatch.setattr(bench.arch, "ROOT", tmp_path)
    cfg = tiny("olmo1b")
    if value is None:
        del cfg["bench_arch"]
    else:
        cfg["bench_arch"] = value
    with pytest.raises(ValueError, match="bench_arch"):
        weights.leaf_specs(cfg)


def test_arch_file_missing_a_function_is_refused(tmp_path, monkeypatch):
    (tmp_path / "half.py").write_text(STUB.replace("def sequence_flops", "def _sequence_flops"))
    monkeypatch.setattr(bench.arch, "ROOT", tmp_path)
    cfg = tiny("olmo1b")
    cfg["bench_arch"] = "half"
    with pytest.raises(ValueError, match="sequence_flops"):
        bench.arch.load(cfg)

