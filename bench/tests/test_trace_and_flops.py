"""Trace reduction on a recorded trace, and the operation/byte counts.

``data/olmo1b_steps.json.gz`` holds the device operations of one
decode step and one prefill-chunk step of olmo-1b (8 slots, 1280
positions, quantized weights) as read from a TPU v5 lite trace: every
Pallas call with its full instruction text, other operations with the
first 160 characters of theirs.
"""
import json
from pathlib import Path

import pytest

from bench import arch, flops, trace as tr

DATA = Path(__file__).resolve().parent / "data" / "olmo1b_steps.json.gz"
CFG = json.loads((Path(__file__).resolve().parents[1] / "configs" / "olmo1b.json").read_text())
dense_arch = arch.load(CFG)
PEAKS = json.loads((Path(__file__).resolve().parents[1] / "peaks.json").read_text())["devices"]["TPU v5 lite"]


@pytest.fixture(scope="module")
def recorded():
    return tr.load(str(DATA))


def test_kernels_named_and_counted(recorded):
    ops = recorded["ops"]
    n = {k: len(tr.kernel_calls(ops, k)) for k in ("dequant_matmul", "flash_decode", "flash_verify")}
    # per step: 7 products in each of 16 layers and the unembedding; one
    # attention kernel per layer
    assert n == {"dequant_matmul": 2 * (7 * 16 + 1), "flash_decode": 16, "flash_verify": 16}
    assert tr.kernel_of("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == ""


def test_dequant_shapes_at_olmo1b(recorded):
    seen = set()
    for e in tr.kernel_calls(recorded["ops"], "dequant_matmul"):
        sh = tr.shapes(e["name"])
        (odt, (M, N)), (xdt, (M2, K)), (qdt, (K2, N2)) = sh[0], sh[1], sh[2]
        assert (M, N, K) == (M2, N2, K2) and odt == "f32" and qdt == "u16"
        seen.add((M, K, N, xdt))
    assert seen == {(M, K, N, x) for M in (8, 64)
                    for K, N, x in ((2048, 2048, "bf16"), (2048, 8192, "bf16"),
                                    (8192, 2048, "bf16"), (2048, 50304, "f32"))}


def test_busy_modules_and_roofline_bounds(recorded):
    ops, mods = recorded["ops"], recorded["modules"]
    busy = tr.busy_s(ops)
    span = sum(m["dur_ns"] for m in mods) / 1e9
    assert 0.9 * span <= busy <= span * 1.001
    assert tr.module_s(mods, "chunk_step") == pytest.approx(mods[1]["dur_ns"] / 1e9)
    assert tr.module_s(mods, "decode_step") == pytest.approx(mods[0]["dur_ns"] / 1e9)
    from types import SimpleNamespace
    from bench import harness

    run = SimpleNamespace(trace=recorded, peaks=PEAKS)
    share = harness.load_reader("dequant_matmul_roofline")(run)
    assert 0.0 < share <= 100.0
    # the reader's bytes are the call's own operands less the two affine scalars
    e = tr.kernel_calls(ops, "dequant_matmul")[0]
    sh = tr.shapes(e["name"])
    (xdt, (M, K)), (_, (_, N)) = sh[1], sh[2]
    assert flops.dequant_matmul_cost(M, K, N, tr.DTYPE_BYTES[xdt])[1] == \
        sum(tr.nbytes(s) for s in sh) - 8
    assert harness.load_reader("plane_or_roofline")(run) is None   # no OR in these steps
    top = tr.top_ops(ops)
    assert all(name not in tr.CONTAINERS for name, _ in top)
    assert sum(s for _, s in top) <= sum(e["dur_ns"] for e in ops) / 1e9


def test_busy_union_and_idle_gaps():
    ops = [{"device": 0, "name": "%a.1 = f32[1]{0} x()", "kernel": "", "start_ns": s, "dur_ns": d}
           for s, d in ((0, 10), (5, 10), (40, 10), (100, 5))]
    assert tr.busy_s(ops) == pytest.approx(30e-9)
    host = [{"name": "window", "start_ns": 0.0, "dur_ns": 120.0},
            {"name": "serve", "start_ns": 0.0, "dur_ns": 60.0},
            {"name": "feed", "start_ns": 14.0, "dur_ns": 30.0}]
    gaps = tr.idle_gaps({"ops": ops, "host": host})
    assert gaps[0] == ["window", pytest.approx(50e-9)]    # 50..100, middle past serve
    assert gaps[1] == ["feed", pytest.approx(25e-9)]      # 15..40, feed innermost
    assert gaps[2] == ["window", pytest.approx(15e-9)]    # 105..120


def test_flops_and_bytes_at_olmo1b():
    f, b = flops.dequant_matmul_cost(8, 2048, 8192, 2)
    assert f == 2 * 8 * 2048 * 8192
    assert b == 2048 * 8192 * 2 + 8 * 2048 * 2 + 8 * 8192 * 4
    # KV of one position: K and V, 16 layers, 16 heads of 128, bf16
    assert flops.kv_bytes_per_position(CFG) == 131072
    assert flops.decode_attention_bytes(CFG, 1279) == 1280 * 131072
    assert flops.plane_or_bytes(1_176_764_416) == 6 * 1_176_764_416
    # every weight is used once per token (the tied embedding as the
    # unembedding), plus the attention products over p + 1 keys
    dense = 2.0 * CFG["parameters"]
    assert flops.sequence_flops(CFG, 0, 1) == pytest.approx(dense + 4 * 16 * 16 * 128)
    assert flops.sequence_flops(CFG, 10, 13) == pytest.approx(
        3 * dense + 4 * 16 * 16 * 128 * (11 + 12 + 13))
    # a decode step's products at 8 rows are bound by the codes' bytes
    for k, n, x in dense_arch._matmul_shapes(CFG):
        f, b = flops.dequant_matmul_cost(8, k, n, x)
        assert flops.roofline_s(f, b, PEAKS) == b / PEAKS["hbm_bytes_per_s"]
