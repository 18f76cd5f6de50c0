"""Sharded progressive serving: the PR-7 acceptance surface.

1. ``serving_spec_for_param`` only ever shards non-reduced dims (the
   expert dim of MoE banks, else the output dim) — never a contraction,
   so every GSPMD collective under the serving mesh is a gather (pure
   data movement, bit-exact).
2. Real-mesh subprocess runs (forced host device count, like
   test_sharding_and_dryrun): a sharded server is token-identical to
   the single-device server at EVERY precision stage — dense fp and
   quantized residency on a (2, 2) debug mesh, expert-sliced MoE +
   self-speculative and the slot pool on a 4-way model axis — with
   shard-local plane ingest at pinned launch counts, zero-recompile
   upgrades, and enqueue-only (zero-stall) upgrades surviving the mesh.
3. ``ops.sharded_dequant_matmul`` (shard_map, N-sharded accumulator) is
   bit-identical to the single-device kernel.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch.sharding import serving_spec_for_param


@pytest.fixture(scope="module")
def mesh():
    return AbstractMesh((2, 4), ("data", "model"))


@pytest.fixture(scope="module")
def mesh1():
    return AbstractMesh((8, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# spec rules: nothing reduced is ever sharded
# ---------------------------------------------------------------------------

def test_serving_spec_output_dim_only(mesh):
    # 2-D weight: model axis on the OUTPUT (last) dim, data never used
    assert serving_spec_for_param("decoder/cycles/0_attn/attn/wq",
                                  (3, 64, 128), mesh) == P(None, None, "model")
    assert serving_spec_for_param("embed", (160, 64), mesh) == \
        P(None, "model")


def test_serving_spec_never_shards_contractions_or_data(mesh):
    # every returned spec uses ONLY the model axis, only on the last dim
    # or the expert dim — a contraction (any other dim) stays None
    for shape in [(64, 128), (2, 64, 128), (4, 8, 64, 128)]:
        spec = serving_spec_for_param("decoder/cycles/0_attn/mlp/wo",
                                      shape, mesh)
        assert all(s in (None, "model") for s in spec)
        assert all(s is None for s in spec[:-1])


def test_serving_spec_expert_dim_preferred(mesh):
    # MoE bank (R, E, d, f): expert dim (indexed, never contracted)
    spec = serving_spec_for_param("decoder/cycles/0_moe/moe/we_gate",
                                  (2, 8, 64, 128), mesh)
    assert tuple(spec) == (None, "model", None, None)
    # indivisible E falls back to the output dim, not a contraction
    spec = serving_spec_for_param("decoder/cycles/0_moe/moe/we_up",
                                  (2, 6, 64, 128), mesh)
    assert tuple(spec)[-1] == "model"


def test_serving_spec_minitron_leaves(mesh):
    """The Nemotron block's own leaves: the untied head and the ungated
    up projection shard their output dim; LayerNorm1P's stacked scale
    and bias replicate."""
    assert serving_spec_for_param("lm_head", (3072, 256000), mesh) == P(None, "model")
    assert serving_spec_for_param("decoder/cycles/0_attn/mlp/wi_up",
                                  (4, 3072, 9216), mesh) == P(None, None, "model")
    for leaf in ("norm1/scale", "norm1/bias"):
        assert serving_spec_for_param(f"decoder/cycles/0_attn/{leaf}",
                                      (4, 3072), mesh) == P()


def test_serving_spec_replicates_everything_else(mesh, mesh1):
    assert serving_spec_for_param("final_norm/scale", (64,), mesh) == P()
    assert serving_spec_for_param("b", (), mesh) == P()
    # indivisible output dim -> replicated, never a partial shard
    assert serving_spec_for_param("w", (64, 30), mesh) == P()
    # degenerate 1-wide model axis -> replicated
    assert serving_spec_for_param("embed", (160, 64), mesh1) == P()


# ---------------------------------------------------------------------------
# real-mesh subprocess runs
# ---------------------------------------------------------------------------

def _run_sub(script: str, timeout: int = 560) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


_PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import wire
    from repro.core.progressive import divide
    from repro.kernels import ops
    from repro.models.model import build_model
    from repro.transmission import BandwidthTrace, Session
"""


@pytest.mark.slow
def test_sharded_dense_serving_token_identity_and_ingest():
    """Dense model on a (2, 2) debug mesh (replica rows exercise the
    assembly's cross-row transfers): per-stage token identity for both
    residencies, shard-local ingest at one launch per sub-store per
    stage (no host gather, no replicated OR), one decode executable
    across every upgrade, and the shard_map kernel path bit-identical
    to single-device."""
    out = _run_sub(_PRELUDE + """
    from repro.launch.mesh import make_debug_mesh, make_serving_mesh

    cfg = get_config("olmo-1b").reduced(n_layers=2, d_model=64, d_ff=128,
                                        vocab=128, n_heads=2, n_kv=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prog = divide(params)
    blob = wire.encode(prog)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab).astype(jnp.int32)}
    mesh = make_debug_mesh(2, 2)

    def serve(m, resident):
        sess = Session(blob, BandwidthTrace.constant(2e5))
        return sess.run_serving(model, prog, decode_steps=8, batch=batch,
                                resident=resident, mesh=m)

    out = {}
    r1 = serve(None, "fp")
    ops.reset_launch_counts()
    from repro import obs
    with obs.telemetry(True):
        r2 = serve(mesh, "fp")
        out["store_ingest_spans"] = len(obs.get_tracer().of("store_ingest"))
        out["store_or_spans"] = len(obs.get_tracer().of("store_or"))
    out["fp_tokens_equal"] = bool(np.array_equal(
        np.asarray(r1.tokens), np.asarray(r2.tokens)))
    out["stages_equal"] = r1.stage_at_step == r2.stage_at_step
    out["n_stages_seen"] = len(set(r2.stage_at_step))
    store = r2.client.store
    n_active = sum(1 for sub in store.substores if sub.n_tensors > 0)
    out["ingest_launches"] = ops.LAUNCH_COUNTS["plane_or_segments"]
    out["expected_launches"] = prog.n_stages * n_active
    out["n_stages"] = prog.n_stages
    out["plane_or"] = ops.LAUNCH_COUNTS["plane_or"]
    out["fp_decode_cache"] = r2.server.decode_cache_size()
    r3 = serve(mesh, "quantized")
    out["quant_tokens_equal"] = bool(np.array_equal(
        np.asarray(r1.tokens), np.asarray(r3.tokens)))
    out["quant_decode_cache"] = r3.server.decode_cache_size()

    m4 = make_serving_mesh(4)
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 64))
    q = jax.random.randint(jax.random.PRNGKey(3), (64, 128), 0,
                           1 << 16).astype(jnp.uint16)
    sc, off = jnp.float32(1.7e-4), jnp.float32(-0.51)
    a = ops.dequant_matmul(x, q, sc, off)
    b = ops.sharded_dequant_matmul(x, q, sc, off, mesh=m4)
    out["dqm_identical"] = bool(np.array_equal(np.asarray(a),
                                               np.asarray(b)))
    print(json.dumps(out))
    """)
    assert out["fp_tokens_equal"] and out["quant_tokens_equal"]
    assert out["stages_equal"]
    assert out["n_stages_seen"] > 1, "upgrades must land mid-generation"
    assert out["ingest_launches"] == out["expected_launches"], \
        "shard-local ingest: one batched launch per sub-store per stage"
    assert out["plane_or"] == 0
    # one store_ingest span per shipment (the sub-stores' rounds nest
    # inside it, one store_or each)
    assert out["store_ingest_spans"] == out["n_stages"]
    assert out["store_or_spans"] == out["expected_launches"]
    assert out["fp_decode_cache"] == 1 and out["quant_decode_cache"] == 1
    assert out["dqm_identical"]


@pytest.mark.slow
def test_sharded_moe_speculative_and_pool_token_identity():
    """Expert-parallel MoE on a 4-way model axis: expert slices route
    WHOLE to their owning shard (never split), the self-speculative
    sharded server is token-identical to single-device at every stage
    with exactly two executables, and the slot pool serves identical
    streams on the mesh with enqueue-only (zero-stall) upgrades."""
    out = _run_sub(_PRELUDE + """
    from repro.core.plane_store import ShardedPlaneStore
    from repro.core.policy import ExpertPopularityPolicy
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.speculative import SpecConfig

    cfg = get_config("dbrx-132b").reduced(n_layers=2, d_model=32, d_ff=64,
                                          vocab=64, n_heads=2, n_kv=2,
                                          n_experts=4, top_k=1)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pol = ExpertPopularityPolicy(
        popularity={i: 1.0 / (i + 1) for i in range(4)}, n_experts=4)
    prog = divide(params, pol)
    blob = wire.encode(prog)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab).astype(jnp.int32)}
    mesh = make_serving_mesh(4)

    out = {}
    store = ShardedPlaneStore.from_model(prog, mesh)
    expert_idxs = [i for i, key in enumerate(store.keys)
                   if store._route[key][0] == "expert"]
    out["n_expert_slices"] = len(expert_idxs)
    out["expert_slices_unsplit"] = all(
        len(store._placement[i]) == 1 for i in expert_idxs)

    def serve(m):
        sess = Session(blob, BandwidthTrace.constant(2e5))
        return sess.run_serving(model, prog, decode_steps=8, batch=batch,
                                speculative=SpecConfig(draft_bits=4, k=3),
                                mesh=m)

    r1, r2 = serve(None), serve(mesh)
    out["spec_tokens_equal"] = bool(np.array_equal(
        np.asarray(r1.tokens), np.asarray(r2.tokens)))
    out["spec_stages_equal"] = r1.stage_at_step == r2.stage_at_step
    out["n_stages_seen"] = len(set(r2.stage_at_step))
    out["spec_decode_cache"] = r2.server.decode_cache_size()

    prompts = [jax.random.randint(jax.random.PRNGKey(30 + i), (L,), 0,
                                  cfg.vocab).astype(jnp.int32)
               for i, L in enumerate([6, 8, 7])]

    def pool(m):
        sess = Session(blob, BandwidthTrace.constant(2e5))
        return sess.run_serving_pool(model, prog, prompts=prompts,
                                     max_new_tokens=6, n_slots=2,
                                     resident="quantized", mesh=m)

    p1, p2 = pool(None), pool(mesh)
    out["pool_tokens_equal"] = all(
        p1.tokens[rid] == p2.tokens[rid] for rid in p1.tokens)
    out["pool_decode_cache"] = p2.server.decode_cache_size()
    out["pool_prefill_cache"] = p2.server.prefill_cache_size()
    out["pool_upgrades"] = len(p2.server.upgrade_log)
    out["pool_all_enqueue_only"] = all(
        rec["double_buffer"] for rec in p2.server.upgrade_log)
    print(json.dumps(out))
    """)
    assert out["n_expert_slices"] > 0
    assert out["expert_slices_unsplit"], \
        "expert slices must ingest whole into their owning shard"
    assert out["spec_tokens_equal"] and out["spec_stages_equal"]
    assert out["n_stages_seen"] > 1
    assert out["spec_decode_cache"] == 2
    assert out["pool_tokens_equal"]
    assert out["pool_decode_cache"] == 1
    assert out["pool_prefill_cache"] == 1, \
        "the pooled state must start on the mesh: one chunk-step executable"
    assert out["pool_upgrades"] > 0 and out["pool_all_enqueue_only"], \
        "upgrades must stay enqueue-only (zero-stall) on the mesh"


@pytest.mark.slow
def test_sharded_minitron_pool_token_identity():
    """Minitron's block (ungated relu² MLP, untied head, LayerNorm1P
    with bias, partial rotary, a group of 3 query heads per KV head) in
    the slot pool on a 4-way model axis: the same tokens as one device,
    with quantized residency, one decode executable and the untied head
    sharded on its vocabulary."""
    out = _run_sub(_PRELUDE + """
    from repro.launch.mesh import make_serving_mesh

    cfg = get_config("minitron-4b").reduced(n_layers=2, d_model=64, n_heads=6,
                                            n_kv=2, head_dim=16, d_ff=96,
                                            vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prog = divide(params)
    blob = wire.encode(prog)
    prompts = [jax.random.randint(jax.random.PRNGKey(40 + i), (L,), 0,
                                  cfg.vocab).astype(jnp.int32)
               for i, L in enumerate([6, 9, 7])]

    def pool(m):
        sess = Session(blob, BandwidthTrace.constant(2e5))
        return sess.run_serving_pool(model, prog, prompts=prompts,
                                     max_new_tokens=6, n_slots=2,
                                     resident="quantized", mesh=m)

    p1, p2 = pool(None), pool(make_serving_mesh(4))
    head = p2.server.params["lm_head"].q
    print(json.dumps({
        "tokens_equal": all(p1.tokens[rid] == p2.tokens[rid]
                            for rid in p1.tokens),
        "decode_cache": p2.server.decode_cache_size(),
        "head_spec": str(head.sharding.spec)}))
    """)
    assert out["tokens_equal"]
    assert out["decode_cache"] == 1
    assert out["head_spec"] == str(P(None, "model"))
