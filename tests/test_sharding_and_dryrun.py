"""Sharding rules + a real (small-mesh) dry-run, exercised in a
subprocess so the forced host-device count never leaks into other tests."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch.sharding import spec_for_param


@pytest.fixture(scope="module")
def mesh():
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def mesh3():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_matrix_2d(mesh):
    # (d_model, d_ff): 21504 % 16 == 0 both dims -> model on larger, fsdp other
    spec = spec_for_param("decoder/cycles/0_attn/mlp/wi_gate", (62, 5376, 21504), mesh)
    assert spec == P(None, ("data",), "model")


def test_spec_scalars_and_vectors_replicated(mesh):
    assert spec_for_param("final_norm/scale", (5376,), mesh) == P()
    assert spec_for_param("decoder/shared/gate", (), mesh) == P()


def test_spec_expert_bank_prefers_expert_dim(mesh):
    # dbrx we_gate: (R, E=16, d, f) -> E on model axis (expert parallelism)
    spec = spec_for_param("decoder/cycles/0_moe/moe/we_gate", (40, 16, 6144, 10752), mesh)
    assert spec[1] == "model"
    assert "data" in tuple(spec) or ("data",) in tuple(spec)


def test_spec_indivisible_expert_dim_falls_back(mesh):
    # mixtral 8 experts on a 16-way model axis -> cannot shard E; a big
    # divisible dim takes model instead
    spec = spec_for_param("decoder/cycles/0_swa_moe/moe/we_gate", (56, 8, 6144, 16384), mesh)
    assert spec[1] != "model"
    assert "model" in tuple(spec)


def test_spec_multipod_fsdp_includes_pod(mesh3):
    spec = spec_for_param("embed", (262144, 5376), mesh3)
    assert spec[0] == "model" or spec[1] == "model"
    flat = tuple(x for x in spec if x is not None)
    assert any(isinstance(x, tuple) and "pod" in x for x in flat)


def test_small_tensors_skip_fsdp(mesh):
    spec = spec_for_param("decoder/cycles/0_attn/attn/q_norm_w", (62, 128, 128), mesh)
    # 128*128*62 > threshold -> allowed; but (8, 8): replicated except model
    spec_small = spec_for_param("x", (8, 8), mesh)
    assert all(s is None for s in spec_small)


@pytest.mark.slow
def test_debug_mesh_dryrun_subprocess(tmp_path):
    """Lower+compile train/prefill/decode for a reduced arch on a real
    (4-device) mesh in a subprocess — the full pipeline the production
    dry-run uses, at CI scale."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json, jax
        from repro.configs import get_config
        from repro.launch import sharding
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import make_train_step, make_serve_step
        from repro.models.model import build_model
        from repro.train import optimizer as opt
        import jax.numpy as jnp

        cfg = get_config("minitron-4b").reduced(d_model=128, n_heads=4, n_kv=2,
                                                d_ff=256, vocab=512)
        model = build_model(cfg)
        mesh = make_debug_mesh(2, 2)
        params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        psh = sharding.param_shardings(params_sds, mesh)
        batch = model.input_specs(batch=4, seq_len=32, mode="train")
        batch["labels"] = batch["tokens"]
        opt_sds = jax.eval_shape(opt.init, params_sds)
        osh = {"mu": psh, "nu": psh, "step": sharding.replicated(mesh)}
        bsh = sharding.batch_shardings(batch, mesh)
        step = make_train_step(model, opt.OptConfig())
        with mesh:
            compiled = jax.jit(step, in_shardings=(psh, osh, bsh)).lower(
                params_sds, opt_sds, batch).compile()
        ca = compiled.cost_analysis()
        # decode too
        caches_sds = jax.eval_shape(lambda: model.init_caches(4, 64))
        csh = sharding.cache_shardings(caches_sds, mesh, batch=4)
        tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        serve = make_serve_step(model)
        with mesh:
            compiled2 = jax.jit(serve, in_shardings=(
                psh, csh, sharding.batch_shardings(tok, mesh),
                sharding.replicated(mesh))).lower(
                params_sds, caches_sds, tok, pos).compile()
        print(json.dumps({"train_flops": ca["flops"],
                          "decode_ok": compiled2 is not None}))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["train_flops"] > 0
    assert result["decode_ok"]


def test_extrapolation_math():
    from repro.launch.hlo_analysis import extrapolate_counts

    c1 = {"flops": 10.0, "hbm_bytes": 100.0,
          "coll_counts": {"all-reduce": 2}, "coll_result_bytes": {"all-reduce": 8.0},
          "coll_wire_bytes": {"all-reduce": 16.0},
          "arg_bytes": 1, "temp_bytes": 1, "output_bytes": 1, "alias_bytes": 0}
    c2 = {"flops": 16.0, "hbm_bytes": 150.0,
          "coll_counts": {"all-reduce": 3, "all-gather": 1},
          "coll_result_bytes": {"all-reduce": 12.0, "all-gather": 4.0},
          "coll_wire_bytes": {"all-reduce": 24.0, "all-gather": 2.0},
          "arg_bytes": 2, "temp_bytes": 2, "output_bytes": 2, "alias_bytes": 0}
    c10 = extrapolate_counts(c1, c2, 10)
    assert c10["flops"] == 10 + 9 * 6
    assert c10["hbm_bytes"] == 100 + 9 * 50
    assert c10["coll_counts"]["all-reduce"] == 2 + 9 * 1
    assert c10["coll_wire_bytes"]["all-gather"] == 9 * 2.0


def test_collective_parser():
    from repro.launch.hlo_analysis import parse_collectives

    hlo = """
      %ar = f32[128,256]{1,0} all-reduce(%x), channel_id=1, replica_groups=[16,16]<=[256]
      %ag = bf16[512,64]{1,0} all-gather(%y), replica_groups=[32,8]<=[256], dimensions={0}
      %aa = f32[64]{0} all-to-all(%z), replica_groups={{0,1,2,3}}
      %cp = u16[32,32]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
    """
    st = parse_collectives(hlo)
    assert st.counts == {"all-reduce": 1, "all-gather": 1, "all-to-all": 1,
                         "collective-permute": 1}
    ar_bytes = 128 * 256 * 4
    assert st.result_bytes["all-reduce"] == ar_bytes
    assert st.wire_bytes["all-reduce"] == 2 * ar_bytes * 15 / 16
    ag_bytes = 512 * 64 * 2
    assert st.wire_bytes["all-gather"] == ag_bytes * 7 / 8
    assert st.wire_bytes["all-to-all"] == 64 * 4 * 3 / 4
    assert st.wire_bytes["collective-permute"] == 32 * 32 * 2


def test_model_flops_moe_counts_active_only():
    import jax
    from repro.configs import get_config
    from repro.launch.hlo_analysis import active_param_count, param_count
    from repro.models.model import build_model

    cfg = get_config("mixtral-8x22b")
    model = build_model(cfg)
    sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    total = param_count(sds)
    active = active_param_count(cfg, sds)
    assert active < total
    # mixtral: top-2 of 8 experts; expert banks dominate -> active ~ 22/141
    assert 0.1 < active / total < 0.35


def test_megatron_strategy_directional(mesh):
    # column-parallel: output dim on model
    s = spec_for_param("decoder/cycles/0_attn/attn/wq", (16, 2048, 2048), mesh,
                       "megatron")
    assert s[2] == "model" and s[1] in ("data", ("data",), None)
    # row-parallel: input (contraction) dim on model
    s = spec_for_param("decoder/cycles/0_attn/attn/wo", (16, 2048, 2048), mesh,
                       "megatron")
    assert s[1] == "model"
    # non-matching names fall back to greedy
    g = spec_for_param("embed", (50304, 2048), mesh, "greedy")
    m = spec_for_param("embed", (50304, 2048), mesh, "megatron")
    assert g == m
    # expert banks keep expert-parallel override under both strategies
    e = spec_for_param("decoder/cycles/0_moe/moe/we_gate",
                       (40, 16, 6144, 10752), mesh, "megatron")
    assert e[1] == "model"
