import os
os.environ.update(JAX_PLATFORMS="cpu",
                  XLA_FLAGS="--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import: jax locks the platform and device
# count on first init, and the production-mesh dry-run needs 512
# placeholder host devices. The CPU pin also keeps this process and the
# per-combo children it spawns off any attached accelerator, which
# belongs to one process at a time.
"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes, prove the sharding config is coherent, and dump the
roofline source terms.

Usage:
    python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k
    python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all              # every combo, resumable

Each run writes artifacts/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis / cost_analysis / parsed collective schedule; the
EXPERIMENTS.md tables are generated from these files by
benchmarks/roofline.py.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import get_config, all_configs
from repro.launch import hlo_analysis, sharding
from repro.launch.mesh import make_production_mesh, data_axes
from repro.launch.steps import (
    SHAPES,
    WorkloadShape,
    long_context_supported,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    step_input_specs,
)
from repro.models.model import build_model
from repro.train import optimizer as opt


def _shardings_for(cfg, shape: WorkloadShape, mesh, specs, strategy="greedy"):
    """in_shardings tuple matching step_input_specs order."""
    if shape.mode == "train":
        params_sds, opt_sds, batch_sds = specs
        psh = sharding.param_shardings(params_sds, mesh, strategy)
        osh = {"mu": psh, "nu": psh, "step": sharding.replicated(mesh)}
        bsh = sharding.batch_shardings(batch_sds, mesh)
        return (psh, osh, bsh)
    if shape.mode == "prefill":
        params_sds, batch_sds = specs
        psh = sharding.param_shardings(params_sds, mesh, strategy)
        bsh = sharding.batch_shardings(batch_sds, mesh)
        return (psh, bsh)
    params_sds, caches_sds, tokens_sds, pos_sds = specs
    psh = sharding.param_shardings(params_sds, mesh, strategy)
    csh = sharding.cache_shardings(caches_sds, mesh, batch=shape.global_batch)
    tsh = sharding.batch_shardings(tokens_sds, mesh)
    return (psh, csh, tsh, sharding.replicated(mesh))


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            strategy: str = "greedy", param_dtype: str = "f32",
            microbatches: int = 1) -> dict:
    cfg = get_config(arch)
    if param_dtype == "bf16":
        import jax.numpy as jnp

        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"

    if shape_name == "long_500k" and not long_context_supported(cfg):
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "skipped",
            "reason": "pure full-attention arch; long_500k requires "
                      "sub-quadratic attention (DESIGN.md §4)",
        }

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 1
    for s in mesh.shape.values():
        n_chips *= s

    def _lower_compile(use_cfg):
        model = build_model(use_cfg)
        specs = step_input_specs(use_cfg, shape)
        in_sh = _shardings_for(use_cfg, shape, mesh, specs, strategy)
        if shape.mode == "train":
            step = make_train_step(model, opt.OptConfig(),
                                   microbatches=microbatches)
            donate = (0, 1)
        elif shape.mode == "prefill":
            step = make_prefill_step(model)
            donate = ()
        else:
            step = make_serve_step(model)
            donate = (1,)
        t0 = time.time()
        with mesh:
            lowered = jax.jit(
                step, in_shardings=in_sh, donate_argnums=donate
            ).lower(*specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        return specs, compiled, t_lower, t_compile

    # 1) production (scanned) program: sharding/compile proof + memory
    specs, compiled, t_lower, t_compile = _lower_compile(cfg)
    ma = compiled.memory_analysis()
    mf = hlo_analysis.model_flops(cfg, specs[0], shape, mode=shape.mode)
    rl_scanned = hlo_analysis.roofline_from_compiled(
        compiled, n_chips=n_chips, model_flops_global=mf
    )
    out = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "mode": shape.mode,
        "strategy": strategy,
        "param_dtype": param_dtype,
        "microbatches": microbatches,
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        },
        # counts from the *scanned* HLO undercount loop bodies (trip
        # counts are not multiplied by XLA cost analysis); kept for
        # reference only. §Roofline uses `roofline` below.
        "roofline_scanned_reference": rl_scanned.to_dict(),
        "n_params": hlo_analysis.param_count(specs[0]),
        "n_params_active": hlo_analysis.active_param_count(cfg, specs[0]),
    }
    del compiled

    # 2) costing (unrolled) programs: faithful per-device FLOPs / bytes /
    #    collective schedule for the roofline table (single-pod only; the
    #    roofline table is single-pod per the brief).
    #
    #    Every stack is cycle-homogeneous (same block pattern each cycle),
    #    so counts are affine in the cycle count R: total(R) = outside +
    #    R * per_cycle. We compile two small *unrolled* probes (R=1, R=2)
    #    and extrapolate to the full R — exact for homogeneous stacks and
    #    two orders of magnitude cheaper to compile than the full unroll
    #    (validated against a full 16-cycle unroll in tests/test_dryrun).
    if not multi_pod:
        t0 = time.time()
        R = cfg.n_cycles
        if R <= 2:
            _, compiled_c, _, _ = _lower_compile(cfg.for_costing())
            counts = hlo_analysis.raw_counts(compiled_c)
        else:
            _, comp1, _, _ = _lower_compile(_probe_cfg(cfg, 1))
            _, comp2, _, _ = _lower_compile(_probe_cfg(cfg, 2))
            c1 = hlo_analysis.raw_counts(comp1)
            c2 = hlo_analysis.raw_counts(comp2)
            counts = hlo_analysis.extrapolate_counts(c1, c2, R)
        supp = hlo_analysis.recurrence_supplement(cfg, shape)
        rl = hlo_analysis.roofline_from_counts(
            counts,
            n_chips=n_chips,
            model_flops_global=mf,
            extra_flops_per_dev=supp["flops"] / n_chips,
            extra_hbm_per_dev=supp["hbm_bytes"] / n_chips,
        )
        out["roofline"] = rl.to_dict()
        out["costing_compile_s"] = round(time.time() - t0, 2)
        out["recurrence_supplement_global"] = supp
    return out


def _probe_cfg(cfg, k: int):
    """Unrolled costing probe with k cycles (tail preserved)."""
    n_layers = k * len(cfg.cycle) + len(cfg.tail)
    return dataclasses.replace(cfg.for_costing(), n_layers=n_layers)


def _out_path(outdir: str, arch: str, shape: str, multi_pod: bool,
              strategy: str = "greedy", param_dtype: str = "f32",
              microbatches: int = 1) -> str:
    mesh = "2x16x16" if multi_pod else "16x16"
    tag = "" if strategy == "greedy" else f"__{strategy}"
    if param_dtype != "f32":
        tag += f"__{param_dtype}"
    if microbatches != 1:
        tag += f"__mb{microbatches}"
    return os.path.join(outdir, f"{arch}__{shape}__{mesh}{tag}.json")


def _drive_subprocesses(combos, args) -> None:
    """One subprocess per combo: isolates compiler memory and enforces a
    wall-clock limit (a hung compile records an error entry instead of
    starving the rest of the table)."""
    import subprocess
    import sys

    for arch, shape, mp in combos:
        path = _out_path(args.out, arch, shape, mp, args.strategy,
                         args.param_dtype)
        if os.path.exists(path) and not args.force:
            print(f"skip (exists): {path}", flush=True)
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape,
               "--strategy", args.strategy, "--param-dtype", args.param_dtype,
               "--out", args.out]
        if mp:
            cmd.append("--multi-pod")
        if args.force:
            cmd.append("--force")
        print(f"== [driver] {arch} x {shape} {'2x16x16' if mp else '16x16'} ==",
              flush=True)
        try:
            r = subprocess.run(cmd, timeout=args.timeout,
                               capture_output=True, text=True)
            tail = (r.stdout or "").strip().splitlines()
            print("   " + (tail[-1] if tail else f"rc={r.returncode}"), flush=True)
            if r.returncode != 0 and not os.path.exists(path):
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape,
                               "mesh": "2x16x16" if mp else "16x16",
                               "status": "error",
                               "error": (r.stderr or "")[-2000:]}, f, indent=2)
        except subprocess.TimeoutExpired:
            with open(path, "w") as f:
                json.dump({"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error",
                           "error": f"compile timeout > {args.timeout}s "
                                    "(XLA-CPU pathological case; see "
                                    "EXPERIMENTS.md §Dry-run notes)"},
                          f, indent=2)
        print("", flush=True, end="")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="every combo, both meshes")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--strategy", default="greedy",
                    choices=["greedy", "megatron"],
                    help="param sharding strategy (megatron = §Perf variant)")
    ap.add_argument("--param-dtype", default="f32", choices=["f32", "bf16"],
                    help="parameter storage dtype (bf16 = §Perf variant)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation slices (§Perf variant)")
    ap.add_argument("--timeout", type=int, default=2400,
                    help="per-combo wall-clock limit under --all (seconds)")
    ap.add_argument("--no-subprocess", action="store_true",
                    help="run --all combos in-process (no isolation)")
    ap.add_argument("--force", action="store_true", help="recompute existing")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    if args.all:
        # canonical hyphenated arch ids (cfg.name), single-pod first.
        # zamba2 (heaviest XLA-CPU compile: SSD chunk einsums) goes last
        # so one slow arch never starves the table.
        arch_ids = [c.name for c in all_configs().values()]
        arch_ids.sort(key=lambda a: a == "zamba2-7b")
        combos = [
            (a, s, mp)
            for mp in (False, True)
            for a in arch_ids
            for s in SHAPES
        ]
        if not args.no_subprocess:
            _drive_subprocesses(combos, args)
            return
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape, args.multi_pod)]

    for arch, shape, mp in combos:
        path = _out_path(args.out, arch, shape, mp, args.strategy,
                         args.param_dtype, args.microbatches)
        if os.path.exists(path) and not args.force:
            print(f"skip (exists): {path}")
            continue
        print(f"== dry-run {arch} x {shape} on {'2x16x16' if mp else '16x16'} ==",
              flush=True)
        try:
            result = run_one(arch, shape, multi_pod=mp, strategy=args.strategy,
                             param_dtype=args.param_dtype,
                             microbatches=args.microbatches)
        except Exception as e:  # a failure here is a bug in our sharding
            result = {
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if mp else "16x16",
                "status": "error", "error": repr(e),
                "traceback": traceback.format_exc()[-4000:],
            }
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
        status = result["status"]
        extra = ""
        if status == "ok":
            r = result.get("roofline")
            if r:
                extra = (f" dominant={r['dominant']} compute={r['compute_s']:.2e}s "
                         f"memory={r['memory_s']:.2e}s coll={r['collective_s']:.2e}s "
                         f"compile={result['compile_s']:.0f}s")
            else:
                extra = f" compile={result['compile_s']:.0f}s (sharding proof)"
        print(f"   -> {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
