"""Roofline-term extraction from a compiled dry-run artifact.

Sources (all per-device, because the compiled module is the SPMD
partition):

* ``compiled.cost_analysis()``  -> HLO FLOPs + HBM bytes accessed
* ``compiled.memory_analysis()``-> argument/temp/output bytes (fits-check)
* ``compiled.as_text()``        -> collective ops; we parse every
  all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute and convert result shapes to wire bytes per device
  using standard ring-algorithm costs.

Hardware constants are TPU v5e (mesh.py). The three roofline terms are
seconds-if-that-resource-were-the-only-bottleneck; the max identifies
the dominant term.
"""
from __future__ import annotations

import dataclasses
import re

from repro.launch import mesh as meshmod

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?((?:bf16|f16|f32|f64|s\d+|u\d+|pred|f8e4m3fn|f8e5m2|c64|c128)\[[^\]]*\])?"
    r"[^=]*?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s\d+|u\d+|pred|f8e4m3fn|f8e5m2|c64|c128)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            if d:
                n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _line_result_bytes(line: str) -> int:
    """Sum result-tuple bytes on an HLO instruction line (left of '=')."""
    lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1].split("(", 1)[0]
    total = 0
    for m in _SHAPE_RE.finditer(lhs):
        total += _shape_bytes(m.group(1), m.group(2))
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 1


@dataclasses.dataclass
class CollectiveStats:
    """Per-device wire bytes by collective kind (ring-algorithm model)."""

    counts: dict
    result_bytes: dict
    wire_bytes: dict

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict[str, int] = {}
    result_bytes: dict[str, float] = {}
    wire_bytes: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        if f"{kind}-done" in line:
            continue
        rb = _line_result_bytes(line)
        g = _group_size(line)
        if kind == "collective-permute":
            wire = float(rb)  # point-to-point; no replica groups
        elif g <= 1:
            wire = 0.0
        elif kind == "all-reduce":
            wire = 2.0 * rb * (g - 1) / g          # reduce-scatter + all-gather
        elif kind == "all-gather":
            wire = rb * (g - 1) / g                # result is the gathered buf
        elif kind == "reduce-scatter":
            wire = rb * (g - 1)                    # operand = result * g
        else:  # all-to-all
            wire = rb * (g - 1) / g
        counts[kind] = counts.get(kind, 0) + 1
        result_bytes[kind] = result_bytes.get(kind, 0.0) + rb
        wire_bytes[kind] = wire_bytes.get(kind, 0.0) + wire
    return CollectiveStats(counts=counts, result_bytes=result_bytes, wire_bytes=wire_bytes)


@dataclasses.dataclass
class Roofline:
    flops: float              # per device
    hbm_bytes: float          # per device
    wire_bytes: float         # per device
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    arg_bytes: int            # per device (params+inputs residency)
    temp_bytes: int
    fits: bool
    collective_detail: dict
    model_flops: float = 0.0  # 6*N*D useful flops, global
    useful_ratio: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def raw_counts(compiled) -> dict:
    """Additive per-device counters from one compiled module."""
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    stats = parse_collectives(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "hbm_bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_counts": dict(stats.counts),
        "coll_result_bytes": dict(stats.result_bytes),
        "coll_wire_bytes": dict(stats.wire_bytes),
        "arg_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }


def _affine(v1, v2, r):
    """outside + r*per_cycle given values at r=1 and r=2."""
    per = v2 - v1
    return v1 + (r - 1) * per


def extrapolate_counts(c1: dict, c2: dict, r: int) -> dict:
    """Counts for the R-cycle stack from the 1- and 2-cycle probes.

    Exact for cycle-homogeneous stacks: every additive counter is affine
    in the cycle count. Memory-analysis numbers are NOT extrapolated
    (residency is taken from the production scanned compile instead).
    """
    out = {"flops": _affine(c1["flops"], c2["flops"], r),
           "hbm_bytes": _affine(c1["hbm_bytes"], c2["hbm_bytes"], r)}
    for key in ("coll_counts", "coll_result_bytes", "coll_wire_bytes"):
        kinds = set(c1[key]) | set(c2[key])
        out[key] = {
            k: max(0.0, _affine(c1[key].get(k, 0.0), c2[key].get(k, 0.0), r))
            for k in kinds
        }
    for key in ("arg_bytes", "temp_bytes", "output_bytes", "alias_bytes"):
        out[key] = c2[key]  # probe-local; unused downstream
    return out


def roofline_from_counts(counts: dict, *, n_chips: int,
                         model_flops_global: float = 0.0,
                         ici_links: int = 4,
                         extra_flops_per_dev: float = 0.0,
                         extra_hbm_per_dev: float = 0.0,
                         memory_analysis=None) -> Roofline:
    # clamp: affine extrapolation of near-zero probe deltas can produce
    # tiny negatives for very small models
    flops = max(counts["flops"] + extra_flops_per_dev, 0.0)
    hbm = max(counts["hbm_bytes"] + extra_hbm_per_dev, 0.0)
    wire = max(sum(counts["coll_wire_bytes"].values()), 0.0)

    compute_s = flops / meshmod.PEAK_FLOPS_BF16
    memory_s = hbm / meshmod.HBM_BW
    collective_s = wire / (meshmod.ICI_BW * ici_links)
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)

    if memory_analysis is not None:
        arg_b = int(memory_analysis.argument_size_in_bytes)
        tmp_b = int(memory_analysis.temp_size_in_bytes)
        out_b = int(memory_analysis.output_size_in_bytes)
        alias_b = int(memory_analysis.alias_size_in_bytes)
    else:
        arg_b = counts.get("arg_bytes", 0)
        tmp_b = counts.get("temp_bytes", 0)
        out_b = counts.get("output_bytes", 0)
        alias_b = counts.get("alias_bytes", 0)
    fits = (arg_b + tmp_b + out_b - alias_b) < meshmod.HBM_PER_CHIP

    useful = (
        model_flops_global / (n_chips * flops)
        if flops > 0 and model_flops_global > 0
        else 0.0
    )
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        wire_bytes=wire,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        arg_bytes=arg_b,
        temp_bytes=tmp_b,
        fits=fits,
        collective_detail={
            "counts": counts["coll_counts"],
            "result_bytes": counts["coll_result_bytes"],
            "wire_bytes": counts["coll_wire_bytes"],
        },
        model_flops=model_flops_global,
        useful_ratio=useful,
    )


def roofline_from_compiled(compiled, *, n_chips: int, model_flops_global: float = 0.0,
                           ici_links: int = 4,
                           extra_flops_per_dev: float = 0.0,
                           extra_hbm_per_dev: float = 0.0) -> Roofline:
    return roofline_from_counts(
        raw_counts(compiled),
        n_chips=n_chips,
        model_flops_global=model_flops_global,
        ici_links=ici_links,
        extra_flops_per_dev=extra_flops_per_dev,
        extra_hbm_per_dev=extra_hbm_per_dev,
        memory_analysis=compiled.memory_analysis(),
    )


# -- per-token recurrence supplements ---------------------------------------------
#
# The costing variant unrolls every *chunked* scan, but per-token
# recurrences (xLSTM's mLSTM/sLSTM cells) cannot be unrolled at T up to
# 512k. Their loop bodies are counted once by cost_analysis; we add the
# missing (T - 1) trips analytically from the cell's arithmetic. Only
# xlstm-125m has such blocks.

def recurrence_supplement(cfg, shape) -> dict:
    """Global extra (flops, hbm_bytes) for per-token scan bodies."""
    kinds = list(cfg.cycle) * cfg.n_cycles + list(cfg.tail)
    n_mlstm = kinds.count("mlstm")
    n_slstm = kinds.count("slstm")
    if not (n_mlstm or n_slstm):
        return {"flops": 0.0, "hbm_bytes": 0.0}
    B = shape.global_batch
    T = shape.seq_len if shape.mode in ("train", "prefill") else 1
    extra_trips = max(T - 1, 0)
    bwd = 2.0 if shape.mode == "train" else 0.0  # bwd scan ~2x fwd cell cost

    d_in_m = int(cfg.lstm_proj_factor * cfg.d_model)
    hd_m = d_in_m // cfg.n_heads
    # mLSTM cell: C update (4 flops/elem) + h=Cq (2) => ~6*H*hd^2; carries
    # C read+write dominate bytes: 2*4B*H*hd^2
    ml_flops = 6.0 * B * cfg.n_heads * hd_m * hd_m
    ml_bytes = 8.0 * B * cfg.n_heads * hd_m * hd_m
    # sLSTM cell: block-diag recurrent matmul 8*d*hd + ~20*d elementwise
    hd_s = cfg.d_model // cfg.n_heads
    sl_flops = B * (8.0 * cfg.d_model * hd_s + 20.0 * cfg.d_model)
    sl_bytes = 16.0 * B * cfg.d_model
    f = extra_trips * (1.0 + bwd) * (n_mlstm * ml_flops + n_slstm * sl_flops)
    by = extra_trips * (1.0 + bwd) * (n_mlstm * ml_bytes + n_slstm * sl_bytes)
    return {"flops": f, "hbm_bytes": by}


# -- model FLOPs (the "useful work" numerator) -----------------------------------

def param_count(params_sds) -> int:
    import numpy as np
    import jax

    total = 0
    for leaf in jax.tree.leaves(params_sds):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
    return total


def active_param_count(cfg, params_sds) -> int:
    """MoE: count only top_k/E of each expert bank."""
    import jax

    total = 0
    leaves = jax.tree_util.tree_flatten_with_path(params_sds)[0]
    from repro.core.wire import path_str

    for path, leaf in leaves:
        n = 1
        for d in leaf.shape:
            n *= d
        p = path_str(path)
        if cfg.n_experts and re.search(r"we_(gate|up|down)", p):
            n = n * cfg.top_k // cfg.n_experts
        total += n
    return total


def model_flops(cfg, params_sds, shape, *, mode: str) -> float:
    """6*N_active*D for training; 2*N_active*D for a forward-only step
    (prefill processes D=B*S tokens; decode processes D=B tokens)."""
    n_active = active_param_count(cfg, params_sds)
    if mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # one new token per sequence
    return 2.0 * n_active * tokens
