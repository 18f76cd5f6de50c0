"""Reduction of a profiler trace to device time.

``read(path)`` reads the ``.xplane.pb`` the JAX profiler writes and keeps
three lists, all on the trace's clock:

- ``ops``: every operation on a TPU device's ``XLA Ops`` line,
  ``{"device", "name", "kernel", "start_ns", "dur_ns"}``. On the TPU
  an operation's name is its HLO instruction text, so ``kernel`` (the
  instruction's name for a Pallas custom call, e.g. ``dequant_matmul``)
  and the operand shapes are read from it;
- ``modules``: the executables (``XLA Modules`` line), ``{"device",
  "name", "start_ns", "dur_ns"}``, named ``jit_<function>(<hash>)``;
- ``host``: the benchmark's own host spans (``bench:*`` annotations).

The rest of the benchmark reads only these lists, so a recorded trace
(``tests/data``) exercises the same arithmetic as a live one.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench:"
CONTAINERS = ("while", "conditional", "call")
_NAME = re.compile(r"%([A-Za-z_][\w\-]*?)(?:\.\d+)* = ")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)\[([\d,]*)\]")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
               "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def read(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1].split()[0])
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for e in line.events:
                    rec = {"device": dev, "name": e.name,
                           "start_ns": float(e.start_ns), "dur_ns": float(e.duration_ns)}
                    if line.name == OPS_LINE:
                        rec["kernel"] = kernel_of(e.name)
                        ops.append(rec)
                    else:
                        modules.append(rec)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append({"name": e.name[len(HOST_PREFIX):],
                                     "start_ns": float(e.start_ns),
                                     "dur_ns": float(e.duration_ns)})
    for lst in (ops, modules, host):
        lst.sort(key=lambda e: (e.get("device", 0), e["start_ns"]))
    return {"ops": ops, "modules": modules, "host": host}


def find_xplane(log_dir: str) -> str:
    hits = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True)
    if len(hits) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {hits}")
    return hits[0]


def op_kind(name: str) -> str:
    """The instruction's name without its number: ``%copy.12 = ...`` ->
    ``copy``."""
    m = _NAME.match(name)
    return m.group(1) if m else name.split(" ", 1)[0]


def kernel_of(name: str) -> str:
    """The Pallas kernel a custom call runs ('' for XLA's own ops)."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return ""
    return op_kind(name)


def shapes(name: str) -> list[tuple[str, tuple[int, ...]]]:
    """``(dtype, dims)`` of the result and then each operand, in the
    order the instruction text gives them (layouts ignored)."""
    head = name.split("custom_call_target", 1)[0]
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(head)]


def nbytes(shape: tuple[str, tuple[int, ...]]) -> int:
    n = 1
    for d in shape[1]:
        n *= d
    return n * DTYPE_BYTES[shape[0]]


def window_ns(trace: dict) -> tuple[float, float]:
    """The traced stretch on the trace's clock: from the first to the last
    recorded device operation or benchmark host span."""
    evs = trace["ops"] + trace["host"]
    if not evs:
        return 0.0, 0.0
    return (min(e["start_ns"] for e in evs),
            max(e["start_ns"] + e["dur_ns"] for e in evs))


def _devices(evs) -> int:
    return len({e["device"] for e in evs}) or 1


def busy_s(ops: list[dict]) -> float:
    """Seconds in which some operation ran, averaged over the devices:
    the length of the union of each device's operation intervals."""
    by_dev = defaultdict(list)
    for e in ops:
        by_dev[e["device"]].append((e["start_ns"], e["start_ns"] + e["dur_ns"]))
    total = 0.0
    for iv in by_dev.values():
        iv.sort()
        cur_s, cur_e = iv[0]
        for s, e in iv[1:]:
            if s > cur_e:
                total += cur_e - cur_s
                cur_s, cur_e = s, e
            else:
                cur_e = max(cur_e, e)
        total += cur_e - cur_s
    return total / _devices(ops) / 1e9 if by_dev else 0.0


def kernel_calls(ops: list[dict], kernel: str) -> list[dict]:
    return [e for e in ops if e["kernel"] == kernel]


def module_s(modules: list[dict], function: str) -> float:
    """Device seconds in the executables of one jitted function."""
    sel = [e for e in modules if e["name"].startswith(f"jit_{function}(")]
    return sum(e["dur_ns"] for e in sel) / 1e9 / _devices(modules)


def top_ops(ops: list[dict], n: int = 10) -> list[list]:
    """The ``n`` operation kinds (Pallas kernels by name) that took most
    device time, loop containers left out: ``[[name, seconds], ...]``."""
    tot = defaultdict(float)
    for e in ops:
        k = e["kernel"] or op_kind(e["name"])
        if k in CONTAINERS:
            continue
        tot[k] += e["dur_ns"] / 1e9
    d = _devices(ops)
    return [[k, v / d] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of the window with no operation on the
    first device, each named by the innermost benchmark host span open at
    its middle: ``[[label, seconds], ...]``."""
    w0, w1 = window_ns(trace)
    ops = trace["ops"]
    if not ops:
        return [["window", (w1 - w0) / 1e9]]
    d0 = min(e["device"] for e in ops)
    iv = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops if e["device"] == d0)
    gaps, t = [], w0
    for s, e in iv:
        if s > t:
            gaps.append((t, min(s, w1)))
        t = max(t, e)
        if t >= w1:
            break
    if w1 > t:
        gaps.append((t, w1))
    spans = [h for h in trace["host"] if h["name"] != "window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid, label, best = (s + e) / 2, "window", None
        for h in spans:
            if h["start_ns"] <= mid < h["start_ns"] + h["dur_ns"]:
                if best is None or h["start_ns"] > best:
                    label, best = h["name"], h["start_ns"]
        out.append([label, (e - s) / 1e9])
    return out


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
