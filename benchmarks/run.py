"""Benchmark orchestrator: one module per paper table + the roofline
report. ``python -m benchmarks.run [--quick] [--only a,b] [--list]``.

Every bench writes its ``BENCH_*.json`` under ``artifacts/bench/``;
after a bench SUCCEEDS, the files it produced (new or updated) are
mirrored to the repo root so the latest numbers are diffable in review
without digging into (gitignored or CI-uploaded) artifact trees. A
failing bench mirrors nothing — the root copies never go stale from a
mid-run crash."""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import time
import traceback


def _bench_snapshot(src_dir: str = "artifacts/bench") -> dict[str, float]:
    """``{path: mtime}`` of the BENCH artifacts currently on disk."""
    return {p: os.path.getmtime(p)
            for p in glob.glob(os.path.join(src_dir, "BENCH_*.json"))}


def mirror_artifacts(src_dir: str = "artifacts/bench",
                     dst_dir: str = ".",
                     since: dict[str, float] | None = None) -> list[str]:
    """Copy ``BENCH_*.json`` from ``src_dir`` to ``dst_dir`` (repo root
    by default). With ``since`` (a :func:`_bench_snapshot`), only files
    created or modified after the snapshot are mirrored. Returns the
    mirrored paths."""
    out = []
    for path in sorted(glob.glob(os.path.join(src_dir, "BENCH_*.json"))):
        if since is not None and os.path.getmtime(path) <= since.get(
                path, -1.0):
            continue
        dst = os.path.join(dst_dir, os.path.basename(path))
        shutil.copyfile(path, dst)
        out.append(dst)
    return out


def _bench_modules() -> dict:
    from benchmarks import table1_execution_time, table2_accuracy, table3_ttfi
    from benchmarks import calibration, fault_tolerance, resident_serving
    from benchmarks import roofline, serving_throughput, speculative_decode
    from benchmarks import upgrade_latency

    return {
        "table1": table1_execution_time,
        "table2": table2_accuracy,
        "table3": table3_ttfi,
        "roofline": roofline,
        "upgrade_latency": upgrade_latency,
        "resident_serving": resident_serving,
        "serving_throughput": serving_throughput,
        "speculative_decode": speculative_decode,
        "calibration": calibration,
        "fault_tolerance": fault_tolerance,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced training steps / fewer archs")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (see --list)")
    ap.add_argument("--list", action="store_true",
                    help="print the available benchmark names and exit")
    args = ap.parse_args()

    benches = _bench_modules()
    if args.list:
        for name, mod in benches.items():
            doc = (mod.__doc__ or "").strip().splitlines()
            print(f"{name:20s} {doc[0] if doc else ''}")
        return
    selected = (args.only.split(",") if args.only else list(benches))
    unknown = [n for n in selected if n not in benches]
    if unknown:
        raise SystemExit(
            f"unknown benchmark name(s): {', '.join(unknown)} "
            f"(available: {', '.join(benches)})")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    os.makedirs("artifacts/bench", exist_ok=True)
    failures = []
    mirrored_all: list[str] = []
    for name in selected:
        mod = benches[name]
        t0 = time.time()
        print(f"\n########## {name} ##########")
        before = _bench_snapshot()
        try:
            mod.main(quick=args.quick)
        except Exception:
            failures.append(name)
            traceback.print_exc()
        else:
            # mirror only what this (successful) bench wrote
            mirrored_all += mirror_artifacts(since=before)
        print(f"[{name}: {time.time() - t0:.1f}s]")
    if mirrored_all:
        print(f"\nmirrored to repo root: {', '.join(sorted(set(mirrored_all)))}")
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
