"""Find a serving cell's knee: one set-up, then one window per offered rate.

    python bench/sweep.py --config olmo1b --traffic chat --rates 0.5 1 2 --seconds 30

Each window uses the mix at the given Poisson rate, with fresh
requests; between windows the engine drains. Prints one JSON line per
rate: requests due and finished, TTFT p50/p90, ITL p95, tokens/s and the
requests still waiting at the window's end. Not part of a benchmark run;
a cell's rate is fixed in its mix file from what this shows.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True, help="a name under configs in BENCHMARK.json")
    ap.add_argument("--traffic", required=True, help="a mix under bench/traffic/")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    import jax
    import numpy as np

    from bench import harness, traffic

    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: no TPU")
    confs = {c["name"]: c for c in harness.load_benchmark()["configs"]}
    cfg = json.loads((harness.REPO / confs[args.config]["file"]).read_text())
    mix = traffic.load_mix(args.traffic, harness.ROOT)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    cell = harness.setup_cell(cfg, mix, args.seed, log)
    base = 0
    for rate in args.rates:
        m = copy.deepcopy(mix)
        m["arrivals"]["rate_per_s"] = rate
        reqs = traffic.generate(m, args.seed, args.seconds, cfg["vocab"])
        win = harness.Window(cell.engine, cell.client, m, reqs, args.seconds, rid_base=base)
        rec = win.run()
        base += len(reqs)
        cell.engine.run()
        T = rec.window_s
        due = [r for r in rec.requests.values() if r["due"] <= T]
        ttft = [((r["tokens"][0] if r["tokens"] and r["tokens"][0] <= T else T) - r["due"])
                for r in due]
        gaps = [b - a for r in due for a, b in zip(r["tokens"], r["tokens"][1:]) if b <= T]
        toks = sum(1 for r in due for t in r["tokens"] if t <= T)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "finished": sum(1 for r in due if r["finished"] is not None and r["finished"] <= T),
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p90_s": float(np.percentile(ttft, 90)) if ttft else None,
            "itl_p95_s": float(np.percentile(gaps, 95)) if gaps else None,
            "tokens_per_s": toks / T,
            "waiting_at_end": sum(1 for r in due if not r["tokens"])}), flush=True)


if __name__ == "__main__":
    main()
