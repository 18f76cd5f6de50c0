"""Put a cell's control in the program's place: one run as ``run.py``
makes it, judged on the token the fp8 control (``reference.forward(low=True)``)
puts first at each position of the same prompts and served tokens.

    python bench/control.py --workload <name> --seed <n> --seconds <s>

Prints the run's result line: ``correct`` is false when the check
separates the control, ``checks.logit_gap_<group>`` is the control's
reading and ``checks.program_logit_gap_<group>`` the program's, for each
group of stages served (``correct.py``). The limits in
``limits/<workload>.json`` are set from these readings (PERF.md gives
them); the benchmark's own runs never compute the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        sys.exit("control: no TPU")
    result = harness.run_cell(args.workload, args.seed, args.seconds, False, T_START,
                              control=True,
                              log=lambda m: print(m, file=sys.stderr, flush=True))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
