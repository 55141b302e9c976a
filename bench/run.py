#!/usr/bin/env python3
"""Run one cell of the serving benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout: the cells are ``BENCHMARK.json``'s
``workloads``, the system under test is ``src/repro``.  Exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell
needs.  See ``harness/runner.py`` for what one run does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import runner
    try:
        result = runner.run(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START)
    except runner.NoDevice as e:
        runner.log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        runner.log(f"compared {name} {c['value']} limit {c['limit']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
