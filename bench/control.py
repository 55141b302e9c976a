#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for sound runs and the controls,
and judge each by the harness's own comparison (``runner.judge``).

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51

For each seed, in one process, two whole runs of the cell as
``bench/run.py`` makes them (use the benchmark's ``run_seconds``):

  sound   the program as the configuration states it.  Its sample of
          finished requests is also read by the control below.
  w4      the reference with its projections at int4, the precision
          below the configuration's int8, in the program's place: at
          the same positions, the gap of the token it puts first.
  kv8     the program's own int8 K/V path (``kv_quant="int8"``), the
          precision below the configuration's bf16 K/V, served and
          checked as a run is.

One JSON line per seed with each reading, its ``compared`` numbers and
``correct``, then a summary: the largest sound reading, each control's
smallest, and whether every sound run was correct and every control not.
The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def read(workload, seeds, seconds, *, root=None, bench_dir=None, **kw):
    """Yields one dict of readings per seed."""
    from harness import runner, serve, spec

    cell = spec.load_cell(workload, root or spec.ROOT, bench_dir)
    kv8 = runner.with_serving(cell, kv_quant="int8")
    for seed in seeds:
        t0 = serve.clock()
        res = runner.run_cell(cell, seed, seconds, False, t0,
                              controls=("w4",), **kw)
        gap = res["compared"]["max_logit_gap"]["value"]
        prog8 = runner.run_cell(kv8, seed, seconds, False, serve.clock(),
                                **kw)
        yield {"seed": seed,
               "sound": {"value": gap, "correct": res["correct"],
                         "compared": res["compared"]},
               "w4": res["controls"]["w4"],
               "kv8": {"value":
                       prog8["compared"]["max_logit_gap"]["value"],
                       "correct": prog8["correct"],
                       "compared": prog8["compared"]},
               "seconds": serve.clock() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for row in read(args.workload, seeds, args.seconds):
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"seeds": len(rows),
               "sound_max": max(r["sound"]["value"] for r in rows),
               "w4_min": min(r["w4"]["value"] for r in rows),
               "kv8_gap_range": [min(r["kv8"]["value"] for r in rows),
                                 max(r["kv8"]["value"] for r in rows)],
               "sound_all_correct": all(r["sound"]["correct"]
                                        for r in rows),
               "controls_all_not_correct": not any(
                   r[c]["correct"] for r in rows for c in ("w4", "kv8"))}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
