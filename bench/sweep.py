#!/usr/bin/env python3
"""Find an open-loop mix's knee on the chip, once, when a cell is defined.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 45 \
        --rates 1.0,1.5,2.0

One process: the seed's weights and a warm-up, then for each rate a fresh
scheduler offered the cell's mix at that rate, pre-roll included, for
``--seconds``.  Per rate it prints the tokens per second completed, the
time to first token (median over the first and the second half of the
window's arrivals, and the 95th percentile), the queue and the live
batch when the window opened and when it closed, the mean live batch
over the decode ticks of each half of the window, and the requests due
that were still waiting for their first token at the close.  The knee is
the highest rate at which the window is in steady state: the queue does
not grow through it, the live batch of its second half is within a
tenth of the slots of its first half's, and the second half's median
wait is at most a tenth longer than the first's.  The cell's ``rate_per_s`` is then set to about
four fifths of it, and both numbers go into the mix's file.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from harness import runner, serve, spec, stats, traffic

    cell = spec.load_cell(args.workload)
    cfg, shapes, _ = runner.prepare(cell)
    warm = False
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        sched = runner.scheduler(cell, cfg, shapes, args.seed)
        if not warm:
            serve.warm_up(sched, mix, cell.config["serving"], shapes.vocab,
                          args.seed)
            warm = True
        reqs = traffic.generate(mix, args.seconds, args.seed, shapes.vocab)
        at_open = {}

        def opened():
            at_open.update(queue=len(sched.queue), live=sched.n_active)

        # the trace hook, called once the window opens, notes the state
        w = serve.drive(sched, reqs, mix, args.seconds, trace_from=0.0,
                        start_trace=opened, drain_s=0.0)
        at_close = {"queue": len(sched.queue), "live": sched.n_active}
        del sched
        gc.collect()
        run = stats.Run(dataclasses.replace(cell, traffic=mix), shapes,
                        None, args.seconds, 0.0, w)
        due = sorted(stats.due_in_window(run), key=lambda r: r.due)
        half = max(1, len(due) // 2)
        mid = (w.start + w.end) / 2

        def live(lo, hi):
            x = [t.live for t in w.ticks if lo <= t.start < hi]
            return float(np.mean(x)) if x else None

        def ttft(rs):
            t = [r.first - r.due for r in rs if r.first is not None]
            return float(np.median(t)) * 1e3 if t else None

        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "tokens_per_s": stats.tokens_in_window(run) / args.seconds,
            "ttft_p50_first_half_ms": ttft(due[:half]),
            "ttft_p50_second_half_ms": ttft(due[half:]),
            "ttft_p95_ms": (stats.pct(stats.ttfts(run), 95) or 0) * 1e3,
            "itl_p95_ms": (stats.pct(stats.gaps_in_window(run), 95)
                           or 0) * 1e3,
            "at_open": at_open, "at_close": at_close,
            "live_mean_halves": [live(w.start, mid), live(mid, w.end)],
            "due_without_first_token": sum(r.first is None for r in due)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
