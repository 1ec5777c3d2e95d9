#!/usr/bin/env python3
"""Find the highest request rate a serve cell's system sustains.

    python bench/sweep.py --workload <serve cell> --seed <n> --seconds <s> \
        --rates 8,10,12

Builds the cell's server once, with no node lost, and offers each rate in
turn for ``--seconds`` on the open-loop schedule, draining between rates.
Prints one JSON line per rate: requests offered and served in the window,
the backlog left at its end, and the latency quantiles. A rate is sustained
while the backlog stays within one round's worth of requests. The cell's
traffic file records the rate found and the rate it runs at.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    import numpy as np
    from bench import arrivals, harness as h, run
    from bench.loops import serve
    cell = h.find_cell(ROOT, args.workload)
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, fault=dict(cell.traffic["fault"], round=10 ** 9)))
    run.use_compile_cache()
    ctx = h.Ctx(cell=cell, seed=args.seed, seconds=args.seconds, trace=False,
                t_process=T_PROCESS, devices=h.chips(cell.chips),
                meter=h.CompileMeter())
    server, _ = serve.build(ctx)
    for rate in [float(r) for r in args.rates.split(",")]:
        times = arrivals.schedule(args.seed, rate, args.seconds,
                                  cell.traffic["arrival_block"])
        loop = serve.Loop(server.engine, times)
        loop.run_until(args.seconds)
        due = list(loop.due)
        served = sum(1 for r in due if loop.done.get(r, np.inf) <= loop.now())
        backlog = server.engine.pending
        loop.drain(due, 60.0)
        lat = [loop.done.get(r, loop.now()) - loop.due[r] for r in due]
        print(json.dumps({
            "rate_per_s": rate, "offered": len(due), "served_in_window": served,
            "backlog_at_end": backlog,
            "served_per_s": served / args.seconds,
            "p50_s": float(np.percentile(lat, 50)),
            "p95_s": float(np.percentile(lat, 95)),
            "rounds": len(loop.rounds),
            "median_round_s": float(np.median([r["seconds"]
                                               for r in loop.rounds]))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
