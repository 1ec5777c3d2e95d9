#!/usr/bin/env python3
"""The control and the planted faults of a cell, read on the chip.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 10]

For each seed it prints one JSON line with the numbers the cell's check
compares, read against the float32 reference:

- train cells: the program's own readings (a short window); the reference
  computed with float8 products (the control); the reference on half of
  each step's shards (a step that leaves half the batch out and takes the
  mean over the rest). A step that leaves the state unchanged reads 1 on
  every leaf measure by construction and needs no run.
- serve cells: the program's served tokens (a short window at the cell's
  load); the tokens a float8 forward puts first (the control); and the
  served tokens with one token of each sampled request altered.

The limits in the configuration file are set between the program's
readings and these.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from bench import harness as h, run
    cell = h.find_cell(ROOT, args.workload)
    run.use_compile_cache()
    devices = h.chips(cell.chips)
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = h.Ctx(cell=cell, seed=seed, seconds=args.seconds, trace=False,
                    t_process=T_PROCESS, devices=devices,
                    meter=h.CompileMeter())
        line = {"seed": seed, **readings(ctx)}
        print(json.dumps(line), flush=True)
    return 0


def readings(ctx) -> dict:
    import numpy as np
    from bench.loops import common
    if ctx.cell.traffic["loop"] == "train":
        from bench.loops import train
        _, mine = train.measure(ctx)
        steps = train.plan(ctx)[0]
        ref = train.reference(ctx, steps)
        half = [s[:len(s) // 2] for s in steps]
        return {"program": train.gaps(mine, ref),
                "control_fp8": train.gaps(
                    train.reference(ctx, steps, "fp8"), ref),
                "half_batch": train.gaps(train.reference(ctx, half), ref),
                "state_unchanged": dict.fromkeys(
                    ("grad_gap", "change_gap", "fault_change_gap",
                     "fault_mu_gap", "fault_nu_gap"), 1.0)}
    from bench.loops import serve
    outcome, prompts, served = serve.measure(ctx)
    got = common.reference_gaps(ctx, prompts, served, controls=("fp8",))
    altered = served.copy()
    t = served.shape[1] // 2
    altered[:, t] = (altered[:, t] + 1) % ctx.cell.model["vocab_size"]
    got["token_altered"] = common.reference_gaps(ctx, prompts,
                                                 altered)["program"]
    got["failed"] = outcome.failed
    got["served_sample"] = int(np.prod(served.shape))
    return got


if __name__ == "__main__":
    sys.exit(main())
