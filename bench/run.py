#!/usr/bin/env python3
"""Legio benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, refuses to run without the TPU chips it
asks for, builds the system under test from the seed, warms it up (set-up),
measures for ``--seconds``, checks what the timed path produced against the
plain reference, and prints one JSON line last on standard output. With
``--trace 1`` a short steady stretch after the window is profiled and the
line carries the cell's per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv: list[str] | None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    JAX_COMPILATION_CACHE_DIR places it; every program is kept, so a second
    run of a cell compiles nothing."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def main(argv: list[str] | None = None, *, need_chip: bool = True,
         cell=None) -> int:
    """``need_chip=False`` and a ``cell`` are for the benchmark's own tests,
    which drive a run on the CPU at a small size."""
    args = parse(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness as h
    try:
        cell = cell or h.find_cell(ROOT, args.workload)
        import jax
        use_compile_cache()
        devices = (h.chips(cell.chips) if need_chip
                   else jax.devices()[:cell.chips])
        if need_chip:
            h.peaks(devices[0].device_kind)
        loop = h.load_module(h.BENCH / "loops" / f"{cell.traffic['loop']}.py",
                             "bench_loop")
        ctx = h.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_process=T_PROCESS,
                    devices=devices, meter=h.CompileMeter())
        outcome = loop.run(ctx)
    except h.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"bench: set-up {ctx.setup_s:.3f} s, whole run "
          f"{time.perf_counter() - T_PROCESS:.3f} s", file=sys.stderr)
    h.print_result(ctx, outcome)
    return 0


if __name__ == "__main__":
    sys.exit(main())
