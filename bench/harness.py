"""What every loop of the benchmark shares: the cell's files, the seed, the
chip, the compile meter, the clock and the result line.

A cell is found by its name in ``BENCHMARK.json``: its configuration is
``bench/configs/<config>.json``, its traffic ``bench/traffic/<traffic>.json``
(whose ``loop`` names ``bench/loops/<loop>.py``), and each per-layer metric
``bench/layer_metrics/<metric>.py``. Nothing here names a cell.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent


class BenchError(RuntimeError):
    """The run cannot be made or measured; no result is printed."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import a file found by name (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def model(self) -> dict:
        """The model as run, in the program's names."""
        return self.config["program"]


def find_cell(root: Path, name: str) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(name=name,
                config=load_json(BENCH / "configs" / f"{w['config']}.json"),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                chips=int(w["chips"]),
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)])


# -- seeds -------------------------------------------------------------------

def seed_key(seed: int, salt: int = 0):
    """A PRNG key from a seed of up to 64 bits (PRNGKey alone keeps 32)."""
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, salt)


def small_seed(seed: int, salt: int) -> int:
    """A 31-bit seed for what takes a small one (the program's data)."""
    import numpy as np
    return int(np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32, salt])
               .generate_state(1)[0] & 0x7FFFFFFF)


# -- the chip ----------------------------------------------------------------

def chips(need: int):
    """The accelerator's devices; refuses a CPU or too few chips."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < need:
        raise BenchError(
            f"needs {need} TPU chip(s); JAX found platform={d.platform} "
            f"device_kind={d.device_kind} count={len(devs)}")
    return devs[:need]


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device_kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileMeter:
    """Sums JAX's compile events: tracing, lowering, and the backend compile
    or persistent-cache fetch. ``mark()`` returns a reading to diff with."""

    SECONDS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event in self.SECONDS:
            self.seconds += duration
        if event == self.BACKEND:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.hits

    def since(self, mark: tuple[float, int, int]) -> dict:
        s, c, h = mark
        return {"compile_s": self.seconds - s, "compiles": self.compiles - c,
                "cache_hits": self.hits - h}


# -- one run -----------------------------------------------------------------

@dataclass
class Compared:
    """A number checked against its limit; ``ok`` if value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a loop hands back: end-to-end numbers, the record the per-layer
    readers read, and the comparison that decides ``correct``."""
    end_to_end: dict[str, float]
    record: dict[str, Any]
    compared: list[Compared]
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: dict | None = None


@dataclass
class Ctx:
    """A run's parameters and services, handed to the loop."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float
    devices: list = field(default_factory=list)
    meter: CompileMeter | None = None
    setup_s: float | None = None

    def setup_done(self) -> None:
        """The set-up ends here: the next thing is the first measured step."""
        self.setup_s = time.perf_counter() - self.t_process

    @contextlib.contextmanager
    def traced(self, kernels: dict[str, list[str]]):
        """Profile the block when the run is traced; the reduced trace is put
        in the yielded dict under "trace"."""
        box: dict = {}
        if not self.trace or self.devices[0].platform != "tpu":
            yield box     # no device trace off the chip (the CPU tests)
            return
        import jax
        from bench import trace as tr
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            t0 = time.perf_counter()
            try:
                yield box
            finally:
                t1 = time.perf_counter()
                jax.profiler.stop_trace()
            box["trace"] = tr.reduce(tr.load(d), kernels=kernels,
                                     n_devices=len(self.devices),
                                     host_window_s=t1 - t0)


def free() -> None:
    """Collect the program's state, once the caller has dropped it, before
    the reference runs."""
    gc.collect()


def print_result(ctx: Ctx, outcome: Outcome) -> dict:
    """The run's last standard-output line; the compared numbers also end
    standard error."""
    d = ctx.devices[0]
    cell = ctx.cell
    if ctx.trace:
        record = dict(outcome.record, trace=outcome.trace,
                      end_to_end=outcome.end_to_end, cell=cell, ctx=ctx)
        metrics = {}
        for m in cell.per_layer:
            mod = load_module(BENCH / "layer_metrics" / f"{m['name']}.py",
                              f"bench_layer_metric_{len(metrics)}")
            v = mod.read(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(outcome.end_to_end, setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    correct = all(c.ok for c in outcome.compared) and outcome.failed == 0
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result: dict = {"correct": correct, "attempted": outcome.attempted,
                    "failed": outcome.failed, "metrics": metrics,
                    "device": device}
    if ctx.trace and outcome.trace:
        device["busy_s"] = outcome.trace["busy_s"]
        device["window_s"] = outcome.trace["window_s"]
        result["breakdown"] = outcome.trace["breakdown"]
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in outcome.compared}
    for c in outcome.compared:
        print(f"compared {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(f"correct = {correct} (failed {outcome.failed} of "
          f"{outcome.attempted})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result
