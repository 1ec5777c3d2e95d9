"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a small
form. On a TPU the trace has one plane per chip (``/device:TPU:<n>``) with a
line of program executions (``XLA Modules``, which never overlap) and a line
of operations (``XLA Ops``, nested: a loop op spans its body's ops; a Pallas
kernel shows as ``%<kernel>.<n>``). Host planes carry the benchmark's own
spans (``bench.*`` annotations) on the same clock.

The small form keeps, per chip, the program executions as intervals and the
operations summed by name; and the benchmark's host spans. ``reduce`` turns
it into busy time (the union of the executions), kernel time by name,
collective time, the operations that took most time, and the longest idle
gaps by the host span they fall in.
"""
from __future__ import annotations

import glob
import gzip
import json
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_SPAN = "bench."
CONTAINERS = ("%while", "%conditional", "%call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def op_name(full: str) -> str:
    """``%fusion.7 = bf16[8,64]{...} fusion(...)`` -> ``%fusion.7 bf16[8,64]``"""
    name, _, rest = full.partition(" = ")
    return f"{name} {rest.split('{')[0][:48]}".strip()


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    devices, host = {}, []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith(DEVICE_PLANE) and \
                plane.name[len(DEVICE_PLANE):].isdigit():
            mods, ops = [], defaultdict(lambda: [0.0, 0])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods = [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                            for e in line.events]
                elif line.name == OPS_LINE:
                    for e in line.events:
                        o = ops[op_name(e.name)]
                        o[0] += e.duration_ns / 1e9
                        o[1] += 1
            devices[plane.name] = {"modules": mods, "ops": dict(ops)}
        else:
            host += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_SPAN)]
    return {"devices": devices, "host": host}


def read_saved(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: list) -> list[list[float]]:
    out: list[list[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def span_at(host: list, t: float) -> str:
    """The innermost benchmark span covering time ``t``."""
    best = None
    for name, s, e in host:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside bench spans"


def reduce(events: dict, *, kernels: dict[str, list[str]], n_devices: int,
           host_window_s: float) -> dict:
    """``kernels`` maps a kernel's name to the prefixes of its op names.
    Seconds throughout; busy, collective and top-op time are averaged over
    the chips, kernel time is summed over them."""
    planes = sorted(events["devices"])
    if len(planes) < n_devices:
        raise RuntimeError(f"trace holds {len(planes)} device planes, "
                           f"expected {n_devices}")
    busy = coll = 0.0
    by_op: dict[str, float] = defaultdict(float)
    found = {k: {"seconds": 0.0, "events": 0} for k in kernels}
    gaps: dict[str, float] = defaultdict(float)
    for i, plane in enumerate(planes[:n_devices]):
        dev = events["devices"][plane]
        spans = union(dev["modules"])
        busy += sum(e - s for s, e in spans) / 1e9 / n_devices
        for name, (sec, n) in dev["ops"].items():
            if any(c in name for c in COLLECTIVES):
                coll += sec / n_devices
            if not name.startswith(CONTAINERS):
                by_op[name] += sec / n_devices
            for k, prefixes in kernels.items():
                if name.startswith(tuple(prefixes)):
                    found[k]["seconds"] += sec
                    found[k]["events"] += n
        if i == 0:
            for (_, e0), (s1, _) in zip(spans, spans[1:]):
                gaps[span_at(events["host"], (e0 + s1) / 2)] += \
                    (s1 - e0) / 1e9
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy, "window_s": host_window_s,
            "collective_s": coll, "kernels": found,
            "breakdown": {"device_ops": [[n, s] for n, s in top],
                          "idle_gaps": [[n, s] for n, s in idle]}}
