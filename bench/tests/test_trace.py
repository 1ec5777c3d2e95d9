"""The trace reduction against a trace recorded on a TPU v5 lite: one
micro-batch of 8 through mamba2-130m's serve work function (prefill with
the Pallas SSD kernel, then 128 decode steps), inside a ``bench.work``
span, kept as the gzipped JSON of ``trace.load``'s small form. Expected
numbers are worked out here independently, by rasterising the recorded
program executions on a 1 us grid."""
from pathlib import Path

import numpy as np

from bench import trace

FIXTURE = Path(__file__).parent / "data" / "trace_mamba2_serve_batch8.json.gz"


def raster_busy(modules, t0, t1, step_ns=1000):
    grid = np.zeros(int((t1 - t0) // step_ns) + 1, bool)
    for _, s, e in modules:
        grid[int((s - t0) // step_ns):int(np.ceil((e - t0) / step_ns))] = True
    return grid.sum() * step_ns / 1e9


def test_busy_time_matches_raster():
    ev = trace.read_saved(FIXTURE)
    (plane,) = ev["devices"]
    mods = ev["devices"][plane]["modules"]
    t0 = min(s for _, s, _ in mods)
    t1 = max(e for _, _, e in mods)
    host = ev["host"][0]
    r = trace.reduce(ev, kernels={}, n_devices=1,
                     host_window_s=(host[2] - host[1]) / 1e9)
    assert abs(r["busy_s"] - raster_busy(mods, t0, t1)) < 2e-3
    # the decode loop is dispatch bound: under a third of the span is busy
    assert 0.2 < r["busy_s"] < r["window_s"] / 3


def test_kernel_time_and_idle_gaps():
    ev = trace.read_saved(FIXTURE)
    host = ev["host"][0]
    window = (host[2] - host[1]) / 1e9
    r = trace.reduce(ev, kernels={"ssd_scan": ["%ssd_scan."]}, n_devices=1,
                     host_window_s=window)
    k = r["kernels"]["ssd_scan"]
    assert k["events"] == 24                 # one call per layer
    assert 0.010 < k["seconds"] < 0.020
    gaps = dict(r["breakdown"]["idle_gaps"])
    # every gap between executions lies inside the bench.work span
    assert gaps["bench.work"] > 0.9 * sum(gaps.values())
    assert abs(r["busy_s"] + sum(gaps.values()) - window) < 0.02
    assert len(r["breakdown"]["device_ops"]) == 10


def test_union_merges_overlaps():
    assert trace.union([["a", 0, 2], ["b", 1, 3], ["c", 5, 6]]) == \
        [[0, 3], [5, 6]]
