"""A kernel's share of its roofline in the traced stretch: the least time
the chip could take for the calls the stretch made (the larger of their
operations over the bf16 peak and their bytes over the HBM bandwidth)
over the summed device time of the kernel's events in the trace."""
from __future__ import annotations

from bench import harness


def share(run: dict, kernel: str, cost) -> float | None:
    """``cost(batch) -> (flops, bytes)`` for one prefill's calls of the
    kernel at micro-batch ``batch``."""
    t = run["trace"]
    if not t or not t["kernels"].get(kernel, {}).get("seconds"):
        return None
    pk = harness.peaks(run["ctx"].devices[0].device_kind)
    flops = nbytes = 0.0
    for b in run["traced_batches"]:
        f, n = cost(b)
        flops, nbytes = flops + f, nbytes + n
    least = max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / t["kernels"][kernel]["seconds"]
