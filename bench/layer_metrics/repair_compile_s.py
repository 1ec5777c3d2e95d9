"""Seconds JAX spent tracing, lowering and compiling (or fetching from the
persistent cache) in the steps the node loss costs."""


def read(run):
    return sum(s["compile_s"] for s in run["event"])
