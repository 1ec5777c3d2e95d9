"""Median host seconds of the serving engine's own work in a round:
scheduling, admission and the gather, i.e. the ``legio.serve.round`` span
less the ``legio.serve.work`` spans inside it, over the window's rounds
that ``round_s.serve`` takes (those that admitted work)."""
import statistics

from bench import program_spans


def read(run):
    got = program_spans.recorded(run)
    if got is None:
        return None
    busy = [r for r in got.starting("legio.serve.round")
            if r.attrs.get("dispatched", 0) > 0]
    # the rounds round_s.serve takes, or nothing
    if not busy or len(busy) != sum(1 for r in run["rounds"]
                                    if r["dispatched"]):
        return None
    return statistics.median(
        r.seconds - sum(w.seconds for w in got.below(r, "legio.serve.work"))
        for r in busy)
