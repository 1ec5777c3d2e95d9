"""Median host-clock seconds of the window's serving rounds that ran work
(RoundReport.wall_seconds; the round's work function ends on the host)."""
import statistics


def read(run):
    busy = [r["seconds"] for r in run["rounds"] if r["dispatched"]]
    return statistics.median(busy) if busy else None
