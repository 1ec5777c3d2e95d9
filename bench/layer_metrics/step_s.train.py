"""Median host-clock seconds of the window's steady training steps
(TrainerReport.step_seconds; a step that compiled or repaired is not
steady)."""
import statistics


def read(run):
    event = {s["step"] for s in run["event"]}
    steady = [s["seconds"] for s in run["steps"]
              if not s["compiled"] and s["step"] not in event]
    return statistics.median(steady) if steady else None
