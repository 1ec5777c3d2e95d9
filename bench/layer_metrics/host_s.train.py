"""Median host seconds of a steady training step outside the wait on its
loss, nearly all of it before the device has the step's work: the
``legio.train.step`` span less its ``legio.train.sync``, over the window's
steps that ``step_s.train`` takes (matched by the span's ``step``). The
rest is the gradient norm's fetch after the sync."""
import statistics

from bench import program_spans


def read(run):
    got = program_spans.recorded(run)
    if got is None:
        return None
    event = {s["step"] for s in run["event"]}
    steady = {s["step"] for s in run["steps"]
              if not s["compiled"] and s["step"] not in event}
    host = [r.seconds - sum(s.seconds for s in got.below(r, "legio.train.sync"))
            for r in got.starting("legio.train.step")
            if r.attrs.get("step") in steady]
    return statistics.median(host) if host else None
