"""Host seconds the fault pipeline spends in the steps the node loss costs:
the ``legio.pipeline.*`` stage spans' own time (the data plane's nested
``legio.reshard`` left out), summed over the fault event's steps."""
from bench import program_spans


def read(run):
    got = program_spans.recorded(run)
    if got is None:
        return None
    event = {s["step"] for s in run["event"]}
    stages = [r for r in got.starting("legio.pipeline.")
              if r.attrs.get("step") in event]
    return sum(got.own_seconds(r) for r in stages) if stages else None
