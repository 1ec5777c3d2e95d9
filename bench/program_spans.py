"""What the readers of the program's own spans share.

The program records each ``legio.*`` span in ``repro.spans``: a bounded
buffer of records on the ``perf_counter`` clock, each with the id of the
span around it and its attributes. A reader takes the spans that start in
the measured window, ``[t_process + setup_s, that + window_s]``, the window
the end-to-end metrics are taken over, and walks down from them. Where the
program has no such module, or its buffer dropped a record that may have
started in the window, there is nothing to read and the reader returns
``None``.
"""
from __future__ import annotations

from collections import defaultdict


class Spans:
    """The program's span records, with the window's bounds in ns."""

    def __init__(self, records, t0_ns: float, t1_ns: float):
        self.records, self.t0, self.t1 = records, t0_ns, t1_ns
        self.kids = defaultdict(list)
        for r in records:
            self.kids[r.parent].append(r)

    def starting(self, name: str) -> list:
        """The spans named ``name`` (or under the prefix ``name`` if it ends
        in a dot) that start inside the window."""
        prefix = name.endswith(".")
        return [r for r in self.records
                if (r.name.startswith(name) if prefix else r.name == name)
                and self.t0 <= r.start_ns <= self.t1]

    def below(self, rec, name: str) -> list:
        """The outermost spans named ``name`` inside ``rec``."""
        out, todo = [], list(self.kids[rec.id])
        while todo:
            r = todo.pop()
            if r.name == name:
                out.append(r)
            else:
                todo += self.kids[r.id]
        return out

    def own_seconds(self, rec) -> float:
        """``rec``'s seconds less its direct children's."""
        return rec.seconds - sum(c.seconds for c in self.kids[rec.id])


def recorded(run) -> Spans | None:
    try:
        from repro import spans
    except ImportError:
        return None
    ctx = run["ctx"]
    t0 = ctx.t_process + ctx.setup_s
    records = spans.records()
    # a record dropped from the full buffer closed no later than the oldest
    # kept one; if that one closed inside the window, the dropped one may
    # have started there
    if spans.dropped() and (not records or records[0].end_ns >= t0 * 1e9):
        return None
    return Spans(records, t0 * 1e9, (t0 + run["window_s"]) * 1e9)
