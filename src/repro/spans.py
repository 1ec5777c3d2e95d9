"""Spans: the program's own timing, on the profiler's clock.

``span(name, **attrs)`` times a region of host code and does two things:

  * it opens a ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation``
    with ``step_trace=True``), so a profile taken with ``jax.profiler``
    shows the span on the host plane, on the same clock as the device's
    program executions;
  * on exit it appends a :class:`Record` to a bounded in-memory buffer:
    name, ``perf_counter_ns`` start and end, the id of the enclosing span
    and the attributes. ``records()`` returns the buffer.

Counts that make a span's time comparable (batch size, request ids, decode
steps, moved bytes, the step) ride as its attributes, so a ratio is read
from one record. The enclosing span is tracked per thread (and per asyncio
task). Recording is always on: when no profile is being taken a span costs
two clock stamps and one append, and makes no annotation.

Every span the program opens is named ``legio.<layer>.<what>``; the list,
with what reads each, is in docs/architecture.md ("Tracing").
"""
from __future__ import annotations

import contextvars
import itertools
import threading
import time
from collections import deque
from typing import Any, NamedTuple

import jax

MAXLEN = 1 << 16        # records kept; the oldest go first


class Record(NamedTuple):
    """One finished span. ``id`` grows in the order spans open; ``parent``
    is the id of the span open around this one, or None."""

    id: int
    parent: int | None
    name: str
    start_ns: int
    end_ns: int
    attrs: dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_buffer: deque[Record] = deque(maxlen=MAXLEN)
_dropped = 0
_lock = threading.Lock()
_ids = itertools.count()
_current: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "legio_span", default=None)
_Annotation = jax.profiler.TraceAnnotation
_StepAnnotation = jax.profiler.StepTraceAnnotation


def _shown(attrs: dict[str, Any]) -> dict[str, Any]:
    """Attributes as the profiler takes them: a sequence becomes one
    space-separated string (the annotation's own separator is a comma)."""
    return {k: " ".join(map(str, v)) if isinstance(v, (list, tuple)) else v
            for k, v in attrs.items()}


class Span:
    """The handle ``span`` yields: its ``id``, ``attrs`` and, after exit,
    ``seconds``."""

    __slots__ = ("name", "attrs", "step_trace", "id", "parent", "start_ns",
                 "end_ns", "_note", "_token")

    def __init__(self, name: str, step_trace: bool, attrs: dict[str, Any]):
        self.name, self.step_trace, self.attrs = name, step_trace, attrs
        self._note = None
        self.end_ns: int | None = None

    def __enter__(self) -> Span:
        self.id = next(_ids)
        self.parent = _current.get()
        self._token = _current.set(self.id)
        # an annotation made while no profile is taken records nothing,
        # even if a profile starts before it closes: make none then
        if _Annotation.is_enabled():
            shown = _shown(self.attrs)
            self._note = (
                _StepAnnotation(self.name, step_num=shown.pop("step"), **shown)
                if self.step_trace else _Annotation(self.name, **shown))
            self._note.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._note is not None:
            self._note.__exit__(*exc)
        _current.reset(self._token)
        record = Record(self.id, self.parent, self.name, self.start_ns,
                        self.end_ns, self.attrs)
        global _dropped
        with _lock:
            if len(_buffer) == MAXLEN:
                _dropped += 1
            _buffer.append(record)

    def set(self, **attrs: Any) -> None:
        """Add attributes known only once the span has run a while."""
        self.attrs.update(attrs)
        if self._note is not None:
            self._note.set_metadata(**_shown(attrs))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def span(name: str, *, step_trace: bool = False, **attrs: Any) -> Span:
    """A context manager that times its block as one span. With
    ``step_trace`` the annotation is a ``StepTraceAnnotation`` whose step
    number is the ``step`` attribute (the profiler's per-step view)."""
    return Span(name, step_trace, attrs)


def records() -> list[Record]:
    """The buffer, oldest first (in the order the spans closed)."""
    with _lock:
        return list(_buffer)


def dropped() -> int:
    """Records pushed out of the full buffer since the last ``clear``."""
    return _dropped


def clear() -> None:
    global _dropped
    with _lock:
        _buffer.clear()
        _dropped = 0
