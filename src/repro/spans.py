"""Host spans and counters of the program, off unless switched on.

``span(name)`` brackets a stretch of host work and ``count(name, n)`` adds
to a counter.  Off, the default, ``span`` returns one shared no-op context
after a single flag check (no clock read, no allocation, no ``jax`` import)
and ``count`` does nothing.  ``enable(annotate)`` switches both on: a span
then reads ``time.perf_counter()`` at entry and exit and records
``(name, start, end, parent, query)``, where ``parent`` names the span that
was open when it began and ``query`` is the number of the :class:`Recorder`
open around it.  With ``annotate`` a span is also a
``jax.profiler.TraceAnnotation``, so it lands on the profiler's host plane
on the clock of the device's ops.

A :class:`Recorder` is opened around one query.  It gives, per span name,
the calls, the inclusive seconds and the *self* seconds (inclusive minus the
time its child spans cover), and the counters, each counted afresh.

Spans never feed a ledger, a plan or an output: the simulator paths stay
replayable.  They must nest on one thread, so no span stays open across a
``yield``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional


class Span(NamedTuple):
    name: str
    start: float
    end: float
    parent: Optional[str]
    query: Optional[int]


@dataclasses.dataclass
class Totals:
    calls: int = 0
    inclusive_s: float = 0.0
    self_s: float = 0.0


class _State:
    """What an enabled tracer holds: the open spans, and the finished spans
    and counters of the open :class:`Recorder` (spans that end outside one
    are not kept)."""

    def __init__(self, annotation):
        self.annotation = annotation  # TraceAnnotation, or None
        self.stack: List[list] = []
        # Finished spans, each [name, start, end, parent record, query, child_s].
        self.records: Optional[List[list]] = None
        self.counts: Dict[str, int] = {}
        self.query: Optional[int] = None


_on = False
_state = _State(None)
_OFF = contextlib.nullcontext()


class _Open:
    __slots__ = ("state", "record", "note")

    def __init__(self, name: str):
        state = self.state = _state
        parent = state.stack[-1] if state.stack else None
        self.record = [name, 0.0, 0.0, parent, state.query, 0.0]
        self.note = None if state.annotation is None else state.annotation(name)

    def __enter__(self):
        self.state.stack.append(self.record)
        self.record[1] = time.perf_counter()
        if self.note is not None:
            self.note.__enter__()
        return self

    def __exit__(self, *exc):
        if self.note is not None:
            self.note.__exit__(*exc)
        record = self.record
        record[2] = time.perf_counter()
        state = self.state
        state.stack.pop()
        parent = record[3]
        if parent is not None:
            parent[5] += record[2] - record[1]
        if state.records is not None:
            state.records.append(record)
        return False


def span(name: str):
    """A context that records ``name`` while the tracer is on."""
    if not _on:
        return _OFF
    return _Open(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while the tracer is on."""
    if _on:
        counts = _state.counts
        counts[name] = counts.get(name, 0) + n


def enable(annotate: bool = False) -> None:
    """Switch the tracer on, from nothing recorded; with ``annotate`` every
    span is also a ``jax.profiler.TraceAnnotation``."""
    global _on, _state
    annotation = None
    if annotate:
        from jax.profiler import TraceAnnotation as annotation
    _state = _State(annotation)
    _on = True


def disable() -> None:
    """Switch the tracer off; spans open now still close cleanly."""
    global _on
    _on = False


def _public(record: list) -> Span:
    parent = record[3]
    return Span(record[0], record[1], record[2],
                None if parent is None else parent[0], record[4])


class Recorder:
    """One query's spans and counters.

    Inside it the tracer's records and counters start empty and carry the
    query's number; on exit ``totals`` holds the calls, inclusive and self
    seconds per span name, ``counts`` the counters and ``spans`` the spans
    that ended inside it, in the order they ended, and what was recorded
    before it is restored.  With the tracer off it records nothing.
    """

    def __init__(self, query: int):
        self.query = query
        self.spans: List[Span] = []
        self.totals: Dict[str, Totals] = {}
        self.counts: Dict[str, int] = {}
        self._saved = None

    def __enter__(self) -> "Recorder":
        state = _state
        self._saved = (state, state.records, state.counts, state.query)
        state.records, state.counts, state.query = [], {}, self.query
        return self

    def __exit__(self, *exc):
        state, records, counts, query = self._saved
        mine = state.records
        self.counts = dict(state.counts)
        state.records, state.counts, state.query = records, counts, query
        self.spans = [_public(r) for r in mine]
        for r in mine:
            t = self.totals.setdefault(r[0], Totals())
            t.calls += 1
            t.inclusive_s += r[2] - r[1]
            t.self_s += r[2] - r[1] - r[5]
        return False
