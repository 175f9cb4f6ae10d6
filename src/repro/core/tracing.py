"""Spans: the program's one way to time a stretch of its own work.

    from repro.core import tracing

    with tracing.span("s4.prefill", tokens=n) as sp:
        ...
    sp.elapsed                   # wall seconds, always measured
    sp.end                       # time.perf_counter() at exit

Every span measures its wall time on ``time.perf_counter``; callers read
``elapsed`` (``LatencyBreakdown.wall_s``, ``BatchJob.retrieval_wall``).
Beyond that a span costs one flag check and one profiler check, unless:

* a ``jax.profiler`` trace is being collected: the span then enters
  ``jax.profiler.TraceAnnotation`` with its bare name, so it lands in the
  trace's host plane on the profiler's clock, beside the device programs
  it launched;
* ``enable()`` has been called: the span then appends a :class:`Record`
  (name, start and end on ``perf_counter``, the index of the enclosing
  recorded span, the request it served and small integer attributes) to
  an in-memory list that ``records()`` returns.  ``disable()`` stops
  recording, ``reset()`` empties the list.

Request ids: a span opened with ``batch=`` or ``query=`` sets that part of
the request id ``(batch, query)`` for itself and every span inside it;
``RAGEngine.answer_batch`` opens ``rag.answer_batch`` with ``batch=`` and
the generator's per-query spans sit under one opened with ``query=``.

Spans assume one serving thread: the enclosing span is the last one opened
and not yet closed.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

_profiling = TraceAnnotation.is_enabled


class Record:
    """One finished (or still open: ``end`` None) recorded span."""

    __slots__ = ("name", "start", "end", "parent", "request", "attrs")

    def __init__(self, name: str, start: float, parent: Optional[int],
                 request: Tuple[Optional[int], Optional[int]],
                 attrs: Dict[str, int]):
        self.name, self.start, self.end = name, start, None
        self.parent, self.request, self.attrs = parent, request, attrs

    @property
    def elapsed(self) -> float:
        return self.end - self.start


_records: List[Record] = []
_open: List[Tuple[int, Record]] = []     # the open recorded spans
_enabled = False


def enable():
    """Keep a :class:`Record` of every span from now on."""
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def records() -> List[Record]:
    """The spans recorded since the last ``reset()``, in the order they
    were opened (a record's ``parent`` indexes this list)."""
    return list(_records)


def reset():
    """Forget every record (spans still open keep timing, unrecorded)."""
    _records.clear()
    _open.clear()


class span:
    """Context manager: time the block; annotate it in a running profiler
    trace; record it after ``enable()``.  ``attrs`` are small integers
    (rows, tokens, step); ``batch`` and ``query`` set the request id."""

    __slots__ = ("name", "attrs", "start", "end", "_ann", "_rec")

    def __init__(self, name: str, **attrs: int):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "span":
        self._rec = self._ann = None
        if _enabled:
            parent, request = None, (None, None)
            if _open:
                parent, request = _open[-1][0], _open[-1][1].request
            attrs = dict(self.attrs)
            request = (attrs.pop("batch", request[0]),
                       attrs.pop("query", request[1]))
            self._rec = Record(self.name, 0.0, parent, request, attrs)
            _open.append((len(_records), self._rec))
            _records.append(self._rec)
        if _profiling():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.start = time.perf_counter()
        if self._rec is not None:
            self._rec.start = self.start
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._rec is not None:
            self._rec.end = self.end
            if _open and _open[-1][1] is self._rec:
                _open.pop()

    @property
    def elapsed(self) -> float:
        return self.end - self.start

    def note(self, **attrs: int) -> None:
        """Add attributes known only inside the span (recorded spans)."""
        if self._rec is not None:
            self._rec.attrs.update(attrs)
