"""Spans of the program's layers, recorded only under a profiler session.

``span(name, **attrs)`` marks one piece of work (DESIGN.md §16).  While no
``jax.profiler`` session is collecting it costs one
``TraceAnnotation.is_enabled()`` check and returns a shared do-nothing
context.  While a session collects it does two things:

- opens a ``jax.profiler.TraceAnnotation``, so the span lands in the
  profiler's own trace, on the device trace's clock;
- appends a :class:`Span` record to a bounded in-memory buffer, read with
  :func:`recorded` (``time.perf_counter_ns`` clock).

A record names its parent, the span open on the same thread when it began
(``-1`` at the root), and its request, the index of that thread's root
span: the spans of one call share it.  The buffer keeps the newest
:data:`MAX_RECORDS` records and counts the ones it dropped.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: records the buffer keeps; older ones are dropped and counted
MAX_RECORDS = 2 ** 20


class Span(NamedTuple):
    """One finished span: times from ``time.perf_counter_ns``."""

    name: str
    start_ns: int
    end_ns: int
    index: int       # this span's number, unique in the process
    parent: int      # index of the enclosing span on its thread, -1 at root
    request: int     # index of the root span on its thread
    attrs: dict


class Recorder:
    """A bounded, thread-safe buffer of finished spans."""

    def __init__(self, max_records: int = MAX_RECORDS):
        self._lock = threading.Lock()
        self._records = collections.deque(maxlen=max_records)
        self._dropped = 0

    def append(self, record: Span) -> None:
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self._dropped += 1
            self._records.append(record)

    def recorded(self) -> tuple:
        with self._lock:
            return list(self._records), self._dropped

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self._dropped = 0


_RECORDER = Recorder()
_INDEX = itertools.count()
_OPEN = threading.local()      # .stack: this thread's open spans


class _NullSpan:
    """What ``span`` returns while no profiler session collects."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return None

    def set(self, **attrs) -> None:
        pass


NULL = _NullSpan()


class _OpenSpan:
    __slots__ = ("name", "attrs", "index", "parent", "request", "_me",
                 "_start")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        self.index = next(_INDEX)
        if stack:
            self.parent, self.request = stack[-1].index, stack[-1].request
        else:
            self.parent, self.request = -1, self.index
        stack.append(self)
        self._me = TraceAnnotation(self.name, **self.attrs)
        self._me.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only once the work is under way."""
        self.attrs.update(attrs)
        self._me.set_metadata(**attrs)

    def __exit__(self, exc_type, exc, tb):
        end = time.perf_counter_ns()
        self._me.__exit__(exc_type, exc, tb)
        _OPEN.stack.pop()
        _RECORDER.append(Span(self.name, self._start, end, self.index,
                              self.parent, self.request, self.attrs))
        return None


def span(name: str, **attrs):
    """Context manager marking one piece of work as ``name``.  The value
    of the ``with`` takes late attributes through ``.set(**attrs)``."""
    if not TraceAnnotation.is_enabled():
        return NULL
    return _OpenSpan(name, attrs)


def recorded() -> tuple:
    """``(records, dropped)``: the buffered :class:`Span` records, oldest
    first, and how many the bound dropped since the last :func:`clear`."""
    return _RECORDER.recorded()


def clear() -> None:
    """Empty the buffer and its dropped count."""
    _RECORDER.clear()
