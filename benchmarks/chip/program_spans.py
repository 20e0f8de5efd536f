"""The program's own spans, as the per-layer readers of ``program_span``
metrics see them.

The program records its layer spans (``repro.core.spans``) in memory on
the ``time.perf_counter_ns`` clock while a profiler session collects.  A
traced run keeps those inside the measured window and maps them onto the
device trace's clock with the window as the anchor: the loop's
``window_start`` (``perf_counter``) is the start of the ``bench.window``
span (``trace.lo``).

Every reader returns ``None`` where:

- the trace holds no device busy time (no trace, or the CPU);
- the program records no spans (a program without ``repro.core.spans``);
- the recorder dropped records, so the window's spans may be incomplete.
"""

from __future__ import annotations

from collections import defaultdict

import numpy as np

import trace_reduce

PREFIX = "spgemm."


def recorded():
    """The program's ``(records, dropped)``, or ``None`` where it keeps
    no spans."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.recorded()


class WindowSpans:
    """The program's ``spgemm.*`` spans inside the window, as
    ``[start, end)`` rows in nanoseconds on the trace's clock, by name."""

    def __init__(self, ctx, records):
        w, t = ctx.window, ctx.trace
        lo = w.window_start * 1e9
        hi = lo + w.window_s * 1e9
        rows = defaultdict(list)
        for r in records:
            if r.name.startswith(PREFIX) and r.start_ns >= lo \
                    and r.end_ns <= hi:
                rows[r.name].append((r.start_ns - lo + t.lo,
                                     r.end_ns - lo + t.lo))
        self.trace = t
        self.calls = len(w.latencies)
        self.rows = {k: np.array(v, float) for k, v in rows.items()}

    def get(self, name: str) -> np.ndarray:
        return self.rows.get(name, np.zeros((0, 2)))

    def per_call_ms(self, name: str) -> float:
        """Summed duration of ``name`` over the window's calls."""
        iv = self.get(name)
        return float((iv[:, 1] - iv[:, 0]).sum()) * 1e-6 / self.calls

    def idle_ns(self, iv: np.ndarray) -> float:
        """Device idle time inside the union of the rows ``iv``, averaged
        over the devices."""
        iv = trace_reduce.clip(trace_reduce.merge(iv), self.trace.lo,
                               self.trace.hi)
        if len(iv) == 0:
            return 0.0
        busy = np.mean([trace_reduce.covered(b, iv[:, 0], iv[:, 1]).sum()
                        for b in self.trace.busy.values()])
        return float((iv[:, 1] - iv[:, 0]).sum() - busy)

    def outside(self) -> np.ndarray:
        """The parts of the trace's window in which no program span is
        open, as disjoint rows."""
        spans = list(self.rows.values())
        inside = trace_reduce.clip(
            trace_reduce.merge(np.concatenate(spans) if spans
                               else np.zeros((0, 2))),
            self.trace.lo, self.trace.hi)
        edges = np.concatenate([[self.trace.lo], inside.ravel(),
                                [self.trace.hi]]).reshape(-1, 2)
        return edges[edges[:, 1] > edges[:, 0]]


def window_spans(ctx, loop: str):
    """:class:`WindowSpans` of a traced run of ``loop``, or ``None`` where
    there is nothing to read (module docstring)."""
    t = ctx.trace
    if ctx.window.loop != loop or t is None or t.busy_s <= 0:
        return None
    got = recorded()
    if got is None:
        return None
    records, dropped = got
    if dropped:
        return None
    return WindowSpans(ctx, records)


def per_call_ms(ctx, loop: str, name: str):
    """Summed duration of span ``name`` per call of a ``loop`` window, or
    ``None``; ``None`` too where the window holds no such span."""
    s = window_spans(ctx, loop)
    if s is None or len(s.get(name)) == 0:
        return None
    return s.per_call_ms(name)
