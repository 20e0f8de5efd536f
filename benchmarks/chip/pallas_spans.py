"""The per-group Pallas lowering as the readers of its metrics see it.

The program's ``backend="pallas"`` path launches one SPA or SPARS kernel
per plan group and compacts the tiles, on the device where the plan
allows (one gather, C's values read back once) or tile by tile on the
host, each phase a span (``spgemm.pallas.{operands,group,readback,
compact}``).

- kernel time: the device time of each kind's ``pallas_call`` in the
  trace, found by the name of the jitted function that holds it
  (``spa_spgemm``, ``spars_spgemm``);
- the work of each kind: every call's least time (``ctx.least_time_s``:
  ``work.py``'s bytes and flops at ``peaks.json``'s peaks, with the
  reference's C), split between the kinds by the ``products`` attribute
  of the call's ``spgemm.pallas.group`` spans.  Those products must add
  up to ``work.py``'s product count of the multiply, an independent total,
  or the reader refuses the split.

Every reader returns ``None`` where :mod:`program_spans` finds nothing to
read (no device busy time, no spans, dropped records, another loop).
"""

from __future__ import annotations

import re
from collections import defaultdict

import program_spans

GROUP_SPAN = "spgemm.pallas.group"
#: each kind's device operation: the custom call is named after the jitted
#: function that holds the ``pallas_call``
KERNEL_OP = {"spa": re.compile(r"(^|[^A-Za-z])spa_spgemm"),
             "spars": re.compile(r"(^|[^A-Za-z])spars_spgemm")}


def kernel_s(trace, kind: str) -> float:
    """Device seconds of the ``kind`` kernel's launches in the window."""
    return sum(s for name, s in trace.op_time.items()
               if KERNEL_OP[kind].search(name))


def per_call_ms(ctx, name: str):
    """Span ``name`` summed per call of a traced replay window, or
    ``None``."""
    return program_spans.per_call_ms(ctx, "replay", name)


def group_spans(ctx):
    """The window's ``spgemm.pallas.group`` records, one list per call in
    call order, or ``None`` where there is nothing to read."""
    if program_spans.window_spans(ctx, "replay") is None:
        return None
    records, _ = program_spans.recorded()
    w = ctx.window
    lo = w.window_start * 1e9
    hi = lo + w.window_s * 1e9
    calls = defaultdict(list)
    for r in records:
        if r.name == GROUP_SPAN and r.start_ns >= lo and r.end_ns <= hi:
            calls[r.request].append(r)
    if not calls:
        return None
    return [calls[k] for k in sorted(calls,
                                     key=lambda k: calls[k][0].start_ns)]


def least_time_s(ctx, kind: str):
    """The summed least time of the products the ``kind`` launches of the
    window computed, or ``None`` where no such launch ran."""
    calls = group_spans(ctx)
    if calls is None or ctx.least_time_s is None:
        return None
    w = ctx.window
    if len(calls) != len(w.latencies):
        raise ValueError(f"{len(calls)} calls hold {GROUP_SPAN} spans, the "
                         f"window made {len(w.latencies)}")
    least, launches = 0.0, 0
    for i, spans in enumerate(calls):
        total = sum(int(s.attrs["products"]) for s in spans)
        if total != int(w.products[i]):
            raise ValueError(f"call {i}: the launches' products add up to "
                             f"{total}, the multiply has {w.products[i]}")
        mine = [s for s in spans if s.attrs["kind"] == kind]
        launches += len(mine)
        least += ctx.least_time_s[i] \
            * sum(int(s.attrs["products"]) for s in mine) / total
    return least if launches else None


def roofline_pct(ctx, kind: str):
    """The ``kind`` kernel's share of its roofline: the least time of its
    products over its device time, in percent, or ``None``."""
    least = least_time_s(ctx, kind)
    if least is None:
        return None
    t = kernel_s(ctx.trace, kind)
    return 100.0 * least / t if t > 0 else None
