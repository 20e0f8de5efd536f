"""From a profiler trace to device busy time, idle gaps and top device ops.

A trace is reduced to a list of events ``(plane, line, name, start_ns,
dur_ns)`` on one clock: the device's operations (plane ``/device:...``,
line ``XLA Ops``) and the benchmark's own host spans (names starting with
``bench.``, written with ``jax.profiler.TraceAnnotation``).  The span
``bench.window`` marks the measured window.

- busy: the union of device operation intervals inside the window,
  averaged over the devices that ran any;
- idle gaps: the rest of the window, each gap labelled by the innermost
  benchmark span open at its midpoint (``bench.window`` when no call was
  open: the loop between calls);
- top ops: device time summed by operation name.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

DEVICE_OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(trace_dir: str) -> list:
    """The device-op and benchmark-span events of the one ``.xplane.pb``
    under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    events = []
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != DEVICE_OPS_LINE:
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    events.append((plane.name, line.name, e.name,
                                   float(e.start_ns), float(e.duration_ns)))
    return events


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of ``[start, end)`` rows, as sorted disjoint rows."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    group = np.cumsum(new) - 1
    out_end = np.zeros(len(starts))
    np.maximum.at(out_end, group, ends)
    return np.stack([starts, out_end], axis=1)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def covered(intervals: np.ndarray, lo, hi) -> np.ndarray:
    """Length of ``[lo, hi)`` covered by disjoint sorted ``intervals``,
    for arrays of ``lo``/``hi``."""
    if len(intervals) == 0:
        return np.zeros(np.shape(lo))
    starts, ends = intervals[:, 0], intervals[:, 1]
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def upto(t):
        # busy time in (-inf, t)
        k = np.searchsorted(starts, t, side="right")
        partial = np.where(k > 0, np.minimum(t, ends[np.maximum(k - 1, 0)])
                           - starts[np.maximum(k - 1, 0)], 0.0)
        return cum[np.maximum(k - 1, 0)] + np.where(k > 0, partial, 0.0)

    return upto(np.asarray(hi, float)) - upto(np.asarray(lo, float))


class Reduced:
    """What a trace says about the measured window."""

    def __init__(self, events: list):
        spans = defaultdict(list)
        per_device = defaultdict(list)
        op_time = defaultdict(float)
        for plane, _line, name, start, dur in events:
            if plane.startswith("/device:"):
                per_device[plane].append((start, start + dur))
            elif name.startswith(SPAN_PREFIX):
                spans[name].append((start, start + dur))
        windows = spans.pop(WINDOW_SPAN, [])
        if len(windows) != 1:
            raise RuntimeError(f"the trace holds {len(windows)} "
                               f"{WINDOW_SPAN!r} spans, not one")
        self.lo, self.hi = windows[0]
        self.window_s = (self.hi - self.lo) * 1e-9
        self.spans = {k: np.array(v) for k, v in spans.items()}
        self.busy = {}
        for plane, iv in per_device.items():
            merged = clip(merge(np.array(iv)), self.lo, self.hi)
            if len(merged):
                self.busy[plane] = merged
        for plane, _line, name, start, dur in events:
            if plane in self.busy:
                end = min(start + dur, self.hi)
                op_time[name] += max(0.0, end - max(start, self.lo)) * 1e-9
        self.op_time = dict(op_time)

    @property
    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the devices."""
        if not self.busy:
            return 0.0
        return float(np.mean([(iv[:, 1] - iv[:, 0]).sum()
                              for iv in self.busy.values()])) * 1e-9

    def busy_in(self, span: str) -> np.ndarray:
        """Device busy seconds inside each ``span`` event, averaged over
        the devices."""
        iv = self.spans.get(span, np.zeros((0, 2)))
        if not self.busy or len(iv) == 0:
            return np.zeros(len(iv))
        return np.mean([covered(b, iv[:, 0], iv[:, 1])
                        for b in self.busy.values()], axis=0) * 1e-9

    def span_s(self, span: str) -> np.ndarray:
        """Durations (seconds) of the ``span`` events inside the window."""
        iv = self.spans.get(span, np.zeros((0, 2)))
        inside = (iv[:, 0] >= self.lo) & (iv[:, 1] <= self.hi)
        return (iv[inside, 1] - iv[inside, 0]) * 1e-9

    def idle_gaps(self) -> dict:
        """Idle seconds of the first device by the innermost span open at
        each gap's midpoint."""
        busy = next(iter(self.busy.values()), np.zeros((0, 2)))
        edges = np.concatenate([[self.lo], busy.ravel(), [self.hi]])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        mids = gaps.mean(axis=1)
        label = np.full(len(gaps), WINDOW_SPAN, dtype=object)
        opened = np.full(len(gaps), -np.inf)
        for name, iv in self.spans.items():
            # spans of one name run one after another: the last one to
            # start before a midpoint is the only one that can cover it
            iv = iv[np.argsort(iv[:, 0], kind="stable")]
            k = np.searchsorted(iv[:, 0], mids, side="right") - 1
            s, e = iv[np.maximum(k, 0), 0], iv[np.maximum(k, 0), 1]
            hit = (k >= 0) & (mids < e) & (s > opened)
            label[hit] = name
            opened[hit] = s[hit]
        out = defaultdict(float)
        for name, (s, e) in zip(label, gaps):
            out[name] += (e - s) * 1e-9
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        """``breakdown`` of the result line: the device ops that took most
        time and the idle time by what the host was doing."""
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
        # an op's trace name is its whole HLO instruction: keep the head
        return {"device_ops": [[n[:120], s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}
