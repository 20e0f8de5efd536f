"""CPU tests of the readers of the program's spans (``program_spans.py``
and the ``program_span`` metrics): each gives its hand-computed value on a
hand-built window, and nothing where there is no device busy time, where
the recorder dropped records, where the program keeps no spans, or in the
other loop."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import program_spans  # noqa: E402
from repro.core.spans import Span  # noqa: E402

BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
# the readers of the program's own span records
READERS = [m["name"] for m in BENCH["per_layer"]
           if "import program_spans" in
           (HERE / "metrics" / f"{m['name']}.py").read_text()]

T0 = 1000.0                     # the window's start, perf_counter seconds
LO = 5e9                        # bench.window's start on the trace clock
WINDOW_NS = 100_000


def _span(name, start, end, attrs=None):
    """A record ``start``..``end`` ns into the window."""
    base = int(T0 * 1e9)
    return Span(name, base + start, base + end, 0, -1, 0, attrs or {})


# two replay calls: execute 10-30 us (dispatch 20-28) and 50-65 us
# (dispatch 55-62); the device is busy 25-45 us and 60-90 us.  Idle
# inside execute: 15 + 10 us; outside every span: 10 + 5 + 10 us.
REPLAY = [
    _span("spgemm.execute", -5_000, -1_000),        # before the window
    _span("spgemm.execute", 10_000, 30_000),
    _span("spgemm.dispatch", 20_000, 28_000),
    _span("spgemm.execute", 50_000, 65_000),
    _span("spgemm.dispatch", 55_000, 62_000),
    _span("other.span", 0, 100_000),                # not the program's
]
REPLAY_BUSY = [[25_000, 45_000], [60_000, 90_000]]

# two misses
CHURN = [
    _span("spgemm.plan", 0, 4_000),
    *[_span("spgemm.fingerprint", 500 * i, 500 * i + 500) for i in range(4)],
    _span("spgemm.symbolic", 4_000, 7_000),
    _span("spgemm.device_lift", 7_000, 8_000),
    _span("spgemm.first_call", 8_000, 18_000),
    _span("spgemm.plan", 40_000, 46_000),
    *[_span("spgemm.fingerprint", 40_000 + 500 * i, 40_500 + 500 * i)
      for i in range(4)],
    _span("spgemm.symbolic", 46_000, 49_000),
    _span("spgemm.device_lift", 49_000, 51_000),
    _span("spgemm.first_call", 51_000, 61_000),
    _span("spgemm.plan", WINDOW_NS + 10, WINDOW_NS + 20),   # after it
]
CHURN_BUSY = [[18_000, 19_000], [61_000, 62_000]]

WANT = {
    "execute_ms.replay": ("replay", 0.0175),
    "dispatch_ms.replay": ("replay", 0.0075),
    "idle_in_execute_ms.replay": ("replay", 0.0125),
    "idle_outside_program_ms.replay": ("replay", 0.0125),
    "plan_ms.churn": ("churn", 0.005),
    "fingerprint_ms.churn": ("churn", 0.002),
    "symbolic_ms.churn": ("churn", 0.003),
    "device_lift_ms.churn": ("churn", 0.0015),
    "first_call_ms.churn": ("churn", 0.01),
}


def _ctx(loop, busy):
    busy = {"/device:TPU:0": LO + np.array(busy, float)} if busy else {}
    busy_ns = sum(float((b[:, 1] - b[:, 0]).sum()) for b in busy.values())
    trace = SimpleNamespace(busy=busy, busy_s=busy_ns * 1e-9, lo=LO,
                            hi=LO + WINDOW_NS, window_s=WINDOW_NS * 1e-9)
    window = SimpleNamespace(loop=loop, window_start=T0,
                             window_s=WINDOW_NS * 1e-9,
                             latencies=np.zeros(2))
    return SimpleNamespace(window=window, trace=trace)


def _read(name, ctx, records, monkeypatch, dropped=0):
    monkeypatch.setattr(program_spans, "recorded",
                        lambda: None if records is None
                        else (records, dropped))
    return harness.load_module(HERE / "metrics" / f"{name}.py").read(ctx)


def _cell(loop):
    return (REPLAY, REPLAY_BUSY) if loop == "replay" else (CHURN, CHURN_BUSY)


def test_every_program_span_metric_is_tested():
    assert sorted(READERS) == sorted(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_hand_computed_value(name, monkeypatch):
    loop, want = WANT[name]
    records, busy = _cell(loop)
    got = _read(name, _ctx(loop, busy), records, monkeypatch)
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("case", ["no_busy", "dropped", "no_spans",
                                  "other_loop"])
def test_reader_gives_nothing_where_there_is_nothing_to_read(
        name, case, monkeypatch):
    loop, _ = WANT[name]
    records, busy = _cell(loop)
    dropped = 0
    if case == "no_busy":
        busy = []
    elif case == "dropped":
        dropped = 1
    elif case == "no_spans":
        records = None
    else:
        loop = "churn" if loop == "replay" else "replay"
    ctx = _ctx(loop, busy)
    assert _read(name, ctx, records, monkeypatch, dropped) is None


def test_the_replay_split_adds_up_to_the_window_idle(monkeypatch):
    ctx = _ctx("replay", REPLAY_BUSY)
    parts = [_read(n, ctx, REPLAY, monkeypatch)
             for n in ("idle_in_execute_ms.replay",
                       "idle_outside_program_ms.replay")]
    idle_ms = (ctx.trace.window_s - ctx.trace.busy_s) * 1e3 / 2
    assert sum(parts) == pytest.approx(idle_ms, rel=1e-9)
