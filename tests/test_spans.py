"""The span recorder (``core.spans``, DESIGN.md §16): nothing recorded
without a profiler session; under one, a plan miss and two replays record
the layer spans with their parents and request ids, in memory and in the
profiler's own trace; the bound counts drops; threads keep their own
parents."""

import glob
import os
import threading

import jax
import numpy as np
import pytest

from repro import runtime
from repro.core import api, cached_plan, spans
from repro.sparse.generate import random_powerlaw_csc


class Session:
    """A profiler session into ``dir``; ``stop`` ends it once."""

    def __init__(self, dir):
        self.dir = dir
        self.on = True
        jax.profiler.start_trace(str(dir))

    def stop(self):
        if self.on:
            self.on = False
            jax.profiler.stop_trace()


@pytest.fixture
def session(tmp_path):
    """A profiler session around the test, the recorder emptied first."""
    spans.clear()
    s = Session(tmp_path)
    try:
        yield s
    finally:
        s.stop()


def _replay_twice(n=48, seed=3):
    api.plan_cache_clear()
    a = random_powerlaw_csc(n, 2.5, seed=seed)
    plan = cached_plan(a, a, backend="jax")
    for _ in range(2):
        jax.block_until_ready(plan.execute(a, a).values)
    return a


def test_nothing_is_recorded_without_a_profiler_session():
    spans.clear()
    assert spans.span("spgemm.plan", hit=1) is spans.NULL
    _replay_twice()
    assert spans.recorded() == ([], 0)


@pytest.mark.parametrize("limits, table", [
    (None, "one"), (runtime.PrefetchLimits(0, 0, 0), "two")])
def test_calls_carry_the_value_table_form(session, monkeypatch, limits,
                                          table):
    """``spgemm.first_call`` and ``spgemm.dispatch`` say which value-table
    form ran and how many bytes of values the executable read: one table
    packs each operand's first nnz, two tables read both arrays whole."""
    monkeypatch.setattr(runtime, "prefetch_limits", lambda: limits)
    api.plan_cache_clear()
    a = random_powerlaw_csc(48, 2.5, seed=3)
    plan = cached_plan(a, a, backend="jax")
    values = np.arange(a.nnz + 9, dtype=np.float32)     # oversized
    for _ in range(2):
        jax.block_until_ready(plan.execute(values, values).values)
    calls = [r for r in spans.recorded()[0]
             if r.name in ("spgemm.first_call", "spgemm.dispatch")]
    want = 4 * (2 * a.nnz if table == "one" else 2 * values.size)
    assert [(r.name, r.attrs) for r in calls] == [
        ("spgemm.first_call", {"table": table, "table_bytes": want}),
        ("spgemm.dispatch", {"table": table, "table_bytes": want})]


def test_execute_after_warm_plan_is_a_dispatch(session):
    """A plan warmed by ``warm_plan`` (the builder's warm step) has the
    executable ``plan.execute`` dispatches: its first execute records
    ``spgemm.dispatch``, not ``spgemm.first_call``."""
    from repro.core import warm_plan

    api.plan_cache_clear()
    a = random_powerlaw_csc(48, 2.5, seed=3)
    plan = cached_plan(a, a, backend="jax")
    warm_plan(plan)
    spans.clear()
    jax.block_until_ready(plan.execute(a, a).values)
    assert [r.name for r in spans.recorded()[0]
            if r.name in ("spgemm.first_call", "spgemm.dispatch")] == [
        "spgemm.dispatch"]


def _names(records, parent):
    return [r.name for r in records if r.parent == parent]


def test_a_miss_then_replays_record_the_layer_spans(session):
    a = _replay_twice()
    cached_plan(a, a, backend="jax")                 # a hit
    records, dropped = spans.recorded()
    assert dropped == 0
    roots = sorted((r for r in records if r.parent == -1),
                   key=lambda r: r.start_ns)
    assert [(r.name, r.attrs) for r in roots] == [
        ("spgemm.plan", {"hit": 0}), ("spgemm.execute", {}),
        ("spgemm.execute", {}), ("spgemm.plan", {"hit": 1})]
    for root in roots:
        assert root.request == root.index
        kids = [r for r in records if r.request == root.index
                and r is not root]
        assert all(r.parent == root.index for r in kids)
        assert all(root.start_ns <= r.start_ns <= r.end_ns <= root.end_ns
                   for r in kids)
    miss, first, replay, hit = (_names(records, r.index) for r in roots)
    assert miss == ["spgemm.fingerprint"] * 4
    assert first == ["spgemm.symbolic", "spgemm.device_lift",
                     "spgemm.first_call"]
    assert replay == ["spgemm.dispatch"]
    assert hit == ["spgemm.fingerprint"] * 2
    by_name = {r.name: r for r in records}
    assert by_name["spgemm.fingerprint"].attrs == {"nnz": a.nnz}
    plan = cached_plan(a, a, backend="jax")
    assert by_name["spgemm.symbolic"].attrs == {
        "products": plan.stream.n_products}
    assert by_name["spgemm.device_lift"].attrs == {
        "bytes": plan.device_stream_nbytes}
    session.stop()
    (path,) = glob.glob(os.path.join(session.dir, "**", "*.xplane.pb"),
                        recursive=True)
    traced = {e.name for plane in
              jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("spgemm.")}
    assert traced == {r.name for r in records}


@pytest.mark.parametrize("compacted_on", ["device", "host"])
def test_pallas_execute_records_its_four_spans(session, compacted_on,
                                               monkeypatch):
    """A replay on the per-group kernels: the values' upload and padding
    (on the device: once a call; on the host: A's, then each launch's B),
    one span per launch with its attributes, the readback and the
    compaction (on the device: one gather, C's values read back once; on
    the host: each tile read back and compacted), all inside
    ``spgemm.execute``; the launches' products add up to the multiply's."""
    from repro.sparse.stats import ops_per_column

    if compacted_on == "host":                      # tiles over the limit
        monkeypatch.setattr(runtime, "device_tile_bytes", lambda: 1)
    api.plan_cache_clear()
    a = random_powerlaw_csc(96, 3.0, seed=4)
    plan = cached_plan(a, a, "h-spa-40/40", backend="pallas")
    assert (plan.pallas.device is not None) == (compacted_on == "device")
    spans.clear()
    stats = {}
    plan.execute(a.values, a.values, stats=stats)
    assert stats["compacted_on"] == compacted_on
    records, dropped = spans.recorded()
    assert dropped == 0
    (root,) = [r for r in records if r.parent == -1]
    assert root.name == "spgemm.execute"
    assert all(r.parent == root.index for r in records if r is not root)
    lay = plan.pallas
    n = len(lay.groups)
    names = _names(records, root.index)
    if compacted_on == "device":
        assert names == ["spgemm.pallas.operands"] + [
            "spgemm.pallas.group"] * n + [
            "spgemm.pallas.compact", "spgemm.pallas.readback",
            "spgemm.pallas.compact"]
    else:
        assert names == ["spgemm.pallas.operands"] + [
            "spgemm.pallas.operands", "spgemm.pallas.group",
            "spgemm.pallas.readback", "spgemm.pallas.compact"] * n + [
            "spgemm.pallas.compact"]
    groups = [r for r in records if r.name == "spgemm.pallas.group"]
    assert [r.attrs for r in groups] == [
        {"kind": g.kind, "cols": g.n_real, "zb": int(g.b_rows.shape[1]),
         "za": int(lay.a_rows.shape[1]), "products": g.products,
         "planes": 5}      # m = 96: one plane of row codes, four of values
        for g in lay.groups]
    assert {g.kind for g in lay.groups} == {"spa", "spars"}
    assert sum(r.attrs["products"] for r in groups) == int(
        ops_per_column(a, a).sum())
    api.plan_cache_clear()


def test_the_bound_keeps_the_newest_records_and_counts_drops(
        session, monkeypatch):
    monkeypatch.setattr(spans, "_RECORDER", spans.Recorder(3))
    for i in range(5):
        with spans.span("t.span", i=i):
            pass
    records, dropped = spans.recorded()
    assert [r.attrs["i"] for r in records] == [2, 3, 4] and dropped == 2
    spans.clear()
    assert spans.recorded() == ([], 0)


def test_spans_of_two_threads_do_not_cross_parent(session):
    both = threading.Barrier(2, timeout=30)

    def work(i):
        with spans.span("t.root", thread=i):
            both.wait()
            with spans.span("t.child", thread=i):
                both.wait()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    records, _ = spans.recorded()
    root = {r.attrs["thread"]: r for r in records if r.name == "t.root"}
    child = {r.attrs["thread"]: r for r in records if r.name == "t.child"}
    assert sorted(root) == sorted(child) == [0, 1]
    for i in (0, 1):
        assert root[i].parent == -1 and root[i].request == root[i].index
        assert child[i].parent == child[i].request == root[i].index
