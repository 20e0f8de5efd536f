"""Plan-cache locking, single-flight builds, and the background builder.

The DESIGN.md §12 contracts: the plan LRU is safe under concurrent
readers/writers (no lost entries, no double-builds, consistent counters),
and ``PlanBuilder`` keeps plan construction off the calling thread — a
latency-critical tick gets a fallback plan immediately while the device
build lands in the background.
"""

import threading
import time

import numpy as np
import pytest

from repro.core import (
    PlanBuilder, api, cached_plan, plan_cache_clear, plan_cache_info,
    plan_cache_key, plan_cache_peek, spgemm, warm_plan,
)
from repro.sparse import random_density_csc
from repro.sparse.format import csc_to_dense


@pytest.fixture(autouse=True)
def fresh_cache():
    plan_cache_clear()
    yield
    plan_cache_clear()


def _mats(n_patterns, n=24, density=0.2):
    return [(random_density_csc(n, n, density, seed=2 * i),
             random_density_csc(n, n, density, seed=2 * i + 1))
            for i in range(n_patterns)]


@pytest.fixture
def counting_builds(monkeypatch):
    """Wrap the symbolic build so tests can count real plan constructions."""
    calls = []
    real = api.plan_spgemm

    def counting(*a, **kw):
        calls.append(1)
        time.sleep(0.002)  # widen the race window
        return real(*a, **kw)

    monkeypatch.setattr(api, "plan_spgemm", counting)
    return calls


# ---------------------------------------------------------------------------
# LRU locking + single-flight (the ISSUE's plan-cache race bugfix)
# ---------------------------------------------------------------------------


def test_concurrent_hammer_no_double_builds(counting_builds):
    """8 threads x 4 patterns: each pattern's plan is built exactly once,
    nothing is lost, and the hit/miss counters stay consistent."""
    mats = _mats(4)
    n_threads, reps = 8, 6
    plans: dict = {}
    errs = []
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        try:
            barrier.wait()
            for r in range(reps):
                for i, (a, b) in enumerate(mats):
                    p = cached_plan(a, b, "expand", backend="host")
                    prev = plans.setdefault(i, p)
                    assert p is prev  # everyone sees the one shared plan
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(counting_builds) == len(mats)  # no double-builds
    info = plan_cache_info()
    assert info["size"] == len(mats)  # no lost entries
    assert info["misses"] == len(mats)
    assert info["hits"] + info["misses"] == n_threads * reps * len(mats)
    assert info["in_flight"] == 0


def test_single_flight_failed_build_retries(monkeypatch):
    """A failed owner build wakes waiters; a later caller rebuilds."""
    a, b = _mats(1)[0]
    real = api.plan_spgemm
    boom = {"on": True}

    def flaky(*args, **kw):
        if boom["on"]:
            raise RuntimeError("injected build failure")
        return real(*args, **kw)

    monkeypatch.setattr(api, "plan_spgemm", flaky)
    with pytest.raises(RuntimeError, match="injected"):
        cached_plan(a, b, "expand", backend="host")
    assert plan_cache_info()["in_flight"] == 0  # no leaked build event
    boom["on"] = False
    plan = cached_plan(a, b, "expand", backend="host")
    assert plan is plan_cache_peek(
        plan_cache_key(a, b, "expand", backend="host"))


def test_peek_does_not_promote_or_count():
    a, b = _mats(1)[0]
    key = plan_cache_key(a, b, "expand", backend="host")
    assert plan_cache_peek(key) is None
    before = plan_cache_info()
    assert plan_cache_peek(key) is None
    after = plan_cache_info()
    assert (before["hits"], before["misses"]) == (after["hits"],
                                                  after["misses"])
    plan = cached_plan(a, b, "expand", backend="host")
    assert plan_cache_peek(key) is plan


def test_eviction_counter():
    mats = _mats(5)
    orig = plan_cache_info()["max_size"]
    api.plan_cache_resize(2)
    try:
        for a, b in mats:
            cached_plan(a, b, "expand", backend="host")
        info = plan_cache_info()
        assert info["size"] == 2
        assert info["evictions"] == 3
    finally:
        api.plan_cache_resize(orig)


# ---------------------------------------------------------------------------
# PlanBuilder: background builds, dedup, shedding, fallback protocol
# ---------------------------------------------------------------------------


def test_builder_submit_and_poll():
    a, b = _mats(1)[0]
    with PlanBuilder() as builder:
        status = builder.submit(a, b, "expand", backend="host", warm=False)
        assert status == "submitted"
        assert builder.wait_idle(30)
        results = builder.poll()
    assert len(results) == 1
    assert results[0].ok
    key = plan_cache_key(a, b, "expand", backend="host")
    assert results[0].key == key
    assert plan_cache_peek(key) is results[0].plan


def test_builder_dedup_and_cached_statuses():
    a, b = _mats(1)[0]
    gate = threading.Event()
    with PlanBuilder() as builder:
        builder.submit_task(gate.wait, tag="gate")  # pin the worker
        assert builder.submit(a, b, "expand", backend="host") == "submitted"
        assert builder.submit(a, b, "expand", backend="host") == "inflight"
        assert builder.stats["deduped"] == 1
        gate.set()
        assert builder.wait_idle(30)
        assert builder.submit(a, b, "expand", backend="host") == "cached"
        assert builder.stats["cached"] == 1


def test_builder_sheds_over_max_pending():
    mats = _mats(4)
    gate = threading.Event()
    with PlanBuilder(max_pending=2) as builder:
        builder.submit_task(gate.wait, tag="gate")  # occupies one slot
        statuses = [builder.submit(a, b, "expand", backend="host")
                    for a, b in mats]
        assert statuses.count("shed") >= 2  # bounded queue under churn
        gate.set()
        assert builder.wait_idle(30)
    assert builder.stats["shed"] >= 2


def test_builder_shutdown_rejects_new_work():
    builder = PlanBuilder()
    builder.shutdown()
    a, b = _mats(1)[0]
    with pytest.raises(RuntimeError, match="shut down"):
        builder.submit(a, b, "expand", backend="host")


def test_builder_reports_failed_builds(monkeypatch):
    a, b = _mats(1)[0]
    monkeypatch.setattr(api, "plan_spgemm",
                        lambda *x, **k: (_ for _ in ()).throw(
                            RuntimeError("injected")))
    with PlanBuilder() as builder:
        builder.submit(a, b, "expand", backend="host", warm=False)
        assert builder.wait_idle(30)
        results = builder.poll()
    assert len(results) == 1
    assert not results[0].ok
    assert "injected" in str(results[0].error)
    assert builder.stats["failed"] == 1


def test_plan_or_fallback_never_blocks_then_promotes():
    """Cold pattern: the call returns a host plan immediately (status
    'fallback') while the device build runs behind it; once the build
    lands, the same call serves the device plan ('ready')."""
    a, b = _mats(1)[0]
    with PlanBuilder() as builder:
        plan, status = builder.plan_or_fallback(a, b, "expand",
                                                backend="jax")
        assert status == "fallback"
        assert plan.backend == "host"
        assert builder.wait_idle(120)
        plan2, status2 = builder.plan_or_fallback(a, b, "expand",
                                                  backend="jax")
    assert status2 == "ready"
    assert plan2.backend == "jax"


def test_warm_plan_materializes_stream():
    a, b = _mats(1)[0]
    plan = cached_plan(a, b, "expand", backend="jax")
    assert plan.stream_nbytes == 0  # lazy until warmed
    warm_plan(plan)
    assert plan.stream_nbytes > 0
    assert plan.device_stream_nbytes > 0


@pytest.mark.parametrize("limits, key", [
    (None, "jax_fn_table"), ((0, 0, 0), "jax_fn")], ids=["one", "two"])
def test_warm_plan_builds_the_executable_execute_dispatches(monkeypatch,
                                                            limits, key):
    """``warm_plan`` compiles the value-table form ``plan.execute`` will
    dispatch, and only that one: the first execute after it builds
    nothing new."""
    from repro import runtime

    monkeypatch.setattr(
        runtime, "prefetch_limits",
        lambda: limits and runtime.PrefetchLimits(*limits))
    a, b = _mats(1)[0]
    plan = cached_plan(a, b, "expand", backend="jax")
    warm_plan(plan)

    def fns():
        return sorted(k for k in plan._stream_memo if k.startswith("jax_fn"))

    assert fns() == [key]
    got = plan.execute(a, b)
    assert fns() == [key]
    np.testing.assert_allclose(csc_to_dense(got),
                               csc_to_dense(a) @ csc_to_dense(b), rtol=1e-5)


def test_allmiss_churn_bit_identical_to_cold_cache():
    """Adversarial eviction churn must not change numerics: results under
    a too-small LRU (every request misses + evicts) are bit-identical to
    uncached cold builds — whichever of the fallback (host) or promoted
    (device) plan serves a given lap.  Small-integer values make every f32
    sum exact, so host f64 and device f32 agree with atol=0."""
    from repro.sparse.format import csc_to_dense

    def integerize(m, seed):
        rng = np.random.default_rng(seed)
        vals = rng.integers(1, 4, size=m.nnz).astype(np.float64)
        return type(m)(vals, m.row_indices, m.col_ptr, m.shape)

    mats = [(integerize(a, 3 * i), integerize(b, 3 * i + 1))
            for i, (a, b) in enumerate(_mats(6, n=32, density=0.15))]
    ref = [csc_to_dense(spgemm(a, b, method="expand", backend="host",
                               cache=False))
           for a, b in mats]
    orig = plan_cache_info()["max_size"]
    api.plan_cache_resize(2)
    try:
        with PlanBuilder(max_pending=2) as builder:
            for _ in range(3):  # three churn laps
                for (a, b), r in zip(mats, ref):
                    plan, _ = builder.plan_or_fallback(
                        a, b, "expand", backend="jax", warm=False)
                    got = plan.execute(a, b)
                    if hasattr(got, "to_host"):
                        got = got.to_host()
                    np.testing.assert_array_equal(csc_to_dense(got), r)
            builder.wait_idle(120)
    finally:
        api.plan_cache_resize(orig)
    assert plan_cache_info()["evictions"] > 0  # churn actually happened


# ---------------------------------------------------------------------------
# post-shrink waste accounting + re-warm (the resize-under-builds fix)
# ---------------------------------------------------------------------------


def test_wasted_builds_counts_insert_then_evict():
    """A build completing into a cache too small to keep it (the resize-
    below-in-flight-builds race) must be surfaced, not silent."""
    a, b = _mats(1)[0]
    orig = plan_cache_info()["max_size"]
    gate = threading.Event()
    try:
        with PlanBuilder() as builder:
            builder.submit_task(gate.wait, tag="gate")
            # queued behind the gate: the shrink lands mid-"flight"
            assert builder.submit(a, b, "expand", backend="host",
                                  warm=False) == "submitted"
            api.plan_cache_resize(0)
            gate.set()
            assert builder.wait_idle(60)
        info = plan_cache_info()
        assert info["size"] == 0
        assert info["wasted_builds"] == 1, info
        # a hit-then-evicted entry is NOT waste
        api.plan_cache_resize(2)
        plan = cached_plan(a, b, "expand", backend="host")   # miss, insert
        assert cached_plan(a, b, "expand", backend="host") is plan  # hit
        api.plan_cache_resize(0)
        assert plan_cache_info()["wasted_builds"] == 1
    finally:
        api.plan_cache_resize(orig)


def test_rewarm_hook_rebuilds_after_shrink():
    mats = _mats(2)
    orig = plan_cache_info()["max_size"]
    try:
        api.plan_cache_resize(4)
        with PlanBuilder() as builder:
            builder.enable_rewarm()
            builder.enable_rewarm()   # idempotent
            for a, b in mats:
                builder.submit(a, b, "expand", backend="host", warm=False)
            assert builder.wait_idle(60)
            keys = [plan_cache_key(a, b, "expand", backend="host")
                    for a, b in mats]
            assert all(plan_cache_peek(k) is not None for k in keys)
            # shrink evicts the LRU entry; the listener resubmits it
            api.plan_cache_resize(1)
            assert builder.wait_idle(60)
            assert builder.stats["rewarmed"] == 1, builder.stats
            # the re-warmed build landed back in the (now size-1) cache,
            # evicting the survivor through ordinary capacity pressure —
            # which must NOT re-notify (no listener ping-pong)
            rewarmed = builder.stats["rewarmed"]
            assert sum(plan_cache_peek(k) is not None for k in keys) == 1
            assert builder.stats["rewarmed"] == rewarmed
        # shutdown unhooked the listener
        assert api._EVICTION_LISTENERS == []
        api.plan_cache_resize(0)   # no listener left to fire
    finally:
        api.plan_cache_resize(orig)


def test_rewarm_skips_unknown_keys():
    a, b = _mats(1)[0]
    with PlanBuilder() as builder:
        key = plan_cache_key(a, b, "expand", backend="host")
        assert builder.rewarm([key, ("bogus",)]) == 0   # never submitted
        builder.submit(a, b, "expand", backend="host", warm=False)
        assert builder.wait_idle(60)
        api.plan_cache_resize(0)
        api.plan_cache_resize(64)
        assert builder.rewarm([key]) == 1
        assert builder.wait_idle(60)
        assert plan_cache_peek(key) is not None
