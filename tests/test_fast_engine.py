"""Product-stream engine (core/fast.py, DESIGN.md §9): differential
equivalence against every naive host method on the adversarial harness, the
fp-reassociation contract (exact with exactly-representable values,
canonical structure always), batched-vs-looped bit-identity across both
batch strategies, plan-LRU sharing of stream metadata, the memory-guard
fallback path, and engine argument validation."""

import numpy as np
import pytest

from conftest import bit_identical
from test_differential import CASES, _adversarial, oracle_product

from repro.core import (
    ALGORITHMS,
    build_product_stream,
    plan_cache_clear,
    plan_cache_info,
    plan_spgemm,
    plan_spgemm_tiled,
    spgemm,
    spgemm_batched,
)
from repro.core import api as core_api
from repro.core import fast
from repro.sparse import BatchedCSC, random_density_csc, random_powerlaw_csc
from repro.sparse.format import (
    CSC, csc_to_dense, segment_reduce, validate_csc,
)

try:  # optional dev dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _integerize(m: CSC, seed: int = 0) -> CSC:
    """Same pattern, small-integer values: every fp sum is exact, so
    re-associated summation must agree with the oracles with atol=0."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 4, size=m.nnz).astype(np.float64)
    return CSC(vals, m.row_indices, m.col_ptr, m.shape)


# --- differential: stream vs every naive host method -----------------------


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("method", sorted(ALGORITHMS))
def test_stream_vs_naive_differential(method, case):
    """engine="stream" computes the same C as every naive executor (and the
    external oracle) on every adversarial pattern, to summation-order
    tolerance."""
    a, b = _adversarial(case)
    plan = plan_spgemm(a, b, method)
    c_stream = plan.execute(a, b, engine="stream")
    c_naive = plan.execute(a, b, engine="naive")
    validate_csc(c_stream, sorted_rows=True)   # canonical structure
    ref = oracle_product(a, b)
    np.testing.assert_allclose(
        csc_to_dense(c_stream), ref, rtol=1e-9, atol=1e-11,
        err_msg=f"stream diverged from the oracle on {case!r}")
    np.testing.assert_allclose(
        csc_to_dense(c_stream), csc_to_dense(c_naive), rtol=1e-9, atol=1e-11,
        err_msg=f"stream diverged from naive {method} on {case!r}")


@pytest.mark.parametrize("case", CASES)
def test_stream_exact_and_structured_like_expand(case):
    """The stream engine shares ``expand``'s canonical layout and summation
    order: structure is bit-identical always, and with exactly-representable
    values (no rounding, so re-association is invisible) the values match
    the naive expand executor with atol=0."""
    a, b = _adversarial(case)
    a, b = _integerize(a, 1), _integerize(b, 2)
    plan = plan_spgemm(a, b, "expand")
    c_stream = plan.execute(a, b, engine="stream")
    c_naive = plan.execute(a, b, engine="naive")
    assert np.array_equal(np.asarray(c_stream.col_ptr),
                          np.asarray(c_naive.col_ptr))
    assert np.array_equal(np.asarray(c_stream.row_indices)[: c_stream.nnz],
                          np.asarray(c_naive.row_indices)[: c_naive.nnz])
    np.testing.assert_array_equal(
        np.asarray(c_stream.values)[: c_stream.nnz],
        np.asarray(c_naive.values)[: c_naive.nnz])


def test_stream_is_default_engine_for_expand_only():
    a = random_powerlaw_csc(40, 3.0, seed=1)
    for method, engine in (("expand", "stream"), ("spa", "naive"),
                           ("h-hash-256/256", "naive")):
        stats = {}
        plan_spgemm(a, a, method).execute(a, a, stats=stats)
        assert stats["engine"] == engine, method


# --- batched vs looped bit-identity (both batch strategies) ----------------


@pytest.mark.parametrize("n, avg", [(24, 2.0),    # short stream: 2-D passes
                                    (96, 5.0)])   # long stream: row loop
def test_stream_batched_bit_identical_to_looped(n, avg):
    a = random_powerlaw_csc(n, avg, seed=2)
    plan = plan_spgemm(a, a, "expand")
    threshold = fast.STREAM_BATCH_VECTOR_MAX
    # make sure the parametrization actually covers both strategies
    assert (plan.stream.n_products <= threshold) == (n == 24)
    rng = np.random.default_rng(3)
    vals = rng.normal(size=(5, a.nnz))
    looped = [plan.execute(vals[i], vals[i], engine="stream")
              for i in range(5)]
    stats = {}
    batched = plan.execute_batched(vals, vals, engine="stream", stats=stats)
    assert stats["path"] == ("vectorized" if n == 24 else "rowloop")
    assert stats["batch"] == 5
    for x, y in zip(batched, looped):
        assert bit_identical(x, y)


def test_spgemm_batched_default_engine_rides_stream():
    a = random_powerlaw_csc(48, 3.0, seed=4)
    rng = np.random.default_rng(5)
    ab = BatchedCSC.from_values(a, rng.normal(size=(3, a.nnz)))
    got = spgemm_batched(ab, ab, method="expand", cache=False)
    want = [spgemm(ab[i], ab[i], method="expand", cache=False)
            for i in range(3)]
    for x, y in zip(got, want):
        assert bit_identical(x, y)


# --- plan-LRU reuse of stream metadata -------------------------------------


def test_plan_cache_shares_stream_metadata():
    plan_cache_clear()
    a = random_powerlaw_csc(36, 3.0, seed=6)
    p1 = core_api._cached_plan(a, a, "expand", "host", {})
    assert p1.stream is not None
    p2 = core_api._cached_plan(a, a, "expand", "host", {})
    assert p2 is p1 and p2.stream is p1.stream   # one stream, shared
    assert plan_cache_info()["hits"] == 1
    # tiled child plans inherit the stream through the same LRU
    tiled = plan_spgemm_tiled(a, a, tile=(a.n_cols, a.n_cols),
                              candidates=("expand",))
    assert tiled.tiles[0].plan is p1
    plan_cache_clear()


def test_stream_bytes_reported_and_guard_keys_stream_carriers():
    plan_cache_clear()
    a = random_powerlaw_csc(40, 3.0, seed=30)
    spgemm(a, a, method="expand")            # default engine builds a stream
    assert plan_cache_info()["stream_bytes"] > 0
    # the guard knob keys every stream-carrying plan — all three backends
    # (pallas plans carry a stream for ``stream_apply``), so a knob change
    # rebuilds pallas and host plans alike
    spgemm(a, a, method="spa", backend="pallas")
    misses = plan_cache_info()["misses"]
    old = fast.STREAM_MAX_PRODUCTS
    try:
        fast.STREAM_MAX_PRODUCTS = old + 1
        spgemm(a, a, method="spa", backend="pallas")
        assert plan_cache_info()["misses"] == misses + 1  # pallas: rebuilt
        spgemm(a, a, method="expand")
        assert plan_cache_info()["misses"] == misses + 2  # host: rebuilt
    finally:
        fast.STREAM_MAX_PRODUCTS = old
    plan_cache_clear()


def test_stream_result_arrays_are_frozen():
    """Results share structure with the plan-resident stream; mutating them
    must raise rather than corrupt later same-plan executions."""
    a = random_powerlaw_csc(30, 3.0, seed=31)
    plan = plan_spgemm(a, a, "expand")
    c = plan.execute(a, a)
    with pytest.raises(ValueError):
        np.asarray(c.row_indices)[0] = 99
    with pytest.raises(ValueError):
        np.asarray(c.col_ptr)[0] = 1


def test_tiled_engine_forwarding():
    a = random_powerlaw_csc(40, 3.0, seed=7)
    a = _integerize(a, 8)
    plan = plan_spgemm_tiled(a, a, tile=(13, 9), cache=False)
    base = csc_to_dense(plan.execute(a, a))
    for engine in ("naive", "stream"):
        np.testing.assert_array_equal(
            csc_to_dense(plan.execute(a, a, engine=engine)), base)
    # batched forwarding too
    vals = np.stack([np.asarray(a.values)] * 2)
    outs = plan.execute_batched(vals, vals, engine="stream")
    np.testing.assert_array_equal(csc_to_dense(outs[0]), base)


# --- memory-guard fallback -------------------------------------------------


def test_memory_guard_fallback_bit_identical():
    a = random_powerlaw_csc(50, 4.0, seed=9)
    full = plan_spgemm(a, a, "expand")
    assert full.stream is not None
    guarded = plan_spgemm(a, a, "expand", stream_limit=1)
    assert guarded.stream is None     # guard tripped: nothing plan-resident
    stats_g, stats_f = {}, {}
    c_g = guarded.execute(a, a, engine="stream", stats=stats_g)
    c_f = full.execute(a, a, engine="stream", stats=stats_f)
    assert bit_identical(c_g, c_f)    # transient rebuild: same results
    assert stats_g["stream_cached"] is False
    assert stats_f["stream_cached"] is True
    assert stats_g["stream_products"] == stats_f["stream_products"]
    # batched rides the same fallback
    rng = np.random.default_rng(10)
    vals = rng.normal(size=(3, a.nnz))
    for x, y in zip(guarded.execute_batched(vals, vals, engine="stream"),
                    full.execute_batched(vals, vals, engine="stream")):
        assert bit_identical(x, y)


def test_build_product_stream_guard_and_counts():
    a = random_powerlaw_csc(30, 3.0, seed=11)
    s = build_product_stream(a, a)
    from repro.sparse import ops_per_column

    assert s.n_products == int(ops_per_column(a, a).sum())
    assert build_product_stream(a, a, max_products=s.n_products - 1) is None
    assert build_product_stream(
        a, a, max_products=s.n_products) is not None


# --- engine argument validation & edge cases -------------------------------


def test_engine_argument_errors():
    a = random_powerlaw_csc(20, 2.0, seed=12)
    with pytest.raises(ValueError, match="engine"):
        spgemm(a, a, method="spa", engine="bogus", cache=False)
    with pytest.raises(ValueError, match="host-backend"):
        spgemm(a, a, method="spa", backend="pallas", engine="stream",
               cache=False)
    with pytest.raises(ValueError, match="host-backend"):
        plan_spgemm_tiled(a, a, backend="pallas", cache=False).execute(
            a, a, engine="stream")
    # engine="naive" is a no-op on pallas plans (they have no host engine)
    c = spgemm(a, a, method="spa", backend="pallas", engine="naive",
               cache=False)
    validate_csc(c)


def test_stream_empty_operands():
    ea = CSC(np.zeros(0), np.zeros(0, np.int32), np.zeros(13, np.int32),
             (10, 12))
    eb = CSC(np.zeros(0), np.zeros(0, np.int32), np.zeros(8, np.int32),
             (12, 7))
    plan = plan_spgemm(ea, eb, "expand")
    c = plan.execute(ea, eb, engine="stream")
    assert c.shape == (10, 7) and c.nnz == 0
    outs = plan.execute_batched(np.zeros((2, 0)), np.zeros((2, 0)),
                                engine="stream")
    assert all(o.nnz == 0 for o in outs)


def test_segment_reduce_edges():
    assert segment_reduce(np.zeros(0), np.zeros(0, np.int64)).shape == (0,)
    assert segment_reduce(
        np.zeros((3, 0)), np.zeros(0, np.int64), axis=1).shape == (3, 0)
    out = segment_reduce(np.array([1.0, 2.0, 4.0]), np.array([0, 2]))
    np.testing.assert_array_equal(out, [3.0, 4.0])


# --- guarded hypothesis sweep ----------------------------------------------


if HAVE_HYPOTHESIS:

    @given(
        seed=st.integers(0, 10_000),
        n=st.integers(6, 36),
        density=st.floats(0.0, 0.4),
        guard=st.booleans(),
    )
    @settings(max_examples=40, deadline=None)
    def test_property_stream_matches_oracle(seed, n, density, guard):
        a = random_density_csc(n, n, density, seed=seed)
        b = random_density_csc(n, n, density, seed=seed + 1)
        plan = plan_spgemm(a, b, "expand",
                           stream_limit=0 if guard else None)
        c = plan.execute(a, b, engine="stream")
        validate_csc(c, sorted_rows=True)
        np.testing.assert_allclose(
            csc_to_dense(c), oracle_product(a, b), rtol=1e-9, atol=1e-11)
