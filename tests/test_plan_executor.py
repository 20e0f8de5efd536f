"""Plan/execute architecture: reuse bit-identity, cache behavior, and the
no-dense-intermediate guarantee of the Pallas backend (DESIGN.md §6)."""

import numpy as np
import pytest

from repro.core import (
    ALGORITHMS, pattern_fingerprint, plan_cache_clear, plan_cache_info,
    plan_cache_resize, plan_spgemm, spgemm, spgemm_dense,
)
from repro.core import api as core_api
from repro.sparse import random_powerlaw_csc, random_uniform_csc
from repro.sparse.format import (
    CSC, CSCBuilder, csc_equal, csc_from_dense, validate_csc,
)

PALLAS_METHODS = [m for m in ALGORITHMS if m not in ("esc", "expand")]


def _reweight(m: CSC, seed: int) -> CSC:
    """Same sparsity pattern, fresh values."""
    rng = np.random.default_rng(seed)
    return CSC(rng.normal(size=m.nnz), m.row_indices, m.col_ptr, m.shape)


def _bit_identical(x: CSC, y: CSC) -> bool:
    return (
        x.shape == y.shape
        and np.array_equal(np.asarray(x.col_ptr), np.asarray(y.col_ptr))
        and np.array_equal(np.asarray(x.row_indices)[: x.nnz],
                           np.asarray(y.row_indices)[: y.nnz])
        and np.array_equal(np.asarray(x.values)[: x.nnz],
                           np.asarray(y.values)[: y.nnz])
    )


# --- plan reuse is bit-identical to planning from scratch ----------------


@pytest.mark.parametrize("method", sorted(ALGORITHMS))
def test_plan_reuse_bit_identical_host(method):
    a = random_powerlaw_csc(80, 3.0, seed=1)
    plan = plan_spgemm(a, a, method)          # planned on a's values
    a2 = _reweight(a, seed=7)                 # same pattern, new values
    fresh = spgemm(a2, a2, method=method, cache=False)
    reused = plan.execute(a2, a2)
    assert _bit_identical(reused, fresh), method
    validate_csc(reused)
    # raw value arrays are accepted too
    raw = plan.execute(np.asarray(a2.values), np.asarray(a2.values))
    assert _bit_identical(raw, fresh), method


@pytest.mark.parametrize("method", sorted(PALLAS_METHODS))
def test_plan_reuse_bit_identical_pallas(method):
    a = random_powerlaw_csc(64, 3.0, seed=2)
    plan = plan_spgemm(a, a, method, backend="pallas", block_cols=16)
    a2 = _reweight(a, seed=8)
    fresh = spgemm(a2, a2, method=method, backend="pallas", cache=False)
    reused = plan.execute(a2, a2)
    assert _bit_identical(reused, fresh), method
    assert csc_equal(reused, spgemm_dense(a2, a2), rtol=1e-4, atol=1e-5)


def test_spgemm_plan_kwarg():
    a = random_uniform_csc(48, 3, seed=3)
    plan = plan_spgemm(a, a, "spars-40/40")
    assert _bit_identical(spgemm(a, a, plan=plan),
                          spgemm(a, a, method="spars-40/40", cache=False))


def test_host_only_methods_rejected_on_pallas():
    a = random_uniform_csc(32, 2, seed=0)
    for method in ("esc", "expand"):
        with pytest.raises(ValueError):
            plan_spgemm(a, a, method, backend="pallas")


def test_unknown_method_rejected_at_plan_time():
    from repro.kernels.ops import spgemm_pallas

    a = random_uniform_csc(32, 2, seed=0)
    for backend in ("host", "pallas"):
        with pytest.raises(ValueError, match="unknown method"):
            plan_spgemm(a, a, "bogus", backend=backend)
    with pytest.raises(ValueError, match="unknown method"):
        spgemm_pallas(a, a, method="bogus")
    # unregistered but well-formed family names stay accepted (seed behavior)
    assert plan_spgemm(a, a, "spars-128/128").method == "spars-128/128"
    # ... but malformed bounds specs are rejected, not silently defaulted
    for bad in ("hash-64", "spars-16//64", "hash-a/b"):
        with pytest.raises(ValueError, match="malformed|unknown"):
            plan_spgemm(a, a, bad)


def test_execute_rejects_mismatched_operands():
    a = random_uniform_csc(32, 2, seed=0)
    plan = plan_spgemm(a, a, "hash-256/256")
    with pytest.raises(ValueError, match="shape"):
        plan.execute(random_uniform_csc(16, 2, seed=1), a)
    bigger = random_uniform_csc(32, 4, seed=2)  # same shape, different nnz
    assert bigger.nnz != a.nnz
    with pytest.raises(ValueError, match="pattern does not match"):
        spgemm(bigger, bigger, plan=plan)
    # a [B, nnz] stack belongs to execute_batched, not execute
    stack = np.zeros((3, a.nnz))
    with pytest.raises(ValueError, match="execute_batched"):
        plan.execute(stack, stack)


def _colliding_pair(n=16):
    """Two patterns with identical (shape, nnz) — and even col_ptr — but
    different row structure: the O(1) compatibility check cannot tell them
    apart."""
    a = csc_from_dense(np.eye(n))
    b = csc_from_dense(np.roll(np.eye(n), 1, axis=0))
    assert a.shape == b.shape and a.nnz == b.nnz
    assert np.array_equal(np.asarray(a.col_ptr), np.asarray(b.col_ptr))
    return a, b


def test_validate_fingerprint_rejects_corrupt_pattern():
    a, corrupt = _colliding_pair()
    plan = plan_spgemm(a, a, "hash-256/256")
    # the O(1) default accepts the wrong pattern silently (documented hole)
    plan.execute(corrupt, corrupt)
    # the opt-in O(nnz) re-hash catches it, on both entry points
    with pytest.raises(ValueError, match="fingerprint"):
        plan.execute(corrupt, corrupt, validate="fingerprint")
    with pytest.raises(ValueError, match="fingerprint"):
        spgemm(corrupt, corrupt, plan=plan, validate="fingerprint")
    # a matching operand passes validation with an unchanged result
    ok = plan.execute(a, a, validate="fingerprint")
    assert _bit_identical(ok, plan.execute(a, a))
    # raw value arrays carry no structure: validation is vacuous for them
    vals = np.asarray(a.values)
    plan.execute(vals, vals, validate="fingerprint")
    with pytest.raises(ValueError, match="validate"):
        plan.execute(a, a, validate="bogus")


def test_validate_fingerprint_batched():
    from repro.sparse import BatchedCSC

    a, corrupt = _colliding_pair()
    plan = plan_spgemm(a, a, "spa")
    bad = BatchedCSC.stack([corrupt, corrupt])
    plan.execute_batched(bad, bad)               # O(1) check passes
    with pytest.raises(ValueError, match="fingerprint"):
        plan.execute_batched(bad, bad, validate="fingerprint")
    good = BatchedCSC.stack([a, a])
    got = plan.execute_batched(good, good, validate="fingerprint")
    assert _bit_identical(got[0], plan.execute(a, a))


def test_plan_cache_distinct_entries_for_colliding_shape_nnz():
    """Two patterns that collide on every O(1) statistic (shape, nnz, even
    col_ptr) must still occupy distinct LRU entries and execute correctly."""
    plan_cache_clear()
    a, b = _colliding_pair()
    assert pattern_fingerprint(a) != pattern_fingerprint(b)
    ca = spgemm(a, a, method="spa")
    cb = spgemm(b, b, method="spa")
    info = plan_cache_info()
    assert (info["hits"], info["misses"], info["size"]) == (0, 2, 2)
    assert csc_equal(ca, spgemm_dense(a, a), rtol=1e-12, atol=0)
    assert csc_equal(cb, spgemm_dense(b, b), rtol=1e-12, atol=0)
    assert not csc_equal(ca, cb)                 # the results really differ
    # re-running hits each pattern's own entry
    assert _bit_identical(spgemm(a, a, method="spa"), ca)
    assert _bit_identical(spgemm(b, b, method="spa"), cb)
    assert plan_cache_info()["hits"] == 2
    plan_cache_clear()


# --- plan cache hit/miss behavior ----------------------------------------


def test_plan_cache_hit_miss_and_eviction(monkeypatch):
    plan_cache_clear()
    a = random_powerlaw_csc(60, 3.0, seed=4)
    spgemm(a, a, method="spa")
    info = plan_cache_info()
    assert (info["hits"], info["misses"]) == (0, 1)
    # same pattern again -> hit, even with different values
    spgemm(_reweight(a, 1), _reweight(a, 2), method="spa")
    info = plan_cache_info()
    assert (info["hits"], info["misses"]) == (1, 1)
    # different pattern -> miss; different method/backend -> miss
    b = random_powerlaw_csc(60, 3.0, seed=5)
    assert pattern_fingerprint(b) != pattern_fingerprint(a)
    spgemm(b, b, method="spa")
    spgemm(a, a, method="hash-256/256")
    info = plan_cache_info()
    assert (info["hits"], info["misses"]) == (1, 3)
    # bounded: evicts least-recently-used beyond PLAN_CACHE_SIZE
    monkeypatch.setattr(core_api, "PLAN_CACHE_SIZE", 2)
    spgemm(a, a, method="spars-40/40")
    assert plan_cache_info()["size"] <= 2
    plan_cache_clear()
    cleared = plan_cache_info()
    # the cost-profile provenance block is machine-dependent (fingerprint,
    # age) and survives a cache clear by design — covered in
    # test_profile.py, compared loosely here
    assert cleared.pop("profile")["source"] in ("default", "measured")
    assert cleared == {
        "hits": 0, "misses": 0, "evictions": 0, "size": 0, "max_size": 2,
        "hit_rate": 0.0, "in_flight": 0, "stream_bytes": 0,
        "device_stream_bytes": 0, "fused_stream_bytes": 0,
        "mesh_stream_bytes": 0, "wasted_builds": 0,
        "listener_errors": 0, "wait_timeouts": 0, "host_fallbacks": 0,
        "builders": []}


def test_plan_cache_resize_and_hit_rate(monkeypatch):
    """plan_cache_resize() is the supported capacity knob (no module-constant
    mutation) and plan_cache_info() reports the hit rate."""
    monkeypatch.setattr(core_api, "PLAN_CACHE_SIZE", 64)
    plan_cache_clear()
    mats = [random_powerlaw_csc(40, 3.0, seed=s) for s in range(4)]
    for m in mats:
        spgemm(m, m, method="spa")
    assert plan_cache_info()["size"] == 4
    # shrinking evicts the least-recently-used down to the new capacity
    info = plan_cache_resize(2)
    assert info["size"] == 2 and info["max_size"] == 2
    spgemm(mats[0], mats[0], method="spa")     # evicted earlier -> miss
    spgemm(mats[3], mats[3], method="spa")     # most recent -> hit
    info = plan_cache_info()
    assert info["hits"] == 1 and info["misses"] == 5
    assert info["hit_rate"] == pytest.approx(1 / 6)
    # growing keeps entries; zero disables caching entirely
    assert plan_cache_resize(64)["max_size"] == 64
    assert plan_cache_resize(0)["size"] == 0
    spgemm(mats[1], mats[1], method="spa")
    assert plan_cache_info()["size"] == 0
    with pytest.raises(ValueError):
        plan_cache_resize(-1)
    plan_cache_resize(64)
    plan_cache_clear()


# --- held-plan argument conflicts (ISSUE 3 satellite) ---------------------


def test_held_plan_conflicting_arguments_raise():
    a = random_uniform_csc(32, 3, seed=5)
    plan = plan_spgemm(a, a, "h-hash-256/256")
    # conflicting method/backend/params are loud, not silently ignored
    with pytest.raises(ValueError, match="conflict.*method"):
        spgemm(a, a, method="spa", plan=plan)
    with pytest.raises(ValueError, match="conflict.*backend"):
        spgemm(a, a, backend="pallas", plan=plan)
    with pytest.raises(ValueError, match="conflict.*t="):
        spgemm(a, a, t=7.0, plan=plan)
    with pytest.raises(ValueError, match="conflict.*b_min"):
        spgemm(a, a, b_min=16, plan=plan)
    with pytest.raises(ValueError, match="conflict.*b_max"):
        spgemm(a, a, b_max=16, plan=plan)
    # matching arguments (and None) pass through
    c = spgemm(a, a, method="h-hash-256/256", backend="host", t=40,
               b_min=256, b_max=256, plan=plan)
    assert _bit_identical(c, plan.execute(a, a))
    # a parameterless plan rejects any explicit parameter
    spa_plan = plan_spgemm(a, a, "spa")
    with pytest.raises(ValueError, match="conflict"):
        spgemm(a, a, t=40.0, plan=spa_plan)


def test_held_plan_conflicts_batched():
    from repro.core import spgemm_batched
    from repro.sparse import BatchedCSC

    a = random_uniform_csc(24, 2, seed=6)
    plan = plan_spgemm(a, a, "spa")
    ab = BatchedCSC.stack([a, a])
    with pytest.raises(ValueError, match="conflict"):
        spgemm_batched(ab, ab, method="hash-256/256", plan=plan)
    got = spgemm_batched(ab, ab, method="spa", plan=plan)
    assert _bit_identical(got[0], plan.execute(a, a))


def test_fingerprint_ignores_values():
    a = random_powerlaw_csc(50, 3.0, seed=6)
    assert pattern_fingerprint(a) == pattern_fingerprint(_reweight(a, 9))


# --- the Pallas path never materializes an [m, n] dense array ------------


def test_pallas_peak_intermediate_is_tile_bounded():
    n, block = 256, 32
    a = random_powerlaw_csc(n, 3.0, seed=0)
    for method in ("spa", "h-hash-256/256", "spars-40/40"):
        plan = plan_spgemm(a, a, method, backend="pallas", block_cols=block)
        stats = {}
        c = plan.execute(a, a, stats=stats)
        m_dim, n_dim = stats["result_shape"]
        assert stats["peak_tile_elems"] < m_dim * n_dim, method
        for kind, shape in stats["tile_shapes"]:
            if kind == "dense":
                assert shape[0] == m_dim and shape[1] <= block, (method, shape)
            else:  # hash tables are [H, L]: never m-sized at all
                assert shape[1] <= block, (method, shape)
        assert csc_equal(c, spgemm_dense(a, a), rtol=1e-4, atol=1e-5), method


def test_builder_matches_dense_compaction():
    rng = np.random.default_rng(0)
    m, n = 40, 24
    dense = rng.normal(size=(m, n)) * (rng.uniform(size=(m, n)) < 0.2)
    dense = dense.astype(np.float32)
    builder = CSCBuilder((m, n), np.float32)
    builder.add_dense_tile(np.arange(8), dense[:, :8])
    builder.add_dense_tile(np.arange(16, 24), dense[:, 16:24])  # out of order
    builder.add_dense_tile(np.arange(8, 16), dense[:, 8:16])
    got = builder.build()
    assert _bit_identical(got, csc_from_dense(dense))
    assert builder.peak_tile_elems == m * 8


def test_builder_hash_tables_match_densified():
    from repro.kernels.ref import hash_tables_to_dense

    rng = np.random.default_rng(1)
    m, H, L = 30, 8, 6
    keys = np.full((H, L), -1, np.int32)
    vals = np.zeros((H, L), np.float32)
    for l in range(L):
        rows = rng.choice(m, size=rng.integers(0, H), replace=False)
        slots = rng.choice(H, size=len(rows), replace=False)
        keys[slots, l] = rows
        vals[slots, l] = rng.normal(size=len(rows)).astype(np.float32)
    ref = csc_from_dense(np.asarray(hash_tables_to_dense(
        np.asarray(keys), np.asarray(vals), m)))
    builder = CSCBuilder((m, L), np.float32)
    builder.add_hash_tables(np.arange(L), keys, vals)
    assert _bit_identical(builder.build(), ref)


def test_builder_rejects_double_assembly():
    builder = CSCBuilder((4, 4), np.float32)
    builder.add_dense_tile([0, 1], np.zeros((4, 2), np.float32))
    with pytest.raises(ValueError):
        builder.add_dense_tile([1], np.ones((4, 1), np.float32))
