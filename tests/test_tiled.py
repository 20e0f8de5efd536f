"""Tiled SpGEMM with cost-model-driven per-tile method selection
(DESIGN.md §8): differential correctness of ``method="auto"`` on the
adversarial harness, bit-identity of column-only grids, exact equality of
2D grids, degenerate tiles, batched execution, and plan-cache sharing."""

import numpy as np
import pytest

from conftest import bit_identical as _bit_identical
from test_differential import CASES, _adversarial, oracle_product

from repro.core import (
    ALGORITHMS,
    AUTO_CANDIDATES,
    choose_method,
    estimate_cost,
    plan_cache_clear,
    plan_cache_info,
    plan_spgemm_tiled,
    spgemm,
    spgemm_batched,
)
from repro.sparse import BatchedCSC, random_powerlaw_csc, tile_stats, \
    validate_csc
from repro.sparse.format import CSC, csc_from_dense, csc_to_dense


def _integerize(m: CSC, seed: int = 0) -> CSC:
    """Same pattern, small-integer values: every sum is exact in fp, so
    tiled (re-associated) results must equal untiled ones with atol=0."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 4, size=m.nnz).astype(np.float64)
    return CSC(vals, m.row_indices, m.col_ptr, m.shape)


# --- method="auto" against the differential harness ------------------------


@pytest.mark.parametrize("case", CASES)
def test_auto_differential_host(case):
    a, b = _adversarial(case)
    c = spgemm(a, b, method="auto", cache=False)
    validate_csc(c)
    np.testing.assert_allclose(
        csc_to_dense(c), oracle_product(a, b), rtol=1e-9, atol=1e-11,
        err_msg=f"auto diverged from the oracle on {case!r}")


@pytest.mark.parametrize("case", CASES)
def test_auto_differential_pallas(case):
    a, b = _adversarial(case)
    c = spgemm(a, b, method="auto", backend="pallas", cache=False)
    validate_csc(c)
    np.testing.assert_allclose(
        csc_to_dense(c), oracle_product(a, b), rtol=1e-4, atol=1e-5,
        err_msg=f"pallas auto diverged from the oracle on {case!r}")


@pytest.mark.parametrize("case", CASES)
def test_auto_2d_grid_exact_vs_single_plan_host(case):
    """Explicit 2D grids: with integer values every fp sum is exact, so the
    tiled result must equal the untiled single-plan result with atol=0
    after canonical (dense) ordering — on every adversarial pattern."""
    a, b = _adversarial(case)
    a, b = _integerize(a, 1), _integerize(b, 2)
    single = csc_to_dense(spgemm(a, b, method="spa", cache=False))
    plan = plan_spgemm_tiled(a, b, tile=(8, 8), cache=False)
    tiled = plan.execute(a, b)
    validate_csc(tiled)
    np.testing.assert_array_equal(csc_to_dense(tiled), single)
    # auto-sized grid as well
    auto = spgemm(a, b, method="auto", cache=False)
    np.testing.assert_array_equal(csc_to_dense(auto), single)


@pytest.mark.parametrize("case", ("random", "empty_cols", "dup_heavy"))
def test_auto_2d_grid_exact_vs_single_plan_pallas(case):
    a, b = _adversarial(case)
    a, b = _integerize(a, 3), _integerize(b, 4)
    single = csc_to_dense(
        spgemm(a, b, method="spa", backend="pallas", cache=False))
    plan = plan_spgemm_tiled(a, b, backend="pallas", tile=(8, 8),
                             cache=False)
    tiled = plan.execute(a, b)
    np.testing.assert_array_equal(csc_to_dense(tiled), single)


# --- column-only grids are bit-identical to the untiled method -------------


@pytest.mark.parametrize("method", sorted(ALGORITHMS))
def test_column_tiling_bit_identical_host(method):
    a = random_powerlaw_csc(48, 3.0, seed=11)
    fresh = spgemm(a, a, method=method, cache=False)
    plan = plan_spgemm_tiled(a, a, tile=(a.n_cols, 7),
                             candidates=(method,), cache=False)
    assert plan.grid[0] == 1
    assert _bit_identical(plan.execute(a, a), fresh), method


@pytest.mark.parametrize("method", ("spa", "h-hash-256/256"))
def test_column_tiling_bit_identical_pallas(method):
    a = random_powerlaw_csc(36, 3.0, seed=12)
    fresh = spgemm(a, a, method=method, backend="pallas", cache=False)
    plan = plan_spgemm_tiled(a, a, backend="pallas", tile=(a.n_cols, 12),
                             candidates=(method,), cache=False)
    assert _bit_identical(plan.execute(a, a), fresh), method


# --- degenerate tiles (ISSUE 3 satellite) ----------------------------------


def test_tile_larger_than_matrix_is_single_tile():
    a = random_powerlaw_csc(20, 3.0, seed=13)
    plan = plan_spgemm_tiled(a, a, tile=(1000, 1000),
                             candidates=("spa",), cache=False)
    assert plan.grid == (1, 1) and len(plan.tiles) == 1
    assert _bit_identical(plan.execute(a, a),
                          spgemm(a, a, method="spa", cache=False))


def test_all_empty_column_blocks():
    # B columns 8..23 empty: two whole column blocks produce no tiles
    d = np.zeros((32, 32))
    rng = np.random.default_rng(14)
    d[:, :8] = rng.normal(size=(32, 8)) * (rng.uniform(size=(32, 8)) < 0.4)
    d[:, 24:] = rng.normal(size=(32, 8)) * (rng.uniform(size=(32, 8)) < 0.4)
    a = csc_from_dense(d)
    plan = plan_spgemm_tiled(a, a, tile=(8, 8), cache=False)
    assert {t.n for t in plan.tiles}.isdisjoint({1, 2})
    c = plan.execute(a, a)
    validate_csc(c)
    np.testing.assert_allclose(csc_to_dense(c), oracle_product(a, a),
                               rtol=1e-9, atol=1e-11)
    # empty A column blocks drop the matching row-block tiles too
    assert {t.k for t in plan.tiles}.isdisjoint({1, 2})


def test_empty_operands_produce_no_tiles():
    e = csc_from_dense(np.zeros((16, 16)))
    plan = plan_spgemm_tiled(e, e, tile=(4, 4), cache=False)
    assert plan.tiles == ()
    c = plan.execute(e, e)
    assert c.shape == (16, 16) and c.nnz == 0


def test_width_one_tiles():
    a = random_powerlaw_csc(12, 2.0, seed=15)
    plan = plan_spgemm_tiled(a, a, tile=(a.n_cols, 1),
                             candidates=("expand",), cache=False)
    assert plan.grid[1] == a.n_cols
    assert _bit_identical(plan.execute(a, a),
                          spgemm(a, a, method="expand", cache=False))


# --- batched tiled execution ----------------------------------------------


def test_auto_batched_bit_identical_to_looped():
    a = random_powerlaw_csc(40, 3.0, seed=16)
    rng = np.random.default_rng(17)
    vals = rng.normal(size=(4, a.nnz))
    plan = plan_spgemm_tiled(a, a, tile=(13, 9), cache=False)
    looped = [plan.execute(vals[i], vals[i]) for i in range(4)]
    batched = plan.execute_batched(vals, vals)
    assert len(batched) == 4
    for x, y in zip(batched, looped):
        assert _bit_identical(x, y)
    # the spgemm_batched entry point rides the same path
    ab = BatchedCSC.from_values(a, vals)
    via_api = spgemm_batched(ab, ab, method="auto", tile=(13, 9),
                             cache=False)
    for x, y in zip(via_api, looped):
        assert _bit_identical(x, y)


def test_auto_batched_pallas_single_launch_set():
    a = random_powerlaw_csc(24, 2.0, seed=18)
    rng = np.random.default_rng(19)
    vals = rng.normal(size=(3, a.nnz))
    plan = plan_spgemm_tiled(a, a, backend="pallas", tile=(24, 12),
                             cache=False)
    stats = {}
    batched = plan.execute_batched(vals, vals, stats=stats)
    assert stats["batch"] == 3
    assert stats["n_launches"] > 0     # aggregated over tiles, B-independent
    looped = [plan.execute(vals[i], vals[i]) for i in range(3)]
    for x, y in zip(batched, looped):
        assert _bit_identical(x, y)


# --- plan caching and tile-pattern sharing ---------------------------------


def test_tiled_plan_cached_and_tiles_shared():
    plan_cache_clear()
    a = random_powerlaw_csc(30, 3.0, seed=20)
    # B with two identical-pattern column blocks -> identical tile patterns
    dup = csc_from_dense(np.hstack([csc_to_dense(a)[:, :15]] * 2))
    c1 = spgemm(a, dup, method="auto", tile=(a.n_cols, 15))
    plan = plan_spgemm_tiled(a, dup, tile=(a.n_cols, 15))  # LRU hit
    assert len(plan.tiles) == 2
    # identical tile patterns share one child plan through the LRU
    assert plan.tiles[0].plan is plan.tiles[1].plan
    before = plan_cache_info()["hits"]
    c2 = spgemm(a, dup, method="auto", tile=(a.n_cols, 15))
    assert plan_cache_info()["hits"] > before
    assert _bit_identical(c1, c2)
    plan_cache_clear()


def test_tiled_held_plan_through_spgemm():
    a = random_powerlaw_csc(26, 3.0, seed=21)
    plan = plan_spgemm_tiled(a, a, tile=(9, 9), cache=False)
    assert _bit_identical(spgemm(a, a, plan=plan), plan.execute(a, a))
    # fingerprint validation works on tiled plans too
    bigger = random_powerlaw_csc(26, 5.0, seed=22)
    with pytest.raises(ValueError, match="pattern does not match"):
        spgemm(bigger, bigger, plan=plan)


def test_tiled_execute_stats():
    a = random_powerlaw_csc(40, 3.0, seed=23)
    plan = plan_spgemm_tiled(a, a, tile=(13, 9), cache=False)
    stats = {}
    plan.execute(a, a, stats=stats)
    k_blocks, n_blocks = stats["grid"]
    assert k_blocks > 1 and n_blocks > 1
    assert stats["tiles"] and all(
        set(t) == {"k", "n", "method"} for t in stats["tiles"])
    assert stats["merged_blocks"] > 0
    assert stats["result_shape"] == (40, 40)


# --- the cost model --------------------------------------------------------


def _dense_tile_stats():
    rng = np.random.default_rng(24)
    a = csc_from_dense(rng.uniform(0.5, 1.5, size=(64, 64)))
    b = csc_from_dense(
        (rng.uniform(size=(64, 8)) < 0.5) * rng.uniform(size=(64, 8)))
    return tile_stats(a, b)


def _sparse_tile_stats():
    a = random_powerlaw_csc(64, 1.5, seed=25)
    b = random_powerlaw_csc(64, 1.5, seed=26)
    return tile_stats(a, b)


def _guard_tripped_tile_stats():
    """A tile whose product stream exceeds the plan-memory guard: flops =
    nnz_b * m > fast.STREAM_MAX_PRODUCTS (pattern built directly — values
    are never read by the cost model)."""
    import repro.core.fast as fast

    k, nb, per = 64, 8, 32
    m = fast.STREAM_MAX_PRODUCTS // (nb * per) + 1
    a = CSC(np.zeros(0), np.tile(np.arange(m, dtype=np.int32), k),
            np.arange(k + 1, dtype=np.int32) * m, (m, k))
    rng = np.random.default_rng(29)
    b_rows = np.concatenate(
        [np.sort(rng.choice(k, size=per, replace=False)) for _ in range(nb)])
    b = CSC(np.zeros(0), b_rows.astype(np.int32),
            np.arange(nb + 1, dtype=np.int32) * per, (k, nb))
    return tile_stats(a, b)


def test_cost_model_host_regimes():
    # while the product stream fits the plan-memory guard the stream engine
    # (method "expand") dominates every host tile profile (DESIGN.md §9)...
    assert choose_method(_dense_tile_stats(), "host") == "expand"
    assert choose_method(_sparse_tile_stats(), "host") == "expand"
    # ...above the guard, executions pay a per-call transient stream rebuild
    # and SPA wins back flop-heavy tiles
    import repro.core.fast as fast

    st = _guard_tripped_tile_stats()
    assert st.flops > fast.STREAM_MAX_PRODUCTS
    assert choose_method(st, "host") == "spa"


def test_cost_model_pallas_regimes():
    # dense tiles keep the [m, L] accumulator busy -> SPA; sparse tiles
    # favour the small-H hash tables (the paper's crossover)
    assert choose_method(_dense_tile_stats(), "pallas") == "spa"
    assert choose_method(
        _sparse_tile_stats(), "pallas") in ("hash-256/256", "spars-40/40")
    sp = _sparse_tile_stats()
    assert (estimate_cost(sp, "hash-256/256", "pallas")
            < estimate_cost(sp, "spa", "pallas"))


def test_cost_model_monotone_in_flops():
    small, big = _sparse_tile_stats(), _dense_tile_stats()
    for method in ("spa", "expand"):
        assert (estimate_cost(big, method, "host")
                > estimate_cost(small, method, "host"))


def test_cost_model_candidate_restriction_and_errors():
    st = _sparse_tile_stats()
    assert choose_method(st, "host", candidates=("spa",)) == "spa"
    with pytest.raises(ValueError):
        choose_method(st, "host", candidates=())
    with pytest.raises(ValueError):
        estimate_cost(st, "expand", "pallas")   # host-only family
    with pytest.raises(ValueError):
        estimate_cost(st, "bogus", "host")


def test_auto_candidates_are_valid_methods():
    from repro.core import backend_names

    assert sorted(AUTO_CANDIDATES) == sorted(backend_names())
    for backend, cands in AUTO_CANDIDATES.items():
        for m in cands:
            # "jax" is the cross-backend candidate spelling: the device
            # stream riding a tile grid (DESIGN.md §10)
            assert (m in ALGORITHMS or m == "jax"
                    or m.startswith(("spars", "hash", "h-")))


# --- argument validation ---------------------------------------------------


def test_auto_argument_errors():
    a = random_powerlaw_csc(16, 2.0, seed=27)
    with pytest.raises(ValueError, match="auto"):
        spgemm(a, a, method="auto", t=40.0)
    with pytest.raises(ValueError, match="auto"):
        spgemm(a, a, method="spa", tile=(4, 4))
    with pytest.raises(ValueError, match="tile"):
        plan_spgemm_tiled(a, a, tile=(4, 4, 4))
    with pytest.raises(ValueError, match="tile"):
        plan_spgemm_tiled(a, a, tile=0)
    with pytest.raises(ValueError, match="host-only"):
        plan_spgemm_tiled(a, a, backend="pallas", candidates=("expand",))
    with pytest.raises(ValueError, match="shape mismatch"):
        plan_spgemm_tiled(a, random_powerlaw_csc(12, 2.0, seed=28))
