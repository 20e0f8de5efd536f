"""Self-calibrating cost-model profiles (``core.profile``, DESIGN.md §15).

Covers the machine fingerprint, JSON persistence + fingerprint-mismatch
invalidation, the lazy current-profile state, the weighted least-squares
fit, the Spearman cross-check on synthetic timings, profile-driven
``choose_method``/``should_distribute`` decisions (including the comm-x100
flip), the stale-constants warning, structural-knob tuning, and the
provenance stamped into plan params / cache keys / ``plan_cache_info``.

No microbenchmarks run here — fitting and decision logic are exercised on
synthetic rows/timings so the suite stays fast and deterministic.
"""

import dataclasses
import json
import os
import warnings

import numpy as np
import pytest

import repro.core.fast as fast
from repro.core import plan_cache_clear, plan_cache_info, profile
from repro.core.cost import (
    DEFAULT_CONSTANTS,
    CostConstants,
    choose_method,
    estimate_cost,
    should_distribute,
)
from repro.core.planner import plan_spgemm_tiled
from repro.sparse.format import csc_from_dense
from repro.sparse.partition import auto_tile_grid
from repro.sparse.stats import tile_stats


@pytest.fixture(autouse=True)
def _isolated_profile(tmp_path, monkeypatch):
    """Every test starts with no loaded profile, a private profile dir,
    and the stock structural knobs (several tests retune them)."""
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path / "profiles"))
    monkeypatch.delenv("REPRO_PROFILE_FILE", raising=False)
    monkeypatch.delenv("REPRO_AUTO_CALIBRATE", raising=False)
    guard = fast.STREAM_MAX_PRODUCTS
    profile.reset()
    yield
    profile.reset()
    fast.STREAM_MAX_PRODUCTS = guard
    plan_cache_clear()


def _measured(constants=None, tuning=None, fitted=()):
    return profile.MachineProfile(
        constants=constants or DEFAULT_CONSTANTS,
        fingerprint=profile.machine_fingerprint(),
        source="measured", created_at=1.0, fitted=tuple(fitted),
        tuning=dict(tuning or {}))


def _pair(m=24, n=16, per=2, seed=0):
    rng = np.random.default_rng(seed)
    ad = rng.uniform(0.5, 1.5, size=(m, m)) * (rng.random((m, m)) < 0.3)
    bd = np.zeros((m, n))
    for j in range(n):
        bd[rng.integers(m, size=per), j] = 1.0
    return csc_from_dense(ad), csc_from_dense(bd)


# ---------------------------------------------------------------------------
# fingerprint + persistence
# ---------------------------------------------------------------------------


def test_fingerprint_deterministic():
    fp1, fp2 = profile.machine_fingerprint(), profile.machine_fingerprint()
    assert fp1 == fp2
    assert profile.fingerprint_key(fp1) == profile.fingerprint_key(fp2)
    for field in ("cpu", "platform", "device_kind", "device_count", "jax"):
        assert field in fp1


def test_fingerprint_key_sensitive_to_fields():
    fp = profile.machine_fingerprint()
    other = dict(fp, device_count=fp["device_count"] + 7)
    assert profile.fingerprint_key(fp) != profile.fingerprint_key(other)


def test_save_load_roundtrip(tmp_path):
    c = dataclasses.replace(DEFAULT_CONSTANTS, jax_base=1.25e-4,
                            comm_byte=3.5e-9)
    prof = _measured(c, tuning={"stream_max_products": 2_000_000},
                     fitted=("jax_base",))
    path = profile.save_profile(prof, directory=str(tmp_path))
    assert os.path.exists(path)
    back = profile.load_profile(directory=str(tmp_path))
    assert back is not None
    assert back.source == "measured"
    assert back.constants.jax_base == pytest.approx(1.25e-4)
    assert back.constants.comm_byte == pytest.approx(3.5e-9)
    assert back.constants.spa_col == DEFAULT_CONSTANTS.spa_col
    assert back.fitted == ("jax_base",)
    assert back.tuning == {"stream_max_products": 2_000_000}
    assert back.tag == prof.tag


def test_profile_written_with_retired_keys_still_loads(tmp_path):
    """A profile file from before the fused stream kernel was retired
    carries its constants, fit and tuning key: they are dropped on load
    and everything else is kept."""
    doc = _measured(fitted=("jax_base",)).to_json()
    doc["constants"].update(jax_base=1.25e-4, fused_base=7.9e-5,
                            fused_prod=3.0e-7)
    doc["fitted"] = ["fused_base", "fused_prod", "jax_base"]
    doc["tuning"] = {"fused_block": 64, "stream_max_products": 2_000_000}
    path = tmp_path / "old.json"
    path.write_text(json.dumps(doc))
    back = profile.load_profile(path=str(path))
    assert back is not None and back.source == "measured"
    assert back.constants.jax_base == pytest.approx(1.25e-4)
    assert back.constants.spa_col == DEFAULT_CONSTANTS.spa_col
    assert not any(f.name.startswith("fused")
                   for f in dataclasses.fields(back.constants))
    assert back.fitted == ("jax_base",)
    assert back.tuning == {"stream_max_products": 2_000_000}


def test_load_missing_returns_none(tmp_path):
    assert profile.load_profile(directory=str(tmp_path / "empty")) is None


def test_fingerprint_mismatch_invalidates(tmp_path):
    """A profile measured under a different device fingerprint (e.g. a
    forced host device count) is discarded, not silently reused."""
    prof = _measured()
    doc = prof.to_json()
    doc["fingerprint"]["device_count"] += 7   # the XLA_FLAGS-forced run
    path = tmp_path / f"{prof.key}.json"
    path.write_text(json.dumps(doc))
    before = profile.profile_info()["stale_discards"]
    with pytest.warns(RuntimeWarning, match="different machine"):
        got = profile.load_profile(path=str(path))
    assert got is None
    assert profile.profile_info()["stale_discards"] == before + 1


def test_corrupt_profile_falls_back(tmp_path):
    d = tmp_path / "profiles"
    d.mkdir()
    (d / f"{profile.fingerprint_key()}.json").write_text("{not json")
    assert profile.load_profile(directory=str(d)) is None
    assert profile.profile_info()["load_errors"] >= 1


def test_current_profile_lazy_loads_from_dir(tmp_path, monkeypatch):
    d = tmp_path / "profiles"
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(d))
    profile.save_profile(
        _measured(dataclasses.replace(DEFAULT_CONSTANTS, jax_prod=9e-7)),
        directory=str(d))
    profile.reset()
    p = profile.current_profile()
    assert p.source == "measured"
    assert p.constants.jax_prod == pytest.approx(9e-7)
    # and without a persisted file the fallback is the default profile
    profile.reset()
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path / "nothing"))
    assert profile.current_profile().source == "default"
    assert profile.current_constants() is DEFAULT_CONSTANTS


# ---------------------------------------------------------------------------
# fitting
# ---------------------------------------------------------------------------


def test_fit_fields_recovers_exact_coefficients():
    rows = [[1.0, f] for f in (10, 100, 1000, 50_000)]
    times = [2e-5 + 3e-8 * f for _, f in rows]
    out = profile.fit_fields(("base", "slope"), rows, times)
    assert out["base"] == pytest.approx(2e-5, rel=1e-6)
    assert out["slope"] == pytest.approx(3e-8, rel=1e-6)


def test_fit_fields_clamps_negative_coefficients():
    # a decreasing "cost" drives the slope negative; physical durations
    # cannot be, so the fit clamps at the floor instead
    rows = [[1.0, f] for f in (10, 100, 1000)]
    times = [1e-3 - 9e-7 * f for _, f in rows]
    out = profile.fit_fields(("base", "slope"), rows, times)
    assert out["slope"] == pytest.approx(1e-12)


def test_fit_fields_weights_relative_error():
    # one giant config must not drown the small ones: with 1/t weighting
    # the base term of the small rows survives a 1000x larger row
    rows = [[1.0, 1.0], [1.0, 2.0], [1.0, 1e6]]
    times = [1e-4 + 1e-7 * r[1] for r in rows]
    out = profile.fit_fields(("base", "slope"), rows, times)
    assert out["base"] == pytest.approx(1e-4, rel=1e-3)


def test_fit_fields_shape_mismatch():
    with pytest.raises(ValueError, match="inconsistent"):
        profile.fit_fields(("a",), [[1.0, 2.0]], [1.0])


def test_fit_constants_merges_sections():
    c, fitted = profile.fit_constants([
        (("jax_base", "jax_prod"),
         [[1.0, f] for f in (10, 1000, 1e5)],
         [4e-5 + 5e-8 * f for f in (10, 1000, 1e5)]),
        (("comm_base",), [[1.0]], [2e-4]),
    ])
    assert fitted == ("comm_base", "jax_base", "jax_prod")
    assert c.jax_base == pytest.approx(4e-5, rel=1e-5)
    assert c.jax_prod == pytest.approx(5e-8, rel=1e-5)
    assert c.comm_base == pytest.approx(2e-4, rel=1e-6)
    # unmeasured fields ride along from the base constants
    assert c.spa_entry == DEFAULT_CONSTANTS.spa_entry


# ---------------------------------------------------------------------------
# rank correlation
# ---------------------------------------------------------------------------


def test_rank_correlation_basics():
    assert profile.rank_correlation([1, 2, 3], [10, 20, 30]) == 1.0
    assert profile.rank_correlation([1, 2, 3], [3, 2, 1]) == -1.0
    # monotone nonlinear map preserves ranks exactly
    x = np.asarray([1.0, 4.0, 2.0, 8.0, 3.0])
    assert profile.rank_correlation(x, np.exp(x)) == 1.0
    # ties get average ranks on both sides
    assert profile.rank_correlation([1, 1, 2], [5, 5, 9]) == 1.0
    assert profile.rank_correlation([1.0], [2.0]) == 1.0
    assert profile.rank_correlation([2, 2, 2], [1, 5, 9]) == 1.0


def test_rank_correlation_rejects_mismatched():
    with pytest.raises(ValueError):
        profile.rank_correlation([1, 2], [1, 2, 3])


def test_synthetic_fit_ranks_methods(subtests=None):
    """Satellite: a profile fitted from (noisy) synthetic timings must rank
    per-(tile, method) costs with Spearman >= 0.8 against those timings."""
    truth = dataclasses.replace(
        DEFAULT_CONSTANTS, spa_col=5e-6, spa_entry=9e-6, spa_flop=2e-8,
        stream_base=1.2e-5, stream_prod=8e-9, jax_base=9e-5, jax_prod=5e-8)
    rng = np.random.default_rng(7)
    stats = [tile_stats(*_pair(m, n, per, seed))
             for seed, (m, n, per) in enumerate(
                 [(16, 8, 1), (24, 16, 2), (48, 32, 3), (64, 48, 4),
                  (96, 64, 5), (128, 96, 6)])]

    def noisy(t):
        return float(t * rng.uniform(0.9, 1.1))

    sections = [
        (("spa_col", "spa_entry", "spa_flop"),
         [[s.n, s.nnz_b, s.flops] for s in stats],
         [noisy(truth.spa_col * s.n + truth.spa_entry * s.nnz_b
                + truth.spa_flop * s.flops) for s in stats]),
        (("stream_base", "stream_prod"),
         [[1.0, s.flops] for s in stats],
         [noisy(truth.stream_base + truth.stream_prod * s.flops)
          for s in stats]),
        (("jax_base", "jax_prod"),
         [[1.0, s.flops] for s in stats],
         [noisy(truth.jax_base + truth.jax_prod * s.flops)
          for s in stats]),
    ]
    fitted, names = profile.fit_constants(sections)
    assert "spa_flop" in names and "jax_prod" in names

    measured, predicted = [], []
    for (fields, _, times), method in zip(sections,
                                          ("spa", "expand", "jax")):
        for s, t in zip(stats, times):
            measured.append(t)
            predicted.append(estimate_cost(s, method, constants=fitted))
    rc = profile.rank_correlation(predicted, measured)
    assert rc >= 0.8, f"Spearman {rc:.3f} below the 0.8 gate"


# ---------------------------------------------------------------------------
# profile-driven decisions
# ---------------------------------------------------------------------------


def test_choose_method_consults_profile():
    a, b = _pair()
    st = tile_stats(a, b)
    baseline = choose_method(st, "host", constants=DEFAULT_CONSTANTS)
    assert baseline == "expand"
    # a machine where every stream engine's dispatch costs a full second
    # must re-rank the same tile to SPA — via the installed profile, with
    # no constants argument at the call site
    slow_streams = dataclasses.replace(
        DEFAULT_CONSTANTS, stream_base=1.0, expand_base=1.0, jax_base=1.0)
    profile.set_profile(_measured(slow_streams))
    assert choose_method(st, "host") == "spa"
    profile.set_profile(None)


def test_should_distribute_flips_when_comm_scaled_100x():
    """Acceptance: the distribute decision must flip when the profile's
    measured comm terms are scaled x100 (same workload, same shards)."""
    ad = np.ones((64, 64))
    bd = np.ones((64, 64))
    st = tile_stats(csc_from_dense(ad), csc_from_dense(bd))
    assert st.flops == 64 ** 3

    cheap_comm = dataclasses.replace(
        DEFAULT_CONSTANTS, jax_base=1e-6, jax_prod=1e-8,
        comm_base=1e-3, comm_byte=5e-10)
    profile.set_profile(_measured(cheap_comm, fitted=("comm_base",
                                                      "comm_byte")))
    assert should_distribute(st, 4) is True

    expensive_comm = dataclasses.replace(
        cheap_comm, comm_base=cheap_comm.comm_base * 100,
        comm_byte=cheap_comm.comm_byte * 100)
    profile.set_profile(_measured(expensive_comm))
    assert should_distribute(st, 4) is False


def test_default_auto_warns_once_and_counts():
    a, b = _pair()
    st = tile_stats(a, b)
    before = plan_cache_info()["profile"]["default_auto_uses"]
    with pytest.warns(RuntimeWarning, match="uncalibrated"):
        choose_method(st, "host")
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # second consult must stay silent
        choose_method(st, "host")
    info = plan_cache_info()["profile"]
    assert info["default_auto_uses"] == before + 2
    assert info["source"] == "default"


def test_host_only_candidates_do_not_warn():
    a, b = _pair()
    st = tile_stats(a, b)
    before = plan_cache_info()["profile"]["default_auto_uses"]
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        choose_method(st, "host", candidates=("spa", "expand"))
    assert plan_cache_info()["profile"]["default_auto_uses"] == before


def test_measured_profile_does_not_warn():
    a, b = _pair()
    st = tile_stats(a, b)
    profile.set_profile(_measured())
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        choose_method(st, "host")
    assert plan_cache_info()["profile"]["default_auto_uses"] == 0


# ---------------------------------------------------------------------------
# structural-knob tuning
# ---------------------------------------------------------------------------


def test_apply_tuning_sets_knobs():
    # the tile targets are read live by auto_tile_grid: no global to set
    prof = _measured(tuning={"stream_max_products": 123_456,
                             "tile_n_target": 4096})
    applied = profile.apply_tuning(prof)
    assert applied == {"stream_max_products": 123_456}
    assert fast.STREAM_MAX_PRODUCTS == 123_456


def test_apply_tuning_untouched_without_keys():
    before = fast.STREAM_MAX_PRODUCTS
    assert profile.apply_tuning(_measured()) == {}
    assert fast.STREAM_MAX_PRODUCTS == before


def test_auto_tile_grid_consults_tuning():
    a, b = _pair(m=32, n=24, per=4)
    default_grid = auto_tile_grid(a, b)
    assert default_grid == (1, 1)   # far under the shipped targets
    profile.set_profile(_measured(tuning={"tile_n_target": 8,
                                          "tile_k_target": 16}))
    tuned_grid = auto_tile_grid(a, b)
    assert tuned_grid[1] > 1
    assert tuned_grid[0] > 1
    # explicit targets always win over the profile
    assert auto_tile_grid(a, b, n_target=10 ** 9, k_target=10 ** 9) == (1, 1)


# ---------------------------------------------------------------------------
# provenance in plans / cache keys / info
# ---------------------------------------------------------------------------


def test_tiled_plan_params_carry_profile_tag():
    a, b = _pair()
    p_default = plan_spgemm_tiled(a, b, cache=False)
    assert dict(p_default.params)["profile"] == "default"

    profile.set_profile(_measured())
    p_measured = plan_spgemm_tiled(a, b, cache=False)
    tag = dict(p_measured.params)["profile"]
    assert tag.startswith("measured:")
    assert p_measured.cache_key != p_default.cache_key

    p_explicit = plan_spgemm_tiled(a, b, cache=False,
                                   constants=DEFAULT_CONSTANTS)
    assert dict(p_explicit.params)["profile"] == "explicit"


def test_tiled_cache_keyed_by_profile():
    """The plan LRU must not serve picks ranked under one calibration to a
    consult running under another."""
    from repro.core.api import _cached_tiled_plan

    a, b = _pair()
    p1 = _cached_tiled_plan(a, b, "host", None, None)
    assert _cached_tiled_plan(a, b, "host", None, None) is p1
    profile.set_profile(_measured())
    p2 = _cached_tiled_plan(a, b, "host", None, None)
    assert p2 is not p1


def test_plan_cache_info_exposes_profile():
    info = plan_cache_info()["profile"]
    assert info["source"] == "default"
    for key in ("fingerprint_key", "fitted", "tuning",
                "default_auto_uses", "stale_discards", "load_errors"):
        assert key in info
    profile.set_profile(_measured(fitted=("jax_base",)))
    info = plan_cache_info()["profile"]
    assert info["source"] == "measured"
    assert info["fitted"] == ["jax_base"]
    assert info["age_seconds"] is not None


def test_mesh_plan_params_carry_profile_tag():
    pytest.importorskip("jax")
    from repro.distributed.spgemm_mesh import plan_spgemm_mesh

    a, b = _pair(m=16, n=8, per=2)
    plan = plan_spgemm_mesh(a, b, shards=1, cache=False)
    assert dict(plan.params)["profile"] == "default"
    profile.set_profile(_measured())
    plan2 = plan_spgemm_mesh(a, b, shards=1, cache=False)
    assert dict(plan2.params)["profile"].startswith("measured:")
    assert plan.cache_key != plan2.cache_key


def test_bench_env_header_stamps_provenance():
    import sys

    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        import _util
    finally:
        sys.path.remove(bench_dir)
    profile.set_profile(_measured(fitted=("comm_base",)))
    env = _util.env_info()
    assert env["cost_profile"]["source"] == "measured"
    assert env["cost_profile"]["fitted"] == ["comm_base"]
    assert env["cost_profile"]["fingerprint_key"] == profile.fingerprint_key()
