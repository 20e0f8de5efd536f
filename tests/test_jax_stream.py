"""Device-resident stream backend (core/jax_stream.py, DESIGN.md §10):
differential equivalence vs the host stream and the naive oracles on the
adversarial harness, gradient checks (custom vjp vs finite differences and
vs a dense ``jnp.matmul`` oracle), vmap-vs-looped bit-identity, cached-trace
steady state (zero retrace after warmup), guard fallback/capability errors,
fingerprint validation on the stream engines, the backend capability
registry, and the differentiable SparseFFN training path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import bit_identical
from test_differential import CASES, _adversarial, oracle_product

from repro.core import (
    backend_names,
    cached_plan,
    get_backend,
    plan_cache_clear,
    plan_cache_info,
    plan_spgemm,
    plan_spgemm_tiled,
    spgemm,
    spgemm_batched,
)
from repro.core import jax_stream
from repro.core.cost import CostConstants, choose_method
from repro.sparse import BatchedCSC, random_powerlaw_csc
from repro.sparse.format import CSC, csc_from_dense, csc_to_dense

F32 = np.float32


def _integerize(m: CSC, seed: int = 0) -> CSC:
    """Same pattern, small-integer values: every f32 sum is exact, so the
    device stream must agree with the f64 naive oracles with atol=0."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 4, size=m.nnz).astype(np.float64)
    return CSC(vals, m.row_indices, m.col_ptr, m.shape)


def _stored_coords(m: CSC):
    """(rows, cols) of every stored element, in storage order."""
    cp = np.asarray(m.col_ptr)
    rows = np.asarray(m.row_indices)[: m.nnz]
    cols = np.repeat(np.arange(m.n_cols, dtype=np.int32), np.diff(cp))
    return rows, cols


# --- differential: jax stream vs host stream vs oracles ---------------------


@pytest.mark.parametrize("case", CASES)
def test_jax_vs_host_stream_and_oracle(case):
    """backend="jax" computes the same C as the host stream engine and the
    external oracle on every adversarial pattern (f32 tolerance)."""
    a, b = _adversarial(case)
    pj = plan_spgemm(a, b, "expand", backend="jax")
    ph = plan_spgemm(a, b, "expand")
    cj = pj.execute(a, b)
    ch = ph.execute(a, b, engine="stream")
    # canonical structure is shared with the host stream bit-for-bit
    assert np.array_equal(np.asarray(cj.col_ptr), np.asarray(ch.col_ptr))
    assert np.array_equal(np.asarray(cj.row_indices)[: cj.nnz],
                          np.asarray(ch.row_indices)[: ch.nnz])
    np.testing.assert_allclose(
        np.asarray(cj.values), np.asarray(ch.values)[: ch.nnz],
        rtol=1e-5, atol=1e-6,
        err_msg=f"jax stream diverged from the host stream on {case!r}")
    np.testing.assert_allclose(
        csc_to_dense(cj.to_host()), oracle_product(a, b),
        rtol=1e-4, atol=1e-5,
        err_msg=f"jax stream diverged from the oracle on {case!r}")


@pytest.mark.parametrize("case", CASES)
def test_jax_integer_exact_vs_naive_oracles(case):
    """With exactly-representable values the device stream matches the f64
    naive oracles with atol=0 (no rounding anywhere, so f32 vs f64 and any
    re-association are invisible)."""
    a, b = _adversarial(case)
    a, b = _integerize(a, 1), _integerize(b, 2)
    cj = plan_spgemm(a, b, "expand", backend="jax").execute(a, b)
    for method in ("spa", "expand", "h-hash-256/256"):
        cn = plan_spgemm(a, b, method).execute(a, b, engine="naive")
        np.testing.assert_array_equal(
            csc_to_dense(cj.to_host()), csc_to_dense(cn),
            err_msg=f"jax stream != naive {method} on integer {case!r}")


def test_api_spellings_reach_the_jax_backend():
    a = random_powerlaw_csc(24, 2.0, seed=3)
    ref = csc_to_dense(spgemm(a, a, method="expand", cache=False))
    c = spgemm(a, a, method="expand", backend="jax", cache=False)
    np.testing.assert_allclose(csc_to_dense(c.to_host()), ref,
                               rtol=1e-5, atol=1e-6)
    # engine="stream" is the jax backend's (only) engine; explicit works
    c2 = spgemm(a, a, method="expand", backend="jax", engine="stream",
                cache=False)
    np.testing.assert_allclose(csc_to_dense(c2.to_host()), ref,
                               rtol=1e-5, atol=1e-6)


# --- gradients --------------------------------------------------------------


@pytest.mark.parametrize("case", ("random", "dup_heavy", "single_row",
                                  "rect_chain"))
def test_grad_matches_finite_differences(case):
    """jax.grad of sum(C.values) w.r.t. both operands' values matches
    central finite differences on the adversarial patterns."""
    a, b = _adversarial(case)
    plan = plan_spgemm(a, b, "expand", backend="jax")
    av = np.asarray(a.values)[: a.nnz].astype(F32)
    bv = np.asarray(b.values)[: b.nnz].astype(F32)

    def loss(x, y):
        return jnp.sum(plan.stream_apply(x, y))

    ga, gb = jax.grad(loss, argnums=(0, 1))(jnp.asarray(av),
                                            jnp.asarray(bv))
    assert ga.shape == av.shape and gb.shape == bv.shape
    rng = np.random.default_rng(0)
    eps = 1e-2
    for arr, grad, which in ((av, ga, 0), (bv, gb, 1)):
        for i in rng.choice(len(arr), size=min(4, len(arr)), replace=False):
            hi, lo = arr.copy(), arr.copy()
            hi[i] += eps
            lo[i] -= eps
            args_hi = (hi, bv) if which == 0 else (av, hi)
            args_lo = (lo, bv) if which == 0 else (av, lo)
            fd = (float(loss(*map(jnp.asarray, args_hi)))
                  - float(loss(*map(jnp.asarray, args_lo)))) / (2 * eps)
            np.testing.assert_allclose(
                float(grad[i]), fd, rtol=5e-2, atol=5e-3,
                err_msg=f"fd mismatch at {which}/{i} on {case!r}")


@pytest.mark.parametrize("case", ("random", "dup_heavy", "rect_chain"))
def test_grad_matches_dense_matmul_oracle(case):
    """Every product lands in a stored C slot, so sum(C.values) equals
    sum(A_dense @ B_dense) — and the stream's vjp must equal the dense
    matmul gradient gathered at the stored positions."""
    a, b = _adversarial(case)
    plan = plan_spgemm(a, b, "expand", backend="jax")
    av = jnp.asarray(np.asarray(a.values)[: a.nnz].astype(F32))
    bv = jnp.asarray(np.asarray(b.values)[: b.nnz].astype(F32))
    ga, gb = jax.grad(lambda x, y: jnp.sum(plan.stream_apply(x, y)),
                      argnums=(0, 1))(av, bv)

    ar, ac = _stored_coords(a)
    br, bc = _stored_coords(b)

    def dense_loss(x, y):
        ad = jnp.zeros(a.shape, F32).at[ar, ac].set(x)
        bd = jnp.zeros(b.shape, F32).at[br, bc].set(y)
        return jnp.sum(ad @ bd)

    da, db = jax.grad(dense_loss, argnums=(0, 1))(av, bv)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(da),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(db),
                               rtol=1e-4, atol=1e-5)


def test_single_segment_fed_by_every_product():
    """A [1, k] @ B [k, 1]: one C entry, one segment, every product of the
    stream summed into the same output slot."""
    k = 23
    a = csc_from_dense(np.arange(1, k + 1, dtype=np.float64).reshape(1, k))
    b = csc_from_dense(np.ones((k, 1)))
    plan = plan_spgemm(a, b, "expand", backend="jax")
    assert plan.stream.n_products == k and plan.stream.nnz == 1
    c = plan.execute(a, b)
    assert c.nnz == 1
    np.testing.assert_array_equal(np.asarray(c.values)[: c.nnz],
                                  [k * (k + 1) / 2])
    np.testing.assert_array_equal(
        np.asarray(plan.stream_apply(np.asarray(a.values, F32),
                                     np.asarray(b.values, F32))),
        [k * (k + 1) / 2])


def test_grad_of_entries_that_meet_no_product_is_zero():
    """Stored A entries whose B row is empty and stored B entries whose A
    column is empty take part in no product: their cotangent is exactly
    zero, and every other one is the dense matmul gradient."""
    a, b = _adversarial("empty_cols")
    plan = plan_spgemm(a, b, "expand", backend="jax")
    av = jnp.asarray(np.asarray(a.values)[: a.nnz].astype(F32))
    bv = jnp.asarray(np.asarray(b.values)[: b.nnz].astype(F32))
    ga, gb = jax.grad(lambda x, y: jnp.sum(plan.stream_apply(x, y)),
                      argnums=(0, 1))(av, bv)

    _, ac = _stored_coords(a)
    br, _ = _stored_coords(b)
    b_row_empty = np.bincount(br, minlength=b.shape[0]) == 0
    a_col_empty = np.bincount(ac, minlength=a.shape[1]) == 0
    a_idle, b_idle = b_row_empty[ac], a_col_empty[br]
    assert a_idle.any() and b_idle.any() and not a_idle.all()
    np.testing.assert_array_equal(np.asarray(ga)[a_idle], 0.0)
    np.testing.assert_array_equal(np.asarray(gb)[b_idle], 0.0)
    # d sum(C) / dA[i, j] = sum_c B[j, c];  d sum(C) / dB[r, c] = sum_i A[i, r]
    b_rowsum = np.bincount(br, np.asarray(bv, np.float64), b.shape[0])
    a_colsum = np.bincount(ac, np.asarray(av, np.float64), a.shape[1])
    np.testing.assert_allclose(np.asarray(ga), b_rowsum[ac],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gb), a_colsum[br],
                               rtol=1e-5, atol=1e-5)


# --- vmap batched path ------------------------------------------------------


def test_vmap_batched_bit_identical_to_looped():
    a = random_powerlaw_csc(36, 3.0, seed=4)
    plan = plan_spgemm(a, a, "expand", backend="jax")
    rng = np.random.default_rng(5)
    vals = rng.normal(size=(5, a.nnz)).astype(F32)
    stats = {}
    batched = plan.execute_batched(vals, vals, stats=stats)
    assert stats["path"] == "vmap" and stats["batch"] == 5
    looped = [plan.execute(vals[i], vals[i]) for i in range(5)]
    for x, y in zip(batched, looped):
        assert np.array_equal(np.asarray(x.values), np.asarray(y.values))
        assert x.row_indices is y.row_indices  # shared frozen structure


def test_spgemm_batched_rides_the_jax_backend():
    a = random_powerlaw_csc(30, 2.5, seed=6)
    rng = np.random.default_rng(7)
    ab = BatchedCSC.from_values(a, rng.normal(size=(3, a.nnz)).astype(F32))
    got = spgemm_batched(ab, ab, method="expand", backend="jax",
                         engine="stream", cache=False)
    want = [spgemm(ab[i], ab[i], method="expand", cache=False)
            for i in range(3)]
    for x, y in zip(got, want):
        np.testing.assert_allclose(
            csc_to_dense(x.to_host()), csc_to_dense(y),
            rtol=1e-5, atol=1e-6)


# --- cached-trace steady state ---------------------------------------------


def test_zero_retrace_after_warmup():
    """Same-shape executions replay one compiled trace — the per-step
    Python work after warmup is one dispatch, not a plan traversal."""
    a = random_powerlaw_csc(28, 2.5, seed=8)
    plan = plan_spgemm(a, a, "expand", backend="jax")
    fn = jax_stream.stream_fn(plan)
    assert jax_stream.stream_fn(plan) is fn          # memoized on the plan
    rng = np.random.default_rng(9)
    for _ in range(4):
        v = rng.normal(size=a.nnz).astype(F32)
        fn(v, v)
    assert fn.func._cache_size() == 1   # the jitted replay
    # the execute path's one-table fn likewise traces once
    for _ in range(4):
        v = rng.normal(size=a.nnz).astype(F32)
        plan.execute(v, v)
    assert jax_stream.table_fn(plan).func._cache_size() == 1
    # the batched fn is its own single trace per batch shape
    bfn = jax_stream.stream_fn_batched(plan)
    for _ in range(3):
        v = rng.normal(size=(6, a.nnz)).astype(F32)
        bfn(v, v)
    assert bfn.func._cache_size() == 1


def test_replay_takes_the_stream_as_arguments():
    """The plan's index arrays are arguments of the jitted replay, not
    constants compiled into it (which would copy the whole stream into the
    executable)."""
    a = random_powerlaw_csc(200, 4.0, seed=4)
    plan = plan_spgemm(a, a, "expand", backend="jax")
    fn = jax_stream.stream_fn(plan)
    (idx,) = fn.args
    idx_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(idx))
    assert idx_bytes >= 3 * 4 * plan.stream.n_products
    mem = fn.func.lower(idx, a.values, a.values).compile().memory_analysis()
    assert mem.argument_size_in_bytes >= idx_bytes


# --- one value table on the execute path ------------------------------------


def _operands(kind):
    """(plan, a operand, b operand, A's values, B's values) of each way
    the execute path is called."""
    rng = np.random.default_rng(21)
    if kind == "a_times_b":
        a, b = _adversarial("rect_chain")
        assert a.nnz != b.nnz
    else:
        a = b = random_powerlaw_csc(40, 3.0, seed=22)
    plan = plan_spgemm(a, b, "expand", backend="jax")
    av = rng.normal(size=a.nnz).astype(F32)
    bv = av if a is b else rng.normal(size=b.nnz).astype(F32)
    if kind == "a_times_b":
        return plan, av, bv, av, bv
    if kind == "same_buffer":
        return plan, av, av, av, av
    if kind == "csc":
        ao = CSC(av, a.row_indices, a.col_ptr, a.shape)
        return plan, ao, ao, av, av
    if kind == "jax_arrays":
        return plan, jnp.asarray(av), jnp.asarray(av), av, av
    if kind == "mixed":
        return plan, jnp.asarray(av), av, av, av
    assert kind == "oversized"
    long_a = np.concatenate([av, rng.normal(size=7).astype(F32)])
    long_b = np.concatenate([av, rng.normal(size=3).astype(F32)])
    return plan, long_a, long_b, av, av


@pytest.mark.parametrize("kind", ["a_times_b", "same_buffer", "csc",
                                  "jax_arrays", "mixed", "oversized"])
def test_one_table_execute_bit_identical_to_two_tables(kind):
    """On concrete operands the execute path gathers both operands from
    one packed table and builds no other executable; C is the two-table
    contraction's, bit for bit."""
    plan, x, y, av, bv = _operands(kind)
    got = plan.execute(x, y)
    memo = plan._stream_memo
    assert "jax_fn_table" in memo
    assert "jax_fn" not in memo and "jax_fn_batched" not in memo
    want = jax_stream.stream_fn(plan)(av, bv)
    np.testing.assert_array_equal(np.asarray(got.values), np.asarray(want))
    table = jax_stream.pack_table(plan, _operand(x), _operand(y))
    np.testing.assert_array_equal(np.asarray(table),
                                  np.concatenate([av, bv]))


def _operand(x):
    return x.values if isinstance(x, CSC) else x


@pytest.mark.parametrize("oversized", [False, True])
def test_one_table_grad_splits_into_operand_cotangents(oversized):
    """Differentiated through the packing, the one-table contraction's
    cotangent splits back into the two-table vjp's operand cotangents
    (oversized operands keep oversized ones, zero past the nnz)."""
    a, b = _adversarial("rect_chain")
    plan = plan_spgemm(a, b, "expand", backend="jax")
    rng = np.random.default_rng(23)
    extra = 5 if oversized else 0
    av = jnp.asarray(rng.normal(size=a.nnz + extra).astype(F32))
    bv = jnp.asarray(rng.normal(size=b.nnz + extra).astype(F32))
    one = jax_stream._one_table(jax_stream._contract(plan), a.nnz)
    idx = plan._stream_memo["device"].indices

    def packed(x, y):
        table = jnp.concatenate([x[: a.nnz], y[: b.nnz]])
        return jnp.sum(one(idx, table) ** 2)

    def two(x, y):
        return jnp.sum(plan.stream_apply(x, y) ** 2)

    for got, want in zip(jax.grad(packed, argnums=(0, 1))(av, bv),
                         jax.grad(two, argnums=(0, 1))(av, bv)):
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grad_through_execute_keeps_two_tables():
    """Traced operands (a caller's ``jax.grad``) take the two-table traced
    entry: the same gradients as ``stream_apply``."""
    a, b = _adversarial("dup_heavy")
    plan = plan_spgemm(a, b, "expand", backend="jax")
    av = jnp.asarray(np.asarray(a.values)[: a.nnz].astype(F32))
    bv = jnp.asarray(np.asarray(b.values)[: b.nnz].astype(F32))
    got = jax.grad(lambda x, y: jnp.sum(plan.execute(x, y).values),
                   argnums=(0, 1))(av, bv)
    assert "jax_fn_table" not in plan._stream_memo
    want = jax.grad(lambda x, y: jnp.sum(plan.stream_apply(x, y)),
                    argnums=(0, 1))(av, bv)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_table_form_follows_the_prefetch_limits(monkeypatch):
    """One table where two would leave a gather in HBM and one fits the
    cross-program prefetch; two tables otherwise; one without VMEM."""
    from repro import runtime

    monkeypatch.setattr(runtime, "prefetch_limits", lambda: None)
    assert jax_stream.table_form(10 ** 3, 10 ** 3, 10, 4) == "one"
    assert jax_stream.table_form(10 ** 8, 10 ** 8, 10, 4) == "one"
    assert jax_stream.table_form(jax_stream._I32_MAX, 1, 10, 4) == "two"
    monkeypatch.setattr(runtime, "prefetch_limits",
                        lambda: runtime.PrefetchLimits(4000, 400, 1000))
    form = jax_stream.table_form
    assert form(100, 100, 1000, 4) == "two"      # both prefetched anyway
    assert form(101, 500, 1000, 4) == "one"      # B gathered from HBM
    assert form(100, 100, 1001, 4) == "one"      # too long a gather
    assert form(100, 100, 1000, 8) == "two"      # limits found at 4 B
    assert form(101, 500, 1000, 2) == "two"
    assert form(101, 899, 1000, 4) == "one"      # 4000 B: fits
    assert form(101, 900, 1000, 4) == "two"      # one table would not fit
    monkeypatch.setattr(runtime, "prefetch_limits",
                        lambda: runtime.PrefetchLimits(0, 0, 0))
    assert form(1, 1, 1, 4) == "two"


@pytest.mark.parametrize("limits", [(0, 0, 0), (10 ** 9, 10 ** 9, 10 ** 9)],
                         ids=["nothing_fits", "both_prefetched"])
def test_execute_keeps_two_tables_where_one_does_not_help(monkeypatch,
                                                          limits):
    """Where the rule says two, the execute path builds and runs the
    two-table executable alone, with the same C."""
    from repro import runtime

    plan, x, y, av, bv = _operands("a_times_b")
    want = np.asarray(plan.execute(x, y).values)
    plan = plan_spgemm(*_adversarial("rect_chain"), "expand", backend="jax")
    monkeypatch.setattr(runtime, "prefetch_limits",
                        lambda: runtime.PrefetchLimits(*limits))
    got = plan.execute(x, y)
    memo = plan._stream_memo
    assert "jax_fn" in memo and "jax_fn_table" not in memo
    np.testing.assert_array_equal(np.asarray(got.values), want)


# --- guard fallback and capability errors -----------------------------------


def test_guarded_plan_falls_back_to_host_engine():
    a = random_powerlaw_csc(40, 3.0, seed=10)
    guarded = plan_spgemm(a, a, "expand", backend="jax", stream_limit=1)
    full_host = plan_spgemm(a, a, "expand")
    stats = {}
    c = guarded.execute(a, a, stats=stats)
    assert stats["fallback"] == "host" and stats["backend"] == "jax"
    assert bit_identical(c, full_host.execute(a, a, engine="stream"))
    # batched fallback too
    vals = np.random.default_rng(11).normal(size=(3, a.nnz))
    for x, y in zip(guarded.execute_batched(vals, vals),
                    full_host.execute_batched(vals, vals,
                                              engine="stream")):
        assert bit_identical(x, y)


def test_guarded_plan_raises_under_trace():
    a = random_powerlaw_csc(24, 2.5, seed=12)
    guarded = plan_spgemm(a, a, "expand", backend="jax", stream_limit=1)
    vals = jnp.asarray(np.asarray(a.values)[: a.nnz].astype(F32))
    with pytest.raises(ValueError, match="guard"):
        jax.jit(lambda v: guarded.stream_apply(v, v))(vals)
    with pytest.raises(ValueError, match="guard"):
        jax.grad(lambda v: jnp.sum(
            jax_stream.execute_jax(guarded, v, v).values))(vals)


# --- fingerprint validation on the stream engines (host + jax) --------------


def _colliding_pair(n=16):
    a = csc_from_dense(np.eye(n))
    b = csc_from_dense(np.roll(np.eye(n), 1, axis=0))
    assert a.shape == b.shape and a.nnz == b.nnz
    return a, b


@pytest.mark.parametrize("backend, engine", [("host", "stream"),
                                             ("jax", None)])
def test_validate_fingerprint_covers_stream_engines(backend, engine):
    a, corrupt = _colliding_pair()
    plan = plan_spgemm(a, a, "expand", backend=backend)
    plan.execute(corrupt, corrupt, engine=engine)   # O(1) hole: accepted
    with pytest.raises(ValueError, match="fingerprint"):
        plan.execute(corrupt, corrupt, engine=engine,
                     validate="fingerprint")
    ok = plan.execute(a, a, engine=engine, validate="fingerprint")
    assert ok.shape == (16, 16)
    # batched stream paths validate identically
    bad = BatchedCSC.stack([corrupt, corrupt])
    with pytest.raises(ValueError, match="fingerprint"):
        plan.execute_batched(bad, bad, engine=engine,
                             validate="fingerprint")
    good = BatchedCSC.stack([a, a])
    plan.execute_batched(good, good, engine=engine,
                         validate="fingerprint")


# --- engine plumbing and the capability registry ----------------------------


def test_engine_capability_errors():
    a = random_powerlaw_csc(20, 2.0, seed=13)
    pj = plan_spgemm(a, a, "expand", backend="jax")
    with pytest.raises(ValueError, match="unknown engine"):
        pj.execute(a, a, engine="bogus")
    # the retired fused engine is an unknown spelling on both device
    # backends, and the error lists the valid ones
    pp = plan_spgemm(a, a, "spa", backend="pallas")
    for plan in (pj, pp):
        with pytest.raises(ValueError, match="unknown engine 'fused'; one "
                           "of None, 'naive', 'stream'"):
            plan.execute(a, a, engine="fused")
    # the jax backend has no naive oracles (bit_exact_oracle=False)
    with pytest.raises(ValueError, match="naive"):
        pj.execute(a, a, engine="naive")
    with pytest.raises(ValueError, match="naive"):
        pj.execute_batched(np.stack([np.asarray(a.values)] * 2),
                           np.stack([np.asarray(a.values)] * 2),
                           engine="naive")
    # uniform spelling across the api entry points
    ab = BatchedCSC.stack([a, a])
    with pytest.raises(ValueError, match="naive"):
        spgemm_batched(ab, ab, method="expand", backend="jax",
                       engine="naive", cache=False)
    with pytest.raises(ValueError, match="host-backend"):
        spgemm(a, a, method="spa", backend="pallas", engine="stream",
               cache=False)


def test_backend_registry_contracts():
    assert set(backend_names()) >= {"host", "pallas", "jax"}
    host, pallas, jx = (get_backend(n) for n in ("host", "pallas", "jax"))
    assert host.bit_exact_oracle and not host.supports_grad
    assert jx.supports_grad and jx.device_resident and jx.carries_stream
    # pallas plans carry the pattern's product stream too (built lazily,
    # for stream_apply); the XLA stream is the one device lowering of it
    assert pallas.carries_stream and pallas.cost_domain == "relative"
    assert "expand" in pallas.excluded_methods
    assert pallas.engines == (None, "naive")
    assert jx.engines == (None, "stream")
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("cuda")
    with pytest.raises(ValueError, match="unknown backend"):
        spgemm(random_powerlaw_csc(8, 1.0, seed=0),
               random_powerlaw_csc(8, 1.0, seed=0), backend="cuda")


def test_jax_method_spellings_share_one_canonical_plan():
    """The jax numeric phase is method-independent, so every method
    spelling must collapse to one canonical plan (one LRU entry, one
    host+device stream) instead of per-spelling duplicates."""
    plan_cache_clear()
    a = random_powerlaw_csc(26, 2.5, seed=18)
    from repro.core.api import _cached_plan
    from repro.core.planner import resolve_params

    p1 = _cached_plan(a, a, "expand", "jax", {})
    p2 = _cached_plan(a, a, "spa", "jax", {})
    p3 = _cached_plan(a, a, "h-hash-256/256", "jax",
                      resolve_params("h-hash-256/256"))
    assert p1 is p2 is p3 and p1.method == "expand"
    assert plan_cache_info()["size"] == 1
    assert plan_spgemm(a, a, "spa", backend="jax").method == "expand"
    # the public accessor shares the same LRU entry
    from repro.core import cached_plan

    assert cached_plan(a, a, "spa", backend="jax") is p1
    # explicit oracle-tuning knobs are rejected loudly, not discarded
    for fn in (lambda: spgemm(a, a, "h-hash-256/256", backend="jax",
                              b_min=8, cache=False),
               lambda: plan_spgemm(a, a, "h-hash-256/256", backend="jax",
                                   b_min=8),
               lambda: cached_plan(a, a, "h-hash-256/256", backend="jax",
                                   b_min=8)):
        with pytest.raises(ValueError, match="do not apply"):
            fn()
    # ...but a named method whose *defaults* carry knobs still collapses
    assert spgemm(a, a, "h-hash-256/256", backend="jax",
                  cache=False).nnz == p1.execute(a, a).nnz
    plan_cache_clear()


def test_jax_default_method_plans_on_a_miss():
    """With no method, the default ``h-hash-256/256`` collapses to the
    canonical ``expand`` before its knobs reach the planner: a miss
    through every cached entry point plans instead of raising."""
    a = random_powerlaw_csc(30, 2.5, seed=19)
    want = plan_spgemm(a, a, "expand", backend="host").execute(a, a)
    for call in (lambda: cached_plan(a, a, backend="jax").execute(a, a),
                 lambda: spgemm(a, a, backend="jax"),
                 lambda: spgemm_batched(BatchedCSC.stack([a, a]),
                                        BatchedCSC.stack([a, a]),
                                        backend="jax")[1]):
        plan_cache_clear()
        got = call()
        assert np.allclose(np.asarray(got.values), want.values, rtol=1e-5)
        assert plan_cache_info()["misses"] == 1
    plan_cache_clear()


def test_stream_apply_works_on_pallas_plans():
    """Pallas plans carry a product stream, so ``stream_apply`` traces the
    same contraction as a host/jax plan of the pattern."""
    a = random_powerlaw_csc(20, 2.0, seed=19)
    pallas_plan = plan_spgemm(a, a, "spa", backend="pallas")
    host_plan = plan_spgemm(a, a, "expand", backend="host")
    vals = pallas_plan.stream_apply(np.asarray(a.values, F32),
                                    np.asarray(a.values, F32))
    ref = host_plan.execute(a, a, engine="stream")
    np.testing.assert_allclose(np.asarray(vals), ref.values, rtol=2e-6)


def test_stream_apply_checks_operand_shapes():
    """The jitted gathers promise in-bounds indices, so short operands
    must be rejected before tracing, tracer-safely."""
    a = random_powerlaw_csc(22, 2.0, seed=20)
    plan = plan_spgemm(a, a, "expand", backend="jax")
    with pytest.raises(ValueError, match="values"):
        plan.stream_apply(np.zeros(2, F32), np.zeros(a.nnz, F32))
    with pytest.raises(ValueError, match="1-D"):
        plan.stream_apply(np.zeros((2, a.nnz), F32), np.zeros(a.nnz, F32))


def test_device_stream_bytes_reported_separately():
    plan_cache_clear()
    a = random_powerlaw_csc(32, 3.0, seed=14)
    spgemm(a, a, method="expand", cache=True)              # host stream
    info = plan_cache_info()
    assert info["stream_bytes"] > 0 and info["device_stream_bytes"] == 0
    spgemm(a, a, method="expand", backend="jax", cache=True)
    info = plan_cache_info()
    assert info["device_stream_bytes"] > 0
    # the jax plan keeps the host stream it was lifted from (both halves)
    assert info["stream_bytes"] > 0
    plan_cache_clear()


# --- the "jax" auto candidate (mixed tile grids) ----------------------------


def test_tiled_jax_candidate_executes_and_matches():
    a = _integerize(random_powerlaw_csc(40, 3.0, seed=15), 16)
    ref = csc_to_dense(plan_spgemm(a, a, "spa").execute(a, a))
    plan = plan_spgemm_tiled(a, a, tile=(20, 20), candidates=("jax",),
                             cache=False)
    stats = {}
    c = plan.execute(a, a, stats=stats)
    assert stats["methods"] == ["jax"]
    np.testing.assert_array_equal(csc_to_dense(c), ref)
    # an explicit engine must hold on every tile: "stream" does (host and
    # jax tiles both implement it), "naive" does not (device tiles cannot
    # keep its bit-exact f64 promise) and is loudly rejected
    mixed = plan_spgemm_tiled(a, a, tile=(20, 20),
                              candidates=("spa", "jax"), cache=False)
    for engine in (None, "stream"):
        np.testing.assert_array_equal(
            csc_to_dense(mixed.execute(a, a, engine=engine)), ref)
    with pytest.raises(ValueError, match="every tile"):
        mixed.execute(a, a, engine="naive")
    with pytest.raises(ValueError, match="every tile"):
        mixed.execute_batched(np.stack([np.asarray(a.values)] * 2),
                              np.stack([np.asarray(a.values)] * 2),
                              engine="naive")
    outs = mixed.execute_batched(
        np.stack([np.asarray(a.values)] * 2),
        np.stack([np.asarray(a.values)] * 2), engine="stream")
    np.testing.assert_array_equal(csc_to_dense(outs[0]), ref)


def test_cost_model_can_pick_the_jax_candidate():
    """With device-favourable calibrated constants the auto chooser picks
    the jax stream for in-guard tiles (deterministic via constants=)."""
    from repro.sparse.stats import tile_stats

    a = random_powerlaw_csc(48, 4.0, seed=17)
    st = tile_stats(a, a)
    fast_dev = CostConstants(jax_base=1e-7, jax_prod=1e-10)
    assert choose_method(st, "host", candidates=("spa", "expand", "jax"),
                         constants=fast_dev) == "jax"
    slow_dev = CostConstants(jax_base=10.0, jax_prod=1.0)
    assert choose_method(st, "host", candidates=("spa", "expand", "jax"),
                         constants=slow_dev) != "jax"
