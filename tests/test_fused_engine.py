"""Fused Pallas stream kernel (core/pallas_stream.py, DESIGN.md §11):
differential equivalence vs the host stream on the adversarial harness
(atol=0 on integer-valued inputs), segment-boundary edge cases of the
window-accumulate strategy (straddling segments, tile-edge boundaries,
P % block != 0, grad-view empty segments), gradient checks vs finite
differences and the XLA device stream, vmap-vs-looped bit-identity with a
B-independent launch count, cached-trace steady state, guard
fallback/capability errors, cross-backend engine="fused" spellings, tiled
"fused" auto-candidate grids, and fused_stream_bytes cache telemetry."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import bit_identical
from test_differential import CASES, _adversarial, oracle_product

from repro.core import (
    pallas_stream,
    plan_cache_clear,
    plan_cache_info,
    plan_spgemm,
    plan_spgemm_tiled,
    spgemm,
    spgemm_batched,
)
from repro.core.api import cached_plan
from repro.core.pallas_stream import fused_fn, fused_fn_batched, fused_stream
from repro.sparse import BatchedCSC, random_powerlaw_csc
from repro.sparse.format import CSC, csc_from_dense, csc_to_dense

F32 = np.float32


def _integerize(m: CSC, seed: int = 0) -> CSC:
    """Same pattern, small-integer values: every f32 sum is exact, so the
    fused kernel must agree with the f64 host stream with atol=0."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 4, size=m.nnz).astype(np.float64)
    return CSC(vals, m.row_indices, m.col_ptr, m.shape)


def _stored_coords(m: CSC):
    cp = np.asarray(m.col_ptr)
    rows = np.asarray(m.row_indices)[: m.nnz]
    cols = np.repeat(np.arange(m.n_cols, dtype=np.int32), np.diff(cp))
    return rows, cols


def _host_stream(a: CSC, b: CSC) -> CSC:
    return plan_spgemm(a, b, "expand").execute(a, b, engine="stream")


# --- differential: fused kernel vs host stream vs oracle ---------------------


@pytest.mark.parametrize("case", CASES)
def test_fused_vs_host_stream_and_oracle(case):
    """engine="fused" shares the host stream's canonical structure
    bit-for-bit and matches its values at f32 tolerance on every
    adversarial pattern."""
    a, b = _adversarial(case)
    pf = plan_spgemm(a, b, "expand", backend="jax")
    cf = pf.execute(a, b, engine="fused")
    ch = _host_stream(a, b)
    assert np.array_equal(np.asarray(cf.col_ptr), np.asarray(ch.col_ptr))
    assert np.array_equal(np.asarray(cf.row_indices)[: cf.nnz],
                          np.asarray(ch.row_indices)[: ch.nnz])
    np.testing.assert_allclose(
        np.asarray(cf.values), np.asarray(ch.values)[: ch.nnz],
        rtol=1e-5, atol=1e-6,
        err_msg=f"fused kernel diverged from the host stream on {case!r}")
    np.testing.assert_allclose(
        csc_to_dense(cf.to_host()), oracle_product(a, b),
        rtol=1e-4, atol=1e-5,
        err_msg=f"fused kernel diverged from the oracle on {case!r}")


@pytest.mark.parametrize("case", CASES)
def test_fused_integer_exact_vs_host_stream(case):
    """Integer-valued operands: the fused kernel is bit-comparable (atol=0)
    to the host stream — f32 vs f64 and any re-association are invisible
    when every partial sum is exactly representable."""
    a, b = _adversarial(case)
    a, b = _integerize(a, 1), _integerize(b, 2)
    cf = plan_spgemm(a, b, "expand", backend="jax").execute(
        a, b, engine="fused")
    ch = _host_stream(a, b)
    np.testing.assert_array_equal(
        np.asarray(cf.values), np.asarray(ch.values)[: ch.nnz],
        err_msg=f"fused kernel not bit-comparable on integer {case!r}")


def test_api_spellings_reach_the_fused_engine():
    """engine="fused" works through spgemm() on both device backends."""
    a = random_powerlaw_csc(24, 2.0, seed=3)
    ref = csc_to_dense(_host_stream(a, a))
    for backend, method in (("jax", "expand"), ("pallas", "spa")):
        c = spgemm(a, a, method=method, backend=backend, engine="fused",
                   cache=False)
        np.testing.assert_allclose(
            csc_to_dense(c.to_host()), ref, rtol=1e-5, atol=1e-6,
            err_msg=f"engine='fused' wrong through backend={backend!r}")


def test_fused_single_launch_on_both_backends():
    a = random_powerlaw_csc(30, 2.5, seed=4)
    for backend, method in (("jax", "expand"), ("pallas", "spa")):
        plan = plan_spgemm(a, a, method, backend=backend)
        stats = {}
        plan.execute(a, a, engine="fused", stats=stats)
        assert stats["engine"] == "fused"
        assert stats["backend"] == backend
        assert stats["n_launches"] == 1       # the whole numeric phase
        assert stats["fused_block"] == pallas_stream.FUSED_BLOCK


# --- segment-boundary edge cases (the window-accumulate invariant) -----------


def _fused_vals(plan, a, b, block):
    fn = fused_fn(plan, block=block)
    return np.asarray(fn(jnp.asarray(np.asarray(a.values)[: a.nnz], F32),
                         jnp.asarray(np.asarray(b.values)[: b.nnz], F32)))


def test_single_segment_spanning_every_tile():
    """A [1, k] @ B [k, 1] with k products: one output segment straddles
    every product-axis tile, so every grid step accumulates into the same
    output slot."""
    k = 23                                     # not divisible by block=4
    a = csc_from_dense(np.arange(1, k + 1, dtype=np.float64).reshape(1, k))
    b = csc_from_dense(np.ones((k, 1)))
    plan = plan_spgemm(a, b, "expand", backend="jax")
    ch = _host_stream(a, b)
    for block in (1, 4, 8, 64):
        got = _fused_vals(plan, a, b, block)
        np.testing.assert_array_equal(
            got, np.asarray(ch.values)[: ch.nnz],
            err_msg=f"straddling segment wrong at block={block}")


def test_segment_boundary_exactly_on_tile_edge():
    """Segments of exactly block-size products: every segment boundary
    coincides with a tile edge (local ids hit block-1 then reset)."""
    block = 4
    # A = [1, k] dense row blocks, B block-diagonal: C[0, j] sums exactly
    # `block` products for every j, so seg_starts = 0, 4, 8, ...
    n_seg = 6
    k = block * n_seg
    a = csc_from_dense(np.arange(1, k + 1, dtype=np.float64).reshape(1, k))
    bd = np.zeros((k, n_seg))
    for j in range(n_seg):
        bd[j * block:(j + 1) * block, j] = np.arange(1, block + 1)
    b = csc_from_dense(bd)
    plan = plan_spgemm(a, b, "expand", backend="jax")
    s = plan.stream
    assert np.array_equal(np.asarray(s.seg_starts),
                          np.arange(n_seg) * block)
    ch = _host_stream(a, b)
    got = _fused_vals(plan, a, b, block)
    np.testing.assert_array_equal(got, np.asarray(ch.values)[: ch.nnz])


def test_products_not_divisible_by_tile_size():
    """P % block != 0: the padded tail (masked to zero) must not perturb
    the last real segments."""
    a = _integerize(random_powerlaw_csc(20, 2.5, seed=7), 3)
    plan = plan_spgemm(a, a, "expand", backend="jax")
    p = plan.stream.n_products
    ch = _host_stream(a, a)
    for block in (7, 13, p - 1, p + 1):
        if block < 1:
            continue
        got = _fused_vals(plan, a, a, block)
        np.testing.assert_array_equal(
            got, np.asarray(ch.values)[: ch.nnz],
            err_msg=f"padded-tail corruption at block={block} (P={p})")


def test_empty_grad_segments_scatter_zero():
    """Stored operand values with zero products (empty grad segments) must
    receive exactly-zero cotangent through the compact-id out_map scatter —
    the case that would break the [0, block) window invariant if the grad
    views kept empty segments inline."""
    # A[:, 0] has a stored value but B row 0 is empty: a_pos never visits it
    ad = np.array([[1.0, 2.0], [0.0, 3.0]])
    bd = np.array([[0.0, 0.0], [4.0, 5.0]])
    a, b = csc_from_dense(ad), csc_from_dense(bd)
    plan = plan_spgemm(a, b, "expand", backend="jax")
    fs = fused_stream(plan, block=2)
    assert fs.grad_a.n_out < a.nnz            # compact: absent positions
    av = jnp.asarray(np.asarray(a.values)[: a.nnz], F32)
    bv = jnp.asarray(np.asarray(b.values)[: b.nnz], F32)
    fn = fused_fn(plan, block=2)
    ga, gb = jax.grad(lambda x, y: jnp.sum(fn(x, y)),
                      argnums=(0, 1))(av, bv)
    # d sum(C) / dA[0,0] = 0 (row 0 of B empty); dA[0,1] = dA[1,1] = 4+5;
    # d sum(C) / dB[1,j] = sum of A's column 1 = 2+3
    np.testing.assert_array_equal(np.asarray(ga), [0.0, 9.0, 9.0])
    np.testing.assert_array_equal(np.asarray(gb), [5.0, 5.0])


def test_empty_stream_and_empty_operand():
    """P == 0 plans (empty A) still execute and differentiate: zero values
    on the canonical structure, zero gradients."""
    a = csc_from_dense(np.zeros((8, 8)))
    b = csc_from_dense(np.random.default_rng(0).normal(size=(8, 8)))
    plan = plan_spgemm(a, b, "expand", backend="jax")
    c = plan.execute(a, b, engine="fused")
    assert c.nnz == 0
    bv = jnp.asarray(np.asarray(b.values)[: b.nnz], F32)
    fn = fused_fn(plan)
    gb = jax.grad(lambda y: jnp.sum(fn(jnp.zeros(0, F32), y)))(bv)
    np.testing.assert_array_equal(np.asarray(gb), np.zeros(b.nnz, F32))


# --- gradients ---------------------------------------------------------------


@pytest.mark.parametrize("case", ("random", "dup_heavy", "single_row",
                                  "rect_chain"))
def test_fused_grad_matches_finite_differences(case):
    a, b = _adversarial(case)
    plan = plan_spgemm(a, b, "expand", backend="jax")
    fn = fused_fn(plan)
    av = np.asarray(a.values)[: a.nnz].astype(F32)
    bv = np.asarray(b.values)[: b.nnz].astype(F32)

    def loss(x, y):
        return jnp.sum(fn(x, y))

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
def test_fused_grad_matches_dense_matmul_oracle(case):
    a, b = _adversarial(case)
    plan = plan_spgemm(a, b, "expand", backend="jax")
    fn = fused_fn(plan)
    av = jnp.asarray(np.asarray(a.values)[: a.nnz].astype(F32))
    bv = jnp.asarray(np.asarray(b.values)[: b.nnz].astype(F32))
    ga, gb = jax.grad(lambda x, y: jnp.sum(fn(x, y)),
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


def test_fused_grad_matches_xla_stream_grad():
    """Both device lowerings of the same bilinear contraction must agree
    on the gradient (shared custom-vjp machinery, different replays)."""
    a = random_powerlaw_csc(28, 2.5, seed=11)
    plan = plan_spgemm(a, a, "expand", backend="jax")
    fn = fused_fn(plan)
    av = jnp.asarray(np.asarray(a.values)[: a.nnz].astype(F32))
    w = jnp.asarray(np.random.default_rng(12).normal(
        size=plan.stream.nnz).astype(F32))
    gf = jax.grad(lambda x: jnp.sum(w * fn(x, x)))(av)
    gx = jax.grad(lambda x: jnp.sum(w * plan.stream_apply(x, x)))(av)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gx),
                               rtol=1e-5, atol=1e-6)


def test_stream_apply_engine_fused_is_the_traced_entry():
    """``plan.stream_apply(..., engine="fused")`` is the README/traced-code
    spelling of the fused lowering: same values as ``fused_fn``, same
    gradients, and unknown engines are rejected."""
    a = random_powerlaw_csc(24, 2.5, seed=21)
    plan = plan_spgemm(a, a, "spa", backend="pallas")
    av = jnp.asarray(np.asarray(a.values)[: a.nnz].astype(F32))
    via_apply = plan.stream_apply(av, av, engine="fused")
    assert np.array_equal(np.asarray(via_apply),
                          np.asarray(fused_fn(plan)(av, av)))
    ga = jax.grad(
        lambda x: jnp.sum(plan.stream_apply(x, x, engine="fused")))(av)
    gx = jax.grad(lambda x: jnp.sum(plan.stream_apply(x, x)))(av)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gx),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="engine"):
        plan.stream_apply(av, av, engine="naive")


# --- vmap batched path -------------------------------------------------------


def test_fused_vmap_batched_bit_identical_to_looped():
    a = random_powerlaw_csc(36, 3.0, seed=4)
    plan = plan_spgemm(a, a, "expand", backend="jax")
    rng = np.random.default_rng(5)
    vals = rng.normal(size=(5, a.nnz)).astype(F32)
    stats = {}
    batched = plan.execute_batched(vals, vals, engine="fused", stats=stats)
    assert stats["path"] == "vmap" and stats["batch"] == 5
    assert stats["n_launches"] == 1           # independent of B
    looped = [plan.execute(vals[i], vals[i], engine="fused")
              for i in range(5)]
    for x, y in zip(batched, looped):
        assert np.array_equal(np.asarray(x.values), np.asarray(y.values))
        assert x.row_indices is y.row_indices  # shared frozen structure


def test_spgemm_batched_rides_the_fused_engine():
    a = random_powerlaw_csc(30, 2.5, seed=6)
    rng = np.random.default_rng(7)
    ab = BatchedCSC.from_values(a, rng.normal(size=(3, a.nnz)).astype(F32))
    got = spgemm_batched(ab, ab, method="expand", backend="jax",
                         engine="fused", cache=False)
    want = [spgemm(ab[i], ab[i], method="expand", cache=False)
            for i in range(3)]
    for x, y in zip(got, want):
        np.testing.assert_allclose(
            csc_to_dense(x.to_host()), csc_to_dense(y),
            rtol=1e-5, atol=1e-6)


def test_fused_zero_retrace_after_warmup():
    a = random_powerlaw_csc(28, 2.5, seed=8)
    plan = plan_spgemm(a, a, "expand", backend="jax")
    fn = fused_fn(plan)
    assert fused_fn(plan) is fn               # memoized on the plan
    rng = np.random.default_rng(9)
    # the fused contraction is shared by every plan (the views are its
    # arguments), so count the traces this plan's calls add
    for f, shape in ((fn, (a.nnz,)), (fused_fn_batched(plan), (6, a.nnz))):
        f(*[rng.normal(size=shape).astype(F32)] * 2)
        warm = f.func._cache_size()
        for _ in range(3):
            v = rng.normal(size=shape).astype(F32)
            f(v, v)
        assert f.func._cache_size() == warm


# --- guard fallback and capability errors ------------------------------------


def test_guarded_fused_falls_back_to_host_engine():
    a = random_powerlaw_csc(40, 3.0, seed=10)
    full_host = plan_spgemm(a, a, "expand")
    for backend, method in (("jax", "expand"), ("pallas", "spa")):
        guarded = plan_spgemm(a, a, method, backend=backend,
                              stream_limit=1)
        stats = {}
        c = guarded.execute(a, a, engine="fused", stats=stats)
        assert stats["fallback"] == "host"
        assert stats["backend"] == backend
        assert bit_identical(c, full_host.execute(a, a, engine="stream"))
        vals = np.random.default_rng(11).normal(size=(3, a.nnz))
        for x, y in zip(
                guarded.execute_batched(vals, vals, engine="fused"),
                full_host.execute_batched(vals, vals, engine="stream")):
            assert bit_identical(x, y)


def test_guarded_fused_raises_under_trace():
    a = random_powerlaw_csc(24, 2.5, seed=12)
    guarded = plan_spgemm(a, a, "expand", backend="jax", stream_limit=1)
    vals = jnp.asarray(np.asarray(a.values)[: a.nnz].astype(F32))
    with pytest.raises(ValueError, match="guard"):
        jax.jit(lambda v: pallas_stream.execute_fused(
            guarded, v, v).values)(vals)
    with pytest.raises(ValueError, match="guard"):
        fused_fn(guarded)


def test_fused_rejects_streamless_spelling_on_host():
    a = random_powerlaw_csc(16, 2.0, seed=13)
    plan = plan_spgemm(a, a, "expand")          # host backend
    with pytest.raises(ValueError, match="fused"):
        plan.execute(a, a, engine="fused")


# --- tiled "fused" auto candidate --------------------------------------------


def test_tiled_fused_candidate_runs_the_fused_engine():
    a = _integerize(random_powerlaw_csc(40, 3.0, seed=14), 5)
    tp = plan_spgemm_tiled(a, a, backend="jax", candidates=("fused",),
                           cache=False)
    assert set(tp.methods.values()) == {"fused"}
    assert all(t.engine == "fused" for t in tp.tiles)
    ch = _host_stream(a, a)
    ct = tp.execute(a.values, a.values)
    np.testing.assert_array_equal(csc_to_dense(ct), csc_to_dense(ch))
    assert tp.fused_stream_nbytes > 0           # views built by execution
    # an explicit engine= overrides the per-tile choice uniformly
    cs = tp.execute(a.values, a.values, engine="stream")
    np.testing.assert_allclose(csc_to_dense(cs), csc_to_dense(ch),
                               rtol=1e-5, atol=1e-6)


def test_host_auto_never_picks_fused_on_cpu_constants():
    """The calibrated interpret-mode constants keep "fused" out of every
    CPU tile choice even though it is a host auto candidate."""
    a = random_powerlaw_csc(48, 3.0, seed=15)
    tp = plan_spgemm_tiled(a, a, backend="host", cache=False)
    assert "fused" not in set(tp.methods.values())


# --- cache telemetry ---------------------------------------------------------


def test_fused_stream_bytes_reported_separately():
    plan_cache_clear()
    a = random_powerlaw_csc(32, 3.0, seed=16)
    plan = cached_plan(a, a, "expand", backend="jax")
    info = plan_cache_info()
    assert info["fused_stream_bytes"] == 0      # lazy: not built yet
    plan.execute(a, a, engine="stream")
    assert plan_cache_info()["fused_stream_bytes"] == 0   # stream != fused
    plan.execute(a, a, engine="fused")
    info = plan_cache_info()
    assert info["fused_stream_bytes"] > 0
    assert info["fused_stream_bytes"] == plan.fused_stream_nbytes
    # the three stream kinds are accounted independently
    assert info["stream_bytes"] > 0
    assert info["device_stream_bytes"] > 0      # stream engine built it
    plan_cache_clear()
    assert plan_cache_info()["fused_stream_bytes"] == 0
