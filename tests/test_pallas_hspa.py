"""H-SPA(t) on the per-group Pallas kernels (interpret mode) through the
normal path, and the kernels' VMEM budget (DESIGN.md §2).

The patterns are small arrows: a few entries a column plus one full
column and one dense row, the shape of ``iprob`` and ``adder_dcop_01``
(Table 1), whose full column sets A's table width ``za`` and whose dense
row sends the columns of C it reaches through it.
"""

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro import runtime
from repro.core import api, cached_plan
from repro.kernels import spa, vmem
from repro.sparse.format import CSC

#: a v5e's kernel budget
V5E = runtime.KERNEL_VMEM_BYTES["TPU v5 lite"]


def _arrow(n: int, seed: int, per_col: int = 3) -> sp.csc_matrix:
    """``per_col`` random entries a column, a full column at ``d`` and a
    row ``d`` over every other column, values in [0.5, 1.5): the columns
    the row reaches gather the full column (SPA), the others stay light
    (SPARS)."""
    rng = np.random.default_rng(seed)
    d = int(rng.integers(n))
    rows = [rng.choice(n, per_col, replace=False) for _ in range(n)]
    rows[d] = np.arange(n)
    r = np.concatenate(rows + [np.full(n // 2, d)])
    c = np.concatenate([np.full(len(x), j) for j, x in enumerate(rows)]
                       + [np.arange(0, n, 2)[: n // 2]])
    m = sp.csc_matrix((np.ones(len(r)), (r, c)), shape=(n, n))
    m.sum_duplicates()
    m.sort_indices()
    m.data = rng.uniform(0.5, 1.5, m.nnz)
    return m


def _csc(m: sp.csc_matrix, values) -> CSC:
    return CSC(np.asarray(values, np.float32), m.indices.astype(np.int32),
               m.indptr.astype(np.int32), m.shape)


def _compare(c: CSC, ref: sp.csc_matrix) -> tuple:
    """``(structure_mismatches, max_rel_err)`` as the benchmark's check
    (positive values: the reference's pattern is its nonzeros)."""
    ptr = np.asarray(c.col_ptr)
    rows = np.asarray(c.row_indices)[: ptr[-1]]
    cols = np.repeat(np.arange(len(ptr) - 1), np.diff(ptr))
    got = np.zeros(ref.shape)
    got[rows, cols] = np.asarray(c.values, np.float64)[: ptr[-1]]
    want = ref.toarray()
    mismatches = len(set(zip(rows.tolist(), cols.tolist()))
                     ^ set(zip(*(x.tolist() for x in want.nonzero()))))
    nz = want != 0
    return mismatches, float(np.max(np.abs(got[nz] - want[nz]) / want[nz]))


@pytest.mark.parametrize("n,seed", [(96, 0), (160, 1), (256, 2)])
def test_hspa_through_cached_plan_matches_scipy(n, seed):
    """``cached_plan`` then ``plan.execute`` with host value arrays, as
    the benchmark's replay calls them, against scipy in float64."""
    api.plan_cache_clear()
    m = _arrow(n, seed)
    plan = cached_plan(_csc(m, m.data), _csc(m, m.data), "h-spa-40/40",
                       backend="pallas")
    assert {g.kind for g in plan.pallas.groups} == {"spa", "spars"}
    for k in range(2):
        v = np.random.default_rng(seed + 10 * k).uniform(
            0.5, 1.5, m.nnz).astype(np.float32)
        c = plan.execute(v, v)
        a = sp.csc_matrix((v.astype(np.float64), m.indices, m.indptr),
                          shape=m.shape)
        mismatches, err = _compare(c, a @ a)
        assert mismatches == 0 and err <= 1e-4
    # the launches' products add up to the multiply's
    assert sum(g.products for g in plan.pallas.groups) == int(
        np.diff(m.indptr)[m.indices].sum())
    api.plan_cache_clear()


def test_bounded_trip_counts_change_no_bit():
    """The kernel's loops stop at :func:`spa.trip_counts`; the full loops
    (every B entry, every row of A's table) give the same tile to the
    bit."""
    from repro.sparse import csc_to_padded_columns

    m = _arrow(96, 5)
    r, v, z = (np.asarray(x) for x in csc_to_padded_columns(_csc(m, m.data)))
    args = (r.astype(np.int32), v.astype(np.float32), z.astype(np.int32))
    bounded = spa.spa_spgemm(*args, *args, m=96, block_cols=32)
    z8 = -(-r.shape[1] // 8) * 8
    full = (np.full(3, z8, np.int32), np.full(3 * z8, z8, np.int32))
    whole = spa.spa_spgemm(*args, *args, full, m=96, block_cols=32)
    assert np.array_equal(np.asarray(bounded), np.asarray(whole))


def test_trip_counts_by_hand():
    """Block 0 (lanes 0-1): B columns of 2 and 1 entries gathering A
    columns of 3, 1 and 2 entries; block 1: one empty column."""
    a_nnz = np.array([3, 1, 2, 0], np.int32)
    b_rows = np.array([[0, 2], [1, 0], [0, 0], [3, 3]], np.int32)
    b_nnz = np.array([2, 1, 0, 0], np.int32)
    for xp in (jnp, np):                 # in the kernel's jit, in a plan
        b_trips, z_trips = spa.trip_counts(a_nnz, b_rows, b_nnz, 2, xp=xp)
        assert np.asarray(b_trips).tolist() == [2, 0]
        assert np.asarray(z_trips).tolist() == [3, 2, 0, 0, 0, 0, 0, 0,
                                                0, 0, 0, 0, 0, 0, 0, 0]


def test_vmem_rule_counts_the_tables_once():
    """A's byte-plane table takes one buffer: growing A's width by one
    32-row int8 tile adds that tile to each of its six planes (two of row
    codes at m = 1813, four of values) once, and the int32 gather of those
    rows twice (the dot's result and its scratch), not a second copy of
    the table."""
    kw = dict(m=1813, n_a=1813, zb=1332, block_cols=128)
    grow = vmem.vmem_bytes("spa", za=1364, **kw) \
        - vmem.vmem_bytes("spa", za=1332, **kw)
    planes = vmem.row_planes(1813) + vmem.VALUE_PLANES
    assert planes == 6
    table_tile = vmem.tile_bytes(32, 1813, 1)
    assert table_tile == 32 * 1920
    assert grow == planes * table_tile + 2 * planes * vmem.tile_bytes(32, 128)
    assert vmem.tile_bytes(3000, 3001) == 3000 * 3072 * 4
    assert vmem.tile_bytes(3000, 3001, 1) == 3008 * 3072


def _dense18():
    """``(name, n, largest column)`` of each matrix of the cell
    ``table1_dense18.hspa``: the shapes of its 18 plans (A·A, so B's
    widest column is A's)."""
    from pathlib import Path

    data = Path(__file__).parents[1] / "benchmarks" / "chip" / "data"
    with np.load(data / "table1_dense18.npz") as d:
        return [(str(name), len(d[f"{name}.indptr"]) - 1,
                 int(np.diff(d[f"{name}.indptr"]).max()))
                for name in d["names"]]


@pytest.mark.parametrize("kind", ["spa", "spars"])
@pytest.mark.parametrize("i", range(18))
def test_every_dense18_plan_fits_a_v5e(kind, i):
    """Each of the 18 plans' launches passes ``vmem.check`` at a v5e's
    budget."""
    name, n, z = _dense18()[i]
    vmem.check(kind, vmem.vmem_bytes(kind, m=n, za=z, n_a=n, zb=z,
                                     block_cols=128), V5E)


@pytest.mark.parametrize("kind", ["spa", "spars"])
@pytest.mark.parametrize("name,n,z", [("adder_dcop_01", 1813, 1332),
                                      ("iprob", 3001, 3000)])
def test_the_widest_table1_matrices_fit_a_v5e(kind, name, n, z):
    """Over the compiler's default 16 MiB, under a v5e's budget."""
    need = vmem.vmem_bytes(kind, m=n, za=z, n_a=n, zb=z, block_cols=128)
    assert runtime.DEFAULT_KERNEL_VMEM_BYTES < need <= V5E
    vmem.check(kind, need, V5E)


def test_vmem_check_refuses_over_the_budget_and_names_it():
    vmem.check("spa", 100, None)                  # the CPU: no budget
    vmem.check("spa", V5E, V5E)
    with pytest.raises(ValueError, match=f"{V5E:,} bytes"):
        vmem.check("spa", V5E + 1, V5E)
    with pytest.raises(ValueError, match="no VMEM rule"):
        vmem.vmem_bytes("hash", m=8, za=8, n_a=8, zb=8, block_cols=128)


def test_a_plan_over_the_budget_is_refused_when_it_is_built(monkeypatch):
    """Refused by the planner, naming the budget, before any launch."""
    runtime.kernel_vmem_bytes.cache_clear()
    m = _arrow(96, 7)
    a = _csc(m, m.data)
    need = max(vmem.vmem_bytes(k, m=96, za=96, n_a=96, zb=96,
                               block_cols=128) for k in ("spa", "spars"))
    monkeypatch.setattr(runtime, "kernel_vmem_bytes", lambda: need - 1)
    api.plan_cache_clear()
    with pytest.raises(ValueError, match=f"budget of {need - 1:,} bytes"):
        cached_plan(a, a, "h-spa-40/40", backend="pallas")
    monkeypatch.setattr(runtime, "kernel_vmem_bytes", lambda: need)
    assert cached_plan(a, a, "h-spa-40/40", backend="pallas").pallas.groups
    api.plan_cache_clear()


def test_no_budget_off_the_chip():
    runtime.kernel_vmem_bytes.cache_clear()
    assert runtime.kernel_vmem_bytes() is None


def _on_host(plan):
    """``plan`` with its tiles compacted on the host, tile by tile."""
    import dataclasses

    return dataclasses.replace(plan, pallas=dataclasses.replace(
        plan.pallas, device=None))


def _same(c: CSC, d: CSC) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in (
        (c.col_ptr, d.col_ptr), (c.row_indices, d.row_indices),
        (c.values, d.values))) and c.values.dtype == d.values.dtype


@pytest.mark.parametrize("method", ["h-spa-40/40", "spa", "spars-40/40"])
@pytest.mark.parametrize("signs", [False, True])
def test_device_compaction_is_the_host_compaction_to_the_bit(method, signs):
    """Operands padded on the device and one gather of a call's tiles give
    C as padding and compacting each tile on the host do, exact zeros
    dropped: values of either sign (``signs``: ±1, so some of C's entries
    cancel to 0) included."""
    from repro.core import plan_spgemm

    m = _arrow(128, 3)
    v = (np.random.default_rng(3).choice([-1.0, 1.0], m.nnz) if signs
         else m.data)
    a = _csc(m, v)
    plan = plan_spgemm(a, a, method, backend="pallas", block_cols=32)
    assert plan.pallas.device is not None
    stats = {}
    c = plan.execute(a, a, stats=stats)
    host_stats = {}
    want = _on_host(plan).execute(a, a, stats=host_stats)
    assert _same(c, want)
    assert (stats["compacted_on"], host_stats["compacted_on"]) == (
        "device", "host")
    assert stats["tile_shapes"] == host_stats["tile_shapes"]
    dropped = len(plan.pallas.device.c_rows) - int(
        np.asarray(c.col_ptr)[-1])
    assert (dropped > 0) == signs


def test_device_form_takes_raw_values_as_the_host_form_does():
    """Raw float64 value arrays longer than the pattern's nnz: both forms
    round them to float32 and read the first nnz."""
    m = _arrow(96, 8)
    a = _csc(m, m.data)
    api.plan_cache_clear()
    plan = cached_plan(a, a, "h-spa-40/40", backend="pallas")
    v = np.concatenate([m.data, [7.0, -3.0]])          # float64, nnz + 2
    w = np.random.default_rng(8).uniform(0.5, 1.5, m.nnz + 5)
    assert _same(plan.execute(v, w), _on_host(plan).execute(v, w))
    api.plan_cache_clear()


def test_device_compaction_holds_c_pattern_frozen():
    """The plan keeps C's symbolic pattern, read-only, and a result with no
    exact zero shares it."""
    m = _arrow(96, 4)
    a = _csc(m, m.data)
    api.plan_cache_clear()
    plan = cached_plan(a, a, "h-spa-40/40", backend="pallas")
    dc = plan.pallas.device
    ref = (sp.csc_matrix((np.ones(m.nnz), m.indices, m.indptr),
                         shape=m.shape) @ sp.csc_matrix(
        (np.ones(m.nnz), m.indices, m.indptr), shape=m.shape)).tocsc()
    ref.sort_indices()
    assert np.array_equal(dc.c_col_ptr, ref.indptr)
    assert np.array_equal(dc.c_rows, ref.indices)
    assert not dc.c_rows.flags.writeable
    assert not dc.c_col_ptr.flags.writeable
    c = plan.execute(a, a)
    assert c.row_indices is dc.c_rows and c.col_ptr is dc.c_col_ptr
    api.plan_cache_clear()


def test_calls_over_the_device_limit_stay_on_the_host(monkeypatch):
    """A plan whose tiles and B operands of one call exceed
    ``runtime.device_tile_bytes`` pads and compacts on the host, as does
    one with a HASH group; both give C as the device form does."""
    from repro.core import plan_spgemm

    m = _arrow(96, 6)
    a = _csc(m, m.data)
    fits = plan_spgemm(a, a, "h-spa-40/40", backend="pallas")
    zb = int(fits.pallas.groups[0].b_rows.shape[1])
    need = 4 * (96 + zb) * sum(fits.pallas.device.widths)
    monkeypatch.setattr(runtime, "device_tile_bytes", lambda: need)
    assert plan_spgemm(a, a, "h-spa-40/40",
                       backend="pallas").pallas.device is not None
    monkeypatch.setattr(runtime, "device_tile_bytes", lambda: need - 1)
    over = plan_spgemm(a, a, "h-spa-40/40", backend="pallas")
    assert over.pallas.device is None
    assert _same(over.execute(a, a), fits.execute(a, a))
    hashed = plan_spgemm(a, a, "h-hash-256/256", backend="pallas",
                         block_cols=32)
    assert "hash" in {g.kind for g in hashed.pallas.groups}
    assert hashed.pallas.device is None


def test_no_tile_limit_off_the_chip():
    runtime.device_tile_bytes.cache_clear()
    assert runtime.device_tile_bytes() is None
