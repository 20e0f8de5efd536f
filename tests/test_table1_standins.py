"""Every Table 1 stand-in the benchmark cells multiply, through the XLA
product stream at its published size, against ``scipy.sparse`` in float64.

The patterns are the committed ``benchmarks/chip/data/table1_*.npz`` files,
read and never written.  Each case computes C = A·A with seeded f32 values
on the ``jax`` backend (the device stream, run by XLA on the CPU here) and
checks C's pattern exactly and its values to f32 rounding.
"""

from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import cached_plan, plan_cache_clear
from repro.sparse.format import CSC

DATA = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "data"
FILES = ("table1_sparse22.npz", "table1_dense18.npz")
# above the largest stand-in's A·A (iprob, 9,003,003 products), so no case
# falls back to the host stream on the CPU's smaller default guard
STREAM_LIMIT = 10_000_000


def _names(file):
    with np.load(DATA / file) as z:
        return [str(n) for n in z["names"]]


CASES = [(f, n) for f in FILES for n in _names(f)]


@pytest.fixture
def fresh_cache():
    plan_cache_clear()
    yield
    plan_cache_clear()


@pytest.mark.parametrize("file,name", CASES, ids=[n for _, n in CASES])
def test_standin_squared_matches_scipy(fresh_cache, file, name):
    with np.load(DATA / file) as z:
        indptr, indices = z[f"{name}.indptr"], z[f"{name}.indices"]
    n = len(indptr) - 1
    # positive values: no product cancels, so no C entry is an exact zero
    # that scipy would drop from its pattern
    values = np.random.default_rng(len(indices)).uniform(
        0.5, 1.5, len(indices)).astype(np.float32)
    a = CSC(values, indices, indptr, (n, n))
    plan = cached_plan(a, a, backend="jax", stream_limit=STREAM_LIMIT)
    assert plan.stream is not None, "the plan fell back to the host"
    c = plan.execute(a, a)

    ref = sp.csc_matrix((values.astype(np.float64), indices, indptr),
                        shape=(n, n))
    ref = (ref @ ref).tocsc()
    ref.sort_indices()
    got = sp.csc_matrix((np.asarray(c.values, np.float64)[: c.nnz],
                         np.asarray(c.row_indices)[: c.nnz],
                         np.asarray(c.col_ptr)), shape=(n, n))
    got.sort_indices()
    np.testing.assert_array_equal(got.indptr, ref.indptr)
    np.testing.assert_array_equal(got.indices, ref.indices)
    np.testing.assert_allclose(got.data, ref.data, rtol=1e-5)
