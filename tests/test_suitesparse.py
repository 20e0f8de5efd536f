"""SuiteSparse stand-in generator: published-statistics fidelity (E4 input)."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.sparse import (
    SUITESPARSE_TABLE1, matrix_stats, synthesize_suitesparse, validate_csc,
)
from repro.sparse.suitesparse import by_name

FAST = ("poli", "olm1000", "oscil_dcop_30", "str_200", "iprob")


@pytest.mark.parametrize("name", FAST)
def test_generated_stats_match_published(name):
    spec = by_name(name)
    m, st = synthesize_suitesparse(spec, seed=0)
    validate_csc(m)
    assert st.nnz == spec.nnz
    assert st.n_rows == spec.n
    assert st.nnz_min == spec.nnz_min
    assert st.nnz_max == spec.nnz_max
    assert abs(st.nnz_var - spec.nnz_var) <= max(0.15 * spec.nnz_var, 0.3)
    assert abs(st.mult_avg - spec.mult_avg) <= max(0.15 * spec.mult_avg, 1.0)


def test_same_seed_same_matrix_in_every_process():
    """The generator's stream does not depend on the process's salted
    ``str`` hash: two interpreters with different hash seeds agree."""
    script = ("import hashlib; from repro.sparse import synthesize_suitesparse;"
              " m, _ = synthesize_suitesparse('olm1000', seed=0);"
              " print(hashlib.sha256(m.row_indices.tobytes()).hexdigest())")
    src = str(Path(__file__).resolve().parents[1] / "src")
    outs = {subprocess.run([sys.executable, "-c", script], check=True,
                           capture_output=True, text=True, timeout=300,
                           env=dict(os.environ, PYTHONHASHSEED=str(salt),
                                    JAX_PLATFORMS="cpu", PYTHONPATH=src)
                           ).stdout for salt in (1, 2)}
    assert len(outs) == 1, outs


def test_arrow_structure_forced():
    """iprob: every column must reference the 3000-nnz mega column."""
    m, st = synthesize_suitesparse("iprob", seed=0)
    assert st.mult_min >= 2900  # published minimum is 3002


def test_table_is_consistent():
    assert len(SUITESPARSE_TABLE1) == 40
    for s in SUITESPARSE_TABLE1:
        assert len(s.paper_speedups) == 9
        assert s.nnz_min <= s.nnz_avg <= s.nnz_max
        assert s.spa_seconds > 0


def test_caching_roundtrip(tmp_path):
    from repro.sparse.suitesparse import load_or_synthesize

    m1, _ = load_or_synthesize("olm1000", seed=0, cache_dir=str(tmp_path))
    m2, _ = load_or_synthesize("olm1000", seed=0, cache_dir=str(tmp_path))
    np.testing.assert_array_equal(np.asarray(m1.row_indices),
                                  np.asarray(m2.row_indices))
