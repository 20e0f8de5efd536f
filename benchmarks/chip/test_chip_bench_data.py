"""CPU tests of the benchmark's data: the committed Table-1 patterns and
the Graph 500 generator."""

import hashlib
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import work  # noqa: E402


def _config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_table1_patterns_have_the_published_sizes():
    from repro.sparse.suitesparse import SUITESPARSE_TABLE1

    cfg = _config("table1_sparse22")
    mats = harness.generate(cfg, 0)
    published = SUITESPARSE_TABLE1[:22]
    assert [m.name for m in mats] == [s.name for s in published]
    for m, spec in zip(mats, published):
        assert (m.n, m.nnz) == (spec.n, spec.nnz)
        assert len(m.indptr) == m.n + 1 and np.all(np.diff(m.indptr) >= 0)
        for j in range(m.n):
            col = m.indices[m.indptr[j]:m.indptr[j + 1]]
            assert np.all(np.diff(col) > 0)
        assert m.indices.min() >= 0 and m.indices.max() < m.n
    assert sum(work.products(m.indptr, m.indices) for m in mats) == 1006334


def test_table1_patterns_are_what_the_script_regenerates():
    from repro.sparse.suitesparse import synthesize_suitesparse

    (m,) = harness.generate({"generator": "table1", "params": {
        "file": "table1_sparse22.npz",
        "matrices": [{"name": "poli", "n": 4008, "nnz": 8188}]}}, 0)
    fresh, _ = synthesize_suitesparse("poli", seed=0)
    assert np.array_equal(np.asarray(fresh.col_ptr), m.indptr)
    assert np.array_equal(np.asarray(fresh.row_indices), m.indices)


def test_table1_loader_refuses_a_wrong_size():
    with pytest.raises(ValueError):
        harness.generate({"generator": "table1", "params": {
            "file": "table1_sparse22.npz",
            "matrices": [{"name": "poli", "n": 4008, "nnz": 8189}]}}, 0)


def _kron(scale, seed):
    """The Kronecker cell's graph, at ``scale``."""
    cfg = _config("graph500_kron14")
    (m,) = harness.generate(
        {**cfg, "params": {**cfg["params"], "scale": scale}}, seed)
    return m


def _kron_digest(seed, scale=13):
    m = _kron(scale, seed)
    h = hashlib.sha256()
    h.update(m.indptr.tobytes())
    h.update(m.indices.tobytes())
    return h.hexdigest()


def test_kron13_is_the_same_graph_in_two_processes():
    seed = 2 ** 35 + 3
    script = ("import sys; sys.path.insert(0, %r); "
              "import test_chip_bench_data as t; print(t._kron_digest(%d))"
              % (str(HERE), seed))
    digests = {subprocess.run([sys.executable, "-c", script], check=True,
                              capture_output=True, text=True,
                              timeout=120).stdout.strip() for _ in range(2)}
    assert digests == {_kron_digest(seed)}
    assert _kron_digest(seed + 1) not in digests


def _assert_undirected(m):
    """Symmetric, with no self-loops."""
    cols = np.repeat(np.arange(m.n), np.diff(m.indptr))
    assert not np.any(cols == m.indices)
    fwd = np.sort(cols * m.n + m.indices)
    assert np.array_equal(fwd, np.sort(m.indices * m.n + cols))


def test_kron13_sizes_at_seed_0():
    # the generator's sizes one scale below the cell, at seed 0
    m = _kron(13, 0)
    assert (m.n, m.nnz) == (8192, 203870)
    assert work.products(m.indptr, m.indices) == 54459894
    _assert_undirected(m)


def test_kron14_sizes_at_seed_0():
    cfg = _config("graph500_kron14")
    (m,) = harness.generate(cfg, 0)
    want = cfg["sizes_at_seed_0"]
    assert cfg["params"]["scale"] == 14
    assert (m.n, m.nnz) == (want["n"], want["nnz"])
    assert work.products(m.indptr, m.indices) == want["products"]
    _assert_undirected(m)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5, 4_000_000_007])
def test_kron14_stays_under_one_chips_plan_guard(seed):
    # the device guard of one v5e (16 GB): 176,138,917 products
    m = _kron(14, seed)
    assert 1.5e8 < work.products(m.indptr, m.indices) < 176_138_917
