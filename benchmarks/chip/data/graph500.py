"""Graph 500 Kronecker graph, made from a seed.

The benchmark's own copy of the generator in the Graph 500 specification
(graph500.org, "Graph 500 Benchmark" section 3): ``edgefactor * 2**scale``
edges, each placed by ``scale`` draws of the 2x2 initiator
``[[A, B], [C, D]]``; vertex numbers are relabelled by a random
permutation and the edge list shuffled.  Kernel 1 of the specification
then builds the undirected graph: every edge is stored both ways, and
self-loops and duplicate edges are removed.

The result is the symmetric adjacency pattern in CSC form (row indices
sorted within each column) with f32 values drawn from the same seed.
"""

from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edgefactor: int, a: float, b: float,
                    c: float, rng: np.random.Generator):
    """``(ii, jj)`` int64 endpoints of the specification's edge list."""
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ii = np.zeros(m, np.int64)
    jj = np.zeros(m, np.int64)
    for bit in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ii |= ii_bit.astype(np.int64) << bit
        jj |= jj_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    ii, jj = perm[ii], perm[jj]
    order = rng.permutation(m)
    return ii[order], jj[order]


def symmetric_pattern(n: int, ii: np.ndarray, jj: np.ndarray):
    """Undirected CSC pattern ``(indptr, indices)``: both directions, no
    self-loops, no duplicates, rows ascending within each column."""
    rows = np.concatenate([ii, jj])
    cols = np.concatenate([jj, ii])
    keep = rows != cols
    key = np.unique(cols[keep] * n + rows[keep])
    cols, rows = np.divmod(key, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(cols, minlength=n), out=indptr[1:])
    return indptr.astype(np.int32), rows.astype(np.int32)


def generate(params: dict, seed: int) -> list:
    """One matrix: ``[(name, indptr, indices, n)]`` for the configuration's
    ``params`` (scale, edgefactor, initiator A/B/C)."""
    rng = np.random.default_rng(seed)
    scale = int(params["scale"])
    ii, jj = kronecker_edges(scale, int(params["edgefactor"]),
                             float(params["A"]), float(params["B"]),
                             float(params["C"]), rng)
    n = 1 << scale
    indptr, indices = symmetric_pattern(n, ii, jj)
    return [(f"kron{scale}", indptr, indices, n)]
