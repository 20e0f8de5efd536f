"""Regenerate ``table1_dense18.npz``: the patterns of the 18 denser
Table-1 stand-ins.

    PYTHONPATH=src python benchmarks/chip/data/make_table1_dense18.py

Rows 23-40 of the paper's Table 1 (``watt_1`` … ``iprob``, mult/col
average 39.4 to 3,003).  As for ``table1_sparse22.npz``, the collection
cannot be fetched here, so the program's stand-in synthesizer
(``repro.sparse.suitesparse``) builds matrices with the published n, nnz
and per-column statistics; they are synthesized once, with seed 0, and
committed pattern-only (values come from each run's ``--seed``).
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

#: rows 23-40 of Table 1: the 22 sparsest come before them
FIRST = 22
OUT = Path(__file__).resolve().with_name("table1_dense18.npz")


def pattern(spec) -> tuple:
    """``(indptr, indices)`` of one Table-1 row's stand-in, int32."""
    from repro.sparse.suitesparse import synthesize_suitesparse

    m, _ = synthesize_suitesparse(spec, seed=0)
    return (np.asarray(m.col_ptr, np.int32),
            np.asarray(m.row_indices, np.int32))


def arrays() -> dict:
    """The npz's arrays: ``names``, then each matrix's indptr and indices."""
    from repro.sparse.suitesparse import SUITESPARSE_TABLE1

    out = {}
    names = []
    for spec in SUITESPARSE_TABLE1[FIRST:]:
        names.append(spec.name)
        indptr, indices = pattern(spec)
        out[f"{spec.name}.indptr"] = indptr
        out[f"{spec.name}.indices"] = indices
        print(f"{spec.name}: n={spec.n} nnz={indptr[-1]}", flush=True)
    return {"names": np.array(names), **out}


def main() -> None:
    np.savez_compressed(OUT, **arrays())
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
