"""Regenerate ``table1_sparse22.npz``: the patterns of the 22 sparsest
Table-1 stand-ins.

    PYTHONPATH=src python benchmarks/chip/data/make_table1_sparse22.py

The paper's Table 1 lists 40 SuiteSparse matrices; the collection cannot
be fetched here, so the program's stand-in synthesizer
(``repro.sparse.suitesparse``) builds matrices with the published n, nnz
and per-column statistics.  The benchmark treats them as fixed files, as
the collection's matrices are: they are synthesized once, with seed 0,
and committed pattern-only (values come from each run's ``--seed``).
Synthesis takes about a minute and a half on one CPU core.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

#: the first 22 rows of Table 1 (mult/col average up to 38.1)
COUNT = 22
OUT = Path(__file__).resolve().with_name("table1_sparse22.npz")


def main() -> None:
    from repro.sparse.suitesparse import SUITESPARSE_TABLE1, synthesize_suitesparse

    arrays = {}
    names = []
    for spec in SUITESPARSE_TABLE1[:COUNT]:
        m, _ = synthesize_suitesparse(spec, seed=0)
        names.append(spec.name)
        arrays[f"{spec.name}.indptr"] = np.asarray(m.col_ptr, np.int32)
        arrays[f"{spec.name}.indices"] = np.asarray(m.row_indices, np.int32)
        print(f"{spec.name}: n={spec.n} nnz={m.nnz}", flush=True)
    np.savez_compressed(OUT, names=np.array(names), **arrays)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
