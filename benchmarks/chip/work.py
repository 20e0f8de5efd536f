"""The work of one sparse multiply C = A·B, from operand and result sizes.

Nothing here reads the program's plan or its product stream: the counts
depend only on the operands' patterns and the size of the reference's C,
so a later lowering of the same multiply is measured against the same
work.

- products: the scalar products A[i,k]·B[k,j] that any SpGEMM must form,
  ``sum over B's entries (k, j) of nnz(A[:, k])``;
- flops: one multiply and one add per product;
- bytes: f32 values and int32 row indices of A, B and C, each read or
  written once: ``(4 + 4) * (nnz_A + nnz_B + nnz_C)``.

The least time of a multiply on a chip is the larger of bytes over peak
HBM bandwidth and flops over peak FLOP/s (``peaks.json``).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

#: bytes of one stored entry: an f32 value and an int32 row index
ENTRY_BYTES = 4 + 4


def products(a_indptr, b_indices) -> int:
    """Scalar products of A·B: each entry (k, j) of B meets column k of A."""
    return int(np.diff(np.asarray(a_indptr, np.int64))[b_indices].sum())


def multiply_work(a_indptr, b_indices, nnz_a: int, nnz_b: int,
                  nnz_c: int) -> dict:
    """``{"products", "flops", "bytes"}`` of one multiply."""
    p = products(a_indptr, b_indices)
    return {"products": p, "flops": 2 * p,
            "bytes": ENTRY_BYTES * (int(nnz_a) + int(nnz_b) + int(nnz_c))}


def load_peaks(kind: str) -> dict:
    """Peaks of one device kind; a kind missing from the table is an error."""
    table = json.loads(Path(__file__).with_name("peaks.json").read_text())
    if kind not in table["kinds"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table["kinds"][kind]


def least_time_s(work: dict, peaks: dict) -> tuple:
    """``(seconds, bound)``: the least time of ``work`` at ``peaks`` and
    which of ``"bytes"`` or ``"flops"`` sets it."""
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_flops = work["flops"] / peaks["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
