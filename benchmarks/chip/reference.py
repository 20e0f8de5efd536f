"""The plain reference for C = A·B and the comparison that decides
``correct``.

The reference is scipy's sparse product in float64 on the host.  It
imports nothing of the program and takes nothing the program made: only
the operands' patterns and values, which the benchmark generated itself.

The comparison holds the program's C to the reference:

- ``structure_mismatches``: entries (i, j) stored by one side and not the
  other; the limit is 0 (the pattern of C is exact);
- ``max_rel_err``: over C's entries, ``|c - ref| / ref``.  The
  configurations' values are positive, so each reference entry is the sum
  of the absolute products that make it, and the error is bounded by the
  rounding of that sum alone: cancellation cannot inflate it.

The control (:func:`bf16_values`) is the reference with its operands
rounded to bfloat16, the nearest precision below the float32 the
configurations state.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def _csc(indptr, indices, values, shape):
    return sp.csc_matrix((np.asarray(values, np.float64), indices, indptr),
                         shape=shape)


def product(a: tuple, b: tuple, a_values, b_values):
    """Reference C = A·B: ``(indptr, indices, values)`` in float64.

    ``a`` and ``b`` are ``(indptr, indices, shape)`` patterns.  The values
    must be non-negative: then no entry of C sums to an exact zero, scipy
    keeps every structurally present entry, and each entry is its own
    ``|A|·|B|``.
    """
    if min(np.min(a_values, initial=0), np.min(b_values, initial=0)) < 0:
        raise ValueError("the reference takes non-negative operand values")
    c = _csc(a[0], a[1], a_values, a[2]) @ _csc(b[0], b[1], b_values, b[2])
    c.sort_indices()
    return c.indptr, c.indices, c.data


def _keys(indptr, indices, n_rows: int) -> np.ndarray:
    """Column-major linear index of every stored entry (ascending)."""
    cols = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64),
                     np.diff(indptr))
    return cols * int(n_rows) + np.asarray(indices, np.int64)


def compare(got: tuple, ref: tuple, n_rows: int) -> dict:
    """Numbers of one answer: ``{"structure_mismatches", "max_rel_err"}``.

    ``got`` is ``(indptr, indices, values)`` of the program's C and ``ref``
    what :func:`product` returned.  The error is taken over the entries
    both sides store.
    """
    g_ptr, g_idx, g_val = got
    r_ptr, r_idx, r_val = ref
    g_val = np.asarray(g_val, np.float64)
    if np.array_equal(g_ptr, r_ptr) and np.array_equal(g_idx, r_idx):
        mismatches = 0
        gi = ri = slice(None)
    else:
        gk = _keys(g_ptr, g_idx, n_rows)
        rk = _keys(r_ptr, r_idx, n_rows)
        common, gi, ri = np.intersect1d(gk, rk, assume_unique=True,
                                        return_indices=True)
        mismatches = len(gk) + len(rk) - 2 * len(common)
    rel = np.abs(g_val[gi] - r_val[ri]) / r_val[ri]
    err = float(rel.max()) if rel.size else 0.0
    return {"structure_mismatches": int(mismatches), "max_rel_err": err}


def bf16_values(values) -> np.ndarray:
    """``values`` rounded to bfloat16 (the control's operands)."""
    import ml_dtypes

    return np.asarray(values, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)
