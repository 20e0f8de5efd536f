"""Product-stream numeric engine: gather → multiply → segment-reduce.

Every SpGEMM algorithm in the paper enumerates the same multiset of scalar
products ``A[i,k] * B[k,j]``; once a symbolic plan has cached C's structure,
the numeric phase is a *fixed contraction* — which products exist, which C
slot each lands in, and in what order they sum is all pattern-only.  This
module precomputes that contraction as a flat :class:`ProductStream` (the
propagation-blocking formulation of Gu et al., built once at plan time) and
replays it with a handful of vectorized numpy kernels::

    prod   = a_values[a_pos] * b_values[b_pos]      # every scalar product
    c_vals = segment_reduce(prod, seg_starts)       # one sum per C slot

No per-column Python loop survives; batching over a leading value axis is a
free broadcast of the same two lines (DESIGN.md §9).

Contract versus the naive executors: output structure is *canonical* (rows
ascending within each column, exactly the ``expand`` method's layout) and
each C slot sums its products in the same sorted stream order ``expand``
uses — but ``np.add.reduceat`` may re-associate long sums pairwise, so
values agree with the oracles to last-ulp accumulation differences, not
necessarily bit-for-bit.  The naive executors remain the faithful oracles;
this engine is the fast path (``engine="stream"``).

Memory guard: a stream costs O(flops) plan-resident memory, so
:func:`build_product_stream` refuses streams above ``max_products`` and the
plan stores ``stream=None``.  Execution then rebuilds the stream
*transiently* (same code path, nothing retained), so results are
bit-identical whether or not the guard tripped.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from repro.core import spans
from repro.core.expand import expand_positions, product_count
from repro.sparse.format import CSC, _np, segment_reduce

# plan-resident stream guard: ~20 bytes per product of retained index data.
# Above this the plan keeps stream=None and executions rebuild transiently.
# DEFAULT_STREAM_MAX_PRODUCTS is the shipped fallback; the live knob below
# is what the cost model and planner consult, and a calibrated machine
# profile can retune it to this host's RAM via
# ``core.profile.apply_tuning`` (DESIGN.md §15).
DEFAULT_STREAM_MAX_PRODUCTS = 8_000_000
STREAM_MAX_PRODUCTS = DEFAULT_STREAM_MAX_PRODUCTS


def default_stream_limit(device: bool = False) -> int:
    """The plan-memory guard a plan gets when none is passed (products).

    Host plans use :data:`STREAM_MAX_PRODUCTS`.  Device plans (``device``:
    the backend's ``device_resident`` flag) use the guard sized from the
    chip's own memory on a TPU (:func:`repro.runtime.device_stream_limit`)
    and the host knob elsewhere.
    """
    if device:
        from repro import runtime

        limit = runtime.device_stream_limit()
        if limit is not None:
            return limit
    return STREAM_MAX_PRODUCTS


# batched execution: streams up to this many products run the whole value
# axis through one 2-D gather/reduce pass (amortizing per-call numpy
# overhead, the regime of small per-tile streams); longer streams loop the
# 1-D pass row by row — numpy's axis-1 fancy gather and reduceat are
# strided per segment and measure ~5x slower per element than the
# contiguous 1-D kernels, so a monolithic [B, P] pass only wins while
# per-row fixed overhead dominates (measured crossover ~1k products)
STREAM_BATCH_VECTOR_MAX = 1024
# ...and 2-D passes are row-blocked to bound the [block, P] working set
STREAM_BATCH_BLOCK_ELEMS = 1 << 20


@dataclasses.dataclass(frozen=True)
class ProductStream:
    """Pattern-only flat layout of every scalar product of ``C = A @ B``.

    ``a_pos``/``b_pos`` index the operands' value arrays, one entry per
    scalar product, stored with the C-slot sort permutation *pre-applied*
    (composed at plan time — re-executions pay no permute pass): products of
    C's p-th stored slot occupy ``[seg_starts[p], seg_starts[p+1])``, slots
    in canonical CSC order (column-major, rows ascending).  Within a
    segment, products keep Gustavson stream order — the same stable-lexsort
    order ``core.expand`` sums in.
    """

    a_pos: np.ndarray       # [P] int64: A value position of each product
    b_pos: np.ndarray       # [P] int64: B value position of each product
    seg_starts: np.ndarray  # [nnz_c] int64: reduceat segment boundaries
    c_rows: np.ndarray      # [nnz_c] int32: C's row indices
    c_col_ptr: np.ndarray   # [n+1] int32: C's column offsets
    shape: Tuple[int, int]

    @property
    def n_products(self) -> int:
        return int(self.a_pos.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.c_col_ptr[-1])

    @property
    def nbytes(self) -> int:
        """Plan-resident size of the stream's index arrays."""
        return (self.a_pos.nbytes + self.b_pos.nbytes
                + self.seg_starts.nbytes + self.c_rows.nbytes
                + self.c_col_ptr.nbytes)


def build_product_stream(a, b, max_products: int | None = None
                         ) -> Optional[ProductStream]:
    """Build the product stream for ``C = A @ B`` from structure alone.

    ``a``/``b``: anything with ``col_ptr``/``row_indices``/``shape``
    (:class:`~repro.core.planner.Pattern` or :class:`CSC`); values are never
    read.  Returns ``None`` when the stream would exceed ``max_products``
    (the plan-memory guard) — pass ``None`` to build unconditionally, as the
    transient fallback in :func:`execute_stream` does.

    The returned stream's arrays are frozen (non-writeable): results built
    by the engine share ``c_rows``/``c_col_ptr`` with the plan-resident
    stream, so an in-place mutation of a result must raise instead of
    silently corrupting every later same-plan execution.  The span
    ``spgemm.symbolic``, with the stream's ``products``.
    """
    with spans.span("spgemm.symbolic") as span:
        s = _build_product_stream(a, b, max_products)
        if s is not None:
            span.set(products=s.n_products)
        return s


def _build_product_stream(a, b, max_products) -> Optional[ProductStream]:
    a_cp = _np(a.col_ptr)
    a_rows = _np(a.row_indices)[: int(a_cp[-1])]
    b_cp = _np(b.col_ptr)
    b_rows = _np(b.row_indices)
    m, n = int(a.shape[0]), int(b.shape[1])

    if max_products is not None and product_count(
            a_cp, b_cp, b_rows) > max_products:
        return None
    # one entry per scalar product in Gustavson stream order — the same
    # index arithmetic core.expand builds on (single source: expand.py)
    a_pos, b_pos, cols = expand_positions(a_cp, b_cp, b_rows)
    total = len(a_pos)
    if total == 0:
        z = np.zeros(0, np.int64)
        return _frozen_stream(z, z.copy(), z.copy(), np.zeros(0, np.int32),
                              np.zeros(n + 1, np.int32), (m, n))
    rows = a_rows[a_pos].astype(np.int64)

    # sort products to C slots (stable: stream order survives per slot) and
    # pre-apply the permutation to the index arrays
    order = np.lexsort((rows, cols))
    rows, cols = rows[order], cols[order]
    key = cols * m + rows                  # ascending after the lexsort
    boundary = np.empty(total, bool)
    boundary[0] = True
    np.not_equal(key[1:], key[:-1], out=boundary[1:])
    starts = np.nonzero(boundary)[0].astype(np.int64)
    c_rows = rows[boundary].astype(np.int32)
    col_ptr = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount(cols[boundary], minlength=n), out=col_ptr[1:])
    return _frozen_stream(a_pos[order], b_pos[order], starts, c_rows,
                          col_ptr, (m, n))


def _frozen_stream(a_pos, b_pos, seg_starts, c_rows, c_col_ptr,
                   shape) -> ProductStream:
    for arr in (a_pos, b_pos, seg_starts, c_rows, c_col_ptr):
        arr.flags.writeable = False
    return ProductStream(a_pos, b_pos, seg_starts, c_rows, c_col_ptr, shape)


def _plan_stream(plan) -> tuple:
    """(stream, was_cached) — transient rebuild when the guard tripped."""
    s = plan.stream
    if s is not None:
        return s, True
    return build_product_stream(plan.a, plan.b), False


def execute_stream(plan, a_values: np.ndarray, b_values: np.ndarray,
                   stats: dict | None = None) -> CSC:
    """Numeric phase of a host plan through the product stream.

    ``a_values``/``b_values``: raw value arrays aligned with the planned
    patterns (already compatibility-checked by the executor).  The result is
    independent of ``plan.method`` — the stream engine computes the one
    canonical contraction every method agrees on.
    """
    s, cached = _plan_stream(plan)
    dtype = np.result_type(a_values.dtype, b_values.dtype)
    if s.n_products == 0:
        vals = np.zeros(0, dtype)
    else:
        prod = a_values[s.a_pos]
        prod = prod * b_values[s.b_pos]
        vals = segment_reduce(prod, s.seg_starts)
    if stats is not None:
        stats["engine"] = "stream"
        stats["stream_products"] = s.n_products
        stats["stream_cached"] = cached
        stats["result_shape"] = s.shape
    return CSC(vals.astype(dtype, copy=False), s.c_rows, s.c_col_ptr,
               s.shape)


def execute_stream_batched(plan, a_values: np.ndarray, b_values: np.ndarray,
                           stats: dict | None = None) -> list:
    """Batched stream execution: ``[B, nnz]`` stacks over the value axis.

    Short streams (``<= STREAM_BATCH_VECTOR_MAX`` products) run the whole
    value axis through 2-D gather/reduce passes in cache-bounded row
    blocks; longer streams loop the contiguous 1-D pass row by row (see
    the constants above for why).  ``np.add.reduceat`` along axis 1 is
    bit-identical per row to the 1-D reduction, so batched == looped either
    way.
    """
    s, cached = _plan_stream(plan)
    batch = a_values.shape[0]
    dtype = np.result_type(a_values.dtype, b_values.dtype)
    path = ("vectorized" if s.n_products <= STREAM_BATCH_VECTOR_MAX
            else "rowloop")
    if s.n_products == 0:
        vals = np.zeros((batch, 0), dtype)
    elif s.n_products <= STREAM_BATCH_VECTOR_MAX:
        blk = max(1, STREAM_BATCH_BLOCK_ELEMS // s.n_products)
        vals = np.empty((batch, s.nnz), dtype)
        for b0 in range(0, batch, blk):
            prod = a_values[b0:b0 + blk, s.a_pos]
            prod = prod * b_values[b0:b0 + blk, s.b_pos]
            vals[b0:b0 + blk] = segment_reduce(prod, s.seg_starts, axis=1)
    else:
        vals = np.empty((batch, s.nnz), dtype)
        for bi in range(batch):
            prod = a_values[bi, s.a_pos]
            prod = prod * b_values[bi, s.b_pos]
            vals[bi] = segment_reduce(prod, s.seg_starts)
    if stats is not None:
        stats["engine"] = "stream"
        stats["path"] = path
        stats["stream_products"] = s.n_products
        stats["stream_cached"] = cached
        stats["result_shape"] = s.shape
    vals = vals.astype(dtype, copy=False)
    return [CSC(vals[b], s.c_rows, s.c_col_ptr, s.shape)
            for b in range(batch)]
