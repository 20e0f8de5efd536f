"""Fused Pallas stream kernel: the numeric phase's reduction in one launch.

``engine="fused"`` lowers a plan's product stream (``core.fast``, DESIGN.md
§9) to a *single* ``pl.pallas_call``.  The product axis ``[P]`` is cut into
blocks of ``FUSED_BLOCK`` products; the kernel reduces every block's
products to per-segment partials, and one plan-static ``segment_sum``
joins the segments that straddle a block edge::

    prod      = x_vals[idx_x] * y_vals[idx_y]                 # XLA gather
    partial_i = onehot(local_i) @ prod_i      (kernel, per block i)  # [T]
    out       = segment_sum(partial, seg_first_i + t)         # combine

where the XLA stream (``backend="jax"``) scatters every product through
``segment_sum`` directly.

**Why the block reduction is safe.**  The stream's segment ids are
non-decreasing and consecutive (every stored C slot has >= 1 product), so
within any block of ``T`` products the local ids ``seg - seg_first`` lie in
``[0, T)`` — each id increment consumes at least one product.  Block ``i``'s
partial at slot ``t`` belongs to segment ``seg_first_i + t``; slots past the
block's last local id hold exact zeros and are pointed at that last
segment, so the combine's indices stay sorted.

**TPU layout.**  Each grid step reduces ``ROWS`` blocks held as one
``[ROWS, T]`` tile (``T`` = 128 lanes): for every row the one-hot
``[T, T]`` is contracted against the products on the MXU at full f32
precision, so integer-valued inputs stay bit-exact.  Every operand is a
2-D, lane-aligned block; there is no in-kernel gather and no unaligned
store (DESIGN.md §11).

**Differentiability.**  The contraction is bilinear, so the backward pass is
two more fused replays of the broadcast cotangent through permuted index
views (:func:`jax_stream.bilinear_custom_vjp`).  The grad views sort the
stream by the differentiated operand's value position; positions with zero
products would break the ``[0, T)`` invariant as empty segments, so the
views reduce into *compact* (rank) ids and a plan-static ``out_map``
scatter places them.

Whether the kernel is interpreted or compiled follows the platform
(:func:`repro.runtime.interpret_mode`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro import runtime
from repro.core import jax_stream
from repro.core.jax_stream import (
    _IN_BOUNDS,
    _guard_error,
    _operand_values,
    _take,
    bilinear_custom_vjp,
    bind_indices,
    check_int32_stream,
    host_fallback,
    stream_seg_ids,
)
from repro.sparse.format import CSC

# products per block (T): the one-hot a block is reduced with is [T, T],
# and T is the lane width of every kernel operand.  Overridable for tests
# (segment-boundary edge cases build plans under tiny blocks); views and
# functions memoized on a plan record the block they were built with and
# rebuild on mismatch.  DEFAULT_FUSED_BLOCK is the shipped fallback; a
# calibrated machine profile can retune the live knob via
# ``core.profile.apply_tuning`` (DESIGN.md §15).
DEFAULT_FUSED_BLOCK = 128
FUSED_BLOCK = DEFAULT_FUSED_BLOCK

# blocks reduced per grid step: one f32 sublane tile of rows
ROWS = 8


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["idx_x", "idx_y", "local", "seg_first", "seg_last",
                 "out_map"],
    meta_fields=["n_out", "n_products", "block"])
@dataclasses.dataclass(frozen=True)
class FusedView:
    """Device-resident index arrays of one fused replay.

    The forward view replays the stream in C-slot order (``out_map`` is
    ``None``).  Grad views replay it sorted by the differentiated operand's
    value position, reduce into compact rank ids, and scatter through
    ``out_map`` (the sorted unique value positions) into the operand-shaped
    cotangent.  The block axis is padded to a multiple of :data:`ROWS`;
    padded products are zeros and padded blocks point at the last segment.
    A pytree: the arrays are the leaves (arguments of a jitted replay), the
    sizes its static structure.
    """

    idx_x: Optional[jax.Array]      # [P] int32 into the x operand
    idx_y: Optional[jax.Array]      # [P] int32 into the y operand
    local: Optional[jax.Array]      # [nb, T] int32 in [0, T): seg - first
    seg_first: Optional[jax.Array]  # [nb] int32: block's first segment
    seg_last: Optional[jax.Array]   # [nb] int32: block's last segment
    out_map: Optional[jax.Array]    # [n_out] int32 scatter (grad views)
    n_out: int                      # segments reduced by the kernel
    n_products: int                 # real (unpadded) product count
    block: int

    @property
    def n_blocks(self) -> int:
        """Padded block count (a multiple of :data:`ROWS`)."""
        nb = -(-max(self.n_products, 1) // self.block)
        return -(-nb // ROWS) * ROWS

    @property
    def nbytes(self) -> int:
        """Device bytes held by this view's index arrays."""
        return sum(a.nbytes for a in (self.idx_x, self.idx_y, self.local,
                                      self.seg_first, self.seg_last,
                                      self.out_map)
                   if a is not None)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["forward", "grad_a", "grad_b"], meta_fields=["block"])
@dataclasses.dataclass(frozen=True)
class FusedStream:
    """The plan's three fused replay views (forward + the two grad views).

    Built lazily from the host :attr:`plan.stream` on first fused execution
    and memoized on the plan alongside the host/XLA-device streams;
    ``plan.fused_stream_nbytes`` / ``plan_cache_info()
    ['fused_stream_bytes']`` report these buffers separately.
    """

    forward: FusedView
    grad_a: FusedView
    grad_b: FusedView
    block: int

    @property
    def nbytes(self) -> int:
        return (self.forward.nbytes + self.grad_a.nbytes
                + self.grad_b.nbytes)


def _build_view(idx_x, idx_y, seg, block: int, n_out: int,
                out_map=None) -> FusedView:
    """One replay view: block metadata on the host, indices to the device.

    ``seg`` must be non-decreasing with unit steps covering ``0..n_out-1``
    (forward: the stream's C-slot ids; grad: compact ranks) — that is what
    bounds every block's local ids to ``[0, block)``.
    """
    p = len(idx_x)
    if p == 0:
        return FusedView(None, None, None, None, None,
                         None if out_map is None else jnp.asarray(
                             out_map, jnp.int32),
                         n_out, 0, block)
    view = FusedView(None, None, None, None, None, None, n_out, p, block)
    nb = view.n_blocks
    seg = np.asarray(seg, np.int64)
    used = -(-p // block)
    starts = np.arange(used, dtype=np.int64) * block       # all < p
    ends = np.minimum(starts + block, p) - 1
    seg_first = np.full(nb, n_out - 1, np.int64)
    seg_last = np.full(nb, n_out - 1, np.int64)
    seg_first[:used] = seg[starts]
    seg_last[:used] = seg[ends]
    local = np.zeros(nb * block, np.int64)
    local[:p] = seg - np.repeat(seg_first[:used], block)[:p]
    with jax.ensure_compile_time_eval():
        # the lazy build may run inside a caller's jit trace (the first
        # traced fused execution of a fresh plan); the index arrays must
        # come out concrete — they are plan state shared by every later
        # trace, not constants of this one (same rule as device_stream)
        dev = (jnp.asarray(np.asarray(idx_x, np.int32)),
               jnp.asarray(np.asarray(idx_y, np.int32)),
               jnp.asarray(local.astype(np.int32).reshape(nb, block)),
               jnp.asarray(seg_first.astype(np.int32)),
               jnp.asarray(seg_last.astype(np.int32)),
               None if out_map is None
               else jnp.asarray(np.asarray(out_map, np.int32)))
    return FusedView(*dev, n_out=n_out, n_products=p, block=block)


def _grad_view(pos, other_pos, seg_ids, block: int) -> FusedView:
    """Replay view for d(operand at ``pos``): sort by ``pos``, compact ids.

    The replay gathers the output cotangent through ``seg_ids`` (x side)
    and the other operand's values through ``other_pos`` (y side); value
    positions with zero products are *absent* (compact ranks keep the
    no-empty-segment invariant), so the kernel output scatters through
    ``out_map`` — the sorted unique positions — into the full cotangent.
    """
    order = np.argsort(pos, kind="stable")
    seq = np.asarray(pos)[order]
    uniq, inv = np.unique(seq, return_inverse=True)
    return _build_view(seg_ids[order], np.asarray(other_pos)[order], inv,
                       block, n_out=len(uniq), out_map=uniq)


def fused_stream(plan, block: int | None = None) -> Optional[FusedStream]:
    """The plan's fused replay views, built lazily and memoized.

    ``None`` when the plan-memory guard tripped (no host stream to lift).
    ``block`` overrides the product-axis tile size (default
    ``FUSED_BLOCK``); a memoized entry built under a different block is
    rebuilt, so tests can shrink the tile on a fresh plan.
    """
    s = plan.stream
    if s is None:
        return None
    block = FUSED_BLOCK if block is None else int(block)
    if block < 1:
        raise ValueError(f"fused block must be >= 1, got {block}")
    memo = plan._stream_memo
    fs = memo.get("fused")
    if fs is None or fs.block != block:
        check_int32_stream(plan, s)
        seg_ids = stream_seg_ids(s)
        fs = FusedStream(
            forward=_build_view(s.a_pos, s.b_pos, seg_ids, block,
                                n_out=s.nnz),
            grad_a=_grad_view(s.a_pos, s.b_pos, seg_ids, block),
            grad_b=_grad_view(s.b_pos, s.a_pos, seg_ids, block),
            block=block,
        )
        memo["fused"] = fs
        # the jitted replays are bound to the views: drop stale entries
        for k in ("fused_fn", "fused_fn_batched"):
            memo.pop(k, None)
    return fs


def _fused_kernel(prod_ref, local_ref, out_ref):
    """One grid step: ``ROWS`` blocks of products -> ``ROWS`` partial rows.

    ``out[r, t] = sum_c prod[r, c] * [local[r, c] == t]``.  Each row's
    one-hot is contracted against the whole ``[ROWS, T]`` tile (an
    ``[8, T] x [T, T]`` MXU pass) and only row ``r`` of the result is kept:
    every operand stays a lane-aligned 2-D tile.
    """
    rows, block = prod_ref.shape
    prod = prod_ref[...]
    local = local_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0)
    out = jnp.zeros((rows, block), out_ref.dtype)
    for r in range(rows):
        onehot = (slot == local[r:r + 1, :]).astype(prod.dtype)   # [t, c]
        part = jax.lax.dot_general(
            prod, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=out_ref.dtype)
        out = jnp.where(row == r, part, out)
    out_ref[...] = out


def _block_partials(prod, local):
    """``[nb, T]`` per-block segment partials of ``[nb, T]`` products."""
    nb, block = local.shape
    spec = pl.BlockSpec((ROWS, block), lambda i: (i, 0))
    return pl.pallas_call(
        _fused_kernel,
        grid=(nb // ROWS,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nb, block), prod.dtype),
        interpret=runtime.interpret_mode(),
    )(prod, local)


def _fused_call(view: FusedView, x, y):
    """Run one fused replay: ``[n_out]`` segment sums, one kernel launch."""
    dt = jnp.result_type(x, y)
    if view.n_products == 0:
        return jnp.zeros((view.n_out,), dt)
    block, nb = view.block, view.n_blocks
    prod = (_take(jnp.asarray(x, dt), view.idx_x)
            * _take(jnp.asarray(y, dt), view.idx_y))
    prod = jnp.pad(prod, (0, nb * block - view.n_products))
    partial = _block_partials(prod.reshape(nb, block), view.local)
    # slot t of block i is segment seg_first + t; slots past the block's
    # last segment hold zeros and clamp onto it (the ids stay sorted)
    target = jnp.minimum(
        view.seg_first[:, None] + jnp.arange(block, dtype=jnp.int32),
        view.seg_last[:, None])
    return jax.ops.segment_sum(partial.reshape(-1), target.reshape(-1),
                               num_segments=view.n_out,
                               indices_are_sorted=True, mode=_IN_BOUNDS)


def _fused_forward(fs: FusedStream, a_values, b_values):
    return _fused_call(fs.forward, a_values, b_values)


def _scatter(view: FusedView, compact, n_primal: int):
    """Place a grad view's compact sums into the operand-shaped cotangent."""
    out = jnp.zeros((n_primal,), compact.dtype)
    if view.out_map is None:      # P == 0: no contributing products
        return out
    return out.at[view.out_map].set(compact, unique_indices=True,
                                    mode=_IN_BOUNDS)


def _fused_grad_a(fs: FusedStream, g, a_values, b_values):
    return _scatter(fs.grad_a, _fused_call(fs.grad_a, g, b_values),
                    a_values.shape[0])


def _fused_grad_b(fs: FusedStream, g, a_values, b_values):
    return _scatter(fs.grad_b, _fused_call(fs.grad_b, g, a_values),
                    b_values.shape[0])


#: the custom-vjp fused contraction ``f(fs, a_values, b_values)``: forward
#: plus two fused grad replays, every view's arrays passed as arguments
_FUSED_CONTRACT = bilinear_custom_vjp(_fused_forward, _fused_grad_a,
                                      _fused_grad_b)


def fused_fn(plan, *, block: int | None = None):
    """The plan's jitted fused function ``f(a_values, b_values) -> c_values``.

    Pure, jit-compatible, differentiable (shared bilinear custom vjp) —
    the fused twin of :func:`jax_stream.stream_fn`.  Memoized on the plan
    (keyed on the block it was built under); guarded plans raise the
    capability error.
    """
    fs = fused_stream(plan, block)
    if fs is None:
        raise _guard_error(plan)
    memo = plan._stream_memo
    if "fused_fn" not in memo:
        memo["fused_fn"] = bind_indices(_FUSED_CONTRACT, fs)
    return memo["fused_fn"]


def fused_fn_batched(plan, *, block: int | None = None):
    """Vmapped twin of :func:`fused_fn`: ``[B, nnz]`` stacks, one trace.

    ``jit(vmap(contract))`` — the batch axis becomes the leading grid
    dimension of the one fused launch (exactly how ``spa_spgemm_batched``
    batches, DESIGN.md §7), so the launch count stays 1 regardless of B.
    """
    fused_fn(plan, block=block)   # builds (or rebuilds) the views
    memo = plan._stream_memo
    if "fused_fn_batched" not in memo:
        memo["fused_fn_batched"] = bind_indices(
            _FUSED_CONTRACT, memo["fused"], batched=True)
    return memo["fused_fn_batched"]


def execute_fused(plan, a_values, b_values, *, stats: dict | None = None,
                  validate: str | None = None) -> CSC:
    """Numeric phase via the fused kernel (executor dispatch target).

    One ``pallas_call`` launch; result values are a device array on the
    plan's canonical stream structure.  Guarded plans fall back to the host
    stream engine on concrete operands (with a warning, counted in
    ``plan_cache_info()``) and raise the capability error under a trace
    (same semantics as the jax backend).
    """
    plan.a.check_compatible(a_values, validate)
    plan.b.check_compatible(b_values, validate)
    av = _operand_values(a_values)
    bv = _operand_values(b_values)
    if plan.stream is None:
        return host_fallback(plan, av, bv, stats)
    vals = fused_fn(plan)(av, bv)
    s = plan.stream
    if stats is not None:
        stats.update(engine="fused", backend=plan.backend, device=True,
                     fallback=None, n_launches=1,
                     stream_products=s.n_products,
                     fused_block=plan._stream_memo["fused"].block,
                     result_shape=s.shape)
    return CSC(vals, s.c_rows, s.c_col_ptr, s.shape)


def execute_fused_batched(plan, a_values, b_values, *,
                          stats: dict | None = None,
                          validate: str | None = None) -> list:
    """Batched fused numeric phase: B value sets, still one launch."""
    from repro.core.executor import _check_batch   # lazy: executor imports us

    av = jax_stream._batched_operand(plan.a, a_values, validate)
    bv = jax_stream._batched_operand(plan.b, b_values, validate)
    batch = _check_batch(av, bv)
    if plan.stream is None:
        return host_fallback(plan, av, bv, stats, batch=batch)
    vals = fused_fn_batched(plan)(av, bv)
    s = plan.stream
    if stats is not None:
        stats.update(engine="fused", backend=plan.backend, device=True,
                     fallback=None, path="vmap", batch=batch, n_launches=1,
                     stream_products=s.n_products,
                     fused_block=plan._stream_memo["fused"].block,
                     result_shape=s.shape)
    return [CSC(vals[b], s.c_rows, s.c_col_ptr, s.shape)
            for b in range(batch)]
