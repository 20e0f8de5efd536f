"""Pallas TPU kernel: SPA SpGEMM over a block of C columns.

TPU adaptation of Algorithm 2 (see DESIGN.md §2): the SParse Accumulator for a
block of ``L`` C columns is a dense ``[m, L]`` tile resident in VMEM for the
whole kernel instance (the paper's accumulator-locality insight transplanted
from L2 to VMEM). Per B non-zero we
  * gather the referenced A column through a one-hot MXU matmul
    (the TPU-idiomatic indexed vector load), and
  * scatter-accumulate via an ``[m, L]`` one-hot mask FMA
    (the TPU-idiomatic indexed vector store — races impossible because row
    indices within one A column are unique, exactly the paper's argument).

Operands are padded-column views (``sparse.csc_to_padded_columns``). Output is
the dense accumulator block; compaction to CSC is the caller's separate store
phase (``sparse.format.CSCBuilder.add_dense_tile``), mirroring the paper's
line-11 "store as sparse".

**Layout.**  Inside the kernel the C columns are the 128-wide lane axis of
every operand: B's padded columns arrive transposed (``[zb, n_b]``, entry
``e`` of the block's columns is sublane row ``e``) and A's tables transposed
(``[za, n_a]``), so each step reads one row and the one-hot gather
``A^T @ onehot`` lands lane-major.  :func:`lane_major` builds these views in
XLA; padded entries carry row id -1 and value 0, so no count operand is
needed.  The gathers run at full f32 precision (row ids and values stay
exact).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime

_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lane_major(rows, vals, nnz, *, lanes: int = 1):
    """Transposed, masked, tile-padded view of a padded-column operand.

    ``rows``/``vals`` ``[n, z]`` -> ``[z8, n']`` with ``z8`` a multiple of 8
    and ``n'`` a multiple of ``lanes``; entries at or past a column's
    ``nnz`` (and all padding) get row id -1 and value 0.  Row ids come back
    as f32 (exact below 2**24) so the one-hot gather is one MXU pass.
    """
    n, z = rows.shape
    valid = jax.lax.broadcasted_iota(jnp.int32, (n, z), 1) < nnz[:, None]
    pad = ((0, _round_up(max(z, 1), 8) - z), (0, _round_up(n, lanes) - n))
    r = jnp.pad(jnp.where(valid, rows, -1).T.astype(jnp.float32), pad,
                constant_values=-1)
    v = jnp.pad(jnp.where(valid, vals, 0).T, pad)
    return r, v


def _spa_kernel(b_rows_ref, b_vals_ref, a_rows_ref, a_vals_ref,
                out_ref, ar_ref, av_ref):
    zb, L = b_rows_ref.shape
    za, n_a = a_rows_ref.shape
    m = out_ref.shape[0]
    a_rows = a_rows_ref[...]
    a_vals = a_vals_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, (n_a, L), 0)
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (m, L), 0)

    def b_step(e, acc):
        k = b_rows_ref[pl.ds(e, 1), :].astype(jnp.int32)   # [1, L] A cols
        bv = b_vals_ref[pl.ds(e, 1), :]                      # [1, L]
        # indexed vector load of the A columns: A^T @ one-hot [n_a, L] (MXU)
        oh = (col == k).astype(a_vals.dtype)
        ar_ref[...] = jnp.dot(a_rows, oh.astype(jnp.float32),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
        av_ref[...] = jnp.dot(a_vals, oh, precision=_HIGHEST,
                              preferred_element_type=a_vals.dtype) * bv

        def z_step(z, acc):
            r = jnp.round(ar_ref[pl.ds(z, 1), :]).astype(jnp.int32)
            contrib = av_ref[pl.ds(z, 1), :]                 # [1, L]
            # indexed vector store: one-hot row mask FMA on the VMEM tile
            return acc + jnp.where(iota_m == r, contrib, 0)

        return jax.lax.fori_loop(0, za, z_step, acc)

    out_ref[...] = jax.lax.fori_loop(
        0, zb, b_step, jnp.zeros((m, L), out_ref.dtype))


@functools.partial(jax.jit, static_argnames=("m", "block_cols"))
def spa_spgemm(a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz,
               *, m: int, block_cols: int = 128):
    """Dense C [m, n_b] = A @ B, SPA dataflow, one grid step per column block.

    n_b must be a multiple of block_cols (callers pad; see ops.py).
    """
    n_b = b_rows.shape[0]
    assert n_b % block_cols == 0, (n_b, block_cols)
    ar, av = lane_major(a_rows, a_vals, a_nnz, lanes=128)
    br, bv = lane_major(b_rows, b_vals, b_nnz)
    za, n_a = ar.shape
    zb = br.shape[0]
    m8 = _round_up(m, 8)
    whole = lambda i: (0, 0)
    out = pl.pallas_call(
        _spa_kernel,
        grid=(n_b // block_cols,),
        in_specs=[
            pl.BlockSpec((zb, block_cols), lambda i: (0, i)),   # b_rows^T
            pl.BlockSpec((zb, block_cols), lambda i: (0, i)),   # b_vals^T
            pl.BlockSpec((za, n_a), whole),                     # a_rows^T
            pl.BlockSpec((za, n_a), whole),                     # a_vals^T
        ],
        out_specs=pl.BlockSpec((m8, block_cols), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m8, n_b), a_vals.dtype),
        scratch_shapes=[pltpu.VMEM((za, block_cols), jnp.float32),
                        pltpu.VMEM((za, block_cols), a_vals.dtype)],
        interpret=runtime.interpret_mode(),
    )(br, bv, ar, av)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("m", "block_cols"))
def spa_spgemm_batched(a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz,
                       *, m: int, block_cols: int = 128):
    """Batched SPA: dense C [B, m, n_b] for B same-pattern value sets.

    Only the value operands carry the batch axis (``a_vals [B, n_a, za]``,
    ``b_vals [B, n_b, zb]``); the pattern operands (rows, nnz) are shared.
    ``jax.vmap`` over the pallas_call turns the batch into a leading grid
    dimension, so all B multiplies run in one launch (DESIGN.md §7), and
    each batch slice is bit-identical to the unbatched kernel.
    """
    f = functools.partial(spa_spgemm, m=m, block_cols=block_cols)
    return jax.vmap(f, in_axes=(None, 0, None, None, 0, None))(
        a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz)
