"""Pallas TPU kernel: block-sparse (BSR) matrix x dense matrix.

This is the *production* TPU re-targeting of the paper's idea (DESIGN.md §2):
at TPU granularity the unit of sparsity worth exploiting is an MXU-aligned
block, and the paper's hybrid density policy becomes "skip absent blocks,
dense-MXU the present ones". Used by ``models.sparse_ffn.SparseFFN`` and the
MoE dispatch-as-SpGEMM path.

Layout: padded BSR — each block-row stores up to ``max_nb`` blocks
(``blocks [n_rb, max_nb, bm, bk]``) with their block-column ids in a
scalar-prefetched index array, so the kernel's inner loop runs a
*data-dependent* trip count (block_nnz[i]) and gathers X tiles by dynamic
slice. Accumulation is f32 on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime


def _bsr_kernel(idx_ref, nnz_ref,         # scalar prefetch (SMEM)
                blocks_ref, x_ref, o_ref, *, bk: int):
    i = pl.program_id(0)
    nnz = nnz_ref[i]
    bm, bn = o_ref.shape

    def body(nb, acc):
        ci = idx_ref[i, nb]
        xt = x_ref[pl.ds(ci * bk, bk), :]          # [bk, bn] gathered tile
        blk = blocks_ref[0, nb]                    # [bm, bk]
        return acc + jnp.dot(blk, xt, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        0, nnz, body, jnp.zeros((bm, bn), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bn",))
def bsr_spmm(block_idx, block_nnz, blocks, x, *, bn: int = 128):
    """[n_rb*bm, N] = BSR(A) @ x.

    block_idx [n_rb, max_nb] int32, block_nnz [n_rb] int32,
    blocks [n_rb, max_nb, bm, bk], x [K, N] with N % bn == 0.
    """
    n_rb, max_nb, bm, bk = blocks.shape
    k_dim, n = x.shape
    assert n % bn == 0, (n, bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_rb, n // bn),
        in_specs=[
            pl.BlockSpec((1, max_nb, bm, bk), lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((k_dim, bn), lambda i, j, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_bsr_kernel, bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rb * bm, n), x.dtype),
        interpret=runtime.interpret_mode(),
    )(block_idx, block_nnz, blocks, x)


def bsr_from_dense(w, bm: int, bk: int, *, threshold: float = 0.0):
    """Host-side converter: dense [M, K] -> padded BSR, dropping all-|.|<=thr
    blocks. Returns (block_idx, block_nnz, blocks)."""
    import numpy as np

    w = np.asarray(w)
    m, k = w.shape
    assert m % bm == 0 and k % bk == 0, (w.shape, bm, bk)
    n_rb, n_cb = m // bm, k // bk
    tiles = w.reshape(n_rb, bm, n_cb, bk).transpose(0, 2, 1, 3)
    keep = np.abs(tiles).max(axis=(2, 3)) > threshold       # [n_rb, n_cb]
    max_nb = max(int(keep.sum(1).max()), 1)
    block_idx = np.zeros((n_rb, max_nb), np.int32)
    block_nnz = keep.sum(1).astype(np.int32)
    blocks = np.zeros((n_rb, max_nb, bm, bk), w.dtype)
    for i in range(n_rb):
        cols = np.nonzero(keep[i])[0]
        block_idx[i, : len(cols)] = cols
        blocks[i, : len(cols)] = tiles[i, cols]
    return block_idx, block_nnz, blocks
