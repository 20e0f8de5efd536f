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

**Trip counts.**  Each loop stops where the rest could add only zeros
(:func:`trip_counts`, scalar-prefetched, computed once by the plan): the
B loop at the block's longest column, the row loop of entry ``e`` at the
longest A column an entry ``e`` gathers.  A's tables stay whole in VMEM,
one buffer each, under the chip's kernel budget
(:mod:`repro.kernels.vmem`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime
from repro.kernels import vmem

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


def _spa_kernel(b_trips_ref, z_trips_ref,      # scalar prefetch
                b_rows_ref, b_vals_ref, a_rows_ref, a_vals_ref,
                out_ref, ar_ref, av_ref):
    zb, L = b_rows_ref.shape
    n_a = a_rows_ref.shape[1]
    m = out_ref.shape[0]
    i = pl.program_id(0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_a, L), 0)
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (m, L), 0)

    def b_step(e, acc):
        k = b_rows_ref[pl.ds(e, 1), :].astype(jnp.int32)   # [1, L] A cols
        bv = b_vals_ref[pl.ds(e, 1), :]                      # [1, L]
        # indexed vector load of the A columns: A^T @ one-hot [n_a, L] (MXU)
        # from the tables' one VMEM copy (a value of a whole table would be
        # a second)
        oh = (col == k).astype(a_vals_ref.dtype)
        ar_ref[...] = jnp.dot(a_rows_ref[...], oh.astype(jnp.float32),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
        av_ref[...] = jnp.dot(a_vals_ref[...], oh, precision=_HIGHEST,
                              preferred_element_type=a_vals_ref.dtype) * bv

        def z_step(z, acc):
            r = jnp.round(ar_ref[pl.ds(z, 1), :]).astype(jnp.int32)
            contrib = av_ref[pl.ds(z, 1), :]                 # [1, L]
            # indexed vector store: one-hot row mask FMA on the VMEM tile
            return acc + jnp.where(iota_m == r, contrib, 0)

        # rows past every gathered column's length add nothing: stop there
        return jax.lax.fori_loop(0, z_trips_ref[i * zb + e], z_step, acc)

    # B entries past every lane's column add nothing: stop there
    out_ref[...] = jax.lax.fori_loop(
        0, b_trips_ref[i], b_step, jnp.zeros((m, L), out_ref.dtype))


def trip_counts(a_nnz, b_rows, b_nnz, block_cols: int, xp=jnp):
    """``(b_trips [n_blocks], z_trips [n_blocks * zb])`` of a launch, ``zb``
    B's padded width (a multiple of 8): per block, its longest B column,
    and per entry ``e`` of its B columns the longest A column an entry
    ``e`` of the block gathers (0 where none).  ``xp`` is ``jnp`` (inside
    the kernel's jit) or ``np`` (a plan, once, on the host).

    The kernel's loops stop there; the steps past them would add only
    zeros, so C is the same to the bit.
    """
    n_b, z = b_rows.shape
    valid = xp.arange(z)[None, :] < b_nnz[:, None]
    an = xp.where(valid, xp.asarray(a_nnz)[xp.clip(b_rows, 0,
                                                   len(a_nnz) - 1)], 0)
    an = xp.pad(an, ((0, 0), (0, _round_up(max(z, 1), 8) - z)))
    z_trips = an.reshape(n_b // block_cols, block_cols, -1).max(axis=1)
    b_trips = xp.asarray(b_nnz).reshape(-1, block_cols).max(axis=1)
    return b_trips.astype(xp.int32), z_trips.reshape(-1).astype(xp.int32)


@functools.partial(jax.jit, static_argnames=("m", "block_cols"))
def spa_spgemm(a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz, trips=None,
               *, m: int, block_cols: int = 128):
    """Dense C [m, n_b] = A @ B, SPA dataflow, one grid step per column block.

    n_b must be a multiple of block_cols (callers pad; see ops.py).
    ``trips`` is :func:`trip_counts` of the operands' patterns, which a
    plan computes once; ``None`` computes them here.
    """
    n_b = b_rows.shape[0]
    assert n_b % block_cols == 0, (n_b, block_cols)
    ar, av = lane_major(a_rows, a_vals, a_nnz, lanes=128)
    br, bv = lane_major(b_rows, b_vals, b_nnz)
    za, n_a = ar.shape
    zb = br.shape[0]
    m8 = _round_up(m, 8)
    lanes = lambda rows: pl.BlockSpec((rows, block_cols),
                                      lambda i, *_: (0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_b // block_cols,),
        in_specs=[lanes(zb), lanes(zb),                      # b rows, vals
                  vmem.whole((za, n_a)), vmem.whole((za, n_a))],   # A^T
        out_specs=lanes(m8),
        scratch_shapes=[pltpu.VMEM((za, block_cols), jnp.float32),
                        pltpu.VMEM((za, block_cols), a_vals.dtype)],
    )
    out = pl.pallas_call(
        _spa_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m8, n_b), a_vals.dtype),
        compiler_params=vmem.compiler_params(),
        interpret=runtime.interpret_mode(),
    )(*(trip_counts(a_nnz, b_rows, b_nnz, block_cols) if trips is None
        else trips), br, bv, ar, av)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("m", "block_cols"))
def spa_spgemm_batched(a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz,
                       trips=None, *, m: int, block_cols: int = 128):
    """Batched SPA: dense C [B, m, n_b] for B same-pattern value sets.

    Only the value operands carry the batch axis (``a_vals [B, n_a, za]``,
    ``b_vals [B, n_b, zb]``); the pattern operands (rows, nnz) are shared.
    ``jax.vmap`` over the pallas_call turns the batch into a leading grid
    dimension, so all B multiplies run in one launch (DESIGN.md §7), and
    each batch slice is bit-identical to the unbatched kernel.
    """
    f = functools.partial(spa_spgemm, m=m, block_cols=block_cols)
    return jax.vmap(f, in_axes=(None, 0, None, None, 0, None, None))(
        a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz, trips)
