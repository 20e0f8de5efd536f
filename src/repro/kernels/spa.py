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
``e`` of the block's columns is sublane row ``e``), and A's columns as one
transposed int8 table of byte planes (:func:`a_table`), so each step reads
one row and the one-hot gather ``A^T @ onehot`` lands lane-major.
:func:`lane_major` builds the transposed views in XLA; padded entries
carry row id -1 and value 0, so no count operand is needed.

**Exact gather.**  A one-hot matmul with integer accumulation returns each
byte of the gathered word exactly, so A's table holds the bytes of each
entry's row code (row id + 1, padding 0; :func:`~repro.kernels.vmem
.row_planes` of them, from C's row count) and of its f32 bit pattern (4),
one plane after the other, and one ``int8 @ int8 -> int32`` dot on the MXU
gathers every plane of a B entry's A columns.  Shifts, ``|`` and a bitcast
put the row ids and values back together bit for bit: ±0, denormals, inf
and NaN included.

**Trip counts.**  Each loop stops where the rest could add only zeros
(:func:`trip_counts`, scalar-prefetched, computed once by the plan): the
B loop at the block's longest column, the row loop of entry ``e`` at the
longest A column an entry ``e`` gathers.  A's table stays whole in VMEM,
one buffer, under the chip's kernel budget (:mod:`repro.kernels.vmem`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime
from repro.kernels import vmem
from repro.kernels.vmem import VALUE_PLANES, row_planes


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lane_major(rows, vals, nnz, *, lanes: int = 1,
               z_rows: int | None = None):
    """Transposed, masked, tile-padded view of a padded-column operand.

    ``rows``/``vals`` ``[n, z]`` -> int32 row ids and values ``[z_rows,
    n']``, ``z_rows`` by default ``z`` padded to a multiple of 8 and
    ``n'`` a multiple of ``lanes``; entries at or past a column's ``nnz``
    (and all padding) get row id -1 and value 0.
    """
    n, z = rows.shape
    if z_rows is None:
        z_rows = _round_up(max(z, 1), 8)
    valid = jax.lax.broadcasted_iota(jnp.int32, (n, z), 1) < nnz[:, None]
    pad = ((0, z_rows - z), (0, _round_up(n, lanes) - n))
    r = jnp.pad(jnp.where(valid, rows, -1).T.astype(jnp.int32), pad,
                constant_values=-1)
    v = jnp.pad(jnp.where(valid, vals, 0).T, pad)
    return r, v


def byte_planes(words, n: int):
    """int8 ``[n · z, n_a]``: bytes ``0..n-1`` of the int32 ``words [z,
    n_a]``, low byte first, one plane after the other."""
    return jnp.concatenate([jax.lax.bitcast_convert_type(
        ((words >> 8 * p) & 0xFF).astype(jnp.uint8), jnp.int8)
        for p in range(n)])


def from_bytes(planes):
    """The int32 words whose bytes, low first, are the gathered
    ``planes``; the int8 dot reads a byte of 128 or more as negative, so
    each is masked to its 8 bits."""
    word = planes[0] & 0xFF
    for p, b in enumerate(planes[1:], 1):
        word = word | ((b & 0xFF) << 8 * p)
    return word


def a_table(rows, vals, nnz, *, m: int, kind: str):
    """A's gather table: int8 ``[(row_planes(m) + 4) · zt, n_a']``,
    ``zt = vmem.table_rows(kind, za)``: the byte planes of the lane-major
    row codes (row id + 1, padding 0; a SPARS table's row ``za`` holds
    A's column lengths), then those of the f32 values' bit patterns.
    """
    za = rows.shape[1]
    r, v = lane_major(rows, vals, nnz, lanes=128,
                      z_rows=vmem.table_rows(kind, za))
    codes = r + 1
    if kind == "spars":
        codes = codes.at[za].set(jnp.pad(nnz.astype(jnp.int32),
                                         (0, codes.shape[1] - nnz.shape[0])))
    bits = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32)
    return jnp.concatenate([byte_planes(codes, row_planes(m)),
                            byte_planes(bits, VALUE_PLANES)])


def gathered(planes, row_bytes: int):
    """``(row ids, f32 values)`` from the gathered bytes of one table row
    (``planes``: each plane's, the ``row_bytes`` planes of row codes
    first); a padded entry, or a column no one-hot selects, reads row
    -1."""
    r = from_bytes(planes[:row_bytes]) - 1
    return r, jax.lax.bitcast_convert_type(from_bytes(planes[row_bytes:]),
                                           jnp.float32)


def _spa_kernel(b_trips_ref, z_trips_ref,      # scalar prefetch
                b_rows_ref, b_vals_ref, a_ref,
                out_ref, g_ref, *, row_bytes: int):
    zb, L = b_rows_ref.shape
    zp, n_a = a_ref.shape
    planes = row_bytes + VALUE_PLANES
    zt = zp // planes
    m = out_ref.shape[0]
    i = pl.program_id(0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_a, L), 0)
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (m, L), 0)

    def b_step(e, acc):
        k = b_rows_ref[pl.ds(e, 1), :]                       # [1, L] A cols
        bv = b_vals_ref[pl.ds(e, 1), :]                      # [1, L]
        # indexed vector load of the A columns: every byte plane of A^T
        # through one exact int8 one-hot [n_a, L] matmul (MXU), from the
        # table's one VMEM copy (a value of the whole table would be a
        # second)
        g_ref[...] = jnp.dot(a_ref[...], (col == k).astype(jnp.int8),
                             preferred_element_type=jnp.int32)

        def z_step(z, acc):
            r, av = gathered([g_ref[pl.ds(p * zt + z, 1), :]
                              for p in range(planes)], row_bytes)
            # indexed vector store: one-hot row mask FMA on the VMEM tile
            return acc + jnp.where(iota_m == r, av * bv, 0)

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
    table = a_table(a_rows, a_vals, a_nnz, m=m, kind="spa")
    br, bv = lane_major(b_rows, b_vals, b_nnz)
    zb = br.shape[0]
    m8 = _round_up(m, 8)
    lanes = lambda rows: pl.BlockSpec((rows, block_cols),
                                      lambda i, *_: (0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_b // block_cols,),
        in_specs=[lanes(zb), lanes(zb),                      # b rows, vals
                  vmem.whole(table.shape)],                  # A^T planes
        out_specs=lanes(m8),
        scratch_shapes=[pltpu.VMEM((table.shape[0], block_cols),
                                   jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_spa_kernel, row_bytes=row_planes(m)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m8, n_b), jnp.float32),
        compiler_params=vmem.compiler_params(),
        interpret=runtime.interpret_mode(),
    )(*(trip_counts(a_nnz, b_rows, b_nnz, block_cols) if trips is None
        else trips), br, bv, table)
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
