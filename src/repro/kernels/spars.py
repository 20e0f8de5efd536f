"""Pallas TPU kernel: SPARS lock-step SpGEMM (Algorithm 3).

Faithful TPU transliteration of the paper's lane-per-column dataflow: a block
of L C-columns advances in lock-step, one intermediate product per lane per
step, with cursor vectors ``vIndices_B`` / ``vCounter_A`` and masked lanes for
exhausted columns. The per-lane dense accumulators (``SPA_values``/``flags``)
are an ``[m, L]`` VMEM tile. RVV indexed loads become one-hot MXU gathers;
indexed stores become one-hot mask FMAs (races impossible: one product per
lane per step, private accumulator column per lane — the paper's write-
independence argument by layout).

The per-block trip count (max Op_j in the block) is data-dependent; it rides
in as a scalar-prefetch operand per grid step, exactly how a production TPU
kernel consumes CSC pointer structure (PrefetchScalarGridSpec).

Layout and gather as in ``kernels/spa.py``: the lanes are the block's C
columns, B arrives transposed (:func:`~repro.kernels.spa.lane_major`) and
A as one int8 table of byte planes (:func:`~repro.kernels.spa.a_table`),
and the cursor vectors are ``[1, L]`` rows.  A's column lengths ride as
one extra row of the row-code planes, so one exact int8 matmul gathers
row ids, values and lengths together.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime
from repro.kernels import vmem
from repro.kernels.spa import (_round_up, a_table, from_bytes, gathered,
                               lane_major)
from repro.kernels.vmem import VALUE_PLANES, row_planes


def _pick(sel, table):
    """``[1, L]`` row of ``table`` chosen per lane by a one-hot ``sel``."""
    return jnp.sum(jnp.where(sel, table, 0), axis=0, keepdims=True)


def _spars_kernel(steps_ref,            # scalar prefetch: [n_blocks] int32
                  b_rows_ref, b_vals_ref, b_nnz_ref, a_ref,
                  out_ref, flags_ref, *, za: int, row_bytes: int):
    zb, L = b_rows_ref.shape
    zp, n_a = a_ref.shape
    planes = row_bytes + VALUE_PLANES
    zt = zp // planes
    m = out_ref.shape[0]
    steps = steps_ref[pl.program_id(0)]
    b_rows = b_rows_ref[...]
    b_vals = b_vals_ref[...]
    b_nnz = b_nnz_ref[...]            # [1, L]
    iota_na = jax.lax.broadcasted_iota(jnp.int32, (n_a, L), 0)
    iota_zb = jax.lax.broadcasted_iota(jnp.int32, (zb, L), 0)
    iota_zt = jax.lax.broadcasted_iota(jnp.int32, (zt, L), 0)
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (m, L), 0)

    def step(_, carry):
        vidx_b, vcnt_a, acc, flags = carry
        active = vidx_b < b_nnz                           # [1, L] vMask
        # -- indexed vector load of vB (this lane's B column entry)
        sel_b = iota_zb == vidx_b
        bk = _pick(sel_b, b_rows)
        bv = _pick(sel_b, b_vals)
        # -- indexed vector load of vA (A column gather, MXU): every byte
        # plane through one exact int8 one-hot matmul, from the table's
        # one VMEM copy
        g = jnp.dot(a_ref[...], (iota_na == bk).astype(jnp.int8),
                    preferred_element_type=jnp.int32)     # [zp, L]
        an = from_bytes([g[p * zt + za:p * zt + za + 1]   # col lengths
                         for p in range(row_bytes)])
        sel_a = iota_zt == vcnt_a
        r, av = gathered([_pick(sel_a, g[p * zt:(p + 1) * zt])
                          for p in range(planes)], row_bytes)
        # -- FMA + indexed store into the [m, L] accumulator
        hit = (iota_m == r) & active
        acc = acc + jnp.where(hit, av * bv, 0)
        flags = jnp.maximum(flags, hit.astype(flags.dtype))
        # -- cursor update (Algorithm 3 lines 15-19)
        last = vcnt_a + 1 >= an
        vcnt_a = jnp.where(active & ~last, vcnt_a + 1, 0)
        vidx_b = vidx_b + (active & last).astype(vidx_b.dtype)
        return vidx_b, vcnt_a, acc, flags

    init = (
        jnp.zeros((1, L), jnp.int32),
        jnp.zeros((1, L), jnp.int32),
        jnp.zeros((m, L), out_ref.dtype),
        jnp.zeros((m, L), out_ref.dtype),
    )
    _, _, acc, flags = jax.lax.fori_loop(0, steps, step, init)
    out_ref[...] = acc
    flags_ref[...] = flags


@functools.partial(jax.jit, static_argnames=("m", "block_cols"))
def spars_spgemm(a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz, steps,
                 *, m: int, block_cols: int = 128):
    """Dense C [m, n_b] + flags, SPARS dataflow.

    ``steps[i]`` = trip count of block i (max Op_j over its columns, from the
    host-side blocking pre-process). n_b % block_cols == 0.
    """
    n_b = b_rows.shape[0]
    assert n_b % block_cols == 0, (n_b, block_cols)
    n_blocks = n_b // block_cols
    table = a_table(a_rows, a_vals, a_nnz, m=m, kind="spars")
    br, bv = lane_major(b_rows, b_vals, b_nnz)
    zb = br.shape[0]
    m8 = _round_up(m, 8)
    kernel = functools.partial(_spars_kernel, za=a_rows.shape[1],
                               row_bytes=row_planes(m))
    lanes = lambda rows: pl.BlockSpec((rows, block_cols), lambda i, s: (0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[lanes(zb), lanes(zb), lanes(1), vmem.whole(table.shape)],
        out_specs=[lanes(m8), lanes(m8)],
    )
    out, flags = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m8, n_b), jnp.float32),
            jax.ShapeDtypeStruct((m8, n_b), jnp.float32),
        ],
        compiler_params=vmem.compiler_params(),
        interpret=runtime.interpret_mode(),
    )(steps, br, bv, b_nnz.astype(jnp.int32).reshape(1, n_b), table)
    return out[:m], flags[:m]


@functools.partial(jax.jit, static_argnames=("m", "block_cols"))
def spars_spgemm_batched(a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz, steps,
                         *, m: int, block_cols: int = 128):
    """Batched SPARS: C + flags [B, m, n_b] for B same-pattern value sets.

    Value operands carry the batch axis (``a_vals [B, n_a, za]``,
    ``b_vals [B, n_b, zb]``); pattern operands and the per-block trip counts
    are shared.  One vmapped launch for all B (DESIGN.md §7).
    """
    f = functools.partial(spars_spgemm, m=m, block_cols=block_cols)
    return jax.vmap(f, in_axes=(None, 0, None, None, 0, None, None))(
        a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz, steps)
