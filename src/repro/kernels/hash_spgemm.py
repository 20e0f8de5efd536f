"""Pallas TPU kernel: HASH lock-step SpGEMM (Section 3.2).

Same lane-per-column lock-step skeleton as SPARS, but the per-lane accumulator
is a linear-probed hash table of ``H`` slots — ``table_keys``/``table_vals``
are ``[H, L]`` VMEM tiles. ``H`` is a *compile-time* parameter: the paper's
dynamic table shrinking becomes selecting a smaller-H kernel variant per block
group, which shrinks the resident VMEM tile (the TPU re-reading of the paper's
"smaller address range => faster indexed access"; see DESIGN.md §2).

Collision handling: all lanes probe in lock-step; a bounded fori over
MAX_PROBES resolves each lane's slot (first matching-or-empty), mirroring the
paper's observation that one collision stalls all VL lanes for one probe
round. MAX_PROBES = H makes the bound exact.

Not ported to the TPU yet: the v5e compiler aborts the process on this
kernel, so :func:`repro.runtime.check_kernel` refuses it there before
tracing (it runs interpreted on the CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime
from repro.core.analysis import HASH_C

_EMPTY = -1


def _hash_kernel(steps_ref,
                 b_rows_ref, b_vals_ref, b_nnz_ref,
                 a_rows_ref, a_vals_ref, a_nnz_ref,
                 keys_ref, vals_ref,
                 *, m: int, za: int, n_a: int, h: int, max_probes: int):
    L, zb = b_rows_ref.shape
    steps = steps_ref[pl.program_id(0)]
    a_rows_f = a_rows_ref[...].astype(jnp.float32)
    a_vals = a_vals_ref[...]
    a_nnz_f = a_nnz_ref[...].astype(jnp.float32)
    b_nnz = b_nnz_ref[...]
    iota_na = jax.lax.broadcasted_iota(jnp.int32, (L, n_a), 1)
    iota_zb = jax.lax.broadcasted_iota(jnp.int32, (L, zb), 1)
    iota_za = jax.lax.broadcasted_iota(jnp.int32, (L, za), 1)
    iota_h = jax.lax.broadcasted_iota(jnp.int32, (h, L), 0)

    def step(_, carry):
        vidx_b, vcnt_a, keys, vals = carry
        active = vidx_b < b_nnz
        sel_b = (vidx_b[:, None] == iota_zb).astype(vals.dtype)
        bk = jnp.round((sel_b * b_rows_ref[...]).sum(1)).astype(jnp.int32)
        bv = (sel_b * b_vals_ref[...]).sum(1)
        oh = (bk[:, None] == iota_na).astype(vals.dtype)
        ar_all = oh @ a_rows_f
        av_all = oh @ a_vals
        an = jnp.round(oh @ a_nnz_f).astype(jnp.int32)
        sel_a = (vcnt_a[:, None] == iota_za).astype(vals.dtype)
        r = jnp.round((sel_a * ar_all).sum(1)).astype(jnp.int32)   # keys [L]
        av = (sel_a * av_all).sum(1)
        contrib = jnp.where(active, av * bv, 0.0)

        # -- lock-step linear probing: h(i) = (i * c) mod H ----------------
        pos = (r * jnp.int32(HASH_C & 0x7FFFFFFF)) % h
        done = ~active                     # inactive lanes resolve trivially
        pos_final = jnp.zeros_like(pos)

        def probe(_, pc):
            pos, done, pos_final = pc
            sel = (pos[None, :] == iota_h)                  # [h, L]
            k_at = jnp.where(sel, keys, 0).sum(0)           # gather keys
            occ_at = jnp.where(sel, (keys != _EMPTY).astype(jnp.int32),
                               0).sum(0)
            ok = (k_at == r) & (occ_at == 1) | (occ_at == 0)
            newly = ~done & ok
            pos_final = jnp.where(newly, pos, pos_final)
            done = done | ok
            pos = jnp.where(done, pos, (pos + 1) % h)
            return pos, done, pos_final

        _, _, pos_final = jax.lax.fori_loop(
            0, max_probes, probe, (pos, done, pos_final))
        sel = (pos_final[None, :] == iota_h) & active[None, :]     # [h, L]
        vals = vals + jnp.where(sel, contrib[None, :], 0.0)
        keys = jnp.where(sel, r[None, :], keys)

        last = vcnt_a + 1 >= an
        vcnt_a = jnp.where(active & ~last, vcnt_a + 1, 0)
        vidx_b = vidx_b + (active & last).astype(vidx_b.dtype)
        return vidx_b, vcnt_a, keys, vals

    init = (
        jnp.zeros((L,), jnp.int32),
        jnp.zeros((L,), jnp.int32),
        jnp.full((h, L), _EMPTY, jnp.int32),
        jnp.zeros((h, L), vals_ref.dtype),
    )
    _, _, keys, vals = jax.lax.fori_loop(0, steps, step, init)
    keys_ref[...] = keys
    vals_ref[...] = vals


@functools.partial(
    jax.jit, static_argnames=("m", "h", "block_cols"))
def hash_spgemm(a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz, steps,
                *, m: int, h: int, block_cols: int = 128):
    """Per-lane hash tables (keys [h, n_b], vals [h, n_b]), HASH dataflow.

    ``h`` must be a power of two >= max Op_j of any processed column (the
    host blocking pass guarantees it; tables never overflow).
    """
    runtime.check_kernel("hash")
    n_a, za = a_rows.shape
    n_b, zb = b_rows.shape
    assert n_b % block_cols == 0, (n_b, block_cols)
    assert h & (h - 1) == 0, f"h={h} must be a power of two"
    n_blocks = n_b // block_cols
    kernel = functools.partial(
        _hash_kernel, m=m, za=za, n_a=n_a, h=h, max_probes=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_cols, zb), lambda i, s: (i, 0)),
            pl.BlockSpec((block_cols, zb), lambda i, s: (i, 0)),
            pl.BlockSpec((block_cols,), lambda i, s: (i,)),
            pl.BlockSpec((n_a, za), lambda i, s: (0, 0)),
            pl.BlockSpec((n_a, za), lambda i, s: (0, 0)),
            pl.BlockSpec((n_a,), lambda i, s: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((h, block_cols), lambda i, s: (0, i)),
            pl.BlockSpec((h, block_cols), lambda i, s: (0, i)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((h, n_b), jnp.int32),
            jax.ShapeDtypeStruct((h, n_b), a_vals.dtype),
        ],
        interpret=runtime.interpret_mode(),
    )(steps, b_rows, b_vals, b_nnz, a_rows, a_vals, a_nnz)


@functools.partial(
    jax.jit, static_argnames=("m", "h", "block_cols"))
def hash_spgemm_batched(a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz, steps,
                        *, m: int, h: int, block_cols: int = 128):
    """Batched HASH: tables (keys, vals) [B, h, n_b] for B value sets.

    Probing positions depend only on row indices, so every batch element
    fills identical table slots; only ``vals`` differs across the batch.
    Value operands carry the batch axis, pattern operands and trip counts
    are shared, and all B multiplies run in one vmapped launch
    (DESIGN.md §7).
    """
    f = functools.partial(hash_spgemm, m=m, h=h, block_cols=block_cols)
    return jax.vmap(f, in_axes=(None, 0, None, None, 0, None, None))(
        a_rows, a_vals, a_nnz, b_rows, b_vals, b_nnz, steps)
