"""Host-facing jit'd wrappers around the Pallas kernels.

``run_spa``/``run_spars``/``run_hash`` each launch one kernel for a single
plan :class:`~repro.core.planner.KernelGroup` and return its device
result, padded to whole lane blocks (the executor reads it back) — the
per-family column
grouping, padding, trip counts and hash sizes all come pre-computed from the
plan instead of being re-derived per call.  One launch per distinct hash
table size H realizes the paper's dynamic table shrinking as compile-time
VMEM tile selection (DESIGN.md §2).  Where the plan allows, the executor
pads a call's operands (:func:`pad_operands`) and gathers its dense tiles
into C's values (:func:`compact_tiles`) on the device, else it pads and
compacts each group on the host, so no ``[m, n]`` dense intermediate ever
exists on the host (DESIGN.md §6).

``run_*_batched`` are the batched twins (DESIGN.md §7): the same plan group
executed once for B same-pattern value sets — value operands carry a
leading batch axis, pattern operands are shared, and the vmapped kernels
realize the batch as a leading grid dimension.

``spgemm_pallas`` is the device backend of ``core.api.spgemm``: a thin
plan-then-execute wrapper kept for direct use (tests, notebooks).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.sparse.format import CSC
from repro.kernels.spa import spa_spgemm, spa_spgemm_batched
from repro.kernels.spars import spars_spgemm, spars_spgemm_batched
from repro.kernels.hash_spgemm import hash_spgemm, hash_spgemm_batched


def device_operand(rows: np.ndarray, vals: np.ndarray, nnz: np.ndarray):
    """Padded-column operand triple as device arrays (shared by all groups)."""
    return (jnp.asarray(rows), jnp.asarray(vals), jnp.asarray(nnz))


@functools.partial(jax.jit, static_argnames=("a_shape", "widths", "zb"))
def pad_operands(a_vals, b_vals, a_pos, a_src, b_pos, b_src, *,
                 a_shape, widths, zb):
    """A's padded value table and each group's B operand, from the raw
    values: zeros with the values placed where the plan says
    (``planner.DeviceLayout``), as ``padded_values`` gives them."""
    def place(vals, src, pos, size):
        return jnp.zeros(size, vals.dtype).at[pos].set(
            vals[src], mode="promise_in_bounds", unique_indices=True)

    a = place(a_vals, a_src, a_pos, a_shape[0] * a_shape[1])
    b = place(b_vals, b_src, b_pos, sum(widths) * zb)
    ends = np.cumsum(widths) * zb
    return a.reshape(a_shape), tuple(
        b[e - w * zb: e].reshape(w, zb) for w, e in zip(widths, ends))


@jax.jit
def compact_tiles(tiles, src):
    """C's values from one call's dense tiles: the tiles flattened and laid
    end to end, gathered at ``src`` (``planner.DeviceLayout``)."""
    flat = jnp.concatenate([t.reshape(-1) for t in tiles])
    return flat.at[src].get(mode="promise_in_bounds")


def run_spa(group, a_arrs, b_vals, *, m: int, block_cols: int):
    """Dense [m, n_pad] tile for one SPA plan group, on the device."""
    a_rows, a_vals, a_nnz = a_arrs
    return spa_spgemm(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz), group.trips,
        m=m, block_cols=block_cols)


def run_spars(group, a_arrs, b_vals, *, m: int, block_cols: int):
    """Dense [m, n_pad] tile for one SPARS plan group (plan-provided
    steps), on the device."""
    a_rows, a_vals, a_nnz = a_arrs
    out, _flags = spars_spgemm(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz), jnp.asarray(group.steps),
        m=m, block_cols=block_cols)
    return out


def run_hash(group, a_arrs, b_vals, *, m: int, block_cols: int):
    """Hash tables (keys, vals) [H, n_pad] for one HASH plan group, on the
    device."""
    a_rows, a_vals, a_nnz = a_arrs
    return hash_spgemm(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz), jnp.asarray(group.steps),
        m=m, h=int(group.h), block_cols=block_cols)


def run_spa_batched(group, a_arrs, b_vals, *, m: int,
                    block_cols: int) -> np.ndarray:
    """Dense [B, m, n_real] tiles for one SPA plan group, one launch."""
    a_rows, a_vals, a_nnz = a_arrs          # a_vals carries the batch axis
    out = spa_spgemm_batched(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz), group.trips,
        m=m, block_cols=block_cols)
    return np.asarray(out)[:, :, : group.n_real]


def run_spars_batched(group, a_arrs, b_vals, *, m: int,
                      block_cols: int) -> np.ndarray:
    """Dense [B, m, n_real] tiles for one SPARS plan group, one launch."""
    a_rows, a_vals, a_nnz = a_arrs
    out, _flags = spars_spgemm_batched(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz), jnp.asarray(group.steps),
        m=m, block_cols=block_cols)
    return np.asarray(out)[:, :, : group.n_real]


def run_hash_batched(group, a_arrs, b_vals, *, m: int, block_cols: int):
    """Hash tables (keys, vals) [B, H, n_real] for one HASH plan group."""
    a_rows, a_vals, a_nnz = a_arrs
    keys, vals = hash_spgemm_batched(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz), jnp.asarray(group.steps),
        m=m, h=int(group.h), block_cols=block_cols)
    return (np.asarray(keys)[:, :, : group.n_real],
            np.asarray(vals)[:, :, : group.n_real])


def spgemm_pallas(
    a: CSC, b: CSC, method: str = "spa", *, t: float = 40.0,
    b_min: int | None = None, b_max: int | None = None,
    accumulator: str | None = None, block_cols: int = 128,
    tile_cols: int | None = None, tile=None, plan=None,
) -> CSC:
    """C = A @ B on the Pallas backend (plan once, execute once).

    The lock-step kernels use fixed-width column blocks (= ``block_cols``), so
    the b_min/b_max of the named method select the *family*; the dense-tile
    width is the kernel block. Hybrids split at ``t`` exactly as the paper.
    ``method="auto"`` builds a tiled plan whose per-tile kernel families the
    cost model picks (DESIGN.md §8; ``tile=`` sets the grid).  Pass a cached
    ``plan`` (from ``core.plan_spgemm`` / ``core.plan_spgemm_tiled``) to
    skip the symbolic phase entirely.
    """
    del accumulator  # family is selected by the method name
    from repro.core.backends import get_backend

    contract = get_backend("pallas")
    if method != "auto" and method in contract.excluded_methods:
        raise ValueError(
            f"method {method!r} has no {contract.name} kernel family "
            "(host-only)")
    if tile is not None and (plan is not None or method != "auto"):
        raise ValueError(
            "tile= only applies to method='auto' without a held plan")
    if plan is None:
        if method == "auto":
            if (t != 40.0 or b_min is not None or b_max is not None
                    or block_cols != 128 or tile_cols is not None):
                raise ValueError(
                    "t/b_min/b_max/block_cols/tile_cols do not apply to "
                    "method='auto' (per-tile methods use their own "
                    "defaults)")
            from repro.core.planner import plan_spgemm_tiled

            plan = plan_spgemm_tiled(a, b, backend="pallas", tile=tile)
        else:
            from repro.core.planner import plan_spgemm

            plan = plan_spgemm(a, b, method, backend="pallas", t=t,
                               b_min=b_min, b_max=b_max,
                               block_cols=block_cols, tile_cols=tile_cols)
    return plan.execute(a, b)
