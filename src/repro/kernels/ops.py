"""Host-facing jit'd wrappers around the Pallas kernels.

``run_spa``/``run_spars``/``run_hash`` each launch one kernel for a single
plan :class:`~repro.core.planner.KernelGroup` — the per-family column
grouping, padding, trip counts and hash sizes all come pre-computed from the
plan instead of being re-derived per call.  One launch per distinct hash
table size H realizes the paper's dynamic table shrinking as compile-time
VMEM tile selection (DESIGN.md §2); results are compacted per group straight
into CSC by the executor, so no ``[m, n]`` dense intermediate ever exists
(DESIGN.md §6).

``run_*_batched`` are the batched twins (DESIGN.md §7): the same plan group
executed once for B same-pattern value sets — value operands carry a
leading batch axis, pattern operands are shared, and the vmapped kernels
realize the batch as a leading grid dimension.

``spgemm_pallas`` is the device backend of ``core.api.spgemm``: a thin
plan-then-execute wrapper kept for direct use (tests, notebooks).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.sparse.format import CSC
from repro.kernels.spa import spa_spgemm, spa_spgemm_batched
from repro.kernels.spars import spars_spgemm, spars_spgemm_batched
from repro.kernels.hash_spgemm import hash_spgemm, hash_spgemm_batched


def device_operand(rows: np.ndarray, vals: np.ndarray, nnz: np.ndarray):
    """Padded-column operand triple as device arrays (shared by all groups)."""
    return (jnp.asarray(rows), jnp.asarray(vals), jnp.asarray(nnz))


def run_spa(group, a_arrs, b_vals, *, m: int, block_cols: int) -> np.ndarray:
    """Dense [m, n_real] tile for one SPA plan group."""
    a_rows, a_vals, a_nnz = a_arrs
    out = spa_spgemm(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz),
        m=m, block_cols=block_cols)
    return np.asarray(out)[:, : group.n_real]


def run_spars(group, a_arrs, b_vals, *, m: int, block_cols: int) -> np.ndarray:
    """Dense [m, n_real] tile for one SPARS plan group (plan-provided steps)."""
    a_rows, a_vals, a_nnz = a_arrs
    out, _flags = spars_spgemm(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz), jnp.asarray(group.steps),
        m=m, block_cols=block_cols)
    return np.asarray(out)[:, : group.n_real]


def run_hash(group, a_arrs, b_vals, *, m: int, block_cols: int):
    """Hash tables (keys, vals) [H, n_real] for one HASH plan group."""
    a_rows, a_vals, a_nnz = a_arrs
    keys, vals = hash_spgemm(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz), jnp.asarray(group.steps),
        m=m, h=int(group.h), block_cols=block_cols)
    return (np.asarray(keys)[:, : group.n_real],
            np.asarray(vals)[:, : group.n_real])


def run_spa_batched(group, a_arrs, b_vals, *, m: int,
                    block_cols: int) -> np.ndarray:
    """Dense [B, m, n_real] tiles for one SPA plan group, one launch."""
    a_rows, a_vals, a_nnz = a_arrs          # a_vals carries the batch axis
    out = spa_spgemm_batched(
        a_rows, a_vals, a_nnz,
        jnp.asarray(group.b_rows), jnp.asarray(b_vals),
        jnp.asarray(group.b_nnz),
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
