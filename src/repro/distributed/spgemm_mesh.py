"""Multi-device SpGEMM: the tile grid and product stream across a mesh.

Single-device execution is bounded by the plan-memory guard — a product
stream above ``fast.STREAM_MAX_PRODUCTS`` cannot live on one device, so the
biggest multiplies fell back to the slow transient host path.  This module
lifts that ceiling by composing two existing decompositions (DESIGN.md §13):

* the PR 3 outer-block-product grid — ``C[:, n] = Σ_k A[:, k] @ B[k, n]`` —
  provides tiles whose *child* streams each fit a per-shard guard, and
* the propagation-blocking formulation of Gu et al. (arXiv 2002.11302) —
  bin intermediate products by destination at plan time so the runtime
  reduction streams over contiguous segments instead of scattering —
  provides the cross-device merge shape.

:func:`plan_spgemm_mesh` builds a :class:`ShardedSpgemmPlan`: the grid is
sized so every tile's stream fits ``shard_limit`` (the guard applies *per
shard*, which is how matrices above one device's guard become plannable),
tiles are binned to devices by the PR 3/PR 5 cost model balancing predicted
flops — greedy LPT on the calibrated per-tile device-stream cost, not tile
count — and every tile's frozen product stream is rewritten into *global*
coordinates: positions into the full A/B value arrays, C slots into the
plan-wide canonical output structure (the union of the tiles' structures,
assembled per column block with the deterministic k-ordered
``merge_csc_partials`` contract).

Execution is one ``shard_map``: each device replays its own padded slice of
the stacked ``[D, Pmax]`` index arrays (gather → multiply → ``segment_sum``
into the padded slot axis), and the partial-C reduction is a single
plan-static ``psum_scatter`` over the contiguous slot segments — the
destination binning happened at plan time, so no dynamic cross-device
scatter exists at runtime.  The contraction is bilinear, so gradients are
two more sharded replays through the same frozen indices, installed with
the shared :func:`~repro.core.jax_stream.bilinear_custom_vjp` — the mesh
backend is jit-compatible and differentiable end to end.

Determinism contract: within a device, tiles accumulate in the plan's fixed
(n-major, k-ascending) order; across devices, the reduction order is the
mesh order baked into ``psum_scatter``.  Both orders are plan-static —
independent of device *completion* order — so repeated executions are
bit-identical, and integer-valued operands reproduce the single-device
host stream bit for bit (see DESIGN.md §9 for the fp-reassociation
boundary on generic floats).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import repro.core.fast as _fast
from repro.core.cost import CostConstants, DEFAULT_CONSTANTS
from repro.core.executor import register_executor
from repro.core.jax_stream import (
    _IN_BOUNDS,
    _I32_MAX,
    _take,
    bilinear_custom_vjp,
    bind_indices,
    stream_seg_ids,
)
from repro.core.planner import (
    Pattern,
    TilePlan,
    normalize_tile_spec,
    plan_spgemm,
    resolve_params,
)
from repro.sparse.format import CSC, BatchedCSC, _np
from repro.sparse.partition import (
    csc_col_slice,
    csc_empty,
    csc_hstack,
    csc_row_slice,
    merge_csc_partials,
    nnz_balanced_col_bounds,
    width_col_bounds,
)
from repro.sparse.stats import ops_per_column, tile_stats

MESH_AXIS = "shards"


# ---------------------------------------------------------------------------
# the sharded stream: every device's replay indices, stacked and padded
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardStream:
    """Device-stacked product stream of a :class:`ShardedSpgemmPlan`.

    Row ``d`` of the ``[D, Pmax]`` arrays is device ``d``'s replay: global
    positions into the full A/B value arrays (``a_pos``/``b_pos``), the
    *global padded* C slot of each product (``seg``), and a validity mask
    (pad entries gather position 0 and point ``seg`` at the trash slot
    ``num_slots``, so they can never contaminate a real output).  The slot
    axis is padded to ``padded_slots = D * (padded_slots // D)`` so the
    cross-device reduction is one tiled ``psum_scatter`` over contiguous
    segments.  ``c_rows``/``c_col_ptr`` are the plan-wide canonical output
    structure (host, frozen), shared by every result the plan produces.
    """

    a_pos: jax.Array        # [D, Pmax] int32 into A's value array
    b_pos: jax.Array        # [D, Pmax] int32 into B's value array
    seg: jax.Array          # [D, Pmax] int32 global padded C slot
    mask: jax.Array         # [D, Pmax] bool, False on pad entries
    c_rows: np.ndarray      # [nnz_c] int32 (host, frozen)
    c_col_ptr: np.ndarray   # [n+1] int32 (host, frozen)
    shape: Tuple[int, int]
    n_products: int         # real (unpadded) products, all devices
    num_slots: int          # nnz_c
    padded_slots: int       # psum_scatter axis length, divisible by D
    per_device: np.ndarray  # [D] int64 real products per device

    @property
    def nbytes(self) -> int:
        """Device bytes held by the stacked index arrays."""
        return int(self.a_pos.nbytes + self.b_pos.nbytes
                   + self.seg.nbytes + self.mask.nbytes)


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedSpgemmPlan:
    """Immutable symbolic plan for a mesh-distributed ``C = A @ B``.

    Built by :func:`plan_spgemm_mesh`; a ``backend="mesh"`` entry of the
    ``ExecutionContract`` registry.  ``tiles`` are ordinary
    :class:`~repro.core.planner.TilePlan` children (expand-method plans on
    the jax backend, shared through the plan LRU with any same-pattern
    tile); ``device_of[i]`` is the device the cost model placed
    ``tiles[i]`` on.  Execute with ``plan.execute(a, b)`` or trace
    ``plan.stream_apply(a_values, b_values)`` (jit-compatible,
    differentiable).
    """

    a: Pattern
    b: Pattern
    k_bounds: np.ndarray          # [K+1] over A's columns / B's rows
    n_bounds: np.ndarray          # [N+1] over B's columns
    tiles: Tuple[TilePlan, ...]   # non-empty tiles, n-major, k-ascending
    device_of: np.ndarray         # [n_tiles] int32 device index
    n_shards: int
    shard_limit: int              # per-shard plan-memory guard (products)
    predicted_cost: np.ndarray    # [D] float64 placed seconds per device
    predicted_flops: np.ndarray   # [D] int64 placed flops per device
    params: tuple
    _memo: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    method = "expand"             # the canonical stream contraction
    backend = "mesh"

    @property
    def contract(self):
        from repro.core import backends

        return backends.get_backend("mesh")

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.a.shape[0], self.b.shape[1])

    @property
    def grid(self) -> Tuple[int, int]:
        return (len(self.k_bounds) - 1, len(self.n_bounds) - 1)

    @property
    def stream_limit(self) -> int:
        # uniform spelling with SpgemmPlan (the guard here is per shard)
        return self.shard_limit

    @property
    def imbalance(self) -> float:
        """max/mean predicted flops across devices (1.0 = perfect)."""
        mean = float(self.predicted_flops.mean())
        if mean <= 0:
            return 1.0
        return float(self.predicted_flops.max()) / mean

    @property
    def stream(self) -> ShardStream:
        """The device-stacked sharded stream (lazy, memoized)."""
        return shard_stream(self)

    @property
    def mesh_stream_nbytes(self) -> int:
        """Bytes of stacked shard-stream index data currently held.

        Reads the memo without triggering the lazy build — what
        ``plan_cache_info()['mesh_stream_bytes']`` aggregates.  The child
        tile plans' own streams are counted by the existing host/device
        stream totals (children live in the shared LRU).
        """
        ss = self._memo.get("mesh")
        return ss.nbytes if ss is not None else 0

    @property
    def cache_key(self) -> tuple:
        # mirrors core.api's mesh LRU key
        return (self.a.fingerprint, self.b.fingerprint, self.method,
                self.backend, self.params, self.shard_limit)

    def stream_apply(self, a_values, b_values):
        """Jit-compatible, differentiable numeric phase: C values only.

        Mirrors ``SpgemmPlan.stream_apply`` for the mesh backend: value
        arrays (or tracers) aligned with the planned patterns in, the
        ``[nnz_c]`` value array of the plan's canonical output structure
        out — a pure function safe under ``jax.jit``/``jax.grad``.
        """
        self.a.check_compatible(a_values)
        self.b.check_compatible(b_values)
        return mesh_fn(self)(a_values, b_values)

    def execute(self, a_values, b_values, *,
                stats: dict | None = None, validate: str | None = None,
                engine: str | None = None) -> CSC:
        """Numeric phase through the executor dispatch (one shard_map)."""
        from repro.core.executor import execute

        return execute(self, a_values, b_values, stats=stats,
                       validate=validate, engine=engine)

    def execute_batched(self, a_values, b_values, *,
                        stats: dict | None = None,
                        validate: str | None = None,
                        engine: str | None = None) -> list:
        """Batched numeric phase (B same-pattern value sets)."""
        from repro.core.executor import execute_batched

        return execute_batched(self, a_values, b_values, stats=stats,
                               validate=validate, engine=engine)


# ---------------------------------------------------------------------------
# planning: grid sizing, child plans, cost-model placement
# ---------------------------------------------------------------------------


def _ops_balanced_bounds(ops: np.ndarray, n_blocks: int) -> np.ndarray:
    """Column-block boundaries that roughly equalize *predicted flops*.

    The destination-binning twin of ``nnz_balanced_col_bounds``: cuts at
    the quantiles of cumulative ``Op_j`` (flops per output column), so
    column blocks carry comparable work — which is what the placement
    balances — rather than comparable stored entries.
    """
    n = len(ops)
    if n == 0:
        return np.asarray([0], np.int64)
    n_blocks = max(1, min(int(n_blocks), n))
    cum = np.concatenate(([0], np.cumsum(ops, dtype=np.int64)))
    if n == 1 or n_blocks == 1:
        return np.asarray([0, n], np.int64)
    targets = np.linspace(0, cum[-1], n_blocks + 1)[1:-1]
    cuts = np.clip(np.searchsorted(cum, targets, side="left"), 1, n - 1)
    return np.unique(np.concatenate(([0], cuts, [n]))).astype(np.int64)


def _auto_bounds(a: CSC, b: CSC, n_shards: int, budget: int) -> tuple:
    """(k_bounds, n_bounds) sized so every tile's stream fits ``budget``.

    The n axis splits at flop quantiles until the largest column block
    fits (with 2x headroom for placement slack) and there are at least a
    few tiles per device for the LPT bin-packing to balance; a single
    output column hotter than the budget then forces the k axis to split
    (a k split divides one column's products across row blocks).
    """
    ops = ops_per_column(a, b)
    total = int(ops.sum())
    target = max(1, budget // 2)
    n_cols = b.n_cols
    want = max(min(2 * n_shards, max(n_cols, 1)), -(-total // target))
    n_bounds = _ops_balanced_bounds(ops, want)
    for _ in range(32):
        if len(n_bounds) - 1 >= n_cols or len(ops) == 0:
            break
        block = np.add.reduceat(ops, n_bounds[:-1])
        if block.max() <= budget:
            break
        want *= 2
        n_bounds = _ops_balanced_bounds(ops, want)
    hottest = int(ops.max()) if len(ops) else 0
    if hottest > budget:
        k_blocks = min(max(a.n_cols, 1), -(-hottest // target))
        k_bounds = nnz_balanced_col_bounds(a, k_blocks)
    else:
        k_bounds = np.asarray([0, a.n_cols], np.int64)
    return k_bounds, n_bounds


def plan_spgemm_mesh(
    a: CSC,
    b: CSC,
    *,
    shards: int | None = None,
    tile=None,
    shard_limit: int | None = None,
    cache: bool = True,
    constants: CostConstants | None = None,
) -> ShardedSpgemmPlan:
    """Build the mesh-distributed symbolic plan for ``C = A @ B``.

    ``shards`` — mesh size (defaults to every visible device; planning for
    more shards than currently visible is allowed, execution then raises
    with the ``XLA_FLAGS`` fix).  ``shard_limit`` — the *per-shard*
    plan-memory guard (defaults to ``fast.default_stream_limit``): the grid
    is auto-sized so every tile's stream fits it, which is how a multiply
    whose total stream exceeds the single-device guard stays plannable.
    ``tile`` — explicit ``(k_width, n_width)`` grid override (see
    ``normalize_tile_spec``); the default auto grid bins output columns at
    flop quantiles.  ``cache=True`` funnels child tile plans through the
    shared plan LRU.  Raises when the total stream cannot fit
    ``shards x shard_limit`` at all.
    """
    if a.n_cols != b.n_rows:
        raise ValueError(f"shape mismatch {a.shape} @ {b.shape}")
    n_shards = len(jax.devices()) if shards is None else int(shards)
    if n_shards < 1:
        raise ValueError(f"shards must be >= 1, got {n_shards}")
    limit = (_fast.default_stream_limit(device=True) if shard_limit is None
             else int(shard_limit))
    if limit < 1:
        raise ValueError(f"shard_limit must be >= 1, got {limit}")
    # constants=None resolves through the machine profile (core.profile):
    # a measured fit re-ranks the LPT placement below, and its provenance
    # tag becomes part of the plan params / cache key
    if constants is None:
        from repro.core import profile as _profile

        prof = _profile.current_profile()
        c, profile_tag = prof.constants, prof.tag
    else:
        c, profile_tag = constants, "explicit"

    spec = normalize_tile_spec(tile)
    k_width, n_width = spec
    auto_k, auto_n = _auto_bounds(a, b, n_shards, limit)
    k_bounds = (width_col_bounds(a.n_cols, k_width) if k_width else auto_k)
    n_bounds = (width_col_bounds(b.n_cols, n_width) if n_width else auto_n)

    def _child(ta, tb):
        if cache:
            from repro.core.api import _cached_plan

            return _cached_plan(ta, tb, "expand", "jax",
                                resolve_params("expand"),
                                stream_limit=limit)
        return plan_spgemm(ta, tb, "expand", backend="jax",
                           stream_limit=limit)

    a_tiles = [csc_col_slice(a, int(k0), int(k1))
               for k0, k1 in zip(k_bounds[:-1], k_bounds[1:])]
    tiles: list[TilePlan] = []
    tile_flops: list[int] = []
    for ni, (j0, j1) in enumerate(zip(n_bounds[:-1], n_bounds[1:])):
        b_col, (b_lo, _) = csc_col_slice(b, int(j0), int(j1))
        for ki, (k0, k1) in enumerate(zip(k_bounds[:-1], k_bounds[1:])):
            a_tile, (a_lo, a_hi) = a_tiles[ki]
            if a_tile.nnz == 0:
                continue
            b_tile, rel = csc_row_slice(b_col, int(k0), int(k1))
            if b_tile.nnz == 0:
                continue
            st = tile_stats(a_tile, b_tile)
            if st.flops == 0:
                continue
            if st.flops > limit:
                raise ValueError(
                    f"tile (k={ki}, n={ni}) carries {st.flops} products, "
                    f"above the per-shard guard shard_limit={limit}; "
                    "shrink tile= or raise shard_limit")
            tiles.append(TilePlan(
                k=ki, n=ni, a_vals=(a_lo, a_hi), b_vals=b_lo + rel,
                plan=_child(a_tile, b_tile), engine=None))
            tile_flops.append(int(st.flops))

    # LPT placement on the calibrated device-stream cost (dispatch + flat
    # per-product work): heaviest tile first onto the least-loaded device.
    # Cost is affine in flops, so balancing cost balances flops — the
    # imbalance the benchmark gates on.
    cost_of = [c.jax_base + c.jax_prod * f for f in tile_flops]
    device_of = np.zeros(len(tiles), np.int32)
    loads = np.zeros(n_shards, np.float64)
    flops_d = np.zeros(n_shards, np.int64)
    for i in sorted(range(len(tiles)), key=lambda i: -cost_of[i]):
        d = int(np.argmin(loads))
        device_of[i] = d
        loads[d] += cost_of[i]
        flops_d[d] += tile_flops[i]
    if len(tiles) and int(flops_d.max()) > limit:
        raise ValueError(
            f"placement puts {int(flops_d.max())} products on one shard, "
            f"above shard_limit={limit} (total {sum(tile_flops)} products "
            f"over {n_shards} shards); raise shards= or shard_limit=")

    params = (("profile", profile_tag), ("shard_limit", limit),
              ("shards", n_shards), ("tile", spec))
    return ShardedSpgemmPlan(
        Pattern.of(a), Pattern.of(b),
        np.asarray(k_bounds, np.int64), np.asarray(n_bounds, np.int64),
        tuple(tiles), device_of, n_shards, limit,
        loads, flops_d, params)


# ---------------------------------------------------------------------------
# plan -> ShardStream: global structure, destination bins, stacked indices
# ---------------------------------------------------------------------------


def _mesh_guard_error(plan, tile) -> ValueError:
    return ValueError(
        f"tile (k={tile.k}, n={tile.n}) of the mesh plan has no product "
        f"stream (child guard shard_limit={plan.shard_limit} tripped); "
        "replan with a higher shard_limit or a finer tile grid")


def shard_stream(plan: ShardedSpgemmPlan) -> ShardStream:
    """Build (lazily, memoized) the plan's device-stacked stream.

    Three plan-time passes, all pattern-only:

    1. **Global structure** — per column block, the tiles' child C
       structures merge through the deterministic k-ordered
       ``merge_csc_partials`` contract (values zero — structure union
       only); blocks stitch into the plan-wide canonical CSC structure.
    2. **Destination binning** — each tile's child stream slots map into
       the global slot space with one ``searchsorted`` per tile (child
       structures are sub-sequences of their block's union), and the slot
       axis pads to a multiple of D so the runtime reduction is a tiled
       ``psum_scatter`` over contiguous segments.
    3. **Stacking** — per device, its tiles' streams concatenate in the
       plan's fixed n-major/k-ascending order, rewritten to global A/B
       value positions, padded to the longest device's length (pads mask
       off and point at the trash slot past ``nnz_c``), and row ``d``
       is placed on mesh device ``d`` alone.
    """
    memo = plan._memo
    if "mesh" in memo:
        return memo["mesh"]
    m, n = plan.shape
    D = plan.n_shards
    N = len(plan.n_bounds) - 1

    per_block: dict = {ni: [] for ni in range(N)}
    for ti, t in enumerate(plan.tiles):
        s = t.plan.stream
        if s is None:
            raise _mesh_guard_error(plan, t)
        per_block[t.n].append((ti, t, s))

    # pass 1: global canonical structure (per-block k-ordered union)
    blocks = []
    for ni in range(N):
        w = int(plan.n_bounds[ni + 1] - plan.n_bounds[ni])
        parts = [CSC(np.zeros(s.nnz), s.c_rows, s.c_col_ptr, (m, w))
                 for _, _, s in per_block[ni]]
        blocks.append(merge_csc_partials(parts, (m, w))
                      if parts else csc_empty((m, w)))
    gc = csc_hstack(blocks, m) if blocks else csc_empty((m, 0))
    c_rows = np.ascontiguousarray(_np(gc.row_indices), np.int32)
    c_col_ptr = np.ascontiguousarray(_np(gc.col_ptr), np.int32)
    nnz_c = int(c_col_ptr[-1])
    block_off = np.concatenate(
        ([0], np.cumsum([blk.nnz for blk in blocks]))).astype(np.int64)

    # pass 2+3: per-device global index streams (plan order within device)
    dev_parts: list = [[] for _ in range(D)]
    for ni in range(N):
        blk = blocks[ni]
        key_b = (np.repeat(np.arange(blk.n_cols, dtype=np.int64),
                           np.diff(_np(blk.col_ptr).astype(np.int64)))
                 * m + _np(blk.row_indices).astype(np.int64))
        for ti, t, s in per_block[ni]:
            key_t = (np.repeat(np.arange(s.shape[1], dtype=np.int64),
                               np.diff(s.c_col_ptr.astype(np.int64)))
                     * m + s.c_rows.astype(np.int64))
            slot = np.searchsorted(key_b, key_t) + block_off[ni]
            seg = slot[stream_seg_ids(s)]
            a_idx = t.a_vals[0] + s.a_pos
            b_idx = np.asarray(t.b_vals, np.int64)[s.b_pos]
            dev_parts[int(plan.device_of[ti])].append((a_idx, b_idx, seg))

    per_device = np.asarray(
        [sum(len(p[0]) for p in parts) for parts in dev_parts], np.int64)
    total = int(per_device.sum())
    p_max = max(1, int(per_device.max()) if D else 1)
    s_per = -(-(nnz_c + 1) // D)          # >= 1 trash slot past nnz_c
    s_pad = D * s_per
    if max(int(plan.a.col_ptr[-1]), int(plan.b.col_ptr[-1]),
           s_pad, p_max) > _I32_MAX:
        raise ValueError(
            f"sharded stream of {total} products over operands of nnz "
            f"{int(plan.a.col_ptr[-1])}/{int(plan.b.col_ptr[-1])} exceeds "
            "int32 device indexing; lower shard_limit or shrink the tiles")

    ap = np.zeros((D, p_max), np.int32)
    bp = np.zeros((D, p_max), np.int32)
    sg = np.full((D, p_max), nnz_c, np.int32)   # pads -> the trash slot
    mk = np.zeros((D, p_max), bool)
    for d, parts in enumerate(dev_parts):
        if not parts:
            continue
        a_idx = np.concatenate([p[0] for p in parts])
        b_idx = np.concatenate([p[1] for p in parts])
        seg = np.concatenate([p[2] for p in parts])
        L = len(a_idx)
        ap[d, :L] = a_idx
        bp[d, :L] = b_idx
        sg[d, :L] = seg
        mk[d, :L] = True
    # row d lives on device d only: each chip holds its own shard's stream
    rows = NamedSharding(_device_mesh(D), PartitionSpec(MESH_AXIS, None))
    with jax.ensure_compile_time_eval():
        dev_arrays = tuple(jax.device_put(x, rows) for x in (ap, bp, sg, mk))
    memo["mesh"] = ShardStream(
        a_pos=dev_arrays[0], b_pos=dev_arrays[1], seg=dev_arrays[2],
        mask=dev_arrays[3], c_rows=c_rows, c_col_ptr=c_col_ptr,
        shape=(m, n), n_products=total, num_slots=nnz_c,
        padded_slots=s_pad, per_device=per_device)
    return memo["mesh"]


# ---------------------------------------------------------------------------
# execution: one shard_map, plan-static psum_scatter reduction, custom vjp
# ---------------------------------------------------------------------------


def _device_mesh(n_shards: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"mesh plan needs {n_shards} devices, found {len(devs)}; run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} (or replan with shards={len(devs)})")
    return Mesh(np.asarray(devs[:n_shards]), (MESH_AXIS,))


def _pad_to(vec, length):
    """Zero-pad a 1-D array to ``length`` (identity when already there)."""
    if vec.shape[0] == length:
        return vec
    return jnp.zeros((length,), vec.dtype).at[:vec.shape[0]].set(vec)


def mesh_fn(plan: ShardedSpgemmPlan):
    """The plan's jitted sharded numeric function ``f(av, bv) -> c_values``.

    Memoized on the plan.  Forward: every shard gathers/multiplies its own
    ``[Pmax]`` product slice, ``segment_sum``s into the padded global slot
    axis, and one tiled ``psum_scatter`` finishes the reduction — each
    device keeps its contiguous destination bin, and the stitched output
    slices back to ``[nnz_c]``.  Gradients are the same shape twice over
    (bilinear contraction): cotangents broadcast back over the products
    and scatter-add into padded *operand* axes, reduced by the same
    plan-static ``psum_scatter``, so ``jax.grad`` costs two more sharded
    replays.
    """
    memo = plan._memo
    if "mesh_fn" in memo:
        return memo["mesh_fn"]
    ss = shard_stream(plan)
    nnz_a = int(plan.a.col_ptr[-1])
    nnz_b = int(plan.b.col_ptr[-1])
    nnz_c, s_pad = ss.num_slots, ss.padded_slots
    D = plan.n_shards

    if ss.n_products == 0:
        # nothing to contract: C values are structurally zero (or empty)
        idx = ()

        def forward(idx, av, bv):
            dt = jnp.result_type(jnp.asarray(av).dtype,
                                 jnp.asarray(bv).dtype)
            return jnp.zeros((nnz_c,), dt)

        def grad_a(idx, g, av, bv):
            return jnp.zeros_like(jnp.asarray(av))

        def grad_b(idx, g, av, bv):
            return jnp.zeros_like(jnp.asarray(bv))
    else:
        mesh = _device_mesh(D)
        P = PartitionSpec
        a_pad = D * (-(-max(nnz_a, 1) // D))
        b_pad = D * (-(-max(nnz_b, 1) // D))
        sharded = functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(P(), P(), P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS),
                      P(MESH_AXIS)),
            out_specs=P(MESH_AXIS))

        def _scatter(part):
            return jax.lax.psum_scatter(part, MESH_AXIS,
                                        scatter_dimension=0, tiled=True)

        @sharded
        def _fwd(av, bv, ap, bp, sg, mk):
            prod = jnp.where(mk[0], _take(av, ap[0]) * _take(bv, bp[0]), 0)
            part = jax.ops.segment_sum(prod, sg[0], num_segments=s_pad,
                                       mode=_IN_BOUNDS)
            return _scatter(part)

        @sharded
        def _grad_a(gp, bv, ap, bp, sg, mk):
            gq = _take(gp, sg[0])
            contrib = jnp.where(mk[0], gq * _take(bv, bp[0]), 0)
            part = jax.ops.segment_sum(contrib, ap[0], num_segments=a_pad,
                                       mode=_IN_BOUNDS)
            return _scatter(part)

        @sharded
        def _grad_b(gp, av, ap, bp, sg, mk):
            gq = _take(gp, sg[0])
            contrib = jnp.where(mk[0], gq * _take(av, ap[0]), 0)
            part = jax.ops.segment_sum(contrib, bp[0], num_segments=b_pad,
                                       mode=_IN_BOUNDS)
            return _scatter(part)

        idx = (ss.a_pos, ss.b_pos, ss.seg, ss.mask)

        def forward(idx, av, bv):
            return _fwd(av, bv, *idx)[:nnz_c]

        def _fit(cot, primal, nnz):
            # the cotangent must match the primal operand's (possibly
            # oversized) value-array shape; positions past nnz never
            # entered the contraction, so their cotangent is zero
            want = jnp.asarray(primal).shape[0]
            cot = cot[:nnz]
            if want == nnz:
                return cot
            return jnp.zeros((want,), cot.dtype).at[:nnz].set(cot)

        def grad_a(idx, g, av, bv):
            gp = _pad_to(g, s_pad)
            return _fit(_grad_a(gp, bv, *idx), av, nnz_a)

        def grad_b(idx, g, av, bv):
            gp = _pad_to(g, s_pad)
            return _fit(_grad_b(gp, av, *idx), bv, nnz_b)

    memo["mesh_fn"] = bind_indices(
        bilinear_custom_vjp(forward, grad_a, grad_b), idx)
    return memo["mesh_fn"]


def _operand_values(operand):
    return operand.values if isinstance(operand, (CSC, BatchedCSC)) \
        else operand


def _record_stats(plan, ss, stats):
    if stats is None:
        return
    stats.update(engine="stream", backend="mesh", device=True,
                 fallback=None, shards=plan.n_shards, grid=plan.grid,
                 stream_products=ss.n_products,
                 per_device_products=ss.per_device.tolist(),
                 imbalance=plan.imbalance, result_shape=ss.shape)


def execute_mesh(plan, a_values, b_values, *,
                 stats: dict | None = None,
                 validate: str | None = None) -> CSC:
    """Numeric phase of a mesh plan (executor dispatch target).

    One jitted ``shard_map`` dispatch; the result's values are a device
    array on the plan's canonical global output structure.
    """
    plan.a.check_compatible(a_values, validate)
    plan.b.check_compatible(b_values, validate)
    av = _operand_values(a_values)
    bv = _operand_values(b_values)
    vals = mesh_fn(plan)(av, bv)
    ss = shard_stream(plan)
    _record_stats(plan, ss, stats)
    return CSC(vals, ss.c_rows, ss.c_col_ptr, ss.shape)


def execute_mesh_batched(plan, a_values, b_values, *,
                         stats: dict | None = None,
                         validate: str | None = None) -> list:
    """Batched numeric phase: B value sets through the sharded replay.

    Dispatches the jitted sharded function once per batch element (the
    collective-bearing ``shard_map`` does not ride ``vmap``); results are
    bit-identical to looping :func:`execute_mesh` by construction.
    """
    from repro.core.executor import _check_batch

    plan.a.check_batched_compatible(a_values, validate)
    plan.b.check_batched_compatible(b_values, validate)
    av = _operand_values(a_values)
    bv = _operand_values(b_values)
    batch = _check_batch(av, bv)
    fn = mesh_fn(plan)
    ss = shard_stream(plan)
    out = [CSC(fn(av[i], bv[i]), ss.c_rows, ss.c_col_ptr, ss.shape)
           for i in range(batch)]
    _record_stats(plan, ss, stats)
    if stats is not None:
        stats["batch"] = batch
    return out


register_executor("mesh", "stream", execute_mesh, execute_mesh_batched)
