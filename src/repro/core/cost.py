"""Analytical per-tile cost model for ``method="auto"`` (DESIGN.md §8).

Each tile of a :class:`~repro.core.planner.TiledSpgemmPlan` gets the method
the model predicts cheapest for that tile's work profile — the paper's
per-column hybrid switching generalized to per-tile method selection, in
the spirit of Nagasaka et al.'s per-region accumulator choice.

Two separate models, selected by backend:

* **host** — predicts wall time (seconds) of the host executors.  Their
  cost structure is dominated by Python-loop overhead versus vectorized
  throughput: SPA pays a per-column and per-B-entry loop toll but touches
  each product once; ``expand`` replays the plan's cached product stream
  (``core.fast``, DESIGN.md §9) — a flat per-product cost with no sort —
  *when the stream fits the plan-memory guard*; above the guard every
  execution rebuilds the stream transiently (lexsort + boundary scan per
  call), which is where SPA wins back flop-heavy tiles.  The lock-step
  executors (SPARS/HASH) pay a Python iteration per lock-step round.
  Constants are calibrated by ``benchmarks/tiled.py --calibrate`` (values
  below are from that script on the CI container class; they only need to
  be right *relative* to each other, and the regimes they separate differ
  by orders of magnitude).
* **pallas** — predicts relative kernel work from the DESIGN.md §2 cost
  dictionary: SPA streams every B entry against an ``[m, L]`` tile, SPARS
  pays the block-max trip count against the same tile, HASH pays it against
  an ``[H, L]`` table with ``H`` sized from the block's worst column — so
  sparse tiles favour HASH (``H << m``) and dense tiles favour SPA, exactly
  the paper's Figure 3/4 crossover.

The model consumes only :class:`~repro.sparse.stats.TileStats` (pattern
statistics, O(nnz)); it never looks at values.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.core import backends
import repro.core.fast as _fast
from repro.sparse.stats import TileStats

# default per-backend candidate sets for method="auto" (one entry per
# registered backend contract — core/backends.py).  Host: the engines with
# complementary regimes (expand -> the plan-resident product stream,
# cheapest per product while the stream fits the memory guard; SPA: no
# plan-resident O(flops) state, wins guard-tripped flop-heavy tiles; "jax"
# -> the device-resident stream of DESIGN.md §10, picked for in-guard
# tiles wherever the calibrated device per-product cost undercuts the
# numpy stream — on accelerator-backed installs, not the CI CPU, see
# CostConstants.jax_prod; "fused" -> the single-launch fused Pallas
# kernel of DESIGN.md §11, same admission logic with its own calibrated
# constants).  Pallas: the paper's families — dense-tile SPA vs
# small-table HASH, with SPARS between.  Jax: the device stream and its
# fused lowering.
AUTO_CANDIDATES = {
    "host": ("spa", "expand", "jax", "fused"),
    "pallas": ("spa", "spars-40/40", "hash-256/256"),
    "jax": ("jax", "fused"),
    # mesh children are device-stream replays; the distribute-or-not
    # decision itself is estimate_mesh_cost/should_distribute, not a
    # per-tile method race
    "mesh": ("jax",),
}


@dataclasses.dataclass(frozen=True)
class CostConstants:
    """Calibrated coefficients (host entries in seconds; pallas relative).

    Host values measured by ``benchmarks/tiled.py --calibrate``; see module
    docstring.
    """

    # host spa_numpy: per-column loop + per-B-entry vector op + per product
    spa_col: float = 3.0e-6
    spa_entry: float = 6.7e-6
    spa_flop: float = 1.0e-8
    # host stream engine (core/fast.py): fixed kernel-call overhead + flat
    # per-product gather/multiply/segment-reduce cost (plan-resident stream)
    stream_base: float = 5.9e-6
    stream_prod: float = 6.6e-9
    # guard-tripped expand: per-call transient stream rebuild (expansion +
    # lexsort) on top of the per-product stream work
    expand_base: float = 1.0e-4
    expand_prod: float = 1.5e-7
    expand_sort: float = 8.0e-9       # per product per log2(products)
    # jax device stream (core/jax_stream.py): fixed jitted-dispatch
    # overhead + flat per-product device cost (cached-trace steady state;
    # measured by ``benchmarks/tiled.py --calibrate``).  On the CI
    # container class XLA *CPU* scatter-add dominates (segment_sum is
    # near-serial there), so the honest per-product constant is above the
    # numpy stream's and host auto only picks "jax" after re-calibration
    # on hardware where the scatter is parallel (real devices)
    jax_base: float = 1.4e-5
    jax_prod: float = 3.7e-8
    # fused Pallas stream kernel (core/pallas_stream.py): one launch for
    # the whole numeric phase.  Constants are the honest CI-container
    # numbers (``benchmarks/tiled.py --calibrate``), where the kernel runs
    # in the Pallas interpreter and the [block, block] one-hot
    # contraction is emulated on CPU — per-product cost sits ~40x above
    # the numpy stream's, so auto never picks "fused" here.  Re-calibrate
    # on a real device, where the MXU absorbs the one-hot matmul and this
    # becomes the cheapest in-guard family.
    fused_base: float = 7.9e-5
    fused_prod: float = 3.0e-7
    # mesh backend communication terms (DESIGN.md §13): fixed collective
    # dispatch/launch overhead per sharded execution, plus a per-byte toll
    # on the cross-device partial-C reduction — a tiled psum_scatter moves
    # ~(D-1)/D of the padded slot axis through the interconnect.  The
    # defaults are honest CI-container numbers (host mesh of XLA CPU
    # devices: the "interconnect" is memcpy), deliberately conservative so
    # auto only distributes when the stream guard forces it or the matrix
    # is far past single-device scale.
    comm_base: float = 1.0e-3
    comm_byte: float = 5.0e-10
    # host esc_numpy: expand + explicit LSD radix rounds
    esc_base: float = 2.0e-4
    esc_round: float = 1.2e-7         # per product per radix round
    # host lock-step executors: per Python round + per product probe work
    lockstep_iter: float = 3.0e-5
    hash_probe: float = 3.0e-6
    # pallas relative-work coefficients (unitless; compared per backend)
    p_spa_entry: float = 1.0          # x m per streamed B entry
    p_spa_col: float = 1.0            # x m per output column (tile init)
    p_lock_iter: float = 1.0          # x accumulator height per round
    p_hash_col: float = 1.0           # x H per column (compaction)


DEFAULT_CONSTANTS = CostConstants()


def _resolve_constants(constants: CostConstants | None) -> CostConstants:
    """Explicit constants win; otherwise consult the machine profile
    (measured fit for this fingerprint if one is persisted, else
    ``DEFAULT_CONSTANTS`` — see ``core.profile``).  Lazy import: profile
    depends on this module for :class:`CostConstants`."""
    if constants is not None:
        return constants
    from repro.core import profile

    return profile.current_constants()


def _note_if_default(backend: str, candidates: tuple) -> None:
    """Count/warn when auto ranks device engines on uncalibrated defaults
    (satellite: the stale-constants trap)."""
    from repro.core import profile

    if profile.current_profile().source == "default":
        profile.note_default_auto(backend, candidates)


def _family(method: str) -> str:
    if method in ("spa", "expand", "esc", "jax", "fused"):
        return method
    if method.startswith("h-"):
        return "hybrid"
    if method.startswith("spars"):
        return "spars"
    if method.startswith("hash"):
        return "hash"
    raise ValueError(f"cost model does not know method {method!r}")


def _params(method: str) -> dict:
    from repro.core.planner import resolve_params

    return resolve_params(method)


def _lockstep_rounds(steps: np.ndarray, b: int) -> int:
    """Total lock-step iterations: sum of per-block max trip counts.

    Columns are processed sorted by load in blocks of ~``b`` lanes and every
    round runs until the block's slowest lane finishes, so the bound is the
    sum of block maxima over the descending-sorted step counts.
    """
    work = np.sort(steps[steps > 0])[::-1]
    if not len(work):
        return 0
    return int(work[::max(int(b), 1)].sum())


def _next_pow2(x: int) -> int:
    return 1 << max(int(math.ceil(math.log2(max(x, 2)))), 1)


def _guarded_rebuild_cost(flops: int, c: CostConstants) -> float:
    """Per-call transient stream rebuild (expansion + lexsort): what any
    stream engine costs above the plan-memory guard."""
    return c.expand_base + flops * (
        c.expand_prod + c.expand_sort * math.log2(max(flops, 2)))


def _host_cost(stats: TileStats, method: str, c: CostConstants) -> float:
    fam = _family(method)
    flops = stats.flops
    if fam == "spa":
        return (c.spa_col * stats.n + c.spa_entry * stats.nnz_b
                + c.spa_flop * flops)
    if fam == "expand":
        if flops <= _fast.STREAM_MAX_PRODUCTS:
            # plan-resident product stream: flat vectorized replay
            return c.stream_base + c.stream_prod * flops
        # guard-tripped: every call rebuilds the stream transiently
        return _guarded_rebuild_cost(flops, c)
    if fam == "jax":
        if flops <= _fast.default_stream_limit(device=True):
            # jitted device stream: one dispatch, flat per-product cost
            return c.jax_base + c.jax_prod * flops
        # guard-tripped jax plans fall back to the host transient rebuild
        # (core/jax_stream.py), so they cost what guarded expand costs
        return _guarded_rebuild_cost(flops, c)
    if fam == "fused":
        if flops <= _fast.default_stream_limit(device=True):
            # single fused kernel launch: one dispatch, flat per-product
            return c.fused_base + c.fused_prod * flops
        # guard-tripped fused executions fall back to the host transient
        # rebuild (core/pallas_stream.py), same as the other stream engines
        return _guarded_rebuild_cost(flops, c)
    if fam == "esc":
        rounds = (math.ceil(math.log2(max(stats.m, 2)) / 5)
                  + math.ceil(math.log2(max(stats.n, 2)) / 5))
        return c.esc_base + c.esc_round * flops * rounds
    params = _params(method)
    t = params.get("t", np.inf)
    head = stats.ops >= t
    tail_steps = stats.steps[~head]
    cost = (c.spa_col * int(head.sum())
            + c.spa_flop * int(stats.ops[head].sum())
            + c.spa_entry * int(head.sum()) * stats.nnz_b
            / max(stats.n, 1))
    rounds = _lockstep_rounds(tail_steps, params.get("b_max", 256))
    cost += c.lockstep_iter * rounds
    if fam == "hash" or params.get("accumulator") == "hash":
        cost += c.hash_probe * int(stats.ops[~head].sum())
    return cost


def _pallas_cost(stats: TileStats, method: str, c: CostConstants) -> float:
    fam = _family(method)
    m = max(stats.m, 1)
    if fam in ("expand", "esc", "jax", "fused"):
        # "fused" is an engine on pallas plans, not a per-group kernel
        # family the relative-work model ranks — it never competes in a
        # pallas-domain tile grid (host/jax grids admit it in seconds)
        raise ValueError(f"method {method!r} has no Pallas kernel family")
    if fam == "spa":
        return c.p_spa_entry * m * stats.nnz_b + c.p_spa_col * m * stats.n
    params = _params(method)
    t = params.get("t", np.inf)
    head = stats.ops >= t
    cost = (c.p_spa_entry * m * stats.nnz_b * int(head.sum())
            / max(stats.n, 1) + c.p_spa_col * m * int(head.sum()))
    tail_steps = stats.steps[~head]
    rounds = _lockstep_rounds(tail_steps, params.get("b_max", 256))
    acc = params.get("accumulator",
                     "hash" if fam == "hash" else "spa")
    if fam == "spars" or acc == "spa":
        cost += c.p_lock_iter * m * rounds
    else:
        tail_ops = stats.ops[~head]
        h = _next_pow2(int(tail_ops.max()) if len(tail_ops) else 2)
        cost += (c.p_lock_iter * h * rounds
                 + c.p_hash_col * h * int((~head).sum()))
    return cost


def estimate_cost(stats: TileStats, method: str, backend: str = "host",
                  constants: CostConstants | None = None) -> float:
    """Predicted cost of running ``method`` on one tile (lower is better).

    The model is selected by the backend's registered contract
    (``core.backends``): host and jax estimates are wall seconds (the
    "jax" family models the device stream's dispatch + per-product cost,
    so it is directly comparable with the host engines it competes with in
    a mixed tile grid); Pallas estimates are relative work units.  Only
    compare estimates within one cost domain.

    When ``constants`` is ``None`` the machine profile is consulted
    (``core.profile``): the measured fit for this host/device fingerprint
    if one is persisted, ``DEFAULT_CONSTANTS`` otherwise.
    """
    c = _resolve_constants(constants)
    contract = backends.get_backend(backend)
    if contract.cost_domain == "relative":
        return _pallas_cost(stats, method, c)
    return _host_cost(stats, method, c)


def estimate_mesh_cost(stats: TileStats, n_shards: int,
                       constants: CostConstants | None = None) -> float:
    """Predicted wall seconds of a mesh-distributed execution (DESIGN.md §13).

    Compute: the jax device-stream cost of one shard's ~1/D slice of the
    product stream (the guard applies per shard, so the slice never pays
    the transient-rebuild penalty as long as it fits — callers sizing
    shards so it does is the whole point of distributing).  Communication:
    a fixed collective overhead plus the per-byte toll of the tiled
    ``psum_scatter`` partial-C reduction, which moves ``(D-1)/D`` of the
    f32 slot axis (|C| estimated from the flops upper bound) through the
    interconnect.  Seconds domain — directly comparable with the host/jax
    estimates of :func:`estimate_cost`.  ``constants=None`` resolves
    through the machine profile, so a measured ``psum_scatter`` ladder
    (``benchmarks/calibrate_profile.py``) replaces the default comm terms.
    """
    c = _resolve_constants(constants)
    d = max(int(n_shards), 1)
    flops = stats.flops
    per_shard = -(-flops // d)
    if per_shard <= _fast.default_stream_limit(device=True):
        compute = c.jax_base + c.jax_prod * per_shard
    else:
        compute = _guarded_rebuild_cost(per_shard, c)
    if d == 1:
        return compute
    nnz_c = min(flops, stats.m * stats.n)
    comm = c.comm_base + c.comm_byte * 4.0 * nnz_c * (d - 1) / d
    return compute + comm


def should_distribute(stats: TileStats, n_shards: int,
                      constants: CostConstants | None = None,
                      shard_limit: int | None = None) -> bool:
    """Whether ``method="auto"`` on the mesh backend should shard.

    True when distributing is predicted to win: either the whole product
    stream is above the single-device plan-memory guard (a single-device
    execution would pay the per-call transient rebuild; sharding lifts the
    guard to ``n_shards x shard_limit``), or the communication-aware mesh
    estimate undercuts the best single-device stream estimate outright.
    With one shard (or one device) the answer is always False.
    """
    if int(n_shards) <= 1:
        return False
    if constants is None:
        _note_if_default("mesh", AUTO_CANDIDATES["mesh"])
    c = _resolve_constants(constants)
    limit = (_fast.default_stream_limit(device=True) if shard_limit is None
             else int(shard_limit))
    if stats.flops > limit:
        return True
    single = c.jax_base + c.jax_prod * stats.flops
    return estimate_mesh_cost(stats, n_shards, c) < single


def choose_method(stats: TileStats, backend: str = "host",
                  candidates: tuple | None = None,
                  constants: CostConstants | None = None) -> str:
    """Cheapest candidate method for this tile (deterministic: first wins
    ties in candidate order)."""
    cands = AUTO_CANDIDATES[backend] if candidates is None \
        else tuple(candidates)
    if not cands:
        raise ValueError("empty candidate set")
    if constants is None:
        _note_if_default(backend, cands)
        constants = _resolve_constants(None)
    best, best_cost = cands[0], None
    for m in cands:
        cost = estimate_cost(stats, m, backend, constants)
        if best_cost is None or cost < best_cost:
            best, best_cost = m, cost
    return best
