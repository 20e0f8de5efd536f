"""Symbolic SpGEMM planning: analyze a sparsity pattern once, execute often.

The paper times its sort/block/hash-size pre-processing separately from the
numeric kernel (Section 5.3); Nagasaka et al.'s hash SpGEMM makes that split
structural — a *symbolic* phase reused whenever the pattern repeats, and a
*numeric* phase that does the flops.  ``plan_spgemm`` runs every
pattern-dependent step once — Op_j analysis, column sorting, blocking,
hash-table sizing, padded kernel layouts, per-family column groups, per-block
trip counts — and captures the result in an immutable :class:`SpgemmPlan`.
Executing the plan against new numeric values (``core.executor``) performs
only value work, so repeated-pattern workloads (graph analytics A·A chains,
static-weight sparse FFNs, iterative solvers) amortize all host-side analysis
(DESIGN.md §6).

Plans are keyed by :func:`pattern_fingerprint`, which hashes only structure
(shape, col_ptr, row_indices) — never values — so ``core.api``'s bounded LRU
can transparently reuse plans across calls with identical patterns.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import backends, faults, spans
from repro.core.analysis import Preprocess, preprocess
from repro.core.cost import AUTO_CANDIDATES, CostConstants, choose_method
import repro.core.fast as _fast
from repro.core.fast import ProductStream, build_product_stream
from repro.sparse.format import BatchedCSC, CSC, _np, csc_pad_gather
from repro.sparse.partition import (
    auto_tile_grid,
    csc_col_slice,
    csc_row_slice,
    nnz_balanced_col_bounds,
    width_col_bounds,
)
from repro.sparse.stats import ops_per_column, steps_per_column, tile_stats

# method -> base kwargs; the paper's Section 5.3 configurations
ALGORITHMS = {
    "spa": {},
    "spars-16/64": dict(b_min=16, b_max=64),
    "spars-40/40": dict(b_min=40, b_max=40),
    "h-spa-16/64": dict(t=40, b_min=16, b_max=64, accumulator="spa"),
    "h-spa-40/40": dict(t=40, b_min=40, b_max=40, accumulator="spa"),
    "hash-32/256": dict(b_min=32, b_max=256),
    "hash-256/256": dict(b_min=256, b_max=256),
    "h-hash-32/256": dict(t=40, b_min=32, b_max=256, accumulator="hash"),
    "h-hash-256/256": dict(t=40, b_min=256, b_max=256, accumulator="hash"),
    "esc": {},
    "expand": {},  # fast vectorized host executor (not a paper algorithm)
}

# methods with no Pallas kernel family (host-only executors); the canonical
# definition lives on the pallas backend contract (core/backends.py)
HOST_ONLY = backends.HOST_ONLY_METHODS


def resolve_params(
    method: str,
    *,
    t: float | None = None,
    b_min: int | None = None,
    b_max: int | None = None,
) -> dict:
    """Named-method defaults with optional overrides.

    Unregistered ``family-x/y`` names (e.g. ``spars-128/128``, accepted by
    ``spgemm_pallas`` since the seed) are parsed from the name itself.
    """
    params = dict(ALGORITHMS.get(method, ()))
    if method not in ALGORITHMS:
        if "-" in method:
            bounds = method.rsplit("-", 1)[1]
            # a trailing all-digit or x/y token is a bounds spec and must
            # parse; anything else (e.g. a bare family prefix) is not
            if "/" in bounds or bounds.isdigit():
                try:
                    bmin, bmax = (int(x) for x in bounds.split("/"))
                except ValueError:
                    raise ValueError(
                        f"malformed block bounds in method {method!r}; "
                        "expected 'family-bmin/bmax'") from None
                params.setdefault("b_min", bmin)
                params.setdefault("b_max", bmax)
        if method.startswith("h-"):
            params.setdefault("t", 40.0)
            params.setdefault(
                "accumulator", "hash" if "hash" in method else "spa")
    if method.startswith(("spars", "hash", "h-")):
        params.setdefault("b_min", 256)
        params.setdefault("b_max", 256)
    if t is not None:
        params["t"] = t
    if b_min is not None:
        params["b_min"] = b_min
    if b_max is not None:
        params["b_max"] = b_max
    return params


def pattern_fingerprint(m: CSC) -> str:
    """Hash of the sparsity pattern only (shape + col_ptr + row_indices).

    Two CSC matrices with equal fingerprints can share one SpgemmPlan; their
    values never enter the hash.  The span ``spgemm.fingerprint``.
    """
    cp = _np(m.col_ptr)
    nnz = int(cp[-1])
    with spans.span("spgemm.fingerprint", nnz=nnz):
        ri = _np(m.row_indices)[:nnz]
        h = hashlib.blake2b(digest_size=16)
        # raw bytes + dtype tags (no widening copies): fingerprints
        # distinguish index dtypes, which is fine — Pattern.of normalizes
        # to int32 anyway
        h.update(f"{m.shape}:{cp.dtype}:{ri.dtype}".encode())
        h.update(cp.tobytes())
        h.update(ri.tobytes())
        return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class Pattern:
    """Value-free view of one CSC operand: structure + fingerprint."""

    row_indices: np.ndarray
    col_ptr: np.ndarray
    shape: Tuple[int, int]
    fingerprint: str

    @classmethod
    def of(cls, m: CSC) -> "Pattern":
        cp = _np(m.col_ptr)
        return cls(
            np.ascontiguousarray(_np(m.row_indices)[: int(cp[-1])], np.int32),
            np.ascontiguousarray(cp, np.int32),
            tuple(m.shape),
            pattern_fingerprint(m),
        )

    def check_compatible(self, operand, validate: str | None = None) -> None:
        """Compatibility check of an execute-time operand.

        By default O(1): structured operands (CSC/BatchedCSC) must match the
        planned shape and nnz; raw value arrays must cover the planned nnz.
        A same-shape same-nnz operand with a *different* pattern is not
        detected by the default check (the full check costs the O(nnz)
        fingerprint the plan-reuse path exists to avoid) — pass
        ``validate="fingerprint"`` to opt into re-hashing the operand's
        structure and rejecting any pattern mismatch.  Raw value arrays carry
        no structure, so fingerprint validation is vacuous for them.
        """
        if validate not in (None, "fingerprint"):
            raise ValueError(
                f"unknown validate mode {validate!r}; None or 'fingerprint'")
        if isinstance(operand, (CSC, BatchedCSC)):
            if tuple(operand.shape) != self.shape:
                raise ValueError(
                    f"operand shape {tuple(operand.shape)} != planned "
                    f"{self.shape}")
            nnz = int(_np(operand.col_ptr)[-1])
            if nnz != int(self.col_ptr[-1]):
                raise ValueError(
                    f"operand nnz {nnz} != planned {int(self.col_ptr[-1])} "
                    "(sparsity pattern does not match this plan)")
            if (validate == "fingerprint"
                    and pattern_fingerprint(operand) != self.fingerprint):
                raise ValueError(
                    "operand sparsity pattern does not match this plan "
                    "(fingerprint mismatch despite equal shape and nnz)")
        else:
            # shape-only checks (no np.asarray): raw operands may be jax
            # tracers inside a jitted stream execution (DESIGN.md §10)
            shape = np.shape(operand)
            if len(shape) != 1:
                raise ValueError(
                    f"expected a 1-D value array, got shape {shape} "
                    "(use execute_batched for [B, nnz] value stacks)")
            if shape[0] < int(self.col_ptr[-1]):
                raise ValueError(
                    f"need >= {int(self.col_ptr[-1])} values, "
                    f"got {shape[0]}")

    def with_values(self, values, validate: str | None = None) -> CSC:
        """Bind numeric values to this pattern (accepts a CSC or raw array)."""
        self.check_compatible(values, validate)
        v = values.values if isinstance(values, CSC) else np.asarray(values)
        return CSC(v, self.row_indices, self.col_ptr, self.shape)

    def check_batched_compatible(self, operand,
                                 validate: str | None = None) -> None:
        """Batched twin of :meth:`check_compatible`, shape-only for raw
        stacks (tracer-safe — the single source of the batched-operand
        contract, shared by the host/pallas value extraction and the jax
        stream's namespace-preserving path)."""
        if validate not in (None, "fingerprint"):
            raise ValueError(
                f"unknown validate mode {validate!r}; None or 'fingerprint'")
        if isinstance(operand, BatchedCSC):
            self.check_compatible(operand, validate)
            return
        shape = np.shape(operand)
        if len(shape) != 2:
            raise ValueError(
                "batched operand must be a BatchedCSC or a [B, nnz] "
                f"value array, got shape {shape}")
        if shape[1] < int(self.col_ptr[-1]):
            raise ValueError(
                f"need >= {int(self.col_ptr[-1])} values per batch "
                f"element, got {shape[1]}")

    def batched_values(self, values, validate: str | None = None
                       ) -> np.ndarray:
        """Host [B, nnz] value stack from a batched execute-time operand.

        Accepts a :class:`BatchedCSC` with this pattern or a raw ``[B, nnz]``
        array; a single CSC / 1-D array is rejected (use ``execute``).
        """
        self.check_batched_compatible(values, validate)
        v = _np(values.values) if isinstance(values, BatchedCSC) \
            else np.asarray(values)
        return v[:, : int(self.col_ptr[-1])]


@dataclasses.dataclass(frozen=True)
class KernelGroup:
    """One kernel launch of the Pallas execution schedule.

    ``cols`` are the original B/C column ids this launch computes, in lane
    order (pad lanes point at column 0 with nnz forced to 0).
    ``b_rows``/``b_nnz``/``steps`` are the pattern-static halves of the
    padded group operand, stored as device arrays so re-executions pay no
    host-to-device copy; only values are re-gathered per execution.
    ``b_vgather``/``b_vmask`` are that gather, fully precomputed: the
    group's padded value operand is ``where(b_vmask, values[b_vgather], 0)``
    — one fused gather from the raw B value array per launch, composed at
    plan time from the padded layout's gather and the lane-validity mask
    (executions no longer allocate a full padded B nor a per-group
    ``np.where`` mask; the lane selection itself is baked in, so the plan
    retains no separate sel/valid arrays).  ``trips`` are a SPA launch's
    loop bounds (``spa.trip_counts``, computed here once) and ``products``
    its scalar products (the ``spgemm.pallas.group`` span's attribute).
    """

    kind: str                 # "spa" | "spars" | "hash"
    cols: np.ndarray          # [n_real] original column ids
    b_rows: jnp.ndarray       # [n_pad, zb] int32 (device)
    b_nnz: jnp.ndarray        # [n_pad] int32 (device)
    b_vgather: np.ndarray     # [n_pad, zb] int64 into B's raw values
    b_vmask: np.ndarray       # [n_pad, zb] bool, False for pad slots/lanes
    steps: Optional[jnp.ndarray] = None  # [n_pad/block_cols] trip counts
    h: Optional[int] = None              # hash-table size (kind == "hash")
    products: int = 0                    # scalar products of the launch
    trips: Optional[tuple] = None        # SPA: ``spa.trip_counts`` (device)

    @property
    def n_real(self) -> int:
        return len(self.cols)


@dataclasses.dataclass(frozen=True)
class PallasLayout:
    """Everything ``spgemm_pallas`` used to recompute per call, pattern-only.

    The A operand rides whole into every launch (as in the seed kernels); B
    is pre-sliced per group.  ``*_gather``/``*_mask`` re-pad fresh numeric
    values with one vectorized gather each.
    """

    block_cols: int
    tile_cols: int
    a_rows: jnp.ndarray       # [n_a, za] int32 (device)
    a_nnz: jnp.ndarray        # [n_a] int32 (device)
    a_gather: np.ndarray
    a_mask: np.ndarray
    groups: Tuple[KernelGroup, ...]
    device: Optional["DeviceLayout"] = None


@dataclasses.dataclass(frozen=True)
class DeviceLayout:
    """A call of a dense-tile plan (SPA, SPARS groups) kept on the device.

    Where a call's tiles and B operands fit ``runtime.device_tile_bytes``,
    its operands are padded on the device and its tiles compacted there:
    the raw values go up once, ``a_pos``/``b_pos`` place them (from
    ``a_src``/``b_src``) into A's padded table and into the groups' B
    operands laid end to end (``widths`` lanes each, ``zb`` slots a lane),
    every group launches without waiting, and the tiles, flattened and
    laid end to end in group order, are gathered at ``src`` (one int32 per
    C slot, in canonical CSC order), so only C's values come back.
    ``c_rows``/``c_col_ptr`` are C's symbolic pattern, frozen: results
    share them with the plan.  A tile holds nothing outside that pattern,
    so dropping the gathered exact zeros gives what compacting each dense
    tile on the host gives, and the padding is ``padded_values``' to the
    bit.
    """

    a_pos: jnp.ndarray        # [nnz_a] int32 (device) into A's [n_a, za]
    a_src: jnp.ndarray        # [nnz_a] int32 (device): its A value
    b_pos: jnp.ndarray        # [k] int32 (device) into the B operands
    b_src: jnp.ndarray        # [k] int32 (device): its B value
    widths: Tuple[int, ...]   # each group's padded lanes
    src: jnp.ndarray          # [nnz_c] int32 (device) into the tiles
    c_rows: np.ndarray        # [nnz_c] int32
    c_col_ptr: np.ndarray     # [n+1] int32


def _device_layout(a, b, groups, a_gather, a_mask, m: int, n: int
                   ) -> Optional[DeviceLayout]:
    """The plan's :class:`DeviceLayout`, or ``None`` where a group is not
    a dense tile or a call would not fit the device's limit (the executor
    then pads and compacts group by group on the host)."""
    from repro import runtime
    from repro.core.expand import expand_positions

    if not groups or any(g.kind not in ("spa", "spars") for g in groups):
        return None
    widths = tuple(int(g.b_nnz.shape[0]) for g in groups)
    zb = int(groups[0].b_rows.shape[1])
    lanes = sum(widths)
    limit = runtime.device_tile_bytes()
    if max(m, zb) * lanes >= 2 ** 31 or (
            limit is not None and 4 * (m + zb) * lanes > limit):
        return None
    a_pos = np.flatnonzero(a_mask)
    b_pos, b_src = [], []
    group_base = np.zeros(n, np.int64)     # flat offset of column j's lane
    lane_stride = np.zeros(n, np.int64)    # its tile's row stride
    owned = np.zeros(n, bool)
    base = lane0 = 0
    for g, w in zip(groups, widths):
        pos = np.flatnonzero(g.b_vmask)
        b_pos.append(lane0 * zb + pos)
        b_src.append(g.b_vgather.reshape(-1)[pos])
        group_base[g.cols] = base + np.arange(g.n_real)
        lane_stride[g.cols] = w
        owned[g.cols] = True
        base += m * w
        lane0 += w
    p_a, p_b, cols = expand_positions(a.col_ptr, b.col_ptr, b.row_indices)
    pattern = np.zeros((n, m), bool)       # [column, row]: C's pattern
    pattern[cols, _np(a.row_indices)[p_a]] = True
    del p_a, p_b, cols
    c_cols, c_rows = np.nonzero(pattern)   # column-major, rows ascending
    if not owned[c_cols].all():
        raise AssertionError("a column of C lies in no kernel group")
    c_col_ptr = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount(c_cols, minlength=n), out=c_col_ptr[1:])
    src = group_base[c_cols] + c_rows * lane_stride[c_cols]
    c_rows = c_rows.astype(np.int32)
    for arr in (c_rows, c_col_ptr):
        arr.flags.writeable = False
    dev = lambda x: jnp.asarray(np.asarray(x).astype(np.int32))
    return DeviceLayout(dev(a_pos), dev(a_gather.reshape(-1)[a_pos]),
                        dev(np.concatenate(b_pos)),
                        dev(np.concatenate(b_src)), widths, dev(src),
                        c_rows, c_col_ptr)


@dataclasses.dataclass(frozen=True)
class SpgemmPlan:
    """Immutable symbolic plan for C = A @ B with one algorithm/backend.

    Built once per sparsity pattern by :func:`plan_spgemm`; execute with
    ``plan.execute(a_values, b_values)`` (CSC operands or raw value arrays
    aligned with the planned patterns) or ``spgemm(a, b, plan=plan)``.
    """

    method: str
    backend: str
    params: tuple             # sorted (key, value) pairs, hashable
    a: Pattern
    b: Pattern
    pre: Optional[Preprocess]          # host blocking analysis (if any)
    pallas: Optional[PallasLayout]     # kernel layouts (pallas backend)
    stream_limit: Optional[int] = None  # plan-memory guard (products)
    _stream_memo: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def contract(self) -> "backends.ExecutionContract":
        """This plan's backend capability contract (core/backends.py)."""
        return backends.get_backend(self.backend)

    @property
    def stream(self) -> Optional[ProductStream]:
        """Lazily-built product stream (``engine="stream"``, DESIGN.md §9).

        Built on first access so plans that never run the stream engine pay
        neither the plan-time lexsort nor the O(flops) resident memory;
        memoized on the plan, so tiled child plans shared through the LRU
        share one stream.  Carried by every stream-capable backend
        (``contract.carries_stream``: host, jax, and pallas — the jax
        backend builds its device-resident index arrays from this host
        stream, DESIGN.md §10).  ``None`` when the stream would exceed
        ``stream_limit`` (the guard resolved at plan time) — stream
        executions then rebuild transiently.
        """
        if not self.contract.carries_stream:
            return None
        if "stream" not in self._stream_memo:
            self._stream_memo["stream"] = build_product_stream(
                self.a, self.b, self.stream_limit)
        return self._stream_memo["stream"]

    @property
    def stream_nbytes(self) -> int:
        """Bytes of host stream index data currently held by this plan.

        Reads the memo without triggering the lazy build (0 until the
        first stream execution, and 0 when the guard tripped) — this is
        what ``plan_cache_info()['stream_bytes']`` aggregates.
        """
        s = self._stream_memo.get("stream")
        return s.nbytes if s is not None else 0

    @property
    def device_stream_nbytes(self) -> int:
        """Bytes of *device-resident* stream index data held by this plan.

        The jax backend caches the stream's index arrays on device alongside
        the host ones (DESIGN.md §10); this reads the memo without
        triggering the lazy build — ``plan_cache_info()
        ['device_stream_bytes']`` aggregates it separately from host bytes.
        """
        d = self._stream_memo.get("device")
        return d.nbytes if d is not None else 0

    def stream_apply(self, a_values, b_values):
        """Jit-compatible, differentiable numeric phase: C values only.

        The device-backend entry point for traced code (DESIGN.md §10):
        ``a_values``/``b_values`` are value arrays (or tracers) aligned with
        the planned patterns, and the return is the ``[nnz_c]`` C value
        array of the plan's canonical output structure
        (``plan.stream.c_rows`` / ``c_col_ptr``) — a pure function of the
        inputs, safe under ``jax.jit``/``jax.grad``/``jax.vmap``, lowered
        through the XLA stream.  Requires a stream-capable backend and a
        plan-resident stream (guarded plans raise: a traced execution
        cannot fall back to the host rebuild).
        """
        from repro.core import jax_stream

        # shape-only (tracer-safe) operand checks: the jitted gathers run
        # with an in-bounds promise, so a short value array must raise
        # here rather than read undefined memory
        self.a.check_compatible(a_values)
        self.b.check_compatible(b_values)
        return jax_stream.stream_fn(self)(a_values, b_values)

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.a.shape[0], self.b.shape[1])

    @property
    def cache_key(self) -> tuple:
        # mirrors core.api._cached_plan's LRU key (which keys host plans on
        # the stream guard in effect at build time)
        return (self.a.fingerprint, self.b.fingerprint, self.method,
                self.backend, self.params, self.stream_limit)

    def execute(self, a_values, b_values, *,
                stats: dict | None = None, validate: str | None = None,
                engine: str | None = None) -> CSC:
        """Numeric phase only: C for new values on the planned patterns.

        ``engine`` selects the host numeric engine: ``"naive"`` (the
        faithful per-method oracle executors), ``"stream"`` (the vectorized
        product-stream engine, DESIGN.md §9), or ``None`` for the method's
        default (``"stream"`` for ``expand``, ``"naive"`` otherwise).
        The call is the span ``spgemm.execute`` (DESIGN.md §16); on a
        device backend it returns before the device finishes.
        """
        from repro.core.executor import execute

        with spans.span("spgemm.execute"):
            return execute(self, a_values, b_values, stats=stats,
                           validate=validate, engine=engine)

    def execute_batched(self, a_values, b_values, *,
                        stats: dict | None = None,
                        validate: str | None = None,
                        engine: str | None = None) -> list:
        """Batched numeric phase: B same-pattern multiplies, one schedule.

        ``a_values``/``b_values``: :class:`~repro.sparse.format.BatchedCSC`
        operands or raw ``[B, nnz]`` value stacks aligned with the planned
        patterns.  Returns the B results as a list of CSC matrices,
        bit-identical to a Python loop of :meth:`execute` (DESIGN.md §7).
        ``engine`` — as in :meth:`execute`.
        """
        from repro.core.executor import execute_batched

        return execute_batched(self, a_values, b_values, stats=stats,
                               validate=validate, engine=engine)


def _freeze(params: dict) -> tuple:
    return tuple(sorted(params.items()))


def plan_spgemm(
    a: CSC,
    b: CSC,
    method: str = "h-hash-256/256",
    *,
    backend: str = "host",
    t: float | None = None,
    b_min: int | None = None,
    b_max: int | None = None,
    block_cols: int = 128,
    tile_cols: int | None = None,
    stream_limit: int | None = None,
    shards: int | None = None,
) -> SpgemmPlan:
    """Build the symbolic plan for C = A @ B (pattern-dependent work only).

    ``block_cols`` is the Pallas lane-block width; ``tile_cols`` bounds how
    many C columns one kernel launch materializes (defaults to
    ``block_cols``), which caps the transient accumulator tile at
    ``[m, tile_cols]`` — the dense ``[m, n]`` sink of the pre-plan backend is
    gone.

    Host plans also carry the product stream (``engine="stream"``, DESIGN.md
    §9), built lazily on first stream access and kept plan-resident while
    the flop count is within ``stream_limit`` (default:
    ``fast.default_stream_limit`` at plan time — on a TPU, device plans
    are sized from the chip's memory); above it ``plan.stream`` is
    ``None`` and stream executions rebuild it transiently — same results,
    no plan-resident O(flops) memory.

    ``backend="mesh"`` delegates to
    :func:`repro.distributed.spgemm_mesh.plan_spgemm_mesh` and returns a
    :class:`~repro.distributed.spgemm_mesh.ShardedSpgemmPlan` — the tile
    grid placed across ``shards`` devices (default: all visible), with
    ``stream_limit`` acting as the *per-shard* plan-memory guard.
    ``shards`` is mesh-only; any other backend rejects it.
    """
    faults.check("plan_spgemm", key=(backend, method))
    if shards is not None and backend != "mesh":
        raise ValueError(
            f"shards= applies only to backend='mesh', not {backend!r}")
    if a.n_cols != b.n_rows:
        raise ValueError(f"shape mismatch {a.shape} @ {b.shape}")
    if method not in ALGORITHMS and not method.startswith(
            ("spars", "hash", "h-")):
        raise ValueError(
            f"unknown method {method!r}; one of {list(ALGORITHMS)} or a "
            "'spars-*/hash-*/h-*' family name")
    contract = backends.get_backend(backend)
    if method in contract.excluded_methods:
        raise ValueError(
            f"method {method!r} has no {contract.name} kernel family "
            "(host-only)")
    backends.check_method_knobs(contract, t, b_min, b_max)
    if contract.canonical_method:
        # jax: the numeric phase is the method-independent stream
        # contraction, so every method *spelling* shares one canonical
        # plan (plan.method reports the canonical form)
        method = contract.canonical_method
    if backend == "mesh":
        from repro.distributed.spgemm_mesh import plan_spgemm_mesh

        return plan_spgemm_mesh(a, b, shards=shards,
                                shard_limit=stream_limit)
    params = resolve_params(method, t=t, b_min=b_min, b_max=b_max)
    a_pat, b_pat = Pattern.of(a), Pattern.of(b)

    # resolve the guard now (it is a mutable module knob) so every plan's
    # lazy stream build is deterministic no matter when it happens; pallas
    # plans carry it too (``stream_apply`` traces their pattern's stream)
    limit = (_fast.default_stream_limit(contract.device_resident)
             if stream_limit is None else int(stream_limit))
    if backend == "pallas":
        pre, layout = _plan_pallas(a, b, method, params, block_cols,
                                   tile_cols)
        return SpgemmPlan(method, "pallas", _freeze(params), a_pat, b_pat,
                          pre, layout, limit)
    # the remaining stream-capable backends (host, jax) are pattern-only
    # plans.  The jax backend never runs the naive oracles
    # (contract.bit_exact_oracle is False), so it skips the blocking
    # analysis they consume.
    pre = None
    if contract.bit_exact_oracle:
        if method.startswith(("spars", "hash")):
            pre = preprocess(a, b, t=np.inf, b_min=params["b_min"],
                             b_max=params["b_max"])
        elif method.startswith("h-"):
            pre = preprocess(a, b, t=params["t"], b_min=params["b_min"],
                             b_max=params["b_max"])
    return SpgemmPlan(method, backend, _freeze(params), a_pat, b_pat,
                      pre, None, limit)


# ---------------------------------------------------------------------------
# Tiled plans: a 2D grid of per-tile SpgemmPlans (DESIGN.md §8)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """One non-empty tile product ``A[:, k] @ B[k, n]`` of a tiled plan.

    ``a_vals``/``b_vals`` are the pattern-static value-slicing metadata: the
    A tile's values are the contiguous range ``[a_vals[0], a_vals[1])`` of
    the parent A value array, the B tile's values are ``b_parent[b_vals]``
    (a gather — row slicing is not contiguous in CSC).  ``plan`` is an
    ordinary per-tile :class:`SpgemmPlan`, shared through the plan LRU with
    any other tile of identical pattern.
    """

    k: int                       # row-block index (A column block)
    n: int                       # column-block index (B column block)
    a_vals: Tuple[int, int]
    b_vals: np.ndarray
    plan: SpgemmPlan

    @property
    def method(self) -> str:
        # report the candidate spelling the cost model chose: "jax" tiles
        # (the device stream riding a host grid) carry an expand-method
        # child plan on the jax backend
        return "jax" if self.plan.backend == "jax" else self.plan.method


@dataclasses.dataclass(frozen=True)
class TiledSpgemmPlan:
    """Symbolic plan for ``C = A @ B`` as a 2D grid of tile products.

    Built by :func:`plan_spgemm_tiled` (the ``method="auto"`` path of
    ``core.api.spgemm``): A is sliced into column blocks at ``k_bounds``, B
    into matching row blocks crossed with column blocks at ``n_bounds``,
    and every structurally non-empty tile pair gets its own child
    :class:`SpgemmPlan` whose method the cost model picked for that tile's
    work profile.  Execution (``core.executor.execute_tiled``) runs the
    children and merges: per column block, partial products accumulate over
    row blocks in k order; the blocks then stitch left-to-right into the
    final CSC.  A plan with a single row block is bit-identical per column
    to the untiled method (DESIGN.md §8).
    """

    backend: str
    a: Pattern
    b: Pattern
    k_bounds: np.ndarray         # [K+1] over A's columns / B's rows
    n_bounds: np.ndarray         # [N+1] over B's columns
    tiles: Tuple[TilePlan, ...]  # structurally non-empty tiles, n-major
    params: tuple                # frozen ("candidates", ...), ("tile", ...)

    method = "auto"

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.a.shape[0], self.b.shape[1])

    @property
    def grid(self) -> Tuple[int, int]:
        return (len(self.k_bounds) - 1, len(self.n_bounds) - 1)

    @property
    def methods(self) -> dict:
        """{(k, n): chosen method} for every non-empty tile."""
        return {(t.k, t.n): t.method for t in self.tiles}

    @property
    def stream_nbytes(self) -> int:
        """Stream bytes currently held via this plan's child tile plans.

        Children of identical pattern share one plan (and one stream), so
        the sum is over *distinct* child plans.  Note the per-plan guard
        bounds each tile's stream individually — a tiled plan over a huge
        multiply can hold many guard-sized tile streams at once.
        """
        seen = {id(t.plan): t.plan.stream_nbytes for t in self.tiles}
        return sum(seen.values())

    @property
    def device_stream_nbytes(self) -> int:
        """Device-resident stream bytes held via child tile plans (distinct
        children counted once, as in :attr:`stream_nbytes`)."""
        seen = {id(t.plan): t.plan.device_stream_nbytes for t in self.tiles}
        return sum(seen.values())

    @property
    def cache_key(self) -> tuple:
        # mirrors core.api._cached_tiled_plan's LRU key: the stream guard
        # in effect at build time is part of it, because the guard steers
        # the per-tile method choices
        own = dict(self.params)
        return (self.a.fingerprint, self.b.fingerprint, "auto",
                self.backend, own["tile"], own["candidates"],
                own["stream_guard"], own.get("profile", "default"))

    def execute(self, a_values, b_values, *,
                stats: dict | None = None, validate: str | None = None,
                engine: str | None = None) -> CSC:
        """Numeric phase: run every tile plan, merge row blocks, stitch.

        ``engine`` is forwarded to every child tile plan (``None`` lets each
        tile use its method's default engine).
        """
        from repro.core.executor import execute_tiled

        return execute_tiled(self, a_values, b_values, stats=stats,
                             validate=validate, engine=engine)

    def execute_batched(self, a_values, b_values, *,
                        stats: dict | None = None,
                        validate: str | None = None,
                        engine: str | None = None) -> list:
        """Batched numeric phase over ``[B, nnz]`` value stacks."""
        from repro.core.executor import execute_tiled_batched

        return execute_tiled_batched(self, a_values, b_values,
                                     stats=stats, validate=validate,
                                     engine=engine)


def normalize_tile_spec(tile) -> tuple:
    """Canonical ``(k_width, n_width)`` form of the ``tile=`` argument.

    ``None`` → both axes auto-sized from nnz; an int → that column width on
    the n axis (k auto); a 2-tuple gives per-axis widths, ``None`` meaning
    auto for that axis.
    """
    if tile is None:
        return (None, None)
    if isinstance(tile, (int, np.integer)):
        spec = (None, int(tile))
    else:
        spec = tuple(tile)
    if len(spec) != 2:
        raise ValueError(
            f"tile must be None, an int, or a (k_width, n_width) pair; "
            f"got {tile!r}")
    out = []
    for w in spec:
        if w is None:
            out.append(None)
        elif isinstance(w, (int, np.integer)) and int(w) >= 1:
            out.append(int(w))
        else:
            raise ValueError(f"tile widths must be ints >= 1 or None, "
                             f"got {w!r}")
    return tuple(out)


def plan_spgemm_tiled(
    a: CSC,
    b: CSC,
    *,
    backend: str = "host",
    tile=None,
    candidates: tuple | None = None,
    cache: bool = True,
    constants: CostConstants | None = None,
) -> TiledSpgemmPlan:
    """Build the tiled ``method="auto"`` plan for C = A @ B.

    ``tile`` — see :func:`normalize_tile_spec`; auto axes use nnz-balanced
    boundaries (:func:`~repro.sparse.partition.nnz_balanced_col_bounds`)
    with block counts from :func:`~repro.sparse.partition.auto_tile_grid`.
    ``candidates`` restricts the per-tile method choice (defaults to
    ``cost.AUTO_CANDIDATES[backend]``); with a single candidate every tile
    runs that method, which makes single-row-block grids bit-identical to
    the untiled method.  ``cache=True`` funnels child plans through the
    shared plan LRU, so tiles with identical patterns share one plan.
    """
    if a.n_cols != b.n_rows:
        raise ValueError(f"shape mismatch {a.shape} @ {b.shape}")
    contract = backends.get_backend(backend)
    cands = AUTO_CANDIDATES[backend] if candidates is None \
        else tuple(candidates)
    if not cands:
        raise ValueError("empty candidate set")
    bad = [m for m in cands if m in contract.excluded_methods]
    if bad:
        raise ValueError(
            f"candidates {bad} have no {contract.name} kernel family "
            "(host-only)")

    k_width, n_width = normalize_tile_spec(tile)
    auto_k, auto_n = auto_tile_grid(a, b)
    k_bounds = (width_col_bounds(a.n_cols, k_width) if k_width
                else nnz_balanced_col_bounds(a, auto_k))
    n_bounds = (width_col_bounds(b.n_cols, n_width) if n_width
                else nnz_balanced_col_bounds(b, auto_n))

    def _tile_plan(ta, tb, method):
        # the "jax" candidate spelling = the device stream (DESIGN.md §10):
        # it rides an expand-method child plan on the jax backend, so a
        # host grid can mix numpy tiles with device-stream tiles
        meth, be = (("expand", "jax") if method == "jax"
                    else (method, backend))
        if cache:
            from repro.core.api import _cached_plan

            return _cached_plan(ta, tb, meth, be, resolve_params(meth))
        return plan_spgemm(ta, tb, meth, backend=be)

    # A column blocks depend only on k: slice them once, not once per n block
    a_tiles = [csc_col_slice(a, int(k0), int(k1))
               for k0, k1 in zip(k_bounds[:-1], k_bounds[1:])]
    tiles: list[TilePlan] = []
    for ni, (j0, j1) in enumerate(zip(n_bounds[:-1], n_bounds[1:])):
        b_col, (b_lo, _) = csc_col_slice(b, int(j0), int(j1))
        for ki, (k0, k1) in enumerate(zip(k_bounds[:-1], k_bounds[1:])):
            a_tile, (a_lo, a_hi) = a_tiles[ki]
            if a_tile.nnz == 0:
                continue
            b_tile, rel = csc_row_slice(b_col, int(k0), int(k1))
            if b_tile.nnz == 0:
                continue
            stats = tile_stats(a_tile, b_tile)
            if stats.flops == 0:
                continue  # stored B entries only reference empty A columns
            method = choose_method(stats, backend, cands, constants)
            tiles.append(TilePlan(
                k=ki, n=ni, a_vals=(a_lo, a_hi), b_vals=b_lo + rel,
                plan=_tile_plan(a_tile, b_tile, method)))

    # the cost-constant provenance the per-tile choices were ranked under:
    # a plan built on measured constants must never alias one built on
    # defaults (or on an older calibration) in the plan LRU
    if constants is None:
        from repro.core import profile as _profile

        profile_tag = _profile.current_profile().tag
    else:
        profile_tag = "explicit"
    params = (("candidates", cands),
              ("profile", profile_tag),
              # stream-carrying backends only (all three today): the guard
              # steers host/jax per-tile method choices and bounds every
              # child plan's lazy stream build
              ("stream_guard",
               _fast.default_stream_limit(contract.device_resident)
               if contract.carries_stream else None),
              ("tile", (k_width, n_width)))
    return TiledSpgemmPlan(backend, Pattern.of(a), Pattern.of(b),
                           np.asarray(k_bounds, np.int64),
                           np.asarray(n_bounds, np.int64),
                           tuple(tiles), params)


# ---------------------------------------------------------------------------
# Pallas schedule construction (was recomputed on every spgemm_pallas call)
# ---------------------------------------------------------------------------


def _check_vmem(kinds, a, a_rows, b_rows, block_cols):
    """Refuse, before anything is uploaded, a schedule whose SPA or SPARS
    launches need more VMEM than the chip's kernel budget (DESIGN.md §2)."""
    from repro import runtime
    from repro.kernels import vmem

    budget = runtime.kernel_vmem_bytes()
    for kind in sorted(kinds & {"spa", "spars"}):
        vmem.check(kind, vmem.vmem_bytes(
            kind, m=a.n_rows, za=a_rows.shape[1], n_a=a.n_cols,
            zb=b_rows.shape[1], block_cols=block_cols), budget)


def _plan_pallas(a, b, method, params, block_cols, tile_cols):
    from repro.kernels.spa import trip_counts

    if tile_cols is None:
        tile_cols = block_cols
    if tile_cols % block_cols:
        raise ValueError(
            f"tile_cols={tile_cols} not a multiple of block_cols={block_cols}")
    n = b.n_cols
    a_rows, a_gather, a_mask, a_nnz = csc_pad_gather(a)
    b_rows, b_gather, b_mask, b_nnz = csc_pad_gather(b)
    a_nnz = a_nnz.astype(np.int32)
    b_nnz = b_nnz.astype(np.int32)

    groups: list[KernelGroup] = []

    def add_group(kind, cols, steps=None, h=None):
        cols = np.asarray(cols, np.int64)
        n_real = len(cols)
        if n_real == 0:
            return
        n_pad = -(-n_real // block_cols) * block_cols
        sel = np.zeros(n_pad, np.int64)
        sel[:n_real] = cols
        valid = np.zeros(n_pad, bool)
        valid[:n_real] = True
        g_rows = np.where(valid[:, None], b_rows[sel], 0).astype(np.int32)
        g_nnz = np.where(valid, b_nnz[sel], 0).astype(np.int32)
        # the masked value-gather selection, composed once at plan time:
        # executions do where(vmask, values[vgather], 0) per group instead
        # of padding all of B and re-masking on every call
        vgather = b_gather[sel]
        vmask = b_mask[sel] & valid[:, None]
        if steps is not None:
            steps = np.asarray(steps, np.int32)
            assert len(steps) == n_pad // block_cols, (len(steps), n_pad)
            steps = jnp.asarray(steps)
        trips = (tuple(map(jnp.asarray, trip_counts(
            a_nnz, g_rows, g_nnz, block_cols, xp=np)))
            if kind == "spa" else None)
        groups.append(KernelGroup(kind, cols,
                                  jnp.asarray(g_rows), jnp.asarray(g_nnz),
                                  vgather, vmask, steps, h,
                                  int(ops[cols].sum()), trips))

    # the kernels process each lane independently, so splitting a family into
    # tile_cols-wide launches changes peak memory, never values
    if method == "spa":
        pre = None
        head = np.arange(n)
    else:
        tt = params["t"] if method.startswith("h-") else np.inf
        # the lock-step kernels use fixed-width lane blocks: the blocking
        # bounds collapse to block_cols (the named method only selects the
        # family), exactly as the seed backend did
        pre = preprocess(a, b, t=tt, b_min=block_cols, b_max=block_cols)
        head = pre.perm[: pre.split]

    ops = ops_per_column(a, b) if pre is None else pre.ops
    fam = "hash" if "hash" in method else "spars"
    kinds = ({"spa"} if len(head) else set()) | (
        {fam} if method != "spa" and pre.blocks.n_blocks else set())
    _check_vmem(kinds, a, a_rows, b_rows, block_cols)

    for c0 in range(0, len(head), tile_cols):
        add_group("spa", head[c0: c0 + tile_cols])

    if method != "spa" and pre.blocks.n_blocks:
        starts, sizes = pre.blocks.starts, pre.blocks.sizes
        n_blocks = pre.blocks.n_blocks
        # per-block trip count: NOT the block head's Op_j — a lane consumes
        # one step per stored B entry even when it references an empty A
        # column (zero products), so the bound is the block max of
        # steps_per_column.  Blocks tile [split, n) contiguously in sorted
        # order, so reduceat over the sorted steps gives per-block maxima.
        steps_sorted = steps_per_column(a, b)[pre.perm]
        steps_all = np.maximum.reduceat(steps_sorted, starts).astype(np.int32)
        if fam == "hash":
            # blocks with equal table size H form contiguous runs (H shrinks
            # monotonically along sorted blocks, Section 3.2)
            hs = pre.hash_sizes
            run_bounds = np.concatenate(
                ([0], np.nonzero(np.diff(hs))[0] + 1, [n_blocks]))
            runs = list(zip(run_bounds[:-1], run_bounds[1:]))
        else:
            runs = [(0, n_blocks)]
        blocks_per_tile = tile_cols // block_cols
        for r0, r1 in runs:
            h = int(pre.hash_sizes[r0]) if fam == "hash" else None
            for i0 in range(r0, r1, blocks_per_tile):
                i1 = min(i0 + blocks_per_tile, r1)
                lo = int(starts[i0])
                hi = int(starts[i1 - 1] + sizes[i1 - 1])
                add_group(fam, pre.perm[lo:hi], steps=steps_all[i0:i1], h=h)

    layout = PallasLayout(
        block_cols=block_cols,
        tile_cols=tile_cols,
        a_rows=jnp.asarray(a_rows),
        a_nnz=jnp.asarray(a_nnz),
        a_gather=a_gather,
        a_mask=a_mask,
        groups=tuple(groups),
        device=_device_layout(a, b, groups, a_gather, a_mask,
                              a.n_rows, n),
    )
    return pre, layout
