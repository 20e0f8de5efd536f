"""Numeric SpGEMM execution of a cached symbolic plan (DESIGN.md §6–§10).

``execute(plan, a_values, b_values)`` runs only the value-dependent work of
C = A @ B; every pattern-dependent decision (sorting, blocking, hash sizing,
padded layouts, kernel groups, the product stream) was made once by
``core.planner.plan_spgemm``.

Execution is dispatched through the backend/engine registry
(``core.backends``): each backend registers one executor pair per engine in
``_DISPATCH``, and :func:`resolve_engine` turns the caller's ``engine=``
argument into a dispatch key by consulting the plan's
:class:`~repro.core.backends.ExecutionContract` — no backend string
matching at the call sites.  The registered pairs:

* ``("host", "naive")`` — the faithful numpy executors, passing the plan's
  pre-computed ``Preprocess`` so nothing is re-analyzed.  These are the
  bit-exact oracles of the paper's algorithms.
* ``("host", "stream")`` — the plan's precomputed product stream
  (``core.fast``, DESIGN.md §9): one vectorized gather → multiply →
  segment-reduce pass, no per-column Python loop.  Canonical output order,
  last-ulp fp-reassociation vs the oracles.  Default for ``expand``.
* ``("pallas", "naive")`` — gathers each group's padded value operand with
  the plan's precomputed ``b_vgather``/``b_vmask``, launches one kernel per
  plan group via ``kernels.ops.run_{spa,spars,hash}``, and compacts the
  tiles: where the plan holds a ``DeviceLayout`` (dense tiles that fit the
  device), operands padded on the device, one gather of the tiles on the
  device and one copy of C's values back; else each group's tile read back
  and compacted straight into column-sliced CSC on the host (no dense
  ``[m, n]`` sink; peak transient memory is one ``[m, tile_cols]`` tile).
  Its phases are the spans ``spgemm.pallas.{operands,group,readback,
  compact}`` (DESIGN.md §16).
* ``("jax", "stream")`` — the device-resident stream (``core.jax_stream``,
  DESIGN.md §10): a jitted, differentiable pure-JAX replay of the same
  contraction; one device dispatch per execution.

``execute_batched(plan, a_vals [B, nnz], b_vals [B, nnz])`` is the batched
numeric phase (DESIGN.md §7): B same-pattern multiplies through *one*
traversal of the plan (Pallas: each group launches once with a leading
batch axis; jax: one vmapped dispatch; host: vectorized value-axis passes
where available).  Results are bit-identical to a Python loop of
``execute`` per backend engine.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.core import backends, fast, jax_stream, naive, spans
from repro.core.backends import check_engine, default_engine, get_backend
from repro.core.expand import spgemm_expand
from repro.core.planner import SpgemmPlan
from repro.sparse.format import (
    CSC,
    BatchedCSCBuilder,
    CSCBuilder,
    padded_values,
    padded_values_batched,
)
from repro.sparse.partition import csc_empty, csc_hstack, merge_csc_partials

# filled below: host methods whose *naive-engine* batched path is vectorized
# over the value axis (accumulation structure is pattern-only); the stream
# engine is always vectorized and every other naive executor loops
_BATCHED_HOST: dict = {}

# union of every backend's accepted engine= spellings (back-compat alias)
ENGINES = backends.engine_spellings()

# (backend, resolved engine) -> (execute_fn, execute_batched_fn); the
# executor half of the backend registry.  Uniform signature:
# fn(plan, a_values, b_values, *, stats, validate)
_DISPATCH: dict = {}


def register_executor(backend: str, engine: str, fn, fn_batched) -> None:
    _DISPATCH[(backend, engine)] = (fn, fn_batched)


def resolve_engine(plan, engine: str | None) -> str:
    """The engine an execution will run: explicit choice or the default.

    Consults the plan backend's contract (``core.backends``): unknown
    spellings and engines the backend does not implement raise there
    (e.g. ``"stream"`` needs a stream-capable plan, and the jax backend
    has no ``"naive"`` oracles).  ``None`` resolves to the contract's
    default for the plan's method: host defaults to the bit-exact naive
    oracles except for ``expand`` (whose naive executor computes the same
    contraction as the stream, slower); jax always runs its device stream.
    """
    contract = get_backend(plan.backend)
    check_engine(contract, engine)
    if engine is None:
        return default_engine(contract, plan.method)
    return engine


def _check_engine(plan, engine: str | None) -> None:
    """Engine-argument validation shared by the untiled and tiled paths."""
    check_engine(get_backend(plan.backend), engine)


def execute(plan: SpgemmPlan, a_values, b_values, *,
            stats: dict | None = None,
            validate: str | None = None,
            engine: str | None = None) -> CSC:
    """C = A @ B for new numeric values on the plan's sparsity patterns.

    ``a_values``/``b_values``: CSC matrices or raw nnz-length value arrays.
    Shapes and nnz are checked against the planned patterns (O(1)); a
    same-shape same-nnz operand with a different pattern is by default the
    caller's responsibility — pass ``validate="fingerprint"`` to re-hash the
    operand structure (O(nnz)) and reject any pattern mismatch (honoured by
    every engine, including the stream and jax paths).  ``engine`` selects
    the numeric engine (see :func:`resolve_engine`).  ``stats``, if given,
    is filled with execution statistics (engine, tile shapes, launch
    count) — tests use it to assert the no-dense-intermediate guarantee.
    """
    eng = resolve_engine(plan, engine)
    fn, _ = _DISPATCH[(plan.backend, eng)]
    return fn(plan, a_values, b_values, stats=stats, validate=validate)


def execute_batched(plan: SpgemmPlan, a_values, b_values, *,
                    stats: dict | None = None,
                    validate: str | None = None,
                    engine: str | None = None) -> list:
    """B same-pattern multiplies through one execution of the plan.

    ``a_values``/``b_values``: :class:`~repro.sparse.format.BatchedCSC`
    operands or raw ``[B, nnz]`` value stacks (row b = value set b, aligned
    with the planned pattern).  Returns a list of B CSC results,
    bit-identical to ``[plan.execute(a_values[b], b_values[b]) ...]``.

    Pallas backend: every plan group launches once for all B value sets (a
    vmapped leading batch axis), so the launch count is independent of B and
    peak transient memory is one ``[B, m, tile_cols]`` tile.  Jax backend:
    one vmapped device dispatch.  Host backend: the stream engine
    broadcasts its gather/segment-reduce pass over the value axis, naive
    SPA runs one vectorized pass, and the remaining naive executors
    (SPARS/HASH/hybrids/ESC) fall back to a per-element loop
    (DESIGN.md §7/§9/§10).  ``engine``/``validate`` behave exactly as in
    :func:`execute`.
    """
    eng = resolve_engine(plan, engine)
    _, fn = _DISPATCH[(plan.backend, eng)]
    return fn(plan, a_values, b_values, stats=stats, validate=validate)


def _check_batch(av, bv) -> int:
    if av.shape[0] != bv.shape[0]:
        raise ValueError(
            f"batch mismatch: A has {av.shape[0]} value sets, "
            f"B has {bv.shape[0]}")
    batch = int(av.shape[0])
    if batch == 0:
        raise ValueError("empty batch")
    return batch


# ---------------------------------------------------------------------------
# host executors (naive oracles + the product stream)
# ---------------------------------------------------------------------------


def _host_naive(plan, a_values, b_values, *, stats=None,
                validate=None) -> CSC:
    plan.a.check_compatible(a_values, validate)
    plan.b.check_compatible(b_values, validate)
    if stats is not None:
        stats["engine"] = "naive"
    return _execute_host(plan, a_values, b_values)


def _host_stream(plan, a_values, b_values, *, stats=None,
                 validate=None) -> CSC:
    plan.a.check_compatible(a_values, validate)
    plan.b.check_compatible(b_values, validate)
    return fast.execute_stream(plan, _values(a_values), _values(b_values),
                               stats=stats)


def _host_naive_batched(plan, a_values, b_values, *, stats=None,
                        validate=None) -> list:
    av = plan.a.batched_values(a_values, validate)
    bv = plan.b.batched_values(b_values, validate)
    batch = _check_batch(av, bv)
    vectorized = _BATCHED_HOST.get(plan.method)
    if vectorized is not None:
        out = vectorized(plan, av, bv)
    else:
        out = [_execute_host(plan, av[b], bv[b]) for b in range(batch)]
    if stats is not None:
        stats["engine"] = "naive"
        stats["batch"] = batch
        stats["path"] = "vectorized" if vectorized is not None else "loop"
    return out


def _host_stream_batched(plan, a_values, b_values, *, stats=None,
                         validate=None) -> list:
    av = plan.a.batched_values(a_values, validate)
    bv = plan.b.batched_values(b_values, validate)
    batch = _check_batch(av, bv)
    # fast.py reports stats["path"]: "vectorized" (2-D passes) or
    # "rowloop" (per-row 1-D passes on long streams)
    out = fast.execute_stream_batched(plan, av, bv, stats=stats)
    if stats is not None:
        stats["batch"] = batch
    return out


register_executor("host", "naive", _host_naive, _host_naive_batched)
register_executor("host", "stream", _host_stream, _host_stream_batched)
register_executor("jax", "stream", jax_stream.execute_jax,
                  jax_stream.execute_jax_batched)


# ---------------------------------------------------------------------------
# tiled execution: per-tile plans + the merge/stitch reduction (DESIGN.md §8)
# ---------------------------------------------------------------------------


def _tiled_dtype(plan, av, bv):
    return np.float32 if plan.backend == "pallas" \
        else np.result_type(av.dtype, bv.dtype)


def _tile_values(plan, tile, av, bv):
    """Slice the parent value arrays down to one tile (pattern-static)."""
    lo, hi = tile.a_vals
    return av[..., lo:hi], bv[..., tile.b_vals]


def _check_tile_engines(plan, engine) -> None:
    """An explicit engine must hold on *every* tile of the grid.

    A tile grid may mix backends (host tiles + "jax" device-stream tiles);
    silently downgrading a tile that lacks the requested engine would hand
    back e.g. f32 device results where ``engine="naive"`` promised the
    bit-exact f64 oracles — loud rejection instead (``engine=None`` runs
    each tile's per-method default).
    """
    if engine is None:
        return
    missing = sorted({t.plan.backend for t in plan.tiles
                      if engine not in t.plan.contract.engines})
    if missing:
        raise ValueError(
            f"engine={engine!r} is not available on every tile of this "
            f"grid (missing on {missing} tile backends); use engine=None "
            "for per-tile defaults, or restrict candidates= at plan time")


def _host_child(c: CSC) -> CSC:
    """Host view of a child tile result (jax tiles return device values;
    the merge/stitch reduction is a host pass)."""
    if isinstance(c.values, np.ndarray):
        return c
    return CSC(np.asarray(c.values), c.row_indices, c.col_ptr, c.shape)


def _merge_and_stitch(plan, per_block, dtype) -> CSC:
    """Reduce per-column-block partial lists into the final CSC.

    ``per_block[ni]`` holds the row-block partials of column block ``ni``
    in k-ascending order.  Each block merges (single partials pass through
    bit-identically), then the blocks stitch left-to-right.
    """
    m = plan.shape[0]
    blocks = []
    for ni, (j0, j1) in enumerate(zip(plan.n_bounds[:-1],
                                      plan.n_bounds[1:])):
        shape = (m, int(j1 - j0))
        parts = per_block[ni]
        if not parts:
            blocks.append(csc_empty(shape, dtype))
        else:
            blocks.append(merge_csc_partials(parts, shape, dtype=dtype))
    if not blocks:
        return csc_empty((m, 0), dtype)
    return csc_hstack(blocks, m)


def _record_tile_stats(plan, stats, child_stats):
    if stats is None:
        return
    stats["grid"] = plan.grid
    stats["tiles"] = [
        {"k": t.k, "n": t.n, "method": t.method} for t in plan.tiles]
    stats["methods"] = sorted({t.method for t in plan.tiles})
    stats["merged_blocks"] = len(
        {t.n for t in plan.tiles
         if sum(u.n == t.n for u in plan.tiles) > 1})
    stats["result_shape"] = plan.shape
    if child_stats:
        stats["n_launches"] = sum(
            s.get("n_launches", 0) for s in child_stats)
        stats["peak_tile_elems"] = max(
            (s.get("peak_tile_elems", 0) for s in child_stats), default=0)


def execute_tiled(plan, a_values, b_values, *,
                  stats: dict | None = None,
                  validate: str | None = None,
                  engine: str | None = None) -> CSC:
    """Numeric phase of a :class:`~repro.core.planner.TiledSpgemmPlan`.

    Runs every tile's child plan on the tile's value slices, accumulates
    row-block partials per column block (k-ascending; a single row block is
    a bit-identical passthrough), and stitches the column blocks.
    ``engine`` is forwarded to every child plan and must be available on
    every tile's backend (:func:`_check_tile_engines` — a mixed host/jax
    grid accepts ``None``/``"stream"`` but rejects ``"naive"``, whose
    bit-exact promise the device tiles cannot keep); ``engine=None`` runs
    each tile's method default.  ``stats`` records the grid, the per-tile
    method choices, and — on the Pallas backend — the aggregated launch
    count and peak transient tile size.
    """
    plan.a.check_compatible(a_values, validate)
    plan.b.check_compatible(b_values, validate)
    _check_engine(plan, engine)
    _check_tile_engines(plan, engine)
    av = _values(a_values)[: int(plan.a.col_ptr[-1])]
    bv = _values(b_values)[: int(plan.b.col_ptr[-1])]
    dtype = _tiled_dtype(plan, av, bv)
    per_block = {ni: [] for ni in range(plan.grid[1])}
    child_stats = []
    for tile in plan.tiles:
        ta, tb = _tile_values(plan, tile, av, bv)
        cs = {} if (stats is not None
                    and plan.backend == "pallas") else None
        per_block[tile.n].append(_host_child(
            tile.plan.execute(ta, tb, stats=cs, engine=engine)))
        if cs is not None:
            child_stats.append(cs)
    _record_tile_stats(plan, stats, child_stats)
    return _merge_and_stitch(plan, per_block, dtype)


def execute_tiled_batched(plan, a_values, b_values, *,
                          stats: dict | None = None,
                          validate: str | None = None,
                          engine: str | None = None) -> list:
    """Batched tiled execution: B value sets through one plan traversal.

    Each tile's child plan executes batched (one launch set per tile,
    independent of B on the Pallas backend); the merge/stitch reduction
    then runs per batch element, bit-identical to looping
    :func:`execute_tiled`.  ``engine`` forwards per tile exactly as in
    :func:`execute_tiled`.
    """
    av = plan.a.batched_values(a_values, validate)
    bv = plan.b.batched_values(b_values, validate)
    batch = _check_batch(av, bv)
    _check_engine(plan, engine)
    _check_tile_engines(plan, engine)
    dtype = _tiled_dtype(plan, av, bv)
    per_block = [{ni: [] for ni in range(plan.grid[1])}
                 for _ in range(batch)]
    child_stats = []
    for tile in plan.tiles:
        ta, tb = _tile_values(plan, tile, av, bv)
        cs = {} if (stats is not None
                    and plan.backend == "pallas") else None
        outs = tile.plan.execute_batched(ta, tb, stats=cs, engine=engine)
        for bi, c in enumerate(outs):
            per_block[bi][tile.n].append(_host_child(c))
        if cs is not None:
            child_stats.append(cs)
    _record_tile_stats(plan, stats, child_stats)
    if stats is not None:
        stats["batch"] = batch
    return [_merge_and_stitch(plan, per_block[bi], dtype)
            for bi in range(batch)]


def _execute_host(plan: SpgemmPlan, a_values, b_values) -> CSC:
    a = plan.a.with_values(a_values)
    b = plan.b.with_values(b_values)
    method = plan.method
    params = dict(plan.params)
    if method == "spa":
        return naive.spa_numpy(a, b)
    if method == "expand":
        return spgemm_expand(a, b)
    if method == "esc":
        return naive.esc_numpy(a, b)
    if method.startswith("spars"):
        return naive.spars_numpy(a, b, plan.pre)
    if method.startswith("hash"):
        return naive.hash_numpy(a, b, plan.pre)
    if method.startswith("h-"):
        return naive.hybrid_numpy(
            a, b, t=params["t"], b_min=params["b_min"],
            b_max=params["b_max"], accumulator=params["accumulator"],
            pre=plan.pre,
        )
    raise AssertionError(method)


# ---------------------------------------------------------------------------
# vectorized host batched executors (value axis only; structure is
# pattern-only, so every op below repeats naive.py's accumulation order
# element-wise across the batch — bit-identical per element)
# ---------------------------------------------------------------------------


def _spa_host_batched(plan: SpgemmPlan, av: np.ndarray,
                      bv: np.ndarray) -> list:
    """Batched ``naive.spa_numpy``: one pass, SPA arrays carry [B, m]."""
    a_cp, a_rows = plan.a.col_ptr, plan.a.row_indices
    b_cp, b_rows = plan.b.col_ptr, plan.b.row_indices
    m, n = plan.shape
    batch = av.shape[0]
    dtype = np.result_type(av.dtype, bv.dtype)

    spa_values = np.zeros((batch, m), dtype)
    spa_flags = np.zeros(m, bool)       # pattern-only: shared by the batch

    out_rows = [np.zeros(0, np.int32)] * n
    out_vals = [np.zeros((batch, 0), dtype)] * n
    for j in range(n):
        touched = []
        for p in range(b_cp[j], b_cp[j + 1]):
            k = b_rows[p]
            sl = slice(a_cp[k], a_cp[k + 1])
            ar = a_rows[sl]
            spa_values[:, ar] += av[:, sl] * bv[:, p, None]
            new = ar[~spa_flags[ar]]
            spa_flags[new] = True
            if len(new):
                touched.append(new)
        idx = np.concatenate(touched) if touched else np.zeros(0, np.int32)
        out_rows[j] = idx.astype(np.int32)
        out_vals[j] = spa_values[:, idx].astype(dtype)
        spa_values[:, idx] = 0
        spa_flags[idx] = False
    return _assemble_batched(batch, out_rows, out_vals, (m, n), dtype)


# the batched expand fast path lives in core/fast.py now: expand's default
# engine is the product stream, whose batched execution is a broadcast of
# the same gather/segment-reduce pass (no per-row np.add.at loop)
_BATCHED_HOST.update(spa=_spa_host_batched)
VECTORIZED_HOST = tuple(_BATCHED_HOST)


def _assemble_batched(batch, cols_rows, cols_vals, shape, dtype) -> list:
    """Batched ``naive._assemble``: per-column [B, cnt] value slabs."""
    n = shape[1]
    col_ptr = np.zeros(n + 1, np.int32)
    np.cumsum([len(r) for r in cols_rows], out=col_ptr[1:])
    if col_ptr[-1]:
        rows = np.concatenate(cols_rows).astype(np.int32)
        vals = np.concatenate(cols_vals, axis=1)
    else:
        rows = np.zeros(0, np.int32)
        vals = np.zeros((batch, 0), dtype)
    return [CSC(vals[b], rows, col_ptr, shape) for b in range(batch)]


# ---------------------------------------------------------------------------
# Pallas paths
# ---------------------------------------------------------------------------


def _check_kernels(lay) -> None:
    """Refuse the plan's kernels the platform cannot compile, before any
    group launches (``repro.runtime.check_kernel``)."""
    for kind in sorted({g.kind for g in lay.groups}):
        runtime.check_kernel(kind)


def _execute_pallas(plan: SpgemmPlan, a_values, b_values, *,
                    stats: dict | None = None,
                    validate: str | None = None) -> CSC:
    from repro.kernels import ops as kops, vmem

    plan.a.check_compatible(a_values, validate)
    plan.b.check_compatible(b_values, validate)
    lay = plan.pallas
    _check_kernels(lay)
    m, n = plan.shape
    za = int(lay.a_rows.shape[1])
    planes = vmem.row_planes(m) + vmem.VALUE_PLANES
    run = {"spa": kops.run_spa, "spars": kops.run_spars,
           "hash": kops.run_hash}

    def attrs(g) -> dict:
        """The ``spgemm.pallas.group`` span's attributes of group ``g``:
        SPA and SPARS also name the byte planes of A's table."""
        return dict(kind=g.kind, cols=g.n_real, zb=int(g.b_rows.shape[1]),
                    za=za, products=g.products,
                    **({} if g.kind == "hash" else {"planes": planes}))

    if lay.device is not None:
        c = _execute_on_device(plan, a_values, b_values, run, attrs)
        tile_shapes = [("dense", (m, g.n_real)) for g in lay.groups]
    else:
        c, tile_shapes = _execute_on_host(plan, a_values, b_values, run,
                                          attrs)
    if stats is not None:
        stats.update(engine="naive", backend="pallas", device=True,
                     fallback=None)
        stats["tile_shapes"] = tile_shapes
        stats["peak_tile_elems"] = max(
            (r * w for _, (r, w) in tile_shapes), default=0)
        stats["n_launches"] = len(lay.groups)
        stats["result_shape"] = (m, n)
        stats["compacted_on"] = "host" if lay.device is None else "device"
    return c


def _execute_on_host(plan, a_values, b_values, run, attrs) -> tuple:
    """Pad each group's operand on the host, wait for each tile, read it
    back and compact it into C's columns (``CSCBuilder``)."""
    from repro.kernels import ops as kops

    lay = plan.pallas
    m, n = plan.shape
    with spans.span("spgemm.pallas.operands"):
        av = padded_values(_values(a_values), lay.a_gather,
                           lay.a_mask).astype(np.float32, copy=False)
        b_raw = _values(b_values)
        a_arrs = kops.device_operand(lay.a_rows, av, lay.a_nnz)

    builder = CSCBuilder((m, n), np.float32)
    for g in lay.groups:
        with spans.span("spgemm.pallas.operands"):
            # plan-time-composed masked gather: straight from raw values to
            # the group operand, no full padded-B intermediate or per-call
            # mask
            g_vals = jnp.asarray(padded_values(
                b_raw, g.b_vgather, g.b_vmask).astype(np.float32,
                                                      copy=False))
        with spans.span("spgemm.pallas.group", **attrs(g)):
            out = jax.block_until_ready(
                run[g.kind](g, a_arrs, g_vals, m=m,
                            block_cols=lay.block_cols))
        with spans.span("spgemm.pallas.readback"):
            tiles = [np.asarray(t)[:, : g.n_real]
                     for t in (out if g.kind == "hash" else (out,))]
        with spans.span("spgemm.pallas.compact"):
            if g.kind == "hash":
                builder.add_hash_tables(g.cols, *tiles)
            else:
                builder.add_dense_tile(g.cols, *tiles)
    with spans.span("spgemm.pallas.compact"):
        c = builder.build()
    return c, list(builder.tile_shapes)


def _execute_on_device(plan, a_values, b_values, run, attrs) -> CSC:
    """The plan's :class:`~repro.core.planner.DeviceLayout`: the raw values
    up once and padded on the device, every group launched without
    waiting, one gather of C's values from the tiles, one copy back, and
    C's exact zeros dropped as the host compaction of each tile drops
    them."""
    from repro.kernels import ops as kops

    lay = plan.pallas
    dl = lay.device
    with spans.span("spgemm.pallas.operands"):
        raw = [jnp.asarray(np.asarray(_values(v)[: int(p.col_ptr[-1])],
                                      np.float32))
               for v, p in ((a_values, plan.a), (b_values, plan.b))]
        a_vals, b_ops = kops.pad_operands(
            *raw, dl.a_pos, dl.a_src, dl.b_pos, dl.b_src,
            a_shape=tuple(lay.a_rows.shape), widths=dl.widths,
            zb=int(lay.groups[0].b_rows.shape[1]))
        a_arrs = (lay.a_rows, a_vals, lay.a_nnz)
    tiles = []
    for g, g_vals in zip(lay.groups, b_ops):
        with spans.span("spgemm.pallas.group", **attrs(g)):
            tiles.append(run[g.kind](g, a_arrs, g_vals, m=plan.shape[0],
                                     block_cols=lay.block_cols))
    with spans.span("spgemm.pallas.compact"):
        c_dev = kops.compact_tiles(tiles, dl.src)
    jax.block_until_ready(c_dev)
    with spans.span("spgemm.pallas.readback"):
        vals = np.asarray(c_dev)
    with spans.span("spgemm.pallas.compact"):
        keep = np.abs(vals) > 0
        if keep.all():
            return CSC(vals, dl.c_rows, dl.c_col_ptr, plan.shape)
        kept = np.concatenate(([0], np.cumsum(keep))).astype(np.int32)
        return CSC(vals[keep], dl.c_rows[keep], kept[dl.c_col_ptr],
                   plan.shape)


def _execute_pallas_batched(plan: SpgemmPlan, a_values, b_values, *,
                            stats: dict | None = None,
                            validate: str | None = None) -> list:
    from repro.kernels import ops as kops

    av = plan.a.batched_values(a_values, validate)
    bv = plan.b.batched_values(b_values, validate)
    batch = _check_batch(av, bv)
    lay = plan.pallas
    _check_kernels(lay)
    m, n = plan.shape
    avp = padded_values_batched(av, lay.a_gather,
                                lay.a_mask).astype(np.float32, copy=False)
    a_arrs = kops.device_operand(lay.a_rows, avp, lay.a_nnz)

    builder = BatchedCSCBuilder(batch, (m, n), np.float32)
    for g in lay.groups:
        g_vals = padded_values_batched(bv, g.b_vgather,
                                       g.b_vmask).astype(np.float32,
                                                         copy=False)
        if g.kind == "spa":
            tiles = kops.run_spa_batched(g, a_arrs, g_vals, m=m,
                                         block_cols=lay.block_cols)
            builder.add_dense_tile(g.cols, tiles)
        elif g.kind == "spars":
            tiles = kops.run_spars_batched(g, a_arrs, g_vals, m=m,
                                           block_cols=lay.block_cols)
            builder.add_dense_tile(g.cols, tiles)
        elif g.kind == "hash":
            keys, vals = kops.run_hash_batched(g, a_arrs, g_vals, m=m,
                                               block_cols=lay.block_cols)
            builder.add_hash_tables(g.cols, keys, vals)
        else:
            raise AssertionError(g.kind)
    out = builder.build()
    if stats is not None:
        stats.update(engine="naive", backend="pallas", device=True,
                     fallback=None)
        stats["tile_shapes"] = list(builder.tile_shapes)
        stats["peak_tile_elems"] = builder.peak_tile_elems
        stats["n_launches"] = len(lay.groups)   # independent of the batch
        stats["result_shape"] = (m, n)
        stats["batch"] = batch
    return out


register_executor("pallas", "naive", _execute_pallas,
                  _execute_pallas_batched)


def _values(x) -> np.ndarray:
    return np.asarray(x.values) if isinstance(x, CSC) else np.asarray(x)
