"""Device-resident stream execution: jit-compatible, differentiable SpGEMM.

The product stream (``core.fast``, DESIGN.md §9) already reduced the numeric
phase of a cached host plan to a fixed gather → multiply → segment-reduce
contraction.  This module compiles that contraction for the ``"jax"``
backend (DESIGN.md §10): the plan's frozen index arrays move to the device
once (cached on the plan alongside the numpy ones), and the numeric phase
becomes a jitted pure-JAX function of the two value arrays::

    prod   = a_values[a_pos] * b_values[b_pos]          # jnp.take
    c_vals = segment_sum(prod, seg_ids, num_segments)   # plan-static nnz_c

Because every shape in that function is plan-static, it traces once and
replays from XLA's compiled-call cache — an execution is a single device
dispatch, with no per-group Python loop (the Pallas path launches one
kernel per plan group from Python) and no host round-trip.

**One value table.**  On concrete operands :func:`execute_jax` may pack
A's values and then B's into one table outside the executable and dispatch
:func:`table_fn`, whose gathers both index that table (B through
``b_pos + nnz_A``).  XLA's cross-program prefetch copies one entry
parameter into VMEM, so with one table neither gather reads its values
from HBM, while with two a large or long-gathered B stays in HBM.  Where
two tables would both reach VMEM anyway, where one table would not fit
(``runtime.prefetch_limits``, :func:`table_form`), and for batched stacks
and traced operands, the two-table form stays.

**Differentiability.**  The contraction is bilinear, so its VJP is two more
stream replays through the *same* index arrays — no new symbolic work::

    dL/dA[p] = Σ_{q : a_pos[q]=p}  ḡ[seg(q)] · B[b_pos[q]]
    dL/dB[p] = Σ_{q : b_pos[q]=p}  ḡ[seg(q)] · A[a_pos[q]]

i.e. broadcast the output cotangent back over the products (a ``take``
through ``seg_ids``), weight by the *other* operand's gathered values, and
scatter-add through ``a_pos``/``b_pos`` (a ``segment_sum`` with the
operand's nnz as the static segment count).  :func:`stream_fn` installs
this as a ``jax.custom_vjp`` so ``jax.grad`` of anything downstream of the
C values is itself a pair of stream replays.  ``jax.vmap`` composes with
the custom vjp, which is how the batched path (DESIGN.md §7) rides one
trace for a whole ``[B, nnz]`` value stack.

**Guard semantics.**  Device streams obey the same plan-memory guard as
host streams (``stream_limit`` resolved at plan time).  A guarded jax plan
executes by falling back to the *host* stream engine (transient rebuild,
numerically the host stream's result) when the operands are concrete;
under a trace (``jax.jit``/``jax.grad`` — the operands are tracers) the
fallback is impossible and a capability error explains the fix.  A
fallback warns and is counted (``plan_cache_info()["host_fallbacks"]``).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.core import fast, faults, spans
from repro.sparse.format import CSC, BatchedCSC

# int32 device indices: the plan-memory guard caps streams far below 2**31
# products.  a_pos/b_pos index the *operand* value arrays, whose nnz is not
# bounded by the stream length, so the overflow check below covers both.
_I32_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass(frozen=True)
class DeviceStream:
    """Device-resident half of a plan's :class:`~repro.core.fast.ProductStream`.

    ``a_pos``/``b_pos``/``seg_ids`` live on the device (int32; one entry per
    scalar product, C-slot sort permutation pre-applied exactly as in the
    host stream).  ``c_rows``/``c_col_ptr`` stay host-side numpy — they are
    the *structure* of every result this plan produces and are shared
    (frozen) with the host stream.
    """

    a_pos: jax.Array        # [P] int32: A value position of each product
    b_pos: jax.Array        # [P] int32: B value position of each product
    seg_ids: jax.Array      # [P] int32: C slot of each product (ascending)
    c_rows: np.ndarray      # [nnz_c] int32 (host, frozen)
    c_col_ptr: np.ndarray   # [n+1] int32 (host, frozen)
    shape: Tuple[int, int]
    n_products: int
    num_segments: int       # nnz_c — the static segment_sum count

    @property
    def nbytes(self) -> int:
        """Device bytes held by the stream's index arrays."""
        return int(self.a_pos.nbytes + self.b_pos.nbytes
                   + self.seg_ids.nbytes)

    @property
    def indices(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """``(a_pos, b_pos, seg_ids)``: the index arguments of a replay."""
        return (self.a_pos, self.b_pos, self.seg_ids)


def check_int32_stream(plan, s) -> None:
    """Reject streams whose indices overflow int32 device arrays.

    A hard error beats int32-wrapped in-bounds-promised gathers:
    products/output slots (huge guard) or *operand* positions
    (``a_pos``/``b_pos`` index the value arrays — a small stream over a
    >2**31-nnz operand still needs wide indices) past int32.  Checked
    when a plan's index arrays are lifted to the device
    (:func:`device_stream`).
    """
    if max(s.n_products, s.nnz, int(plan.a.col_ptr[-1]),
           int(plan.b.col_ptr[-1])) > _I32_MAX:
        raise ValueError(
            f"stream of {s.n_products} products over operands of nnz "
            f"{int(plan.a.col_ptr[-1])}/{int(plan.b.col_ptr[-1])} "
            "exceeds int32 device indexing; lower stream_limit / "
            "fast.STREAM_MAX_PRODUCTS or shrink the tile")


def stream_seg_ids(s) -> np.ndarray:
    """Per-product C-slot id of a host stream (int32, non-decreasing).

    Segment p spans ``[seg_starts[p], seg_starts[p+1])`` of the sorted
    stream, so the ids are the consecutive integers ``0..nnz_c-1`` repeated
    by segment length — every stored C slot has at least one product.
    """
    lens = np.diff(np.append(s.seg_starts, s.n_products))
    return np.repeat(np.arange(s.nnz, dtype=np.int32), lens)


def device_stream(plan) -> Optional[DeviceStream]:
    """The plan's device-resident stream, built lazily and memoized.

    Derived from the (host) :attr:`plan.stream` on first access and cached
    on the plan alongside it — ``plan.device_stream_nbytes`` /
    ``plan_cache_info()['device_stream_bytes']`` report the device half
    separately.  ``None`` when the plan-memory guard tripped (no host
    stream to lift) or the plan's backend carries no stream.  The first
    build is the span ``spgemm.device_lift``, with the uploaded ``bytes``.
    """
    s = plan.stream
    if s is None:
        return None
    memo = plan._stream_memo
    if "device" not in memo:
        with spans.span("spgemm.device_lift") as span:
            memo["device"] = _lift(plan, s)
            span.set(bytes=memo["device"].nbytes)
    return memo["device"]


def _lift(plan, s) -> DeviceStream:
    faults.check("device_lift", key=getattr(plan, "backend", None))
    check_int32_stream(plan, s)
    seg_ids = stream_seg_ids(s)
    with jax.ensure_compile_time_eval():
        # the lazy build may run *inside* a caller's jit trace (the first
        # traced execution of a fresh plan); the index arrays must still
        # come out concrete — they are plan state shared by every later
        # trace, not constants of this one
        dev_arrays = (jnp.asarray(s.a_pos, jnp.int32),
                      jnp.asarray(s.b_pos, jnp.int32),
                      jnp.asarray(seg_ids))
    return DeviceStream(
        a_pos=dev_arrays[0],
        b_pos=dev_arrays[1],
        seg_ids=dev_arrays[2],
        c_rows=s.c_rows,
        c_col_ptr=s.c_col_ptr,
        shape=s.shape,
        n_products=s.n_products,
        num_segments=s.nnz,
    )


def _guard_error(plan) -> ValueError:
    if not plan.contract.carries_stream:
        # stream-less backend (pallas): a capability gap, not a guard trip
        return ValueError(
            f"the {plan.backend!r} backend carries no product stream — "
            "plan on backend='jax' (or 'host') for stream execution")
    return ValueError(
        f"plan's product stream exceeds its plan-memory guard "
        f"(stream_limit={plan.stream_limit}), so there is no device-resident "
        "stream to trace: a jitted/differentiated execution cannot fall "
        "back to the host engine.  Raise stream_limit= (or "
        "fast.STREAM_MAX_PRODUCTS) when planning, or execute on the host "
        "backend outside the trace")


# the stream's indices are plan-frozen and in-bounds by construction, so
# every gather/scatter skips XLA's out-of-bounds clamping (the default
# "fill" mode materializes [P]-sized bounds-check compares that dominate
# both compile and run time on large streams)
_IN_BOUNDS = jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS


def _take(values, idx):
    return jnp.asarray(values).at[idx].get(mode=_IN_BOUNDS)


def bilinear_custom_vjp(forward, grad_a, grad_b):
    """``jax.custom_vjp`` wrapper for a bilinear stream contraction.

    ``forward(idx, a_values, b_values)`` is the primal replay through the
    plan's frozen index pytree ``idx``; the contraction is bilinear, so its
    VJP is two more replays through the same indices (module docstring):
    ``grad_a(idx, g, a_values, b_values)`` and ``grad_b(idx, g, a_values,
    b_values)`` each take the broadcast output cotangent plus both residual
    operands and return the corresponding operand cotangent (shaped like
    the primal operand — oversized raw value arrays get oversized
    cotangents).  ``idx`` gets no cotangent.  Shared by the XLA device
    stream (:func:`_bilinear_contract`) and the mesh stream, which differ
    only in how a replay is lowered.  ``jax.vmap`` composes with the
    returned function, which is how the batched paths ride one trace for
    a whole ``[B, nnz]`` value stack; :func:`bind_indices` jits it for one
    plan.
    """

    @jax.custom_vjp
    def contract(idx, a_values, b_values):
        return forward(idx, a_values, b_values)

    def fwd(idx, a_values, b_values):
        return contract(idx, a_values, b_values), (idx, a_values, b_values)

    def bwd(residuals, g):
        idx, a_values, b_values = residuals
        return (None, grad_a(idx, g, a_values, b_values),
                grad_b(idx, g, a_values, b_values))

    contract.defvjp(fwd, bwd)
    return contract


def bind_indices(contract, idx, *, batched: bool = False):
    """``f(a_values, b_values)``: ``contract`` jitted for one plan's ``idx``.

    The index arrays are *arguments* of the jitted function, not closure
    constants: a closure would compile the whole stream into the executable
    (slow compiles, a second copy in device memory, and an entry too large
    for the persistent compilation cache).  ``batched`` vmaps the value
    operands over a leading ``[B]`` axis.
    """
    fn = jax.vmap(contract, in_axes=(None, 0, 0)) if batched else contract
    return functools.partial(jax.jit(fn), idx)


def _bilinear_contract(num_segments: int):
    """The custom-vjp gather→multiply→segment-sum contraction over
    ``idx = (a_pos, b_pos, seg_ids)`` with ``num_segments`` C slots."""

    def forward(idx, a_values, b_values):
        a_pos, b_pos, seg_ids = idx
        prod = _take(a_values, a_pos) * _take(b_values, b_pos)
        return jax.ops.segment_sum(prod, seg_ids, num_segments=num_segments,
                                   indices_are_sorted=True, mode=_IN_BOUNDS)

    # cotangent per product (a take through seg_ids), then scatter-add
    # through the same frozen indices the forward gathered through; the
    # shared g_prod gather is deduped by XLA CSE across the two replays
    def grad_a(idx, g, a_values, b_values):
        a_pos, b_pos, seg_ids = idx
        g_prod = _take(g, seg_ids)
        return jax.ops.segment_sum(g_prod * _take(b_values, b_pos), a_pos,
                                   num_segments=a_values.shape[0],
                                   mode=_IN_BOUNDS)

    def grad_b(idx, g, a_values, b_values):
        a_pos, b_pos, seg_ids = idx
        g_prod = _take(g, seg_ids)
        return jax.ops.segment_sum(g_prod * _take(a_values, a_pos), b_pos,
                                   num_segments=b_values.shape[0],
                                   mode=_IN_BOUNDS)

    return bilinear_custom_vjp(forward, grad_a, grad_b)


def _one_table(contract, offset: int):
    """``contract`` over one value table holding A's values and then B's:
    ``f(idx, table)``, B's positions shifted by ``offset`` (A's packed
    length) inside the program, where the add fuses into the index fusion
    of B's gather.  The same products in the same order, so the result is
    the two-table contraction's; the table's cotangent is the sum of the
    two scatter-adds of the custom vjp."""

    def run(idx, table):
        a_pos, b_pos, seg_ids = idx
        return contract((a_pos, b_pos + offset, seg_ids), table, table)

    return run


def _contract(plan):
    """The plan's custom-vjp contraction, memoized; guarded plans raise
    the capability error."""
    memo = plan._stream_memo
    if "jax_contract" not in memo:
        dev = device_stream(plan)
        if dev is None:
            raise _guard_error(plan)
        memo["jax_contract"] = _bilinear_contract(dev.num_segments)
    return memo["jax_contract"]


def stream_fn(plan):
    """The plan's jitted numeric function ``f(a_values, b_values) -> c_values``.

    Pure, jit-compatible, differentiable (custom vjp) — the traced entry
    point of the jax backend.  Memoized on the plan, so repeated calls hit
    one trace cache; guarded plans raise the capability error.
    """
    memo = plan._stream_memo
    if "jax_fn" not in memo:
        contract = _contract(plan)
        memo["jax_fn"] = bind_indices(contract, memo["device"].indices)
    return memo["jax_fn"]


def table_fn(plan):
    """The execute path's jitted ``f(table) -> c_values`` over one value
    table (:func:`pack_table`): both gathers read the one entry parameter
    that XLA's cross-program prefetch puts in VMEM.  Memoized on the plan;
    guarded plans raise the capability error."""
    memo = plan._stream_memo
    if "jax_fn_table" not in memo:
        run = _one_table(_contract(plan), int(plan.a.col_ptr[-1]))
        memo["jax_fn_table"] = bind_indices(run, memo["device"].indices)
    return memo["jax_fn_table"]


def table_form(nnz_a: int, nnz_b: int, n_products: int,
               itemsize: int) -> str:
    """The execute path's value-table form for concrete operands.

    ``"one"`` where the two-table form would leave an operand's gather
    reading HBM and one table of both fits the cross-program prefetch
    (:func:`runtime.prefetch_limits`); else ``"two"``: where both tables
    reach VMEM anyway, one table only adds a host copy and a slower
    dispatch (measured in PERF.md §6), and where one table would not fit,
    two still keep one operand in VMEM.  Values of another width than the
    limits were measured at keep two tables.  Always ``"one"`` without
    VMEM (CPU), within int32 positions.
    """
    n_values = nnz_a + nnz_b
    if n_values > _I32_MAX:
        return "two"
    lim = runtime.prefetch_limits()
    if lim is None:
        return "one"
    if (itemsize != lim.itemsize
            or n_values * itemsize > lim.cross_program_bytes):
        return "two"
    both_in_vmem = (min(nnz_a, nnz_b) * itemsize <= lim.other_bytes
                    and n_products <= lim.other_products)
    return "two" if both_in_vmem else "one"


@functools.cache
def _table_dtype(a_dtype, b_dtype) -> np.dtype:
    """The dtype of the table packed from these operand dtypes, JAX's
    promotion (memoized: it costs ~10 us, a small replay's dispatch
    ~0.4 ms)."""
    return np.dtype(jnp.result_type(a_dtype, b_dtype))


def pack_table(plan, av, bv):
    """The one value table: A's first nnz values, then B's first nnz.

    Packed outside the executable (an in-program concatenate is not an
    entry parameter, so XLA would not prefetch it): on the host for host
    operands, so a call makes one transfer; on the device when either
    operand is a ``jax.Array``.  Oversized raw value arrays contribute only
    the plan's nnz.
    """
    na, nb = int(plan.a.col_ptr[-1]), int(plan.b.col_ptr[-1])
    if isinstance(av, jax.Array) or isinstance(bv, jax.Array):
        return jnp.concatenate([jnp.asarray(av)[:na], jnp.asarray(bv)[:nb]])
    a, b = np.asarray(av)[:na], np.asarray(bv)[:nb]
    return np.concatenate([a, b], dtype=_table_dtype(a.dtype, b.dtype))


def stream_fn_batched(plan):
    """Vmapped twin of :func:`stream_fn`: ``[B, nnz]`` stacks, one trace.

    ``jit(vmap(contract))`` — the batch axis becomes a leading device axis,
    so the dispatch count is independent of B and a new batch size is a
    shape change (one retrace), never B traces.
    """
    memo = plan._stream_memo
    if "jax_fn_batched" not in memo:
        memo["jax_fn_batched"] = bind_indices(
            _contract(plan), memo["device"].indices, batched=True)
    return memo["jax_fn_batched"]


def _is_traced(*arrays) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in arrays)


def _operand_values(operand):
    """Raw value array of an execute-time operand, namespace-preserving."""
    return operand.values if isinstance(operand, (CSC, BatchedCSC)) \
        else operand


def host_fallback(plan, av, bv, stats: dict | None = None, *,
                  batch: int | None = None):
    """Run a guarded device plan on the host stream engine, loudly.

    A plan whose stream tripped the plan-memory guard has no device index
    arrays; on concrete operands it executes through the host stream
    (transient rebuild), which warns, counts ``host_fallbacks`` in
    ``plan_cache_info()`` and reports ``stats["fallback"] = "host"``.
    Under a trace there is nothing to fall back to: the capability error.
    ``batch`` marks ``[B, nnz]`` stacks.
    """
    if _is_traced(av, bv):
        raise _guard_error(plan)
    from repro.core import api

    api._count_host_fallback()
    warnings.warn(
        f"{plan.backend!r} plan's product stream is above its plan-memory "
        f"guard (stream_limit={plan.stream_limit}); executing on the host "
        "stream engine instead of the device", RuntimeWarning, stacklevel=3)
    if batch is None:
        out = fast.execute_stream(plan, np.asarray(av), np.asarray(bv),
                                  stats=stats)
    else:
        out = fast.execute_stream_batched(
            plan, np.asarray(av)[:, : int(plan.a.col_ptr[-1])],
            np.asarray(bv)[:, : int(plan.b.col_ptr[-1])], stats=stats)
    if stats is not None:
        stats.update(backend=plan.backend, device=False, fallback="host")
        if batch is not None:
            stats["batch"] = batch
    return out


def _nbytes(x) -> int:
    """Bytes of a value operand: numpy, device array, tracer or list."""
    dtype = x.dtype if hasattr(x, "dtype") else np.asarray(x).dtype
    return int(np.size(x)) * np.dtype(dtype).itemsize


def _call(plan, memo_key: str, make_fn, table: str, av, bv):
    """``make_fn(plan)`` on the values as the span ``spgemm.first_call``
    when the plan's jitted function is new (trace, lowering, compile or
    executable load, first dispatch), else ``spgemm.dispatch`` (packing,
    value transfer and enqueue).  ``table`` is the function's form:
    ``"one"`` packs ``av`` and ``bv`` into one table first.  The span
    carries ``table`` and the ``table_bytes`` the executable reads."""
    first = memo_key not in plan._stream_memo
    fn = make_fn(plan)
    with spans.span("spgemm.first_call" if first else "spgemm.dispatch") \
            as span:
        values = (pack_table(plan, av, bv),) if table == "one" else (av, bv)
        if span is not spans.NULL:
            span.set(table=table,
                     table_bytes=sum(_nbytes(x) for x in values))
        return fn(*values)


#: the execute path's jitted function of each form: memo key, maker
_FORMS = {"one": ("jax_fn_table", table_fn), "two": ("jax_fn", stream_fn)}


def _concrete(x):
    return x if isinstance(x, jax.Array) else np.asarray(x)


def execute_jax(plan, a_values, b_values, *, stats: dict | None = None,
                validate: str | None = None) -> CSC:
    """Numeric phase of a jax-backend plan (executor dispatch target).

    Returns a CSC whose values are a device array on the plan's canonical
    stream structure.  Concrete operands run :func:`table_fn` on one packed
    value table when :func:`table_form` allows, else :func:`stream_fn`;
    traced ones run :func:`stream_fn`.  Guarded plans (``plan.stream is
    None``) run :func:`host_fallback`.
    """
    plan.a.check_compatible(a_values, validate)
    plan.b.check_compatible(b_values, validate)
    av = _operand_values(a_values)
    bv = _operand_values(b_values)
    if plan.stream is None:
        return host_fallback(plan, av, bv, stats)
    if _is_traced(av, bv):
        form = "two"      # inside a caller's program: no entry parameter
    else:
        av, bv = _concrete(av), _concrete(bv)
        form = table_form(int(plan.a.col_ptr[-1]), int(plan.b.col_ptr[-1]),
                          plan.stream.n_products,
                          _table_dtype(av.dtype, bv.dtype).itemsize)
    memo_key, make_fn = _FORMS[form]
    vals = _call(plan, memo_key, make_fn, form, av, bv)
    s = plan.stream
    if stats is not None:
        stats.update(engine="stream", backend="jax", device=True,
                     fallback=None, stream_products=s.n_products,
                     result_shape=s.shape)
    return CSC(vals, s.c_rows, s.c_col_ptr, s.shape)


def _batched_operand(pattern, operand, validate):
    """[B, nnz] value stack of a batched operand, tracer- and device-safe
    (validation shared with the host paths via the Pattern contract; the
    values keep their namespace — no ``np.asarray`` materialization)."""
    pattern.check_batched_compatible(operand, validate)
    return operand.values if isinstance(operand, BatchedCSC) else operand


def execute_jax_batched(plan, a_values, b_values, *,
                        stats: dict | None = None,
                        validate: str | None = None) -> list:
    """Batched numeric phase: B value sets through one vmapped dispatch."""
    from repro.core.executor import _check_batch   # lazy: executor imports us

    av = _batched_operand(plan.a, a_values, validate)
    bv = _batched_operand(plan.b, b_values, validate)
    batch = _check_batch(av, bv)
    if plan.stream is None:
        return host_fallback(plan, av, bv, stats, batch=batch)
    vals = _call(plan, "jax_fn_batched", stream_fn_batched, "two", av, bv)
    s = plan.stream
    if stats is not None:
        stats.update(engine="stream", backend="jax", device=True,
                     fallback=None, path="vmap", batch=batch,
                     stream_products=s.n_products, result_shape=s.shape)
    return [CSC(vals[b], s.c_rows, s.c_col_ptr, s.shape)
            for b in range(batch)]
