"""Public SpGEMM API: ``spgemm(A, B, method=...)`` over cached plans.

Methods mirror the paper's evaluated algorithms, plus ``method="auto"`` —
the self-tuning entry point (DESIGN.md §8): the operands are sliced into a
2D tile grid and every tile runs the method an analytical cost model picks
for that tile's work profile.  ``backend="host"`` runs the faithful numpy
executors; ``backend="pallas"`` runs the TPU kernels (compiled on a TPU,
interpreted on the CPU — ``repro.runtime``).  Default parameters are the
paper's best settings.

``spgemm`` is a thin wrapper over the plan/execute split (DESIGN.md §6): it
builds — or fetches from a bounded LRU keyed on pattern fingerprints — a
:class:`~repro.core.planner.SpgemmPlan` (or
:class:`~repro.core.planner.TiledSpgemmPlan` for ``"auto"``) and executes
it against the operand values.  Repeated-pattern workloads can also hold a
plan explicitly::

    plan = plan_spgemm(a, b, "h-hash-256/256")
    c1 = plan.execute(a_vals_1, b_vals_1)   # numeric phase only
    c2 = spgemm(a2, b2, plan=plan)          # equivalent spelling

A held plan carries its own method/backend/parameters; passing conflicting
``method=``/``backend=``/``t=``/``b_min=``/``b_max=`` alongside ``plan=``
raises instead of being silently ignored.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict

from repro.core import backends, spans
import repro.core.fast as _fast
from repro.core.cost import AUTO_CANDIDATES
from repro.core.planner import (
    ALGORITHMS,
    SpgemmPlan,
    TiledSpgemmPlan,
    normalize_tile_spec,
    pattern_fingerprint,
    plan_spgemm,
    plan_spgemm_tiled,
    resolve_params,
)
from repro.sparse.format import BatchedCSC, CSC

DEFAULT_METHOD = "h-hash-256/256"

# bounded LRU of plans keyed by (a_fp, b_fp, method, backend, params);
# resize at runtime with plan_cache_resize()
PLAN_CACHE_SIZE = 64
_PLAN_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0, "wasted_builds": 0,
                "listener_errors": 0, "wait_timeouts": 0, "host_fallbacks": 0}

# Default bound (seconds) on how long a synchronous caller may wait on
# ANOTHER thread's in-flight build of the same key before _build_once
# raises PlanBuildTimeout (DESIGN.md §14).  None = wait forever (the
# pre-resilience behavior); cached_plan(build_timeout=...) overrides
# per call.  Owners are never interrupted — only waiters time out.
DEFAULT_BUILD_TIMEOUT: float | None = None


class PlanBuildTimeout(TimeoutError):
    """A single-flight waiter outlived its deadline on another thread's
    in-flight build (the build itself may still complete later)."""

# keys inserted but never since hit: evicting one of these means the build
# was pure waste (typically plan_cache_resize() shrinking below the number
# of in-flight PlanBuilder builds — the build completed into a cache too
# small to hold it).  Surfaced as the "wasted_builds" counter.
_NEVER_HIT: set = set()

# callables fn(keys, reason) notified after evictions caused by an explicit
# plan_cache_resize() shrink (reason="resize"), *outside* the cache lock.
# Capacity-pressure evictions do not notify — re-warming those would fight
# the LRU.  Registered by PlanBuilder.enable_rewarm().
_EVICTION_LISTENERS: list = []

# Weak references to live PlanBuilders: plan_cache_info() surfaces their
# queue-depth / retry / recycle counters next to the cache telemetry, so
# one probe reads the whole pipeline's health (DESIGN.md §14).
_BUILDERS: "list[weakref.ref]" = []


def _register_builder(builder) -> None:
    with _CACHE_LOCK:
        _BUILDERS[:] = [r for r in _BUILDERS if r() is not None]
        _BUILDERS.append(weakref.ref(builder))


def _unregister_builder(builder) -> None:
    with _CACHE_LOCK:
        _BUILDERS[:] = [r for r in _BUILDERS
                        if r() is not None and r() is not builder]

# The LRU locking contract (DESIGN.md §12): every read or write of
# _PLAN_CACHE/_CACHE_STATS holds _CACHE_LOCK — required since the
# background plan builder (core/plan_builder.py) shares the LRU with
# latency-critical serving threads.  Symbolic builds themselves run
# *outside* the lock (they are the expensive part); _BUILDING holds one
# Event per key with a build in flight so concurrent requests for the
# same pattern wait for that build instead of duplicating it
# (single-flight — the "no double-builds" guarantee the hammer test
# asserts).
_CACHE_LOCK = threading.RLock()
_BUILDING: "dict[tuple, threading.Event]" = {}


def plan_cache_clear() -> None:
    """Drop all cached plans and reset hit/miss counters."""
    with _CACHE_LOCK:
        _PLAN_CACHE.clear()
        _NEVER_HIT.clear()
        for k in _CACHE_STATS:
            _CACHE_STATS[k] = 0


def _count_host_fallback() -> None:
    """Count one device-plan execution that ran on the host instead."""
    with _CACHE_LOCK:
        _CACHE_STATS["host_fallbacks"] += 1


def register_eviction_listener(fn) -> None:
    """Register ``fn(keys, reason)`` for post-shrink eviction batches.

    Called *outside* the cache lock after :func:`plan_cache_resize` evicts
    entries (``reason="resize"``); capacity-pressure evictions from normal
    inserts never notify.  Listener exceptions are swallowed — eviction is
    a memory-pressure path and must not fail the resizer.  The standard
    listener is ``PlanBuilder.enable_rewarm()``, which re-queues the
    evicted keys' builds (DESIGN.md §12).
    """
    if fn not in _EVICTION_LISTENERS:
        _EVICTION_LISTENERS.append(fn)


def unregister_eviction_listener(fn) -> None:
    """Remove a listener registered by :func:`register_eviction_listener`."""
    if fn in _EVICTION_LISTENERS:
        _EVICTION_LISTENERS.remove(fn)


def plan_cache_info() -> dict:
    """Current cache occupancy, hit/miss counters, and hit rate.

    ``stream_bytes`` totals the *host* product-stream index data
    materialized by cached plans, including streams held through tiled
    plans' child tile plans (each counted once even when shared) — see
    DESIGN.md §9.  ``device_stream_bytes`` separately totals the
    device-resident index arrays jax-backend plans cache alongside the host
    ones (DESIGN.md §10), and ``fused_stream_bytes`` the fused-kernel
    replay views (padded gather indices + segment metadata,
    ``core.pallas_stream``, DESIGN.md §11) — all three can be resident on
    one plan at once.  The guard bounds each *plan's* stream; the LRU
    bounds entries, but a tiled plan holds one guard-sized stream per
    distinct tile pattern, so watch these numbers (and shrink via
    ``plan_cache_resize`` or a lower guard) when caching large tiled
    workloads.

    ``mesh_stream_bytes`` totals the device-stacked shard-stream index
    arrays held by mesh-backend plans (DESIGN.md §13) on top of their
    children's host/device streams (the children are ordinary jax tile
    plans, counted by the other totals).  ``wasted_builds`` counts evicted
    entries that were never hit after insertion — a build whose result the
    cache could not keep, the signature of :func:`plan_cache_resize`
    shrinking below the number of in-flight ``PlanBuilder`` builds.

    Resilience telemetry (DESIGN.md §14): ``wait_timeouts`` counts
    single-flight waiters that hit their ``build_timeout`` deadline,
    ``listener_errors`` counts eviction-listener exceptions swallowed by
    :func:`plan_cache_resize`, and ``builders`` lists each live
    ``PlanBuilder``'s :meth:`~repro.core.plan_builder.PlanBuilder.info`
    (queue depth, retries, timeouts, recycled workers, backpressure
    policy).  ``host_fallbacks`` counts device-plan executions (jax,
    pallas fused) that ran on the host stream because the plan's stream
    was above its guard.
    """
    with _CACHE_LOCK:
        lookups = _CACHE_STATS["hits"] + _CACHE_STATS["misses"]
        host_seen: dict = {}
        dev_seen: dict = {}
        fused_seen: dict = {}
        mesh_seen: dict = {}
        for p in _PLAN_CACHE.values():
            mesh_seen[id(p)] = getattr(p, "mesh_stream_nbytes", 0)
            for sp in [t.plan for t in getattr(p, "tiles", ())] or [p]:
                host_seen[id(sp)] = getattr(sp, "stream_nbytes", 0)
                dev_seen[id(sp)] = getattr(sp, "device_stream_nbytes", 0)
                fused_seen[id(sp)] = getattr(sp, "fused_stream_nbytes", 0)
        out = dict(_CACHE_STATS, size=len(_PLAN_CACHE),
                   max_size=PLAN_CACHE_SIZE,
                   hit_rate=(_CACHE_STATS["hits"] / lookups
                             if lookups else 0.0),
                   in_flight=len(_BUILDING),
                   stream_bytes=sum(host_seen.values()),
                   device_stream_bytes=sum(dev_seen.values()),
                   fused_stream_bytes=sum(fused_seen.values()),
                   mesh_stream_bytes=sum(mesh_seen.values()))
        refs = list(_BUILDERS)
    # builder.info() takes the builder's own lock — collect outside ours
    builders = []
    for r in refs:
        b = r()
        if b is not None:
            builders.append(b.info())
    out["builders"] = builders
    # cost-profile provenance + telemetry (DESIGN.md §15): which constants
    # ("measured" fit vs "default") the auto plans in this cache were
    # ranked under, how stale the calibration is, and how often auto ran
    # on uncalibrated defaults for device-resident work
    from repro.core import profile

    out["profile"] = profile.profile_info()
    return out


def plan_cache_resize(n: int) -> dict:
    """Set the plan LRU capacity (evicting least-recently-used overflow).

    The supported way to bound plan memory — callers no longer need to
    mutate the ``PLAN_CACHE_SIZE`` module constant.  ``n == 0`` disables
    caching (every insert is immediately evicted).  Returns
    :func:`plan_cache_info` after the resize.
    """
    global PLAN_CACHE_SIZE
    n = int(n)
    if n < 0:
        raise ValueError(f"cache size must be >= 0, got {n}")
    evicted: list = []
    with _CACHE_LOCK:
        PLAN_CACHE_SIZE = n
        while len(_PLAN_CACHE) > PLAN_CACHE_SIZE:
            evicted.append(_evict_locked())
    if evicted:
        # outside the lock: listeners may re-enter the cache (re-warm).
        # One raising listener must not starve the rest or propagate into
        # the resizing caller — count it and continue.
        for fn in list(_EVICTION_LISTENERS):
            try:
                fn(tuple(evicted), "resize")
            except Exception:
                with _CACHE_LOCK:
                    _CACHE_STATS["listener_errors"] += 1
    return plan_cache_info()


def _evict_locked():
    """Pop the LRU head (lock held); accounts eviction + waste, returns key."""
    key, _ = _PLAN_CACHE.popitem(last=False)
    _CACHE_STATS["evictions"] += 1
    if key in _NEVER_HIT:
        _NEVER_HIT.discard(key)
        _CACHE_STATS["wasted_builds"] += 1
    return key


def _cache_get(key):
    with _CACHE_LOCK:
        plan = _PLAN_CACHE.get(key)
        if plan is not None:
            _PLAN_CACHE.move_to_end(key)
            _CACHE_STATS["hits"] += 1
            _NEVER_HIT.discard(key)
            return plan
        _CACHE_STATS["misses"] += 1
        return None


def _cache_put(key, plan):
    with _CACHE_LOCK:
        _PLAN_CACHE[key] = plan
        _NEVER_HIT.add(key)
        while len(_PLAN_CACHE) > PLAN_CACHE_SIZE:
            _evict_locked()


def plan_cache_peek(key):
    """Non-mutating cache lookup: no LRU promotion, no counter updates.

    The latency-critical probe (DESIGN.md §12): a serving tick asks "is the
    device plan for this pattern already built?" without perturbing the
    eviction order or the hit/miss telemetry.  ``key`` comes from
    :func:`plan_cache_key`.  Returns the plan or ``None``.
    """
    with _CACHE_LOCK:
        return _PLAN_CACHE.get(key)


def _build_once(key, build, timeout: float | None = None, span=spans.NULL):
    """Fetch ``key`` from the LRU, or run ``build()`` exactly once.

    Single-flight across threads: the first requester of a missing key
    becomes the owner and runs the (expensive, unlocked) symbolic build;
    concurrent requesters for the same key wait on the owner's completion
    event and then take the cache hit, instead of duplicating the build.
    A failed build wakes the waiters, one of which becomes the new owner
    and retries.  With ``PLAN_CACHE_SIZE == 0`` the published entry is
    evicted immediately, so every caller builds — the documented
    cache-disabled semantics.

    ``timeout`` (default :data:`DEFAULT_BUILD_TIMEOUT`) bounds the total
    time a *waiter* blocks on another thread's in-flight build: past it,
    :class:`PlanBuildTimeout` is raised (counted as ``wait_timeouts`` in
    :func:`plan_cache_info`) instead of blocking unboundedly on a doomed
    or wedged owner.  The owner itself runs its build to completion —
    hung *background* builds are the PlanBuilder watchdog's job.
    ``span`` (:mod:`~repro.core.spans`) gets ``hit`` 1 or 0.
    """
    if timeout is None:
        timeout = DEFAULT_BUILD_TIMEOUT
    deadline = None if timeout is None else time.monotonic() + timeout
    while True:
        with _CACHE_LOCK:
            plan = _PLAN_CACHE.get(key)
            if plan is not None:
                _PLAN_CACHE.move_to_end(key)
                _CACHE_STATS["hits"] += 1
                _NEVER_HIT.discard(key)
                span.set(hit=1)
                return plan
            done = _BUILDING.get(key)
            owner = done is None
            if owner:
                done = _BUILDING[key] = threading.Event()
                _CACHE_STATS["misses"] += 1
        if owner:
            span.set(hit=0)
            try:
                plan = build()
                _cache_put(key, plan)
            finally:
                with _CACHE_LOCK:
                    _BUILDING.pop(key, None)
                done.set()
            return plan
        remaining = None if deadline is None else deadline - time.monotonic()
        if (remaining is not None and remaining <= 0) \
                or not done.wait(remaining):
            with _CACHE_LOCK:
                _CACHE_STATS["wait_timeouts"] += 1
            raise PlanBuildTimeout(
                f"waited {timeout:.3f}s on another thread's in-flight "
                f"build of plan key {key[2:4]}; the build may still land "
                "later — retry, or serve a fallback plan")


def _single_plan_key(a: CSC, b: CSC, method: str, backend: str,
                     params: dict,
                     stream_limit: int | None = None) -> tuple:
    # for stream-capable plans (host, jax) the stream guard is part of the
    # key: plans resolve it at build time, so changing
    # fast.STREAM_MAX_PRODUCTS must not hand back plans built under the old
    # budget (an explicit per-plan stream_limit= keys on its own value).
    # Pallas plans carry no stream (stream_limit=None), so the knob must
    # not invalidate them.
    contract = backends.get_backend(backend)
    if not contract.carries_stream:
        limit = None
    elif stream_limit is not None:
        limit = int(stream_limit)
    else:
        limit = _fast.default_stream_limit(contract.device_resident)
    return (pattern_fingerprint(a), pattern_fingerprint(b), method, backend,
            tuple(sorted(params.items())), limit)


def _canonical(method: str, backend: str, params: dict) -> tuple:
    """``(method, params)`` as ``backend`` plans them.  Method spellings
    collapse on a canonical-method backend (jax: one stream contraction)
    to the canonical method and its own defaults, so they share one LRU
    entry and a default method's knobs never reach its planner."""
    canonical = backends.get_backend(backend).canonical_method
    if canonical:
        return canonical, resolve_params(canonical)
    return method, params


def plan_cache_key(a: CSC, b: CSC, method: str | None = None, *,
                   backend: str | None = None, t: float | None = None,
                   b_min: int | None = None, b_max: int | None = None,
                   stream_limit: int | None = None,
                   shards: int | None = None) -> tuple:
    """The LRU key :func:`cached_plan` would use for these arguments.

    For non-blocking probes (DESIGN.md §12): compute the key once, then
    :func:`plan_cache_peek` it on the latency path while a background
    :class:`~repro.core.plan_builder.PlanBuilder` owns the build.  Costs
    two pattern fingerprints (O(nnz)), no plan construction.  On
    ``backend="mesh"`` the key carries the mesh shape (``shards``,
    defaulting to the visible device count) and the per-shard guard.
    """
    method, backend = _resolve_method_backend(method, backend)
    _check_shards(backend, shards)
    if method == "auto":
        raise ValueError(
            "plan_cache_key addresses single-method plans; method='auto' "
            "uses the tiled entry points")
    _check_canonical_only(backend, t, b_min, b_max)
    if backend == "mesh":
        return _mesh_plan_key(a, b, shards, None, stream_limit)
    method, params = _canonical(method, backend,
                                resolve_params(method, t=t, b_min=b_min,
                                               b_max=b_max))
    return _single_plan_key(a, b, method, backend, params,
                            stream_limit=stream_limit)


def _cached_plan(a: CSC, b: CSC, method: str, backend: str,
                 params: dict,
                 stream_limit: int | None = None,
                 build_timeout: float | None = None,
                 span=spans.NULL) -> SpgemmPlan:
    method, params = _canonical(method, backend, params)
    key = _single_plan_key(a, b, method, backend, params, stream_limit)
    return _build_once(
        key,
        lambda: plan_spgemm(a, b, method, backend=backend,
                            t=params.get("t"), b_min=params.get("b_min"),
                            b_max=params.get("b_max"),
                            stream_limit=stream_limit),
        timeout=build_timeout, span=span)


def cached_plan(a: CSC, b: CSC, method: str | None = None, *,
                backend: str | None = None, t: float | None = None,
                b_min: int | None = None, b_max: int | None = None,
                stream_limit: int | None = None,
                shards: int | None = None,
                build_timeout: float | None = None) -> SpgemmPlan:
    """Fetch-or-build a plan through the shared LRU (public accessor).

    The plan-holding companion of :func:`spgemm`: out-of-package callers
    (model layers, serving) that want to hold a plan *and* share it with
    the api's cache use this instead of reaching for the private LRU
    internals.  Arguments and defaults mirror :func:`spgemm`
    (``method="auto"`` has its own tiled entry point,
    :func:`~repro.core.planner.plan_spgemm_tiled`); ``stream_limit``
    overrides the plan-memory guard for this plan only (part of the cache
    key), without mutating the global ``fast.STREAM_MAX_PRODUCTS`` knob.
    ``build_timeout`` bounds how long this call may wait on *another*
    thread's in-flight build of the same key (:class:`PlanBuildTimeout`
    past it; default :data:`DEFAULT_BUILD_TIMEOUT`).  The call is the
    span ``spgemm.plan`` (DESIGN.md §16), with ``hit`` 1 or 0.
    """
    method, backend = _resolve_method_backend(method, backend)
    _check_shards(backend, shards)
    if method == "auto":
        raise ValueError(
            "cached_plan builds single-method plans; use plan_spgemm_tiled "
            "for method='auto'")
    _check_canonical_only(backend, t, b_min, b_max)
    with spans.span("spgemm.plan") as span:
        if backend == "mesh":
            return _cached_mesh_plan(a, b, shards, None, stream_limit,
                                     span=span)
        return _cached_plan(a, b, method, backend,
                            resolve_params(method, t=t, b_min=b_min,
                                           b_max=b_max),
                            stream_limit=stream_limit,
                            build_timeout=build_timeout, span=span)


def _cached_tiled_plan(a: CSC, b: CSC, backend: str, tile,
                       candidates) -> TiledSpgemmPlan:
    spec = normalize_tile_spec(tile)
    # resolve the default candidate set before keying, so an explicit
    # candidates= equal to the backend default hits the same entry
    cands = AUTO_CANDIDATES[backend] if candidates is None \
        else tuple(candidates)
    # the cost-profile tag keys the entry too (mirrors
    # TiledSpgemmPlan.cache_key): per-tile picks ranked under a measured
    # calibration must not alias picks ranked under defaults
    from repro.core import profile

    contract = backends.get_backend(backend)
    key = (pattern_fingerprint(a), pattern_fingerprint(b), "auto", backend,
           spec, cands,
           _fast.default_stream_limit(contract.device_resident)
           if contract.carries_stream else None,
           profile.current_profile().tag)
    return _build_once(
        key,
        lambda: plan_spgemm_tiled(a, b, backend=backend, tile=tile,
                                  candidates=cands))


def _mesh_plan_key(a: CSC, b: CSC, shards, tile,
                   stream_limit: int | None = None) -> tuple:
    # the mesh key mirrors _single_plan_key but carries the mesh shape and
    # grid spec in the params slot: plans for different shard counts (or
    # per-shard guards) are different placements and must not alias
    import jax

    from repro.core import profile

    n_shards = len(jax.devices()) if shards is None else int(shards)
    limit = (_fast.default_stream_limit(device=True) if stream_limit is None
             else int(stream_limit))
    # the profile tag rides along for the same reason as in the tiled key:
    # the LPT shard placement is ranked on the profile's constants
    params = (("profile", profile.current_profile().tag),
              ("shard_limit", limit), ("shards", n_shards),
              ("tile", normalize_tile_spec(tile)))
    return (pattern_fingerprint(a), pattern_fingerprint(b), "expand",
            "mesh", params, limit)


def _cached_mesh_plan(a: CSC, b: CSC, shards=None, tile=None,
                      stream_limit: int | None = None, span=spans.NULL):
    key = _mesh_plan_key(a, b, shards, tile, stream_limit)
    n_shards = dict(key[4])["shards"]

    def build():
        from repro.distributed.spgemm_mesh import plan_spgemm_mesh

        return plan_spgemm_mesh(a, b, shards=n_shards, tile=tile,
                                shard_limit=stream_limit)

    return _build_once(key, build, span=span)


def _auto_mesh_plan(a: CSC, b: CSC, shards, tile, candidates, cache):
    """``method="auto"`` on the mesh backend: distribute or stay local.

    The communication-aware cost model (``core.cost.should_distribute``)
    decides: shard when the whole product stream is above the single-device
    guard (a mesh plan lifts it per shard) or when the mesh estimate beats
    the single-device stream outright; otherwise fall back to the ordinary
    single-device jax tile grid, where the per-tile method race still
    applies.
    """
    import jax

    from repro.core.cost import should_distribute
    from repro.sparse.stats import tile_stats

    n_shards = len(jax.devices()) if shards is None else int(shards)
    if should_distribute(tile_stats(a, b), n_shards):
        if cache:
            return _cached_mesh_plan(a, b, n_shards, tile)
        from repro.distributed.spgemm_mesh import plan_spgemm_mesh

        return plan_spgemm_mesh(a, b, shards=n_shards, tile=tile,
                                cache=False)
    if cache:
        return _cached_tiled_plan(a, b, "jax", tile, candidates)
    return plan_spgemm_tiled(a, b, backend="jax", tile=tile,
                             candidates=candidates, cache=False)


def _check_shards(backend, shards) -> None:
    if shards is not None and backend != "mesh":
        raise ValueError(
            f"shards= applies only to backend='mesh', not {backend!r}")


def _check_plan_overrides(plan, method, backend, t, b_min, b_max,
                          tile=None, candidates=None) -> None:
    """Reject ``spgemm(plan=...)`` calls whose explicit arguments conflict
    with what the held plan was built with (held-plan misuse is loud)."""
    own = dict(plan.params)
    conflicts = []
    if method is not None and method != plan.method:
        conflicts.append(f"method={method!r} (plan has {plan.method!r})")
    if backend is not None and backend != plan.backend:
        conflicts.append(f"backend={backend!r} (plan has {plan.backend!r})")
    for name, given in (("t", t), ("b_min", b_min), ("b_max", b_max)):
        if given is None:
            continue
        if name not in own or own[name] != given:
            have = own.get(name, "<unset>")
            conflicts.append(f"{name}={given!r} (plan has {have})")
    if tile is not None:
        spec = normalize_tile_spec(tile)
        if own.get("tile") != spec:
            conflicts.append(
                f"tile={tile!r} (plan has {own.get('tile', '<unset>')})")
    if candidates is not None and own.get("candidates") != tuple(candidates):
        conflicts.append(
            f"candidates={tuple(candidates)!r} "
            f"(plan has {own.get('candidates', '<unset>')})")
    if conflicts:
        raise ValueError(
            "arguments conflict with the held plan (a plan carries its own "
            "method/backend/parameters): " + "; ".join(conflicts))


def _resolve_method_backend(method, backend):
    method = DEFAULT_METHOD if method is None else method
    backend = "host" if backend is None else backend
    if method != "auto" and method not in ALGORITHMS:
        raise ValueError(
            f"unknown method {method!r}; one of {list(ALGORITHMS)} or 'auto'")
    backends.get_backend(backend)   # canonical unknown-backend error
    return method, backend


def _check_auto_only(method, t, b_min, b_max, tile, candidates):
    """Arguments specific to one mode must not be passed with the other."""
    if method != "auto" and (tile is not None or candidates is not None):
        raise ValueError(
            "tile=/candidates= only apply to method='auto' "
            f"(got method={method!r})")
    if method == "auto" and (t is not None or b_min is not None
                             or b_max is not None):
        raise ValueError(
            "t/b_min/b_max do not apply to method='auto' (per-tile methods "
            "use their own defaults; restrict candidates= instead)")


def _check_canonical_only(backend, t, b_min, b_max):
    backends.check_method_knobs(backends.get_backend(backend),
                                t, b_min, b_max)


def spgemm(
    a: CSC,
    b: CSC,
    method: str | None = None,
    *,
    backend: str | None = None,
    t: float | None = None,
    b_min: int | None = None,
    b_max: int | None = None,
    tile=None,
    candidates: tuple | None = None,
    plan=None,
    cache: bool = True,
    validate: str | None = None,
    engine: str | None = None,
    shards: int | None = None,
) -> CSC:
    """Compute C = A @ B with one of the paper's algorithms, or ``"auto"``.

    The default method is ``"h-hash-256/256"`` (the paper's best overall).
    Overriding t/b_min/b_max customizes the named method's defaults.
    ``method="auto"`` builds a :class:`~repro.core.planner.TiledSpgemmPlan`:
    the operands are tiled (grid auto-sized from nnz, or set with ``tile=``)
    and each tile runs the candidate method the cost model predicts cheapest
    (DESIGN.md §8).  With ``plan`` the symbolic phase is skipped outright —
    the plan carries its own method/backend/parameters, and explicitly
    passing any that conflict raises.  With ``cache=False`` the plan is
    rebuilt from scratch, bypassing the LRU.  ``validate="fingerprint"``
    re-hashes the operand structure against the plan (O(nnz)) instead of
    the default O(1) shape/nnz check.

    ``engine`` selects the host numeric engine (DESIGN.md §9):
    ``"stream"`` replays the plan's vectorized product stream (canonical
    output order, fp re-association vs the oracles), ``"naive"`` forces the
    faithful per-method executor, ``None`` uses the method's default
    (``"stream"`` for ``expand``, ``"naive"`` otherwise).  Engine choice is
    per *execution*, not baked into the plan, so it never conflicts with
    ``plan=``.

    ``backend="mesh"`` distributes across devices (DESIGN.md §13):
    ``shards`` sets the mesh size (default: all visible devices) and the
    plan-memory guard applies per shard.  With ``method="auto"`` the
    communication-aware cost model decides whether to distribute at all,
    falling back to the single-device jax tile grid when sharding is
    predicted to lose.
    """
    if plan is not None:
        _check_plan_overrides(plan, method, backend, t, b_min, b_max,
                              tile, candidates)
        return plan.execute(a, b, validate=validate, engine=engine)
    method, backend = _resolve_method_backend(method, backend)
    _check_shards(backend, shards)
    _check_auto_only(method, t, b_min, b_max, tile, candidates)
    _check_canonical_only(backend, t, b_min, b_max)
    if backend == "mesh":
        if method == "auto":
            p = _auto_mesh_plan(a, b, shards, tile, candidates, cache)
        elif cache:
            p = _cached_mesh_plan(a, b, shards)
        else:
            p = plan_spgemm(a, b, method, backend="mesh", shards=shards)
        return p.execute(a, b, validate=validate, engine=engine)
    if method == "auto":
        if cache:
            p = _cached_tiled_plan(a, b, backend, tile, candidates)
        else:
            p = plan_spgemm_tiled(a, b, backend=backend, tile=tile,
                                  candidates=candidates, cache=False)
        return p.execute(a, b, validate=validate, engine=engine)
    params = resolve_params(method, t=t, b_min=b_min, b_max=b_max)
    if cache:
        p = _cached_plan(a, b, method, backend, params)
    else:
        p = plan_spgemm(a, b, method, backend=backend, t=t,
                        b_min=b_min, b_max=b_max)
    return p.execute(a, b, validate=validate, engine=engine)


def spgemm_batched(
    a: BatchedCSC,
    b: BatchedCSC,
    method: str | None = None,
    *,
    backend: str | None = None,
    t: float | None = None,
    b_min: int | None = None,
    b_max: int | None = None,
    tile=None,
    candidates: tuple | None = None,
    plan=None,
    cache: bool = True,
    validate: str | None = None,
    engine: str | None = None,
    shards: int | None = None,
) -> list:
    """B same-pattern multiplies C_b = A_b @ B_b through one plan execution.

    ``a``/``b`` are :class:`~repro.sparse.format.BatchedCSC` stacks (shared
    sparsity pattern, values ``[B, nnz]``).  The symbolic plan is built — or
    fetched from the same LRU as ``spgemm`` — once for the shared pattern,
    then all B value sets run through one set of kernel launches
    (``plan.execute_batched``, DESIGN.md §7).  ``method="auto"`` rides the
    tiled plan's batched path (§8).  Returns a list of B CSC results,
    bit-identical to calling ``spgemm`` per element.  ``engine`` — as in
    :func:`spgemm` (the stream engine broadcasts over the value axis).

    With ``plan`` the symbolic phase is skipped (conflicting explicit
    arguments raise, as in :func:`spgemm`) and ``a``/``b`` may also be raw
    ``[B, nnz]`` value stacks aligned with the planned patterns.
    """
    if plan is not None:
        _check_plan_overrides(plan, method, backend, t, b_min, b_max,
                              tile, candidates)
        return plan.execute_batched(a, b, validate=validate, engine=engine)
    if not isinstance(a, BatchedCSC) or not isinstance(b, BatchedCSC):
        raise TypeError(
            "spgemm_batched operands must be BatchedCSC (use BatchedCSC"
            ".stack / .from_values, or pass plan= with raw value stacks)")
    if a.batch != b.batch:
        raise ValueError(f"batch mismatch: {a.batch} vs {b.batch}")
    if a.batch < 1:
        raise ValueError("empty batch")
    method, backend = _resolve_method_backend(method, backend)
    _check_shards(backend, shards)
    _check_auto_only(method, t, b_min, b_max, tile, candidates)
    _check_canonical_only(backend, t, b_min, b_max)
    a0, b0 = a.element(0), b.element(0)
    if backend == "mesh":
        if method == "auto":
            p = _auto_mesh_plan(a0, b0, shards, tile, candidates, cache)
        elif cache:
            p = _cached_mesh_plan(a0, b0, shards)
        else:
            p = plan_spgemm(a0, b0, method, backend="mesh", shards=shards)
        return p.execute_batched(a, b, validate=validate, engine=engine)
    if method == "auto":
        if cache:
            p = _cached_tiled_plan(a0, b0, backend, tile, candidates)
        else:
            p = plan_spgemm_tiled(a0, b0, backend=backend, tile=tile,
                                  candidates=candidates, cache=False)
        return p.execute_batched(a, b, validate=validate, engine=engine)
    params = resolve_params(method, t=t, b_min=b_min, b_max=b_max)
    if cache:
        p = _cached_plan(a0, b0, method, backend, params)
    else:
        p = plan_spgemm(a0, b0, method, backend=backend, t=t,
                        b_min=b_min, b_max=b_max)
    return p.execute_batched(a, b, validate=validate, engine=engine)
