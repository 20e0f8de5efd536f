"""Backend/engine registry: one execution contract per backend (DESIGN.md §10).

Three execution backends host the numeric phase of a cached symbolic plan:

* ``"host"``   — the faithful numpy executors (``engine="naive"``, the
  bit-exact oracles of the paper's algorithms) and the vectorized product
  stream (``engine="stream"``, DESIGN.md §9).
* ``"pallas"`` — the TPU kernel schedule (one launch per plan
  :class:`~repro.core.planner.KernelGroup`, DESIGN.md §2/§6).
* ``"jax"``    — the device-resident stream (``core.jax_stream``,
  DESIGN.md §10): the plan's product stream compiled into a jitted,
  differentiable pure-JAX function.

Rather than each call site string-matching backend names, everything that
needs a capability decision — ``core.api`` argument validation,
``core.planner`` method admission, ``core.executor`` engine resolution, the
cost model's candidate sets, ``kernels.ops`` — consults the
:class:`ExecutionContract` registered here.  Adding a backend means
registering one contract plus its executor pair; no if/elif chain grows.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

#: methods with no Pallas kernel family (host-only executors).  Lives here —
#: not in the planner — because it is a *capability* of the pallas contract.
HOST_ONLY_METHODS = ("esc", "expand")


@dataclasses.dataclass(frozen=True)
class ExecutionContract:
    """Capabilities and engine surface of one execution backend.

    ``engines`` are the accepted ``engine=`` spellings (``None`` always
    means "this backend's default for the plan's method").  The remaining
    flags are the capability matrix DESIGN.md §10 documents: they are what
    callers branch on instead of comparing backend names.
    """

    name: str
    #: engine= spellings valid on this backend's plans (None included)
    engines: Tuple[Optional[str], ...]
    #: engine=None resolution; ``stream_default_methods`` lists the methods
    #: whose default is "stream" instead (host: expand — its naive executor
    #: computes the same contraction, slower)
    default_engine: str
    stream_default_methods: Tuple[str, ...] = ()
    #: methods this backend cannot plan (pallas: the host-only executors)
    excluded_methods: Tuple[str, ...] = ()
    #: one plan execution runs B same-pattern value sets (DESIGN.md §7)
    supports_batched: bool = True
    #: executions can sit inside jax.jit / jax.grad traces (DESIGN.md §10)
    supports_grad: bool = False
    #: the per-method naive oracle executors are reachable (engine="naive")
    bit_exact_oracle: bool = False
    #: numeric phase runs on the accelerator (results carry device arrays)
    device_resident: bool = False
    #: plans carry a product stream (and obey the plan-memory guard)
    carries_stream: bool = False
    #: unit of the backend's cost-model estimates (core/cost.py):
    #: "seconds" (host wall time; comparable across seconds-domain
    #: backends in a mixed tile grid) or "relative" (kernel work units)
    cost_domain: str = "seconds"
    #: when set, every plannable method collapses to this one (jax: the
    #: numeric phase is the method-independent stream contraction, so
    #: distinct method spellings must share one plan/stream, not build
    #: per-spelling duplicates in the LRU)
    canonical_method: Optional[str] = None


_REGISTRY: "dict[str, ExecutionContract]" = {}


def register_backend(contract: ExecutionContract) -> ExecutionContract:
    """Register (or replace) a backend contract; returns it for chaining.

    Module-internal: a contract alone is not a working backend — it must
    also register an executor pair (``core.executor.register_executor``)
    and an ``AUTO_CANDIDATES`` entry (``core.cost``), which is why this is
    not re-exported as a public extension point.
    """
    _REGISTRY[contract.name] = contract
    return contract


def get_backend(name: str) -> ExecutionContract:
    """The contract of ``name``; raises the canonical unknown-backend error."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; one of {backend_names()}") from None


def backend_names() -> list:
    """Registered backend names, registration order."""
    return list(_REGISTRY)


def engine_spellings() -> tuple:
    """Union of every backend's accepted ``engine=`` spellings."""
    seen: list = []
    for c in _REGISTRY.values():
        for e in c.engines:
            if e not in seen:
                seen.append(e)
    return tuple(seen)


def default_engine(contract: ExecutionContract, method: str) -> str:
    """The engine ``engine=None`` resolves to for ``method`` on ``contract``."""
    if method in contract.stream_default_methods:
        return "stream"
    return contract.default_engine


def check_engine(contract: ExecutionContract, engine: Optional[str]) -> None:
    """Validate an ``engine=`` spelling against one backend's contract.

    Unknown spellings raise naming the full spelling union; known spellings
    the backend does not implement raise a capability error (e.g. the
    product stream is a host-backend/jax engine, and the jax backend has no
    naive oracles — ``bit_exact_oracle`` is False there).
    """
    if engine in contract.engines:
        return
    spellings = engine_spellings()
    if engine not in spellings:
        raise ValueError(
            f"unknown engine {engine!r}; one of "
            f"{', '.join(repr(e) for e in spellings)}")
    supported = sorted(
        c.name for c in _REGISTRY.values() if engine in c.engines)
    raise ValueError(
        f"engine={engine!r} is not available on the {contract.name!r} "
        f"backend; a {engine!r} execution needs a "
        f"{'-backend or '.join(supported)}-backend plan")


def check_method_knobs(contract: ExecutionContract, t, b_min, b_max) -> None:
    """Reject explicit oracle-tuning knobs on canonical-method backends.

    On a backend whose methods collapse to one canonical plan (jax), the
    t/b_min/b_max knobs configure executors that never run — loud
    rejection beats silently discarding an explicit argument.  Shared by
    ``core.api`` (cached paths) and ``core.planner.plan_spgemm``.
    """
    if contract.canonical_method and (
            t is not None or b_min is not None or b_max is not None):
        raise ValueError(
            f"t/b_min/b_max do not apply to backend={contract.name!r} "
            "(its numeric phase is the method-independent stream "
            "contraction)")


# ---------------------------------------------------------------------------
# the built-in contracts (DESIGN.md §10 capability matrix)
# ---------------------------------------------------------------------------

HOST = register_backend(ExecutionContract(
    name="host",
    engines=(None, "naive", "stream"),
    default_engine="naive",
    stream_default_methods=("expand",),
    supports_batched=True,
    supports_grad=False,
    bit_exact_oracle=True,
    device_resident=False,
    carries_stream=True,
))

PALLAS = register_backend(ExecutionContract(
    name="pallas",
    # "naive" is a no-op: the per-group kernel schedule.  The plan still
    # carries the pattern's product stream (built lazily: per-group
    # executions never touch it) for the traced entry ``stream_apply``
    engines=(None, "naive"),
    default_engine="naive",
    # the host-only executors have no kernel family, and the "jax" auto
    # candidate (the device stream riding a tile grid) has no pallas lane
    excluded_methods=HOST_ONLY_METHODS + ("jax",),
    supports_batched=True,
    supports_grad=False,
    bit_exact_oracle=False,
    device_resident=True,
    carries_stream=True,
    cost_domain="relative",
))

JAX = register_backend(ExecutionContract(
    name="jax",
    engines=(None, "stream"),
    default_engine="stream",
    supports_batched=True,
    supports_grad=True,
    bit_exact_oracle=False,
    device_resident=True,
    carries_stream=True,
    canonical_method="expand",   # the stream computes expand's contraction
))

MESH = register_backend(ExecutionContract(
    name="mesh",
    # one engine: every device replays its slice of the sharded stream
    # inside a single shard_map, partials reduced by a plan-static
    # psum_scatter over destination bins (DESIGN.md §13).  The per-device
    # replay *is* the jax stream, so the contract mirrors jax — including
    # canonical-method collapse and the bilinear custom_vjp — but the
    # plan-memory guard applies per shard, not to the whole stream.
    engines=(None, "stream"),
    default_engine="stream",
    supports_batched=True,
    supports_grad=True,
    bit_exact_oracle=False,
    device_resident=True,
    carries_stream=True,
    canonical_method="expand",
))
