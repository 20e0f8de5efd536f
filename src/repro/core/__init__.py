"""Core: the paper's SpGEMM algorithms and pre-processing analysis."""

from repro.core.analysis import (
    VL_MAX,
    N_LANES,
    HASH_C,
    BlockSchedule,
    Preprocess,
    blocking_schedule,
    hash_table_size,
    hybrid_split,
    preprocess,
    sort_columns,
)
from repro.core.expand import expand_products, product_col_ptr, spgemm_expand
from repro.core.naive import (
    esc_numpy,
    hash_numpy,
    hybrid_numpy,
    spa_numpy,
    spars_numpy,
)
from repro.core.reference import dense_product, spgemm_dense
from repro.core.cost import (
    AUTO_CANDIDATES,
    CostConstants,
    choose_method,
    estimate_cost,
    estimate_mesh_cost,
    should_distribute,
)
from repro.core.planner import (
    SpgemmPlan,
    TiledSpgemmPlan,
    pattern_fingerprint,
    plan_spgemm,
    plan_spgemm_tiled,
)
# NOTE: the mutable guard knob fast.STREAM_MAX_PRODUCTS is deliberately not
# re-exported by value — read/set it on repro.core.fast so changes take
# effect (planner/cost read it live)
from repro.core.fast import ProductStream, build_product_stream
# NOTE: backends.register_backend stays module-private — registering a
# contract alone does not wire executors/candidates, so it is not a public
# extension point (see core/backends.py)
from repro.core.backends import ExecutionContract, backend_names, get_backend
from repro.core.jax_stream import (
    DeviceStream,
    bilinear_custom_vjp,
    device_stream,
    stream_fn,
)
from repro.core.executor import execute as execute_plan
from repro.core.executor import execute_batched as execute_plan_batched
from repro.core.executor import execute_tiled, execute_tiled_batched
from repro.core.executor import resolve_engine
from repro.core.api import (
    ALGORITHMS,
    PlanBuildTimeout,
    cached_plan,
    plan_cache_clear,
    plan_cache_info,
    plan_cache_key,
    plan_cache_peek,
    plan_cache_resize,
    register_eviction_listener,
    spgemm,
    spgemm_batched,
    unregister_eviction_listener,
)
from repro.core.profile import (
    MachineProfile,
    calibrate_profile,
    current_profile,
    fingerprint_key,
    load_profile,
    machine_fingerprint,
    rank_correlation,
    save_profile,
)
from repro.core.faults import FaultPlan, FaultRule, InjectedFault
from repro.core.plan_builder import (
    BuildCancelled,
    BuildResult,
    BuildShed,
    BuildTimeoutError,
    PlanBuilder,
    RetryPolicy,
    warm_plan,
)

__all__ = [
    "VL_MAX",
    "N_LANES",
    "HASH_C",
    "BlockSchedule",
    "Preprocess",
    "blocking_schedule",
    "hash_table_size",
    "hybrid_split",
    "preprocess",
    "sort_columns",
    "expand_products",
    "product_col_ptr",
    "spgemm_expand",
    "esc_numpy",
    "hash_numpy",
    "hybrid_numpy",
    "spa_numpy",
    "spars_numpy",
    "dense_product",
    "spgemm_dense",
    "SpgemmPlan",
    "TiledSpgemmPlan",
    "pattern_fingerprint",
    "plan_spgemm",
    "plan_spgemm_tiled",
    "execute_plan",
    "execute_plan_batched",
    "execute_tiled",
    "execute_tiled_batched",
    "ProductStream",
    "build_product_stream",
    "ExecutionContract",
    "backend_names",
    "get_backend",
    "DeviceStream",
    "bilinear_custom_vjp",
    "device_stream",
    "stream_fn",
    "resolve_engine",
    "cached_plan",
    "plan_cache_clear",
    "plan_cache_info",
    "plan_cache_key",
    "plan_cache_peek",
    "plan_cache_resize",
    "register_eviction_listener",
    "unregister_eviction_listener",
    "PlanBuildTimeout",
    "FaultPlan",
    "FaultRule",
    "InjectedFault",
    "BuildCancelled",
    "BuildResult",
    "BuildShed",
    "BuildTimeoutError",
    "PlanBuilder",
    "RetryPolicy",
    "warm_plan",
    "spgemm",
    "spgemm_batched",
    "ALGORITHMS",
    "AUTO_CANDIDATES",
    "CostConstants",
    "choose_method",
    "estimate_cost",
    "estimate_mesh_cost",
    "should_distribute",
    "MachineProfile",
    "calibrate_profile",
    "current_profile",
    "fingerprint_key",
    "load_profile",
    "machine_fingerprint",
    "rank_correlation",
    "save_profile",
]
