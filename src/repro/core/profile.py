"""Self-calibrating cost-model profiles (DESIGN.md §15).

Every constant in :mod:`repro.core.cost` used to be a hand-pasted snapshot
of one ``benchmarks/tiled.py --calibrate`` run on one CI container — so
``method="auto"`` on any *other* machine ranked engines with a stale model
(the honest-but-wrong-on-GPU ``jax_base`` of DESIGN.md §10 is the
documented symptom).  This module closes the loop the way the schedtool
exemplar infers LLVM machine models: **measure, fit, persist, predict,
cross-check**.

* :func:`machine_fingerprint` identifies the execution environment (CPU
  model, accelerator kind and count, jax version).  A profile is only ever
  trusted on the fingerprint it was measured on — change the device count
  (``--xla_force_host_platform_device_count``), the platform, or the jax
  version, and the persisted profile is invalidated instead of silently
  reused.
* :func:`calibrate_profile` runs a small synthetic microbenchmark ladder
  per (backend, engine) family — host SPA, the plan-resident product
  stream, the guard-tripped transient rebuild, the jitted device stream,
  and (for the mesh backend) a real
  ``psum_scatter`` payload ladder — and fits each family's
  :class:`~repro.core.cost.CostConstants` terms by weighted least squares.
  It can also *auto-tune* the structural knobs the cost model sits on: the
  plan-memory guard (``fast.STREAM_MAX_PRODUCTS``) and the auto tile-grid
  nnz targets (``sparse.partition``).
* :func:`save_profile` / :func:`load_profile` persist the fit as one JSON
  file per fingerprint under ``REPRO_PROFILE_DIR`` (default
  ``~/.cache/repro-spgemm/profiles``); :func:`current_profile` loads it
  lazily on the first cost-model consult, so ``DEFAULT_CONSTANTS`` is the
  *fallback*, not the truth.  Set ``REPRO_AUTO_CALIBRATE=1`` to run the
  smoke ladder automatically on first use when no profile exists
  (otherwise pre-warm with ``benchmarks/calibrate_profile.py``).

Provenance (``measured`` vs ``default``, fingerprint, age) is stamped into
``plan_cache_info()['profile']``, every ``BENCH_*.json`` ``env`` header,
and the params of every auto plan — a prediction is only as good as the
calibration it came from, so the calibration is always on the record.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import platform
import threading
import time
import warnings
from typing import Optional

import numpy as np

from repro.core.cost import CostConstants, DEFAULT_CONSTANTS

PROFILE_VERSION = 1

#: structural-knob tuning keys a profile may carry (DESIGN.md §15):
#: ``stream_max_products`` -> ``fast.STREAM_MAX_PRODUCTS`` (plan-memory
#: guard), ``tile_n_target``/``tile_k_target`` -> the auto tile-grid nnz
#: targets ``sparse.partition.auto_tile_grid`` sizes from.
TUNING_KEYS = ("stream_max_products", "tile_n_target", "tile_k_target")

_LOCK = threading.RLock()
_STATE: dict = {"profile": None, "loading": False}
_COUNTERS = {"default_auto_uses": 0, "stale_discards": 0, "load_errors": 0,
             "auto_calibrations": 0}
_WARNED: set = set()


# ---------------------------------------------------------------------------
# machine fingerprint
# ---------------------------------------------------------------------------


def _cpu_model() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.processor() or platform.machine()


def machine_fingerprint() -> dict:
    """Identity of the execution environment a profile is valid on.

    Captures everything the measured constants depend on: the host CPU, the
    accelerator platform / device kind / *device count* (a forced
    ``--xla_force_host_platform_device_count`` run is a different machine
    as far as the comm ladder is concerned), and the jax version (compiler
    changes move the constants).  Deliberately excludes anything
    per-process (pid, time, cwd).
    """
    import jax

    devices = jax.devices()
    return {
        "cpu": _cpu_model(),
        "machine": platform.machine(),
        "platform": devices[0].platform if devices else "none",
        "device_kind": devices[0].device_kind if devices else "none",
        "device_count": len(devices),
        "jax": jax.__version__,
        "profile_version": PROFILE_VERSION,
    }


def fingerprint_key(fp: dict | None = None) -> str:
    """Short stable hash of a fingerprint (profile filename stem)."""
    fp = machine_fingerprint() if fp is None else fp
    blob = json.dumps(fp, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def profile_dir() -> str:
    """Where profiles persist: ``$REPRO_PROFILE_DIR`` or the user cache."""
    d = os.environ.get("REPRO_PROFILE_DIR")
    if d:
        return d
    return os.path.join(os.path.expanduser("~"), ".cache", "repro-spgemm",
                        "profiles")


# ---------------------------------------------------------------------------
# the profile object
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MachineProfile:
    """One machine's measured cost model + tuned structural knobs.

    ``fitted`` names the :class:`CostConstants` fields that actually came
    out of this machine's microbenchmark ladder — everything else is the
    ``DEFAULT_CONSTANTS`` fallback riding along (e.g. ``comm_byte`` on a
    single-device host, where no collective moves real payload).
    ``source`` is ``"measured"`` or ``"default"``.
    """

    constants: CostConstants
    fingerprint: dict
    source: str = "default"
    created_at: float = 0.0
    fitted: tuple = ()
    tuning: dict = dataclasses.field(default_factory=dict)
    path: Optional[str] = None

    @property
    def key(self) -> str:
        return fingerprint_key(self.fingerprint)

    @property
    def tag(self) -> str:
        """Provenance token recorded in plan params / cache keys: two
        plans built under different calibrations must never alias."""
        if self.source == "default":
            return "default"
        return f"{self.source}:{self.key}:{int(self.created_at)}"

    def age_seconds(self) -> Optional[float]:
        if not self.created_at:
            return None
        return max(time.time() - self.created_at, 0.0)

    def provenance(self) -> dict:
        """The stamp BENCH ``env`` headers and ``plan_cache_info`` carry."""
        age = self.age_seconds()
        return {
            "source": self.source,
            "fingerprint_key": self.key,
            "fingerprint": dict(self.fingerprint),
            "created_at": self.created_at,
            "age_seconds": None if age is None else round(age, 3),
            "fitted": list(self.fitted),
            "tuning": dict(self.tuning),
            "path": self.path,
        }

    def to_json(self) -> dict:
        return {
            "version": PROFILE_VERSION,
            "fingerprint": dict(self.fingerprint),
            "source": self.source,
            "created_at": self.created_at,
            "fitted": list(self.fitted),
            "tuning": dict(self.tuning),
            "constants": dataclasses.asdict(self.constants),
        }

    @staticmethod
    def from_json(doc: dict, path: str | None = None) -> "MachineProfile":
        known = {f.name for f in dataclasses.fields(CostConstants)}
        vals = {k: float(v) for k, v in doc.get("constants", {}).items()
                if k in known}
        return MachineProfile(
            constants=dataclasses.replace(DEFAULT_CONSTANTS, **vals),
            fingerprint=dict(doc["fingerprint"]),
            source=str(doc.get("source", "measured")),
            created_at=float(doc.get("created_at", 0.0)),
            fitted=tuple(k for k in doc.get("fitted", ()) if k in known),
            tuning={k: v for k, v in doc.get("tuning", {}).items()
                    if k in TUNING_KEYS},
            path=path,
        )


def default_profile() -> MachineProfile:
    """The fallback: hand-tuned ``DEFAULT_CONSTANTS``, no tuning, honest
    ``source="default"`` provenance."""
    return MachineProfile(constants=DEFAULT_CONSTANTS,
                          fingerprint=machine_fingerprint(),
                          source="default")


def save_profile(prof: MachineProfile, directory: str | None = None) -> str:
    """Persist ``prof`` as ``<fingerprint-key>.json`` under ``directory``
    (default :func:`profile_dir`); returns the written path."""
    d = profile_dir() if directory is None else directory
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{prof.key}.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(prof.to_json(), f, indent=2, sort_keys=True)
    os.replace(tmp, path)   # atomic: a concurrent loader never sees a torn file
    return path


def load_profile(directory: str | None = None,
                 path: str | None = None) -> Optional[MachineProfile]:
    """Load the persisted profile for *this* machine, or ``None``.

    Looks for ``<fingerprint-key>.json`` under ``directory`` (default
    :func:`profile_dir`), or reads the explicit ``path``.  A file whose
    stored fingerprint does not match the current machine — the device
    count changed (e.g. a forced host-device run), different platform,
    different jax — is **discarded**, not silently reused: it returns
    ``None`` and counts a ``stale_discards`` in
    ``plan_cache_info()['profile']``.  Unreadable/corrupt files count
    ``load_errors`` and also fall back to ``None``.
    """
    fp = machine_fingerprint()
    if path is None:
        d = profile_dir() if directory is None else directory
        path = os.path.join(d, f"{fingerprint_key(fp)}.json")
        env_file = os.environ.get("REPRO_PROFILE_FILE")
        if env_file:
            path = env_file
        elif not os.path.exists(path):
            return None
    try:
        with open(path) as f:
            doc = json.load(f)
        prof = MachineProfile.from_json(doc, path=path)
    except (OSError, ValueError, KeyError, TypeError):
        with _LOCK:
            _COUNTERS["load_errors"] += 1
        return None
    if prof.fingerprint != fp:
        # the machine changed under the profile — invalidate, do not reuse
        with _LOCK:
            _COUNTERS["stale_discards"] += 1
        _warn_once(
            f"stale:{path}",
            f"persisted cost profile {path} was measured on a different "
            f"machine fingerprint (e.g. device count "
            f"{prof.fingerprint.get('device_count')} vs "
            f"{fp['device_count']}); discarding it and falling back to "
            "DEFAULT_CONSTANTS — re-run benchmarks/calibrate_profile.py")
        return None
    return prof


# ---------------------------------------------------------------------------
# current-profile state (lazy load; the cost model's constant source)
# ---------------------------------------------------------------------------


def current_profile() -> MachineProfile:
    """The profile the cost model consults when no explicit constants are
    passed: the persisted fit for this machine's fingerprint if one exists
    (loaded lazily, once), else :func:`default_profile`.  With
    ``REPRO_AUTO_CALIBRATE=1`` a missing profile triggers the smoke
    calibration ladder on first use (and persists its result)."""
    p = _STATE["profile"]
    if p is not None:
        return p
    with _LOCK:
        if _STATE["profile"] is not None:
            return _STATE["profile"]
        if _STATE["loading"]:
            # re-entrant consult from inside the auto-calibration ladder
            return default_profile()
        _STATE["loading"] = True
        try:
            prof = load_profile()
            if prof is None and os.environ.get(
                    "REPRO_AUTO_CALIBRATE", "0") not in ("", "0"):
                try:
                    prof = calibrate_profile(scale=0.25, reps=2, save=True)
                    _COUNTERS["auto_calibrations"] += 1
                except Exception as e:   # calibration must never take down
                    _warn_once("autocal",  # the caller's multiply
                               f"first-use auto-calibration failed ({e!r}); "
                               "continuing on DEFAULT_CONSTANTS")
            _STATE["profile"] = prof or default_profile()
        finally:
            _STATE["loading"] = False
        return _STATE["profile"]


def set_profile(prof: Optional[MachineProfile]) -> None:
    """Install ``prof`` as the current profile (``None`` resets to the
    unloaded state, so the next consult re-reads disk).  Test/benchmark
    hook — also clears the warn-once dedup so a fresh profile regime
    warns afresh."""
    with _LOCK:
        _STATE["profile"] = prof
        _WARNED.clear()


def reset(counters: bool = True) -> None:
    """Forget the loaded profile (and optionally zero the telemetry
    counters) — used by tests to isolate profile state."""
    with _LOCK:
        _STATE["profile"] = None
        _WARNED.clear()
        if counters:
            for k in _COUNTERS:
                _COUNTERS[k] = 0


def current_constants() -> CostConstants:
    return current_profile().constants


def profile_info() -> dict:
    """Provenance + telemetry block surfaced as
    ``plan_cache_info()['profile']`` and in BENCH ``env`` headers."""
    prof = current_profile()
    out = prof.provenance()
    with _LOCK:
        out.update(_COUNTERS)
    return out


def _warn_once(dedup_key: str, message: str) -> None:
    with _LOCK:
        if dedup_key in _WARNED:
            return
        _WARNED.add(dedup_key)
    warnings.warn(message, RuntimeWarning, stacklevel=3)


def note_default_auto(backend: str, candidates: tuple = ()) -> None:
    """Record that ``method="auto"`` just ranked device-resident engines on
    ``DEFAULT_CONSTANTS`` — the stale-constants trap.  Counts every use in
    ``plan_cache_info()['profile']['default_auto_uses']`` and warns once
    per backend.  Called by the cost model only when the resolved profile
    is the default *and* the ranking involves a device family (the device
    constants are the ones known to be machine-sensitive)."""
    from repro.core import backends

    contract = backends.get_backend(backend)
    if not (contract.device_resident or "jax" in candidates):
        return
    with _LOCK:
        _COUNTERS["default_auto_uses"] += 1
    _warn_once(
        f"default-auto:{backend}",
        f"method='auto' on backend={backend!r} is ranking device engines "
        "with uncalibrated DEFAULT_CONSTANTS (no cost profile persisted "
        f"for this machine fingerprint {fingerprint_key()}); its picks are "
        "a stale snapshot of another machine — run "
        "benchmarks/calibrate_profile.py (or set REPRO_AUTO_CALIBRATE=1) "
        "to measure this machine")


def apply_tuning(prof: MachineProfile | None = None) -> dict:
    """Apply a profile's tuned structural knobs to the live module globals.

    Sets ``fast.STREAM_MAX_PRODUCTS`` from ``prof.tuning`` (the tile
    targets are consulted live by
    ``sparse.partition.auto_tile_grid`` and need no global).  Explicit —
    never run implicitly on load, because mutating the guard re-keys every
    cached stream plan.  Returns ``{knob: value}`` for what was applied.
    """
    import repro.core.fast as fast

    prof = current_profile() if prof is None else prof
    applied = {}
    t = prof.tuning
    if "stream_max_products" in t:
        fast.STREAM_MAX_PRODUCTS = int(t["stream_max_products"])
        applied["stream_max_products"] = fast.STREAM_MAX_PRODUCTS
    return applied


# ---------------------------------------------------------------------------
# rank correlation (the predict-vs-measure cross-check metric)
# ---------------------------------------------------------------------------


def rank_correlation(x, y) -> float:
    """Spearman rank correlation (average ranks for ties, scipy-free).

    The cross-check the whole subsystem is graded on: the cost model only
    has to order candidates correctly, so the fit is validated by how well
    predicted costs *rank* against measured times, not by absolute error.
    """
    x = np.asarray(x, float)
    y = np.asarray(y, float)
    if x.shape != y.shape or x.ndim != 1:
        raise ValueError(f"need equal-length 1-D arrays, got {x.shape} "
                         f"vs {y.shape}")
    if len(x) < 2:
        return 1.0

    def _ranks(v):
        order = np.argsort(v, kind="stable")
        sv = v[order]
        # average rank per tie group
        boundary = np.empty(len(sv), bool)
        boundary[0] = True
        np.not_equal(sv[1:], sv[:-1], out=boundary[1:])
        group = np.cumsum(boundary) - 1
        counts = np.bincount(group)
        firsts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        avg = firsts + (counts - 1) / 2.0
        out = np.empty(len(v))
        out[order] = avg[group]
        return out

    rx, ry = _ranks(x), _ranks(y)
    rx -= rx.mean()
    ry -= ry.mean()
    denom = math.sqrt(float((rx ** 2).sum()) * float((ry ** 2).sum()))
    if denom == 0.0:
        return 1.0
    return float((rx * ry).sum() / denom)


# ---------------------------------------------------------------------------
# fitting (pure: measurement rows in, constants out)
# ---------------------------------------------------------------------------


def fit_fields(fields: tuple, rows, times, floor: float = 1e-12) -> dict:
    """Weighted least squares fit of ``times ~ rows @ coeffs``.

    ``rows[i]`` holds one feature value per field (e.g. ``[1, flops]`` for
    a base+slope family).  Rows are weighted by ``1/t`` so every config
    contributes its *relative* error — without this the largest config
    dominates and the base terms come out meaningless or negative.
    Coefficients are clamped to ``>= floor`` (a cost term is a physical
    duration; a negative fit means the ladder under-determined it).
    """
    a = np.asarray(rows, float)
    t = np.asarray(times, float)
    if a.ndim != 2 or a.shape != (len(t), len(fields)):
        raise ValueError(
            f"rows {a.shape} inconsistent with {len(t)} times / "
            f"{len(fields)} fields")
    w = 1.0 / np.maximum(t, 1e-12)
    coef, *_ = np.linalg.lstsq(a * w[:, None], t * w, rcond=None)
    return {f: float(max(c, floor)) for f, c in zip(fields, coef)}


def fit_constants(sections, base: CostConstants | None = None
                  ) -> tuple[CostConstants, tuple]:
    """Fold per-family measurement sections into one ``CostConstants``.

    ``sections`` is an iterable of ``(fields, rows, times)`` triples (one
    per microbenchmark family, as produced by the measurement ladder or by
    a synthetic-timing test).  Returns the merged constants (unmeasured
    fields keep ``base``'s values) and the tuple of fitted field names.
    """
    base = DEFAULT_CONSTANTS if base is None else base
    fitted: dict = {}
    for fields, rows, times in sections:
        fitted.update(fit_fields(tuple(fields), rows, times))
    return dataclasses.replace(base, **fitted), tuple(sorted(fitted))


# ---------------------------------------------------------------------------
# the synthetic microbenchmark ladder
# ---------------------------------------------------------------------------


def _best_of(fn, reps: int) -> float:
    """Min-of-reps wall time: the de-noised estimate a fit can trust."""
    best = math.inf
    for _ in range(max(int(reps), 1)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _dense_sparse_pair(m: int, n: int, per_col: int, rng):
    """Dense A (every B entry fans out m products) x sparse B — the flop
    ladder's workhorse: flops = nnz_b * m, exactly controllable."""
    from repro.sparse.format import csc_from_dense

    a = csc_from_dense(np.ones((m, m)))
    bd = np.zeros((m, n))
    for j in range(n):
        bd[rng.integers(m, size=min(per_col, m)), j] = 1.0
    return a, csc_from_dense(bd)


def _measure_spa(scale: float, reps: int, rng):
    """Host SPA family: time = spa_col*n + spa_entry*nnz_b + spa_flop*flops.

    Three regimes isolate the three terms (all-empty columns, entry-heavy,
    flop-heavy) plus a mixed row to anchor the joint fit.
    """
    from repro.core.naive import spa_numpy
    from repro.sparse.format import CSC, csc_from_dense

    fields = ("spa_col", "spa_entry", "spa_flop")
    rows, times = [], []

    n = max(int(3000 * scale), 200)
    a0 = csc_from_dense(np.zeros((32, 32)))
    b0 = CSC(np.zeros(0), np.zeros(0, np.int32),
             np.zeros(n + 1, np.int32), (32, n))
    rows.append([n, 0.0, 0.0])
    times.append(_best_of(lambda: spa_numpy(a0, b0), reps))

    k, n = 256, max(int(1500 * scale), 150)
    ad = np.zeros((k, k))
    ad[0, :] = 1.0
    a1 = csc_from_dense(ad)
    bd = np.zeros((k, n))
    for j in range(n):
        bd[rng.integers(k, size=4), j] = 1.0
    b1 = csc_from_dense(bd)
    rows.append([n, b1.nnz, b1.nnz])     # 1 nnz/A-col: flops == nnz_b
    times.append(_best_of(lambda: spa_numpy(a1, b1), reps))

    m = max(int(768 * scale), 192)
    a2, b2 = _dense_sparse_pair(m, 192, 8, rng)
    rows.append([192, b2.nnz, b2.nnz * m])
    times.append(_best_of(lambda: spa_numpy(a2, b2), reps))

    m = max(int(384 * scale), 96)
    a3, b3 = _dense_sparse_pair(m, max(int(600 * scale), 100), 3, rng)
    rows.append([b3.n_cols, b3.nnz, b3.nnz * m])
    times.append(_best_of(lambda: spa_numpy(a3, b3), reps))
    return fields, rows, times


def _stream_ladder(scale: float, rng):
    """(plan, flops) pairs spanning the stream engine's flop range."""
    from repro.core.planner import plan_spgemm

    out = []
    # the near-empty (8, 4, 1) rung pins the base (dispatch) terms of all
    # three stream families, which a flop ladder alone under-determines:
    # an unpinned base fits negative and clamps to the floor
    for m, n, per in ((8, 4, 1), (64, 32, 2), (192, 96, 4),
                      (max(int(512 * scale), 128), 128, 6),
                      (max(int(1024 * scale), 256), 256, 8)):
        a, b = _dense_sparse_pair(m, n, per, rng)
        out.append((plan_spgemm(a, b, "expand", stream_limit=b.nnz * m + 1),
                    a, b, b.nnz * m))
    return out


def _measure_stream(ladder, reps: int):
    """Plan-resident product stream: time = stream_base + stream_prod*P."""
    fields = ("stream_base", "stream_prod")
    rows, times = [], []
    for plan, a, b, flops in ladder:
        plan.execute(a, b, engine="stream")   # warmup: lazy stream build
        rows.append([1.0, flops])
        times.append(_best_of(
            lambda: plan.execute(a, b, engine="stream"), reps))
    return fields, rows, times


def _measure_expand(ladder, reps: int):
    """Guard-tripped transient rebuild: expand_base + expand_prod*P +
    expand_sort*P*log2(P) per call (nothing plan-resident)."""
    from repro.core.expand import spgemm_expand

    fields = ("expand_base", "expand_prod", "expand_sort")
    rows, times = [], []
    for _, a, b, flops in ladder:
        rows.append([1.0, flops, flops * math.log2(max(flops, 2))])
        times.append(_best_of(lambda: spgemm_expand(a, b), reps))
    return fields, rows, times


def _measure_jax(ladder, reps: int):
    """Jitted device stream: jax_base + jax_prod*P, cached-trace steady
    state (block_until_ready — dispatch is async)."""
    from repro.core.planner import plan_spgemm

    fields = ("jax_base", "jax_prod")
    rows, times = [], []
    for _, a, b, flops in ladder:
        plan = plan_spgemm(a, b, "expand", backend="jax",
                           stream_limit=flops + 1)
        plan.execute(a, b).values.block_until_ready()   # lift + trace
        rows.append([1.0, flops])
        times.append(_best_of(
            lambda: plan.execute(a, b).values.block_until_ready(), reps))
    return fields, rows, times


def _measure_comm(scale: float, reps: int):
    """Mesh collective ladder: a real tiled ``psum_scatter`` over growing
    payloads — comm_base + comm_byte * bytes, where a D-device scatter of
    an S-slot f32 axis moves ``4*S*(D-1)/D`` bytes per device
    (DESIGN.md §13's comm model, measured instead of assumed).

    On a single-device mesh no payload crosses any link, so only
    ``comm_base`` (collective dispatch overhead) is measurable —
    ``comm_byte`` keeps its default and is not reported as fitted.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    devices = jax.devices()
    d = len(devices)
    mesh = Mesh(np.asarray(devices), ("shards",))
    fields = ("comm_base", "comm_byte") if d > 1 else ("comm_base",)
    rows, times = [], []
    for s in (int(8e3 * scale) + d, int(1e5 * scale) + d,
              int(5e5 * scale) + d, int(2e6 * scale) + d):
        s = -(-s // d) * d
        fn = jax.jit(jax.shard_map(
            lambda v: jax.lax.psum_scatter(
                v[0], "shards", scatter_dimension=0, tiled=True)[None],
            mesh=mesh,
            in_specs=PartitionSpec("shards", None),
            out_specs=PartitionSpec("shards", None)))
        x = jnp.ones((d, s), jnp.float32)
        fn(x).block_until_ready()
        row = [1.0, 4.0 * s * (d - 1) / d]
        rows.append(row[: len(fields)])
        times.append(_best_of(lambda: fn(x).block_until_ready(), reps))
    return fields, rows, times


# ---------------------------------------------------------------------------
# structural-knob tuning searches
# ---------------------------------------------------------------------------


def _tune_stream_guard() -> int:
    """Plan-memory guard sized from this machine's RAM instead of the
    hardcoded 8M: ~20 plan-resident bytes per product, budgeted at 5% of
    physical memory, clamped to [1M, 64M] products."""
    import repro.core.fast as fast

    try:
        ram = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return fast.DEFAULT_STREAM_MAX_PRODUCTS
    return int(min(max(ram * 0.05 / 20.0, 1_000_000), 64_000_000))


def _tune_tile_targets(constants: CostConstants, scale: float, reps: int,
                       rng) -> tuple[int, int]:
    """Measured argmin over auto tile-grid nnz targets on a small
    mixed-density probe (the §8 workload in miniature).  Each candidate is
    evaluated through the real consumption path: a trial profile carrying
    the candidate targets is installed, the auto plan built under it, and
    its plan-reuse numeric time measured."""
    from repro.core.planner import plan_spgemm_tiled
    from repro.sparse.format import csc_from_dense

    m, n_sparse, dense = 128, max(int(512 * scale), 128), 12
    ad = np.zeros((m, m))
    ad[:, :dense] = rng.uniform(0.5, 1.5, size=(m, dense))
    for j in range(dense, m):
        ad[rng.integers(m, size=2), j] = 1.0
    bd = np.zeros((m, dense + n_sparse))
    for j in range(dense):
        bd[rng.choice(dense, size=dense, replace=False), j] = 1.0
    for j in range(dense, dense + n_sparse):
        bd[dense + rng.integers(m - dense, size=2), j] = 1.0
    a, b = csc_from_dense(ad), csc_from_dense(bd)

    prev = _STATE["profile"]
    best, best_t = None, math.inf
    try:
        for n_target in (2048, 8192, 32768):
            trial = MachineProfile(
                constants=constants, fingerprint=machine_fingerprint(),
                source="measured", created_at=time.time(),
                tuning={"tile_n_target": n_target,
                        "tile_k_target": 16 * n_target})
            set_profile(trial)
            plan = plan_spgemm_tiled(a, b, cache=False, constants=constants)
            plan.execute(a, b)
            t = _best_of(lambda: plan.execute(a, b), reps)
            if t < best_t:
                best, best_t = n_target, t
    finally:
        set_profile(prev)
    return int(best), int(16 * best)


# ---------------------------------------------------------------------------
# the calibration entry point
# ---------------------------------------------------------------------------

SECTIONS = ("spa", "stream", "expand", "jax", "comm")


def calibrate_profile(*, scale: float = 1.0, reps: int = 3,
                      sections: tuple = SECTIONS, tune: bool = True,
                      seed: int = 0, save: bool = False,
                      directory: str | None = None,
                      base: MachineProfile | None = None) -> MachineProfile:
    """Run the microbenchmark ladder, fit constants, optionally persist.

    ``scale`` shrinks ladder sizes (0.25 = the smoke ladder CI runs);
    ``sections`` restricts which (backend, engine) families are
    re-measured — unmeasured fields keep ``base``'s values (default: the
    currently persisted profile if any, else ``DEFAULT_CONSTANTS``), so a
    forced-8-device run can refresh just the ``comm`` ladder into the same
    directory.  ``tune=True`` additionally searches the structural knobs
    (guard, tile targets).  ``save=True`` persists via
    :func:`save_profile` and installs the result as the current profile.
    """
    bad = [s for s in sections if s not in SECTIONS]
    if bad:
        raise ValueError(f"unknown sections {bad}; one of {SECTIONS}")
    rng = np.random.default_rng(seed)
    if base is None:
        base = load_profile(directory=directory) or default_profile()

    measured = []
    ladder = None
    if {"stream", "expand", "jax"} & set(sections):
        ladder = _stream_ladder(scale, rng)
    if "spa" in sections:
        measured.append(_measure_spa(scale, reps, rng))
    if "stream" in sections:
        measured.append(_measure_stream(ladder, reps))
    if "expand" in sections:
        measured.append(_measure_expand(ladder, reps))
    if "jax" in sections:
        measured.append(_measure_jax(ladder, reps))
    if "comm" in sections:
        measured.append(_measure_comm(scale, reps))

    constants, fitted = fit_constants(measured, base=base.constants)
    fitted = tuple(sorted(set(base.fitted) | set(fitted)))

    tuning = dict(base.tuning)
    if tune:
        tuning["stream_max_products"] = _tune_stream_guard()
        if "spa" in sections or "stream" in sections:
            n_t, k_t = _tune_tile_targets(constants, scale, reps, rng)
            tuning["tile_n_target"], tuning["tile_k_target"] = n_t, k_t

    prof = MachineProfile(constants=constants,
                          fingerprint=machine_fingerprint(),
                          source="measured", created_at=time.time(),
                          fitted=fitted, tuning=tuning)
    if save:
        path = save_profile(prof, directory=directory)
        prof = dataclasses.replace(prof, path=path)
        set_profile(prof)
    return prof
