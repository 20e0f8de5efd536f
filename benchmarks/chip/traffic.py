"""The one traffic generator: closed loops with one caller.

A traffic mix is a JSON file under ``traffic/`` whose ``loop`` names one
of the loops below and whose other keys are its parameters.  Every loop
drives the program's public entry points through :class:`Program`, makes
its values from the run's seed, warms up in set-up every program the
window will run, and then calls back to back for ``seconds``: the window
closes at the end of the first call that ends past that time, so it holds
only whole calls.

- ``replay``: plan each matrix once in set-up, then ``plan.execute``
  round-robin over the matrices.  Each call uploads one of
  ``value_sets`` host value arrays of its matrix and is complete when C's
  values are ready on the device.  Every call is a plan hit.
- ``churn``: every call is a plan miss, ``cached_plan`` then
  ``plan.execute`` on a pattern the plan cache does not hold.  The
  patterns are ``variants`` edits of each matrix: a share ``moved`` of its
  entries (at least one) moved to random free rows of the same column.
  The edits are fixed data (drawn from ``EDIT_SEED``), like the patterns
  they edit: every seed runs the same patterns, in an order and with
  values drawn from the seed.  The pool is cycled in that order and is at
  least twice the plan cache's capacity, so a pattern is always evicted
  before it comes round again.  Set-up runs every pattern once through
  the window's own miss path and then drops every plan, which compiles
  each executable (or loads it from the persistent compilation cache);
  a window's miss then pays fingerprint, symbolic phase, index upload,
  trace, lowering and executable load, and compiles nothing.

The mix also names the entry point's ``backend`` and ``method``, so a mix
that runs another lowering of the same loop is a data file too.

Spans (``jax.profiler.TraceAnnotation``) mark the window
(``bench.window``), each call (``bench.call``) and, in ``churn``, the
planner (``bench.plan``) and the execution (``bench.execute``).
"""

from __future__ import annotations

import dataclasses
import random
import time
from collections import defaultdict

import numpy as np

import work

#: JAX's compile-pipeline durations that ``compile_s`` sums
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
#: the last step of getting one executable: compiled, or loaded from the
#: persistent compilation cache
EXECUTABLE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the seed of the churn pool's edits, the same in every run
EDIT_SEED = 0


@dataclasses.dataclass(frozen=True)
class Matrix:
    """A square sparse pattern in CSC form (rows ascending per column)."""

    name: str
    indptr: np.ndarray
    indices: np.ndarray
    n: int

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    @property
    def pattern(self) -> tuple:
        return (self.indptr, self.indices, (self.n, self.n))


class Program:
    """The system under test, as the loops call it: C = A·A through the
    public entry points, on the mix's ``backend`` and ``method``.  Tests
    replace a method to break the timed path."""

    def __init__(self, backend: str, method: str):
        self.backend = backend
        self.method = method

    def operand(self, m: Matrix, values):
        from repro.sparse.format import CSC

        return CSC(values, m.indices, m.indptr, (m.n, m.n))

    def plan(self, a):
        from repro.core import cached_plan

        return cached_plan(a, a, self.method, backend=self.backend)

    def build_stream(self, plan):
        return plan.stream

    def execute(self, plan, values):
        return plan.execute(values, values)

    def plan_cache_info(self) -> dict:
        from repro.core import api

        return api.plan_cache_info()

    def release(self) -> None:
        """Drop every plan the program holds."""
        from repro.core import api

        api.plan_cache_clear()


@dataclasses.dataclass
class Answer:
    """One result kept for the check: which matrix and values it came
    from, and C as the program returned it."""

    matrix: Matrix
    values: np.ndarray
    c_indptr: np.ndarray
    c_indices: np.ndarray
    c_values: object


@dataclasses.dataclass
class Window:
    """What one loop measured."""

    loop: str
    window_start: float                  # perf_counter at the window's start
    window_s: float
    latencies: np.ndarray                # seconds, one per call
    products: np.ndarray                 # scalar products, one per call
    answers: list
    matrices: list                       # the matrices the calls ran on
    call_matrix: np.ndarray              # index into ``matrices``, per call
    plan_s: np.ndarray | None = None     # churn: planner span per call
    compile_s: float = 0.0               # JAX compile pipeline in the window
    executables: int = 0                 # executables compiled or loaded


class CompileClock:
    """Sums JAX's compile-pipeline durations, and counts the executables
    compiled or loaded, while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.total = 0.0
        self.executables = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_):
        if self.on and event in COMPILE_EVENTS:
            self.total += duration
            self.executables += event == EXECUTABLE_EVENT


def _values(rng, nnz: int, value_range) -> np.ndarray:
    lo, hi = value_range
    return rng.uniform(lo, hi, nnz).astype(np.float32)


def _block(c):
    import jax

    jax.block_until_ready(c.values)
    return c


class Reservoir:
    """One answer per key, drawn uniformly from the seed (Algorithm R)."""

    def __init__(self, seed: int):
        self.rng = random.Random(seed)
        self.seen = defaultdict(int)
        self.kept = {}

    def offer(self, key, make):
        self.seen[key] += 1
        if self.rng.random() * self.seen[key] < 1.0:
            self.kept[key] = make()

    def answers(self) -> list:
        return [self.kept[k] for k in sorted(self.kept)]


def replay(matrices, mix: dict, config: dict, seed: int, seconds: float,
           program: Program, compile_clock: CompileClock, tracer) -> Window:
    import jax

    rng = np.random.default_rng(seed)
    n_sets = int(mix["value_sets"])
    vals = [[_values(rng, m.nnz, config["values"]) for _ in range(n_sets)]
            for m in matrices]
    plans = [program.plan(program.operand(m, vals[k][0]))
             for k, m in enumerate(matrices)]
    for k, plan in enumerate(plans):                 # compile or load
        for v in range(min(2, n_sets)):
            _block(program.execute(plan, vals[k][v]))
    prods = [work.products(m.indptr, m.indices) for m in matrices]
    kept = Reservoir(seed)
    n_mat = len(matrices)
    lat, who = [], []
    annotate = jax.profiler.TraceAnnotation
    i = 0
    tracer.start()
    with annotate("bench.window"):
        t0 = time.perf_counter()
        while True:
            k, v = i % n_mat, (i // n_mat) % n_sets
            with annotate("bench.call"):
                ts = time.perf_counter()
                c = _block(program.execute(plans[k], vals[k][v]))
                te = time.perf_counter()
            lat.append(te - ts)
            who.append(k)
            kept.offer((k, v), lambda: Answer(matrices[k], vals[k][v],
                                               c.col_ptr, c.row_indices,
                                               c.values))
            i += 1
            if te - t0 >= seconds:
                break
    tracer.stop()
    who = np.array(who)
    return Window("replay", t0, te - t0, np.array(lat),
                  np.array(prods)[who], kept.answers(), list(matrices), who)


def churn_edit(m: Matrix, moved: float, rng) -> Matrix:
    """``m`` with a share ``moved`` of its entries (at least one) moved to
    random free rows of the same column: column degrees kept."""
    indices = m.indices.copy()
    k = max(1, int(round(moved * m.nnz)))
    cols = np.repeat(np.arange(m.n), np.diff(m.indptr))
    touched = set()
    for p in rng.choice(m.nnz, size=min(k, m.nnz), replace=False):
        lo, hi = m.indptr[cols[p]], m.indptr[cols[p] + 1]
        if hi - lo >= m.n:
            continue                                 # a full column
        taken = set(indices[lo:hi].tolist())
        row = int(rng.integers(m.n))
        while row in taken:
            row = int(rng.integers(m.n))
        indices[p] = row
        touched.add(int(cols[p]))
    for j in touched:
        indices[m.indptr[j]:m.indptr[j + 1]].sort()
    return Matrix(m.name, m.indptr, indices, m.n)


def churn(matrices, mix: dict, config: dict, seed: int, seconds: float,
          program: Program, compile_clock: CompileClock, tracer) -> Window:
    import jax

    edits = np.random.default_rng(EDIT_SEED)
    pool = [churn_edit(m, float(mix["moved"]), edits)
            for _ in range(int(mix["variants"])) for m in matrices]
    capacity = program.plan_cache_info()["max_size"]
    if len(pool) < 2 * capacity:
        raise RuntimeError(
            f"churn pool of {len(pool)} patterns is under twice the plan "
            f"cache's {capacity} entries: calls would hit the cache")
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(pool))
    vals = [_values(rng, m.nnz, config["values"]) for m in pool]

    annotate = jax.profiler.TraceAnnotation

    def miss(k):
        """One plan miss on pattern ``k``: its start, the planner's end,
        its end, and C."""
        a = program.operand(pool[k], vals[k])
        with annotate("bench.call"):
            ts = time.perf_counter()
            with annotate("bench.plan"):
                plan = program.plan(a)
                program.build_stream(plan)
            tp = time.perf_counter()
            with annotate("bench.execute"):
                c = _block(program.execute(plan, vals[k]))
            te = time.perf_counter()
        return ts, tp, te, c

    for k in order:                                  # compile or load
        miss(k)
    program.release()
    prods = [work.products(m.indptr, m.indices) for m in pool]
    kept = Reservoir(seed)
    n_checked = int(mix["checked"])
    lat, plan_s, who = [], [], []
    hits0 = program.plan_cache_info()["hits"]
    compile_clock.total, compile_clock.executables = 0.0, 0
    compile_clock.on = True
    i = 0
    tracer.start()
    with annotate("bench.window"):
        t0 = time.perf_counter()
        while True:
            k = int(order[i % len(pool)])
            ts, tp, te, c = miss(k)
            lat.append(te - ts)
            plan_s.append(tp - ts)
            who.append(k)
            kept.offer(i % n_checked, lambda: Answer(pool[k], vals[k],
                                                     c.col_ptr,
                                                     c.row_indices, c.values))
            i += 1
            if te - t0 >= seconds:
                break
    tracer.stop()
    compile_clock.on = False
    hits = program.plan_cache_info()["hits"] - hits0
    if hits:
        raise RuntimeError(f"{hits} of {i} churn calls hit the plan cache")
    who = np.array(who)
    return Window("churn", t0, te - t0, np.array(lat), np.array(prods)[who],
                  kept.answers(), pool, who, np.array(plan_s),
                  compile_clock.total, compile_clock.executables)


LOOPS = {"replay": replay, "churn": churn}
