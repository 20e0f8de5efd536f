"""Plan-reuse benchmark: amortized symbolic pre-processing (DESIGN.md §6).

Splits each SpGEMM call into its two phases and measures the per-call *host
overhead* — everything that is not numeric work — with and without a cached
:class:`SpgemmPlan`:

  t_plan     plan_spgemm from scratch: Op_j analysis, sort, blocking, hash
             sizing, padded layouts.  This is the overhead an uncached call
             pays every time.
  t_bind     re-executing a cached plan: bind new values to the planned
             patterns (``plan.execute``'s only non-numeric work).
  t_fetch    the transparent ``spgemm()`` LRU path: fingerprint both
             operands + cache lookup (context; in between the two).
  t_exec     numeric phase, paid either way.

PASS criterion (ISSUE 1): per-call host overhead of a cached plan is >= 2x
lower than planning from scratch, i.e. ``t_plan / t_bind >= 2``.

    PYTHONPATH=src python benchmarks/plan_reuse.py [--n 4000] [--reps 5]
"""

from __future__ import annotations

import argparse
import sys

sys.path.insert(0, "src")

import numpy as np

from _util import median_time
from repro.core import plan_spgemm, spgemm
from repro.core.api import _cached_plan, plan_cache_clear, resolve_params
from repro.sparse import random_powerlaw_csc


def bench_overhead(a, method, backend, reps, header=False):
    """Symbolic-phase cost vs cached-plan per-call cost (no numeric work)."""
    if header:
        print(f"{'method':16s} {'back':6s} "
              f"{'t_plan':>9s} {'t_bind':>9s} {'t_fetch':>9s} "
              f"{'overhead':>9s}   (ms)")
    kw = dict(block_cols=128) if backend == "pallas" else {}
    t_plan = median_time(
        lambda: plan_spgemm(a, a, method, backend=backend, **kw), reps)
    plan = plan_spgemm(a, a, method, backend=backend, **kw)
    vals = np.asarray(a.values)
    t_bind = median_time(
        lambda: (plan.a.with_values(vals), plan.b.with_values(vals)), reps)
    params = resolve_params(method)
    plan_cache_clear()
    _cached_plan(a, a, method, backend, params)  # warm the LRU
    t_fetch = median_time(
        lambda: _cached_plan(a, a, method, backend, params), reps)
    ratio = t_plan / max(t_bind, 1e-9)
    print(f"{method:16s} {backend:6s} "
          f"{t_plan*1e3:9.3f} {t_bind*1e3:9.3f} {t_fetch*1e3:9.3f} "
          f"{ratio:8.0f}x")
    return ratio


def bench_end_to_end(a, method, backend, reps, header=False):
    """Fresh spgemm vs held-plan execute vs LRU-cached spgemm, wall time."""
    if header:
        print(f"\n{'method':16s} {'back':6s} "
              f"{'t_fresh':>9s} {'t_reuse':>9s} {'t_lru':>9s}   (ms)")
    plan = plan_spgemm(a, a, method, backend=backend)
    t_fresh = median_time(
        lambda: spgemm(a, a, method=method, backend=backend, cache=False),
        reps)
    t_reuse = median_time(lambda: plan.execute(a, a), reps)
    t_lru = median_time(
        lambda: spgemm(a, a, method=method, backend=backend), reps)
    print(f"{method:16s} {backend:6s} "
          f"{t_fresh*1e3:9.3f} {t_reuse*1e3:9.3f} {t_lru*1e3:9.3f}")


def main():
    from repro import runtime

    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4000,
                    help="pattern size for the overhead measurement")
    ap.add_argument("--n-e2e", type=int, default=192,
                    help="matrix size for end-to-end context numbers (the "
                         "faithful executors are slow by design)")
    ap.add_argument("--avg", type=float, default=4.0)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    big = random_powerlaw_csc(args.n, args.avg, seed=0)
    small = random_powerlaw_csc(args.n_e2e, args.avg, seed=0)
    print(f"overhead pattern: {args.n}x{args.n}, nnz={big.nnz}")
    ratios = []
    first = True
    for method in ("hash-256/256", "h-hash-256/256", "spars-40/40"):
        ratios.append(bench_overhead(big, method, "host", args.reps,
                                     header=first))
        first = False
    for method in ("h-hash-256/256", "spars-40/40"):
        ratios.append(
            bench_overhead(big, method, "pallas", args.reps))

    print(f"\nend-to-end context ({args.n_e2e}x{args.n_e2e}, "
          f"nnz={small.nnz}):")
    first = True
    for method in ("h-hash-256/256", "spars-40/40"):
        bench_end_to_end(small, method, "host", args.reps, header=first)
        first = False
        bench_end_to_end(small, method, "pallas", args.reps)

    ok = all(r >= 2.0 for r in ratios)
    print(f"\ncached-plan per-call host overhead is "
          f"{min(ratios):.0f}x-{max(ratios):.0f}x lower than planning from "
          f"scratch -> {'PASS (>=2x)' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
