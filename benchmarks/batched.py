"""Batched same-pattern SpGEMM throughput (DESIGN.md §7).

Workload: the pattern-reuse regime — one fixed sparsity pattern, a stream of
B numeric value sets (iterative graph algorithms, per-request masked
weights).  Each (method, backend) pair is measured two ways:

  t_loop     B per-call executions of a cached plan (the pre-batching inner
             loop: B Python round-trips, B sets of kernel launches)
  t_batched  one ``plan.execute_batched`` over ``[B, nnz]`` value stacks
             (one plan traversal; Pallas launches once per group for all B)

and the per-multiply speedup ``t_loop / t_batched`` is recorded to
``BENCH_batched.json`` so later PRs can track the trajectory.  Results are
checked bit-identical between the two paths before timing is trusted.

PASS criterion (ISSUE 2): >= 3x per-multiply throughput at B=32 on the
pattern-reuse workload (host spa — the vectorized value-axis executor).

    PYTHONPATH=src python benchmarks/batched.py [--smoke] [--out PATH]
"""

from __future__ import annotations

import argparse
import sys

sys.path.insert(0, "src")

import numpy as np

from _util import bit_identical, median_time, write_report
from repro.core import plan_spgemm
from repro.sparse import random_powerlaw_csc

REQUIRED_SPEEDUP = 3.0
CRITERION_WORKLOAD = ("spa", "host")   # the vectorized pattern-reuse path


def bench_one(a, method, backend, batch, reps, *, block_cols=None,
              header=False):
    if header:
        print(f"{'method':16s} {'back':6s} {'path':>10s} "
              f"{'t_loop/call':>12s} {'t_batch/call':>13s} {'speedup':>8s}")
    kw = dict(block_cols=block_cols) if block_cols else {}
    plan = plan_spgemm(a, a, method, backend=backend, **kw)
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(batch, a.nnz))

    looped = [plan.execute(vals[b], vals[b]) for b in range(batch)]  # warmup
    stats = {}
    batched = plan.execute_batched(vals, vals, stats=stats)          # warmup
    identical = all(bit_identical(x, y) for x, y in zip(looped, batched))

    t_loop = median_time(
        lambda: [plan.execute(vals[b], vals[b]) for b in range(batch)], reps)
    t_batched = median_time(
        lambda: plan.execute_batched(vals, vals), reps)
    speedup = t_loop / max(t_batched, 1e-12)
    path = stats.get("path", "kernels")
    print(f"{method:16s} {backend:6s} {path:>10s} "
          f"{t_loop/batch*1e3:11.3f}ms {t_batched/batch*1e3:12.3f}ms "
          f"{speedup:7.2f}x {'' if identical else '  !! MISMATCH'}")
    return {
        "method": method,
        "backend": backend,
        "batch": batch,
        "path": path,
        "t_loop_per_call_ms": t_loop / batch * 1e3,
        "t_batched_per_call_ms": t_batched / batch * 1e3,
        "speedup": speedup,
        "bit_identical": identical,
    }


def main():
    from repro import runtime

    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512,
                    help="host-backend pattern size")
    ap.add_argument("--n-pallas", type=int, default=96,
                    help="pallas-backend pattern size (interpret mode)")
    ap.add_argument("--avg", type=float, default=4.0)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="BENCH_batched.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small patterns, B=8, 1 rep)")
    args = ap.parse_args()
    if args.smoke:
        args.n, args.n_pallas, args.batch, args.reps = 128, 32, 8, 1

    host = random_powerlaw_csc(args.n, args.avg, seed=0)
    pallas = random_powerlaw_csc(args.n_pallas, args.avg, seed=0)
    print(f"pattern-reuse workload: host {args.n}x{args.n} nnz={host.nnz}, "
          f"pallas {args.n_pallas}x{args.n_pallas} nnz={pallas.nnz}, "
          f"B={args.batch}, reps={args.reps}\n")

    results = []
    first = True
    for method in ("spa", "expand", "h-hash-256/256"):
        results.append(bench_one(host, method, "host", args.batch, args.reps,
                                 header=first))
        first = False
    for method in ("spa", "h-hash-256/256"):
        results.append(bench_one(pallas, method, "pallas", args.batch,
                                 args.reps, block_cols=32))

    crit = next(r for r in results
                if (r["method"], r["backend"]) == CRITERION_WORKLOAD)
    ok = crit["speedup"] >= REQUIRED_SPEEDUP and all(
        r["bit_identical"] for r in results)
    report = {
        "bench": "batched",
        "config": {"n": args.n, "n_pallas": args.n_pallas, "avg": args.avg,
                   "batch": args.batch, "reps": args.reps,
                   "smoke": args.smoke},
        "results": results,
        "criterion": {
            "workload": f"{CRITERION_WORKLOAD[1]}/{CRITERION_WORKLOAD[0]}",
            "required_speedup": REQUIRED_SPEEDUP,
            "measured_speedup": crit["speedup"],
            "batch": args.batch,
            "passed": ok,
        },
    }
    write_report(args.out, report)
    print(f"criterion: {report['criterion']['workload']} at B={args.batch} "
          f"-> {crit['speedup']:.1f}x (need >= {REQUIRED_SPEEDUP}x) "
          f"{'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
