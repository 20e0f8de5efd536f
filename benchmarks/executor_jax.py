"""Jitted device-stream vs per-group Pallas launch path (DESIGN.md §10).

Workload: the PR 3 mixed-density multiply, executed in the plan-reuse
regime (symbolic phase held, numeric phase timed).  Three execution shapes
of the same plan-cached contraction are compared:

* **pallas** — the per-group kernel schedule: one ``pallas_call`` per plan
  KernelGroup, launched from a Python loop per execution (interpret mode on
  CPU, as in CI).
* **jax single** — the jitted device stream (``backend="jax"``): the whole
  numeric phase is one compiled XLA dispatch.  The first call pays the
  trace+compile (reported as ``t_warmup``); every later same-shape call
  replays the cached trace — the steady state this benchmark times, with a
  zero-retrace assertion (``_cache_size() == 1`` after all reps).
* **jax vmap B=32** — the batched path: one ``jit(vmap)`` dispatch for the
  whole ``[B, nnz]`` value stack, reported per multiply.

Correctness gates before timings are trusted: both jax paths are checked
against the naive host SPA oracle (f32 tolerance), and the vmapped batch
must be bit-identical to looping the single-call jax path.

PASS criterion (ISSUE 5): the jitted stream's cached-trace steady state is
>= 2x faster than the per-group Pallas launch path, with zero retrace
across the timed reps.

    PYTHONPATH=src python benchmarks/executor_jax.py [--smoke] [--out PATH]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from _util import median_time, write_report
from tiled import mixed_density_pair
from repro.core import jax_stream, plan_spgemm
from repro.sparse.format import csc_to_dense

REQUIRED_SPEEDUP = 2.0


def main():
    from repro import runtime

    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=256)
    ap.add_argument("--n-sparse", type=int, default=992)
    ap.add_argument("--dense-a", type=int, default=32)
    ap.add_argument("--dense-b", type=int, default=32)
    ap.add_argument("--per-dense", type=int, default=24)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="BENCH_jax.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small matrices, B=8, 2 reps)")
    args = ap.parse_args()
    if args.smoke:
        args.m, args.n_sparse = 96, 240
        args.dense_a = args.dense_b = args.per_dense = 16
        args.batch, args.reps = 8, 2

    a, b = mixed_density_pair(args.m, args.n_sparse, args.dense_a,
                              args.dense_b, args.per_dense)
    rng = np.random.default_rng(1)
    av = rng.normal(size=(args.batch, a.nnz)).astype(np.float32)
    bv = rng.normal(size=(args.batch, b.nnz)).astype(np.float32)
    ref = csc_to_dense(plan_spgemm(a, b, "spa").execute(a, b))

    # -- pallas: one kernel launch per plan group, per execution ----------
    pp = plan_spgemm(a, b, "spa", backend="pallas")
    pstats = {}
    cp = pp.execute(a, b, stats=pstats)          # warmup (kernel compiles)
    ok_pallas = np.allclose(csc_to_dense(cp), ref, rtol=1e-4, atol=1e-5)
    t_pallas = median_time(lambda: pp.execute(a, b), args.reps)

    # -- jax: the jitted device stream ------------------------------------
    pj = plan_spgemm(a, b, "expand", backend="jax")
    t0 = time.perf_counter()
    cj = pj.execute(a, b)                        # plan + device stream + trace
    np.asarray(cj.values)
    t_warmup = time.perf_counter() - t0
    ok_jax = np.allclose(csc_to_dense(cj.to_host()), ref,
                         rtol=1e-4, atol=1e-5)
    fn = jax_stream.stream_fn(pj)
    t_jax = median_time(
        lambda: pj.execute(a, b).values.block_until_ready(), args.reps)
    zero_retrace = fn._cache_size() == 1

    # -- jax vmap: B multiplies in one dispatch ---------------------------
    batched = pj.execute_batched(av, bv)
    t_batched = median_time(
        lambda: pj.execute_batched(av, bv)[-1].values.block_until_ready(),
        args.reps)
    looped = [pj.execute(av[i], bv[i]) for i in range(args.batch)]
    ok_vmap = all(
        np.array_equal(np.asarray(x.values), np.asarray(y.values))
        for x, y in zip(batched, looped))

    n_groups = pstats.get("n_launches", 0)
    products = pj.stream.n_products if pj.stream is not None else None
    print(f"mixed-density workload: A {a.shape} nnz={a.nnz}, B {b.shape} "
          f"nnz={b.nnz}, products={products}, pallas groups={n_groups}, "
          f"B={args.batch}, reps={args.reps}\n")
    rows = (
        ("pallas/spa (per-group)", t_pallas, ok_pallas),
        ("jax stream (steady)", t_jax, ok_jax),
        ("jax vmap (per mult)", t_batched / args.batch, ok_vmap),
    )
    for name, t, ok in rows:
        print(f"{name:24s} {t*1e3:10.3f}ms"
              f"{'' if ok else '   !! MISMATCH'}")
    print(f"{'jax warmup (plan+trace)':24s} {t_warmup*1e3:10.3f}ms  "
          f"(once per pattern/shape)")

    speedup = t_pallas / max(t_jax, 1e-9)
    ok = (ok_pallas and ok_jax and ok_vmap and zero_retrace
          and speedup >= REQUIRED_SPEEDUP)
    report = {
        "bench": "executor_jax",
        "config": {"m": args.m, "n_sparse": args.n_sparse,
                   "dense_a": args.dense_a, "dense_b": args.dense_b,
                   "per_dense": args.per_dense, "batch": args.batch,
                   "reps": args.reps, "smoke": args.smoke,
                   "stream_products": products,
                   "pallas_groups": n_groups},
        "results": {
            "t_pallas_ms": t_pallas * 1e3,
            "t_jax_steady_ms": t_jax * 1e3,
            "t_jax_warmup_ms": t_warmup * 1e3,
            "t_vmap_per_mult_ms": t_batched / args.batch * 1e3,
            "zero_retrace": zero_retrace,
            "correct": {"pallas": ok_pallas, "jax": ok_jax,
                        "vmap": ok_vmap},
        },
        "criterion": {
            "baseline": "pallas per-group launch path",
            "required_speedup": REQUIRED_SPEEDUP,
            "measured_speedup": speedup,
            "passed": ok,
        },
    }
    write_report(args.out, report)
    print(f"\ncriterion: jitted stream {speedup:.1f}x vs per-group pallas "
          f"(need >= {REQUIRED_SPEEDUP:.0f}x), zero retrace: "
          f"{zero_retrace} -> {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
