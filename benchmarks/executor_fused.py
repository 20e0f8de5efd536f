"""Fused single-launch stream kernel vs per-group Pallas path (DESIGN.md §11).

Workload: the PR 3 mixed-density multiply in the plan-reuse regime
(symbolic phase held, numeric phase timed).  Three execution shapes of the
same plan-cached contraction:

* **pallas per-group** — the original kernel schedule: one ``pallas_call``
  per plan KernelGroup, launched from a Python loop per execution
  (interpret mode on CPU, as in CI).
* **fused single** — ``engine="fused"``: the whole numeric phase is *one*
  ``pallas_call`` over the plan's product stream (gather → multiply →
  segmented window-accumulate inside the kernel).  The first call pays the
  view build + trace (``t_warmup``); every later same-shape call replays
  the cached trace — the steady state this benchmark times, with a
  zero-retrace assertion.
* **fused vmap B=N** — the batched path: one ``jit(vmap)`` dispatch for the
  whole ``[B, nnz]`` value stack, launch count independent of B.

Correctness gates before timings are trusted: both fused paths are checked
against the naive host SPA oracle (f32 tolerance), and the vmapped batch
must be bit-identical to looping the single-call fused path.

The report also carries the fused engine's achieved fraction of the
measured memory-bandwidth bound (``benchmarks/roofline.py`` machinery), so
the artifact states how far the one launch sits from the roofline, not just
how it compares to the per-group schedule.

PASS criterion (ISSUE 6): the fused kernel's cached-trace steady state is
>= 2x faster than the per-group Pallas launch path — in smoke mode too —
with zero retrace across the timed reps.

    PYTHONPATH=src python benchmarks/executor_fused.py [--smoke] [--out PATH]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from _util import median_time, write_report
from roofline import (
    bandwidth_fraction,
    measure_peak_bandwidth,
    stream_bytes_model,
)
from tiled import mixed_density_pair
from repro.core import pallas_stream, plan_spgemm
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
    ap.add_argument("--out", default="BENCH_fused.json")
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

    # -- fused: the whole numeric phase in one launch ----------------------
    # same pallas plan, engine="fused" — the comparison the contract makes
    t0 = time.perf_counter()
    fstats = {}
    cf = pp.execute(a, b, engine="fused", stats=fstats)
    np.asarray(cf.values)                        # views + trace + run
    t_warmup = time.perf_counter() - t0
    ok_fused = np.allclose(csc_to_dense(cf.to_host()), ref,
                           rtol=1e-4, atol=1e-5)
    fn = pallas_stream.fused_fn(pp)
    t_fused = median_time(
        lambda: pp.execute(a, b, engine="fused")
        .values.block_until_ready(), args.reps)
    zero_retrace = fn._cache_size() == 1

    # -- fused vmap: B multiplies in one launch ----------------------------
    batched = pp.execute_batched(av, bv, engine="fused")
    t_batched = median_time(
        lambda: pp.execute_batched(av, bv, engine="fused")[-1]
        .values.block_until_ready(), args.reps)
    looped = [pp.execute(av[i], bv[i], engine="fused")
              for i in range(args.batch)]
    ok_vmap = all(
        np.array_equal(np.asarray(x.values), np.asarray(y.values))
        for x, y in zip(batched, looped))

    # -- roofline fraction of the fused steady state -----------------------
    s = pp.stream
    peak_bw = measure_peak_bandwidth()
    nbytes = stream_bytes_model(s.n_products, a.nnz, b.nnz, s.nnz, 4, 4)
    bw_frac = bandwidth_fraction(nbytes, t_fused, peak_bw)

    n_groups = pstats.get("n_launches", 0)
    print(f"mixed-density workload: A {a.shape} nnz={a.nnz}, B {b.shape} "
          f"nnz={b.nnz}, products={s.n_products}, pallas groups={n_groups} "
          f"-> fused launches={fstats.get('n_launches')}, B={args.batch}, "
          f"reps={args.reps}\n")
    rows = (
        ("pallas/spa (per-group)", t_pallas, ok_pallas),
        ("fused (steady)", t_fused, ok_fused),
        ("fused vmap (per mult)", t_batched / args.batch, ok_vmap),
    )
    for name, t, ok in rows:
        print(f"{name:24s} {t*1e3:10.3f}ms"
              f"{'' if ok else '   !! MISMATCH'}")
    print(f"{'fused warmup (views+trace)':26s} {t_warmup*1e3:8.3f}ms  "
          f"(once per pattern/shape)")
    print(f"{'fused roofline fraction':26s} {bw_frac:8.4f}  "
          f"(of {peak_bw/1e9:.1f} GB/s measured bound; interpret-mode "
          f"emulation on CPU)")

    speedup = t_pallas / max(t_fused, 1e-9)
    ok = (ok_pallas and ok_fused and ok_vmap and zero_retrace
          and speedup >= REQUIRED_SPEEDUP)
    report = {
        "bench": "executor_fused",
        "config": {"m": args.m, "n_sparse": args.n_sparse,
                   "dense_a": args.dense_a, "dense_b": args.dense_b,
                   "per_dense": args.per_dense, "batch": args.batch,
                   "reps": args.reps, "smoke": args.smoke,
                   "stream_products": s.n_products,
                   "pallas_groups": n_groups,
                   "fused_block": fstats.get("fused_block"),
                   "fused_launches": fstats.get("n_launches")},
        "results": {
            "t_pallas_ms": t_pallas * 1e3,
            "t_fused_steady_ms": t_fused * 1e3,
            "t_fused_warmup_ms": t_warmup * 1e3,
            "t_vmap_per_mult_ms": t_batched / args.batch * 1e3,
            "zero_retrace": zero_retrace,
            "roofline": {"peak_bandwidth_gbs": peak_bw / 1e9,
                         "bytes_model": nbytes,
                         "bw_frac": bw_frac},
            "correct": {"pallas": ok_pallas, "fused": ok_fused,
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
    print(f"\ncriterion: fused kernel {speedup:.1f}x vs per-group pallas "
          f"(need >= {REQUIRED_SPEEDUP:.0f}x), zero retrace: "
          f"{zero_retrace} -> {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
