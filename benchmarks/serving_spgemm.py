"""Serving benchmark: stream-backed SpGEMM under live traffic (DESIGN.md §12).

Part 1 — plan-cache regimes.  A request loop plays the serving tick's plan
protocol (``PlanBuilder.plan_or_fallback``: probe the locked LRU, enqueue a
background device build on a miss, run this request on the synchronous host
stream) against three pattern-reuse regimes:

  hit100   every request's device plan is resident — pure compiled replay.
  mixed    half the pattern pool is pre-warmed, half cold; background
           builds land mid-run and later requests promote to them.
  allmiss  adversarial: the pool is cycled round-robin through an LRU too
           small to hold it, so every probe misses and every insert evicts
           (plan churn).  The builder absorbs the builds (shedding excess
           under ``max_pending``) while every request rides the fallback.

Each regime reports ``ops_per_sec`` and ``p99_latency_us``.  PASS: the
all-miss p99 stays below the measured cost of ONE synchronous device-plan
warm (symbolic build + device lift + XLA compile) — the latency a tick
would pay if a cache miss blocked on its build, i.e. the bug this PR's
tentpole removes.

Part 2 — ServeEngine.  A smoke model with spgemm-overlaid FFNs served
under the async-warm protocol: ticks start on the eager host-stream
fallback, promote to the jitted sparse step when the background warm
lands; reports the tick split and per-phase tick latency.

Part 3 — resilience (DESIGN.md §14).  The all-miss churn regime replayed
twice: fault-free, then under an injected ``FaultPlan`` (10% of device
plan builds fail, 5% of builder tasks hang past the build deadline) with
the resilient builder config (shed-by-key-age backpressure, watchdog
deadline, retry/backoff).  PASS: every request is served (the foreground
fallback path never depends on a background build landing) and the
faulted p99 stays within 3x the fault-free p99.  Writes
BENCH_resilience.json with the fault config stamped into its env header.

    PYTHONPATH=src python benchmarks/serving_spgemm.py [--smoke]

Writes BENCH_serving.json and BENCH_resilience.json.
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from _util import write_report
from repro.core import PlanBuilder, api, cached_plan, faults, warm_plan
from repro.sparse import random_density_csc


def _pct_us(lats, q):
    return float(np.percentile(np.asarray(lats) * 1e6, q))


def measure_sync_warm(n, density, seed=10_000):
    """Cost of one blocking device-plan warm: the latency being hidden."""
    a = random_density_csc(n, n, density, seed=seed)
    b = random_density_csc(n, n, density, seed=seed + 1)
    api.plan_cache_clear()
    t0 = time.perf_counter()
    plan = cached_plan(a, b, "expand", backend="jax")
    warm_plan(plan)
    return time.perf_counter() - t0


def serve_request(builder, a, b):
    """One serving-style SpGEMM request; returns (seconds, status)."""
    t0 = time.perf_counter()
    plan, status = builder.plan_or_fallback(a, b, "expand", backend="jax")
    if status == "ready":
        out = plan.stream_apply(np.asarray(plan_values(a), np.float32),
                                np.asarray(plan_values(b), np.float32))
        out.block_until_ready()
    else:
        plan.execute(a, b, engine="stream")
    return time.perf_counter() - t0, status


def plan_values(mat):
    return np.asarray(mat.values, np.float32)


def run_regime(name, pool, requests, *, cache_size, prewarm, max_pending):
    """Replay ``requests`` (indices into ``pool``) under one reuse regime."""
    api.plan_cache_clear()
    api.plan_cache_resize(cache_size)
    for i in prewarm:
        a, b = pool[i]
        warm_plan(cached_plan(a, b, "expand", backend="jax"))
    lats, statuses = [], {"ready": 0, "fallback": 0}
    with PlanBuilder(max_pending=max_pending) as builder:
        t0 = time.perf_counter()
        for i in requests:
            a, b = pool[i]
            dt, status = serve_request(builder, a, b)
            lats.append(dt)
            statuses[status] += 1
        wall = time.perf_counter() - t0
        builder_stats = dict(builder.stats)
    info = api.plan_cache_info()
    row = {
        "regime": name,
        "requests": len(requests),
        "ops_per_sec": len(requests) / wall,
        "p50_latency_us": _pct_us(lats, 50),
        "p99_latency_us": _pct_us(lats, 99),
        "ready": statuses["ready"],
        "fallback": statuses["fallback"],
        "cache_evictions": info["evictions"],
        "builder": builder_stats,
    }
    print(f"{name:8s} {row['ops_per_sec']:10.1f} ops/s "
          f"p50 {row['p50_latency_us']:9.1f}us "
          f"p99 {row['p99_latency_us']:9.1f}us "
          f"ready {statuses['ready']:4d} fallback {statuses['fallback']:4d} "
          f"evict {info['evictions']:4d} shed {builder_stats['shed']:3d}")
    return row


def bench_regimes(n, density, reqs):
    default_size = api.plan_cache_info()["max_size"]
    pool = [(random_density_csc(n, n, density, seed=2 * i),
             random_density_csc(n, n, density, seed=2 * i + 1))
            for i in range(16)]
    print(f"plan-cache regimes: {n}x{n} patterns, density={density}, "
          f"{reqs} requests each")
    print(f"{'regime':8s} {'ops/s':>10s} {'p50':>12s} {'p99':>12s}")
    rows = [
        # 4 resident patterns, LRU comfortably larger: every probe hits.
        run_regime("hit100", pool, [i % 4 for i in range(reqs)],
                   cache_size=64, prewarm=range(4), max_pending=8),
        # 8-pattern pool, half pre-warmed; cold builds land mid-run.
        run_regime("mixed", pool, [i % 8 for i in range(reqs)],
                   cache_size=64, prewarm=range(4), max_pending=8),
        # 16-pattern pool cycled through an 8-entry LRU: pure churn.
        run_regime("allmiss", pool, [i % 16 for i in range(reqs)],
                   cache_size=8, prewarm=(), max_pending=4),
    ]
    api.plan_cache_resize(default_size)
    api.plan_cache_clear()
    return rows


# ---------------------------------------------------------------------------
# Part 2: ServeEngine under the async-warm protocol
# ---------------------------------------------------------------------------


def bench_engine(max_new_tokens):
    import jax

    from repro.configs import ARCHS
    from repro.models import init_model, smoke
    from repro.models.sparse_ffn import sparsify_ffn_params
    from repro.serving import ServeEngine

    cfg = smoke(ARCHS["qwen2-0.5b"])
    params = init_model(cfg, jax.random.PRNGKey(0))
    sparse_params, overlay = sparsify_ffn_params(cfg, params,
                                                 keep_density=0.5)
    fallback_lats, jit_lats = [], []
    with PlanBuilder() as builder:
        eng = ServeEngine(cfg, sparse_params, max_batch=2, cache_len=64,
                          sparse_ffn=overlay, plan_builder=builder)
        for p in ([1, 2, 3, 4], [5, 6, 7]):
            eng.submit(p, max_new_tokens=max_new_tokens)
        while eng.queue or any(eng.slots):
            ready = eng.sparse_ready()
            t0 = time.perf_counter()
            eng.step()
            (jit_lats if ready else fallback_lats).append(
                time.perf_counter() - t0)
        eng.wait_sparse(120)
    row = {
        "fallback_ticks": eng.tick_stats["fallback_ticks"],
        "jit_ticks": eng.tick_stats["jit_ticks"],
        "tokens": sum(len(r.generated) for r in eng.finished.values()),
    }
    if fallback_lats:
        row["fallback_p50_us"] = _pct_us(fallback_lats, 50)
    if jit_lats:
        # first jit tick can still include dispatch warmup; report both
        row["jit_p50_us"] = _pct_us(jit_lats, 50)
        row["jit_p99_us"] = _pct_us(jit_lats, 99)
    print(f"\nServeEngine (smoke qwen2, spgemm FFN overlay): "
          f"{row['fallback_ticks']} fallback ticks -> "
          f"{row['jit_ticks']} jit ticks, {row['tokens']} tokens")
    if fallback_lats and jit_lats:
        print(f"  tick p50: fallback {row['fallback_p50_us']:.0f}us, "
              f"jit {row['jit_p50_us']:.0f}us")
    return row


# ---------------------------------------------------------------------------
# Part 3: all-miss churn under injected faults (DESIGN.md §14)
# ---------------------------------------------------------------------------


def _churn_run(pool, requests, *, workers, max_pending, backpressure,
               build_deadline):
    """One all-miss replay; returns (latencies, unserved, builder stats).

    Requests are paced (2 ms apart, outside the timed window) so the
    background builder makes real progress during the replay — that is
    where the injected failures/hangs live — and the builder is drained
    before stats are read so failed/timed-out/recycled counters reflect
    every admitted build, not just the ones that finished mid-run.
    """
    api.plan_cache_clear()
    api.plan_cache_resize(8)
    lats, unserved = [], 0
    with PlanBuilder(workers=workers, max_pending=max_pending,
                     backpressure=backpressure,
                     build_deadline=build_deadline) as builder:
        for i in requests:
            a, b = pool[i]
            try:
                dt, _ = serve_request(builder, a, b)
                lats.append(dt)
            except Exception:
                unserved += 1
            time.sleep(0.002)
        builder.wait_idle(30)
        stats = dict(builder.stats)
    return lats, unserved, stats


def bench_resilience(n, density, reqs, reps=3):
    default_size = api.plan_cache_info()["max_size"]
    pool = [(random_density_csc(n, n, density, seed=2 * i),
             random_density_csc(n, n, density, seed=2 * i + 1))
            for i in range(16)]
    requests = [i % 16 for i in range(max(reqs, 96))]
    # deadline: ~6x one warm (so only injected hangs trip the watchdog,
    # not a slow-but-healthy compile), hangs injected well past it; two
    # workers so background build attempts — the fault sites — keep
    # flowing while the foreground replays
    cfg = dict(workers=2, max_pending=4, backpressure="shed-by-key-age",
               build_deadline=1.0)

    print("\nresilience: all-miss churn, fault-free vs injected faults "
          f"({reps} reps each, median p99)")
    clean_p99s, clean_served, clean_unserved = [], 0, 0
    for _ in range(reps):
        lats, unserved, clean_stats = _churn_run(pool, requests, **cfg)
        clean_p99s.append(_pct_us(lats, 99))
        clean_served += len(lats)
        clean_unserved += unserved

    rules = (faults.FaultRule("plan_spgemm", "fail", rate=0.10,
                              match="jax"),
             faults.FaultRule("builder_worker", "hang", rate=0.05,
                              seconds=2.0))
    with faults.inject(*rules, seed=2026) as fp:
        fault_p99s, served, fault_unserved = [], 0, 0
        fault_stats = {}
        for _ in range(reps):
            lats, unserved, stats = _churn_run(pool, requests, **cfg)
            fault_p99s.append(_pct_us(lats, 99))
            served += len(lats)
            fault_unserved += unserved
            for k, v in stats.items():
                if isinstance(v, (int, float)):
                    fault_stats[k] = fault_stats.get(k, 0) + v
        p99_clean = float(np.median(clean_p99s))
        p99_fault = float(np.median(fault_p99s))
        total = reps * len(requests)
        ok = (fault_unserved == 0 and served == total
              and p99_fault <= 3.0 * p99_clean)
        fired = {r["site"]: r["fires"]
                 for r in fp.describe()["rules"]}
        print(f"  clean  p99 {p99_clean:9.1f}us  served {clean_served:4d}"
              f"  builder {clean_stats['failed']} failed")
        print(f"  faults p99 {p99_fault:9.1f}us  served {served:4d}  "
              f"builder {fault_stats['failed']} failed "
              f"{fault_stats['timed_out']} timed-out "
              f"{fault_stats['workers_recycled']} recycled, "
              f"fires {fired}")
        print(f"  p99 ratio {p99_fault / max(p99_clean, 1e-9):.2f}x "
              f"(bound 3.00x), unserved {fault_unserved} -> "
              f"{'PASS' if ok else 'FAIL'}")
        # written inside the inject block so env_info() stamps the fault
        # config into the header — this report can never pass as clean
        write_report("BENCH_resilience.json", {
            "bench": "serving_resilience",
            "n": n,
            "density": density,
            "requests_per_rep": len(requests),
            "reps": reps,
            "clean": {"p99_latency_us": p99_clean,
                      "p99_per_rep_us": clean_p99s,
                      "served": clean_served,
                      "unserved": clean_unserved,
                      "builder": clean_stats},
            "faulted": {"p99_latency_us": p99_fault,
                        "p99_per_rep_us": fault_p99s,
                        "served": served,
                        "unserved": fault_unserved,
                        "builder": fault_stats},
            "p99_ratio": p99_fault / max(p99_clean, 1e-9),
            "pass": ok,
        })
    api.plan_cache_resize(default_size)
    api.plan_cache_clear()
    return ok


def main():
    from repro import runtime

    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--density", type=float, default=0.05)
    ap.add_argument("--reqs", type=int, default=96,
                    help="requests per regime")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer requests, short generations)")
    args = ap.parse_args()
    reqs = 32 if args.smoke else args.reqs

    sync_warm = measure_sync_warm(args.n, args.density)
    print(f"one synchronous device-plan warm (build + lift + compile): "
          f"{sync_warm * 1e3:.1f} ms\n")

    regimes = bench_regimes(args.n, args.density, reqs)
    engine = bench_engine(max_new_tokens=4 if args.smoke else 16)
    resilience_ok = bench_resilience(args.n, args.density, reqs)

    allmiss_p99 = next(r for r in regimes
                       if r["regime"] == "allmiss")["p99_latency_us"]
    ok = allmiss_p99 < sync_warm * 1e6 and resilience_ok
    print(f"\nall-miss p99 {allmiss_p99:.0f}us vs one blocking warm "
          f"{sync_warm * 1e6:.0f}us -> "
          f"{'PASS (ticks never block on plan builds)' if ok else 'FAIL'}")

    write_report("BENCH_serving.json", {
        "bench": "serving_spgemm",
        "n": args.n,
        "density": args.density,
        "sync_warm_us": sync_warm * 1e6,
        "regimes": regimes,
        "engine": engine,
        "pass": ok,
    })
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
