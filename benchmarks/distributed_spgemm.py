"""Weak-scaling benchmark for the mesh-distributed SpGEMM backend (§13).

Workload: for each mesh size ``D`` in {1, 2, 4, 8} the operand pair is
sized so the frozen product stream carries ``D x`` a fixed per-device
product target — per-device work is held constant while the mesh grows
(weak scaling).  The per-shard plan-memory guard is lowered so that the
largest multiply exceeds what a *single* device may hold: that matrix is
only executable distributed, which is the tentpole's acceptance scenario.

Gates before timings are trusted, for every mesh size:

* **bit-identity** — the distributed result (one jitted ``shard_map``
  dispatch, psum_scatter merge) must match the guard-lifted single-device
  host-stream oracle bit for bit.  Operand values are integer-valued f32,
  so every partial sum is exact and the cross-device merge order cannot
  hide behind tolerance.
* **imbalance < 2.0** — max/mean predicted flops across devices, the
  cost-model placement quality the plan promises.

PASS criterion (ISSUE 8): the largest mesh's multiply exceeds the
single-device guard yet completes distributed and bit-matches the oracle,
with placement imbalance < 2.0 at every mesh size.

Runs in one process on the devices it finds.  Under ``JAX_PLATFORMS=cpu``
it simulates an 8-device host mesh
(``--xla_force_host_platform_device_count``); timings on such a mesh
share one set of CPU cores, so the weak-scaling table is about
*feasibility and balance*, not parallel speedup — the JSON records both
anyway.

    JAX_PLATFORMS=cpu PYTHONPATH=src \
        python benchmarks/distributed_spgemm.py [--smoke] [--out PATH]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, "src")


def _host_mesh(want: int) -> None:
    """Under ``JAX_PLATFORMS=cpu``, simulate a ``want``-device host mesh.

    XLA reads the flag when the backend starts, so this runs before the
    first device query.  On any other platform the script uses the devices
    it finds.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and "xla_force_host_platform_device_count" not in flags):
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={want}").strip()


def main():
    from repro import runtime

    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--guard", type=int, default=1_500_000,
                    help="per-shard plan-memory guard (products)")
    ap.add_argument("--fill", type=int, default=16,
                    help="nonzeros per column in both operands")
    ap.add_argument("--inner", type=int, default=4096)
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="BENCH_distributed.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small guard/operands, 3 reps)")
    args = ap.parse_args()
    if args.smoke:
        args.guard, args.fill = 40_000, 8
        args.inner, args.rows, args.reps = 1024, 768, 3

    _host_mesh(args.devices)

    import jax
    import numpy as np

    from _util import bit_identical, median_time, write_report
    from repro.core.executor import execute
    from repro.core.planner import plan_spgemm
    from repro.distributed import plan_spgemm_mesh
    from repro.sparse.format import CSC
    from repro.sparse.generate import random_uniform_csc
    from repro.sparse.stats import ops_per_column

    guard = args.guard
    per_device_target = 3 * guard // 4   # weak-scaling per-device work
    mesh_sizes = [d for d in (1, 2, 4, 8)
                  if d <= min(args.devices, len(jax.devices()))]

    def int_csc(n, z, seed, n_rows):
        # integer-valued f32: every partial sum is exact, so the merged
        # distributed result must bit-match the host oracle
        m = random_uniform_csc(n, z, seed=seed, n_rows=n_rows)
        rng = np.random.default_rng(seed + 1000)
        return CSC(rng.integers(1, 8, m.nnz).astype(np.float32),
                   m.row_indices, m.col_ptr, m.shape)

    def host_oracle(a, b):
        plan = plan_spgemm(a, b, "expand", backend="host",
                           stream_limit=10**12)
        return execute(plan, a, b, engine="stream")

    rows = []
    print(f"devices={len(jax.devices())}  guard={guard:,}  "
          f"per-device target={per_device_target:,}\n")
    for d in mesh_sizes:
        # uniform fill => products = cols_b * fill_b * fill_a exactly
        cols_b = max(1, per_device_target * d // (args.fill * args.fill))
        a = int_csc(args.inner, args.fill, seed=2, n_rows=args.rows)
        b = int_csc(cols_b, args.fill, seed=3, n_rows=args.inner)
        products = int(ops_per_column(a, b).sum())

        t0 = time.perf_counter()
        plan = plan_spgemm_mesh(a, b, shards=d, shard_limit=guard)
        t_plan = time.perf_counter() - t0

        av, bv = a.values, b.values
        t0 = time.perf_counter()
        c = jax.block_until_ready(plan.stream_apply(av, bv))
        t_warmup = time.perf_counter() - t0  # trace+compile+stream build
        t_exec = median_time(
            lambda: jax.block_until_ready(plan.stream_apply(av, bv)),
            args.reps)

        ref = host_oracle(a, b)
        stream = plan.stream
        got = CSC(np.asarray(c), stream.c_rows, stream.c_col_ptr,
                  stream.shape)
        row = {
            "shards": d,
            "shape": [args.rows, args.inner, cols_b],
            "nnz_a": a.nnz, "nnz_b": b.nnz, "nnz_c": ref.nnz,
            "products": products,
            "per_device_products": stream.per_device.tolist(),
            "exceeds_single_device_guard": products > guard,
            "grid": list(plan.grid),
            "imbalance": round(plan.imbalance, 4),
            "t_plan_s": round(t_plan, 4),
            "t_warmup_s": round(t_warmup, 4),
            "t_exec_s": round(t_exec, 4),
            "products_per_s": round(products / t_exec),
            "bit_identical": bool(bit_identical(got, ref)),
        }
        rows.append(row)
        print(f"  D={d}: products={products:>12,}  "
              f"imbalance={row['imbalance']:.3f}  "
              f"exec={t_exec * 1e3:8.2f} ms  "
              f"{row['products_per_s'] / 1e6:8.2f} Mprod/s  "
              f"bit_identical={row['bit_identical']}  "
              f"over_guard={row['exceeds_single_device_guard']}")

    top = rows[-1]
    ok_bits = all(r["bit_identical"] for r in rows)
    ok_bal = all(r["imbalance"] < 2.0 for r in rows)
    ok_guard = top["exceeds_single_device_guard"]
    passed = ok_bits and ok_bal and ok_guard

    print(f"\nlargest mesh: {top['products']:,} products over the "
          f"{guard:,}-product single-device guard "
          f"({'needs' if ok_guard else 'fits'} distribution)")
    print(f"bit-identical at every mesh size: {ok_bits}")
    print(f"placement imbalance < 2.0 at every mesh size: {ok_bal}")
    print("PASS" if passed else "FAIL")

    write_report(args.out, {
        "benchmark": "distributed_spgemm",
        "smoke": args.smoke,
        "guard_products": guard,
        "per_device_target": per_device_target,
        "reps": args.reps,
        "weak_scaling": rows,
        "pass": passed,
    })
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
