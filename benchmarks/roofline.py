"""SpGEMM engine roofline: achieved fraction of the bandwidth bound.

The paper's "approaches the roofline" claim needs a number, not prose.  For
each numeric engine (host ``naive``/SPA, host ``stream``, ``jax`` device
stream, ``fused`` Pallas kernel) this script times the plan-reuse numeric
phase of the PR 3 mixed-density workload and reports, per engine:

* **GFLOP/s** — ``2 * P`` flops (one multiply + one accumulate per stream
  product) over the measured time;
* **bytes_model** — the stream-dataflow traffic model of that engine's
  numeric phase (what DESIGN.md §9 calls the replay floor)::

      bytes = P * (2 * isz + 3 * vsz)          # index reads + value
            + (nnz_a + nnz_b + nnz_c) * vsz    # gathers + product pass
                                               # + operand/result arrays

  with ``isz``/``vsz`` the engine's index/value widths (host engines run
  int64/f64, device engines int32/f32 — the device replays move *half* the
  bytes, which is half of their advantage);
* **bw_frac** — the achieved fraction of the memory-bandwidth bound:
  ``(bytes_model / t) / peak_bw``, with ``peak_bw`` *measured* on the spot
  by a large-array triad sweep (not a spec-sheet constant).  This is the
  headline number: an engine at ``bw_frac ~ 1`` cannot be made faster
  without moving fewer bytes.  The bound is the *host's*, so the script
  runs on the CPU only and refuses any other platform.

``bw_frac`` is equivalently ``t_bound / t`` — the per-engine bound uses the
engine's own dtype widths, so the host engines are not penalized for their
f64 contract.  The naive SPA engine does not literally replay a stream; its
fraction reads as "how close this dataflow gets to the stream replay's
bandwidth bound", which is exactly the comparison the paper makes.

    PYTHONPATH=src python benchmarks/roofline.py [--smoke] [--out PATH]

Writes ``BENCH_roofline.json``; importable pieces
(:func:`measure_peak_bandwidth`, :func:`stream_bytes_model`,
:func:`bandwidth_fraction`) are shared with ``benchmarks/executor_fused.py``.
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from _util import median_time, write_report
from tiled import mixed_density_pair
from repro.core import plan_spgemm
from repro.sparse.format import csc_to_dense


def measure_peak_bandwidth(mb: int = 64, reps: int = 5) -> float:
    """Measured host memory bandwidth (bytes/s) from a f64 triad sweep.

    ``x = y * s + z`` over arrays far beyond LLC moves 3 array lengths
    (2 reads + 1 write, write-allocate ignored — a *conservative* peak, so
    reported fractions err low, never high).  Best of ``reps``.
    """
    n = mb * 1024 * 1024 // 8
    y = np.ones(n)
    z = np.full(n, 0.5)
    x = np.empty(n)
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        np.multiply(y, 1.5, out=x)
        x += z
        best = min(best, time.perf_counter() - t0)
    return 3 * n * 8 / best


def stream_bytes_model(products: int, nnz_a: int, nnz_b: int, nnz_c: int,
                       value_size: int, index_size: int) -> int:
    """Stream-dataflow bytes of one numeric phase (see module docstring)."""
    return (products * (2 * index_size + 3 * value_size)
            + (nnz_a + nnz_b + nnz_c) * value_size)


def bandwidth_fraction(bytes_moved: int, seconds: float,
                       peak_bw: float) -> float:
    """Achieved fraction of the bandwidth bound (1.0 = at the roofline)."""
    return (bytes_moved / max(seconds, 1e-12)) / max(peak_bw, 1.0)


def _engines(a, b):
    """(name, plan, run, value_size, index_size) per numeric engine."""
    ph = plan_spgemm(a, b, "expand")                    # host stream plan
    ps = plan_spgemm(a, b, "spa")                       # host naive oracle
    pj = plan_spgemm(a, b, "expand", backend="jax")

    def _dev(fn):
        return lambda: fn().values.block_until_ready()

    return [
        ("naive", ps, lambda: ps.execute(a, b, engine="naive"), 8, 8),
        ("stream", ph, lambda: ph.execute(a, b, engine="stream"), 8, 8),
        ("jax", pj, _dev(lambda: pj.execute(a, b, engine="stream")), 4, 4),
        ("fused", pj, _dev(lambda: pj.execute(a, b, engine="fused")), 4, 4),
    ]


def run(m: int = 256, n_sparse: int = 992, dense_a: int = 32,
        dense_b: int = 32, per_dense: int = 24, reps: int = 5,
        out: str = "BENCH_roofline.json", smoke: bool = False) -> dict:
    from repro import runtime

    if runtime.platform() != "cpu":
        # the bound below is a host triad; device engines need the chip's
        # own peak, keyed by device_kind, which this script does not have
        raise SystemExit(
            f"roofline: no bw_frac on {runtime.platform()!r} — the "
            "bandwidth bound is measured on the host; a device peak table "
            "is needed")
    if smoke:
        m, n_sparse = 96, 240
        dense_a = dense_b = per_dense = 16
        reps = 2
    a, b = mixed_density_pair(m, n_sparse, dense_a, dense_b, per_dense)
    peak_bw = measure_peak_bandwidth()
    ref = None
    rows = []
    engines = _engines(a, b)
    stream = engines[1][1].stream
    p = stream.n_products
    nnz_c = stream.nnz
    flops = 2 * p
    for name, plan, fn, vsz, isz in engines:
        c = fn() if name != "naive" else None           # warmup/trace
        got = csc_to_dense(plan.execute(a, b).to_host()) \
            if name in ("jax", "fused") else csc_to_dense(
                plan.execute(a, b, engine=name))
        if ref is None:
            ref = got
        ok = bool(np.allclose(got, ref, rtol=1e-4, atol=1e-5))
        del c
        t = median_time(fn, reps)
        nbytes = stream_bytes_model(p, a.nnz, b.nnz, nnz_c, vsz, isz)
        rows.append({
            "engine": name,
            "t_ms": t * 1e3,
            "gflops": flops / t / 1e9,
            "bytes_model": nbytes,
            "bw_achieved_gbs": nbytes / t / 1e9,
            "bw_frac": bandwidth_fraction(nbytes, t, peak_bw),
            "correct": ok,
        })

    print(f"workload: A {a.shape} nnz={a.nnz}, B {b.shape} nnz={b.nnz}, "
          f"products={p}, nnz_C={nnz_c}, reps={reps}")
    print(f"measured peak bandwidth: {peak_bw/1e9:.1f} GB/s (f64 triad)\n")
    print("| engine | t (ms) | GFLOP/s | model GB/s | frac of BW bound |")
    print("|" + "---|" * 5)
    for r in rows:
        print(f"| {r['engine']:6s} | {r['t_ms']:8.3f} | {r['gflops']:7.3f} "
              f"| {r['bw_achieved_gbs']:8.3f} | {r['bw_frac']:10.4f} |"
              f"{'' if r['correct'] else '  !! MISMATCH'}")
    print("\n(the Pallas interpreter emulates the kernel on the CPU, and the "
          "bound is the host's: no row here is a device number)")

    report = {
        "bench": "roofline",
        "config": {"m": m, "n_sparse": n_sparse, "dense_a": dense_a,
                   "dense_b": dense_b, "per_dense": per_dense,
                   "reps": reps, "smoke": smoke,
                   "stream_products": p, "nnz_c": nnz_c, "flops": flops},
        "peak_bandwidth_gbs": peak_bw / 1e9,
        "results": rows,
    }
    write_report(out, report)
    return report


def main():
    from repro import runtime

    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=256)
    ap.add_argument("--n-sparse", type=int, default=992)
    ap.add_argument("--dense-a", type=int, default=32)
    ap.add_argument("--dense-b", type=int, default=32)
    ap.add_argument("--per-dense", type=int, default=24)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="BENCH_roofline.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (small matrices, 2 reps)")
    args = ap.parse_args()
    report = run(args.m, args.n_sparse, args.dense_a, args.dense_b,
                 args.per_dense, args.reps, args.out, args.smoke)
    bad = [r["engine"] for r in report["results"] if not r["correct"]]
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
