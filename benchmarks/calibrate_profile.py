"""Measure this machine's cost-model profile and persist it (DESIGN.md §15).

Runs the synthetic microbenchmark ladder of ``repro.core.profile`` — host
SPA regimes, the plan-resident product stream, the guard-tripped transient
rebuild, the jitted device stream, and (with >1 device) a real
``psum_scatter`` payload ladder — fits the ``CostConstants`` terms by
weighted least squares, searches the structural knobs (stream guard, auto
tile targets), and writes one JSON profile per machine fingerprint under
``REPRO_PROFILE_DIR`` (or ``--out``).

After this runs, every ``method="auto"`` consult on this machine ranks
engines on *measured* constants instead of the shipped defaults.  CI runs
``--smoke`` and uploads the profile as an artifact so the tiled
auto-vs-fixed gate (``benchmarks/tiled.py``) judges auto on a calibration
of the machine it actually runs on; re-run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N --sections comm`` to
refresh the mesh comm terms for a forced-device fingerprint (a separate
profile file — the fingerprint differs, by design).

Usage::

    PYTHONPATH=src python benchmarks/calibrate_profile.py [--smoke]
        [--out DIR] [--sections spa,stream,...] [--no-tune]
        [--reps N] [--seed N] [--report PATH]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

from _util import write_report  # noqa: E402

from repro.core import profile  # noqa: E402


def _validate(prof) -> dict:
    """Predict-vs-measure cross-check: re-run a small probe ladder and
    report the Spearman rank correlation between the fitted model's
    predictions and fresh measurements (the schedtool-style closing of the
    loop — a profile that cannot rank its own ladder is not worth
    persisting silently)."""
    import numpy as np

    from repro.sparse.stats import tile_stats

    rng = np.random.default_rng(1)
    pred, meas = [], []
    ladder = profile._stream_ladder(0.25, rng)
    from repro.core.cost import estimate_cost

    for plan, a, b, flops in ladder:
        st = tile_stats(a, b)
        for method in ("spa", "expand", "jax"):
            pred.append(estimate_cost(st, method, constants=prof.constants))
            if method == "spa":
                from repro.core.naive import spa_numpy

                meas.append(profile._best_of(lambda: spa_numpy(a, b), 3))
            elif method == "expand":
                plan.execute(a, b, engine="stream")
                meas.append(profile._best_of(
                    lambda: plan.execute(a, b, engine="stream"), 3))
            else:
                from repro.core.planner import plan_spgemm

                jp = plan_spgemm(a, b, "expand", backend="jax",
                                 stream_limit=flops + 1)
                jp.execute(a, b).values.block_until_ready()
                meas.append(profile._best_of(
                    lambda: jp.execute(a, b).values.block_until_ready(), 3))
    rc = profile.rank_correlation(pred, meas)
    return {"spearman": rc, "points": len(pred)}


def main(argv=None) -> int:
    from repro import runtime

    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small ladder (scale 0.25, 2 reps) for CI")
    ap.add_argument("--out", default=None,
                    help="profile directory (default REPRO_PROFILE_DIR "
                         "or the user cache)")
    ap.add_argument("--sections", default=None,
                    help="comma list of ladder sections to (re-)measure "
                         f"(default all: {','.join(profile.SECTIONS)})")
    ap.add_argument("--no-tune", action="store_true",
                    help="skip the structural-knob searches")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report", default="BENCH_calibrate.json")
    args = ap.parse_args(argv)

    scale = 0.25 if args.smoke else 1.0
    reps = args.reps if args.reps else (2 if args.smoke else 3)
    sections = (profile.SECTIONS if args.sections is None
                else tuple(s for s in args.sections.split(",") if s))

    fp = profile.machine_fingerprint()
    print(f"fingerprint {profile.fingerprint_key(fp)}: {fp}")
    print(f"sections={','.join(sections)} scale={scale} reps={reps} "
          f"tune={not args.no_tune}")

    t0 = time.perf_counter()
    prof = profile.calibrate_profile(
        scale=scale, reps=reps, sections=sections, tune=not args.no_tune,
        seed=args.seed, save=True, directory=args.out)
    elapsed = time.perf_counter() - t0

    print(f"\ncalibrated in {elapsed:.1f}s -> {prof.path}")
    print(f"{'field':14s} {'fitted':>12s} {'default':>12s}")
    from repro.core.cost import DEFAULT_CONSTANTS

    for f in sorted(prof.fitted):
        print(f"{f:14s} {getattr(prof.constants, f):12.3e} "
              f"{getattr(DEFAULT_CONSTANTS, f):12.3e}")
    for k, v in sorted(prof.tuning.items()):
        print(f"tuning {k} = {v}")

    val = _validate(prof)
    print(f"\nvalidation: Spearman(pred, meas) = {val['spearman']:.3f} "
          f"over {val['points']} probe points")

    write_report(args.report, {
        "benchmark": "calibrate_profile",
        "elapsed_seconds": round(elapsed, 3),
        "sections": list(sections),
        "profile_path": prof.path,
        "fitted": list(prof.fitted),
        "constants": {f: getattr(prof.constants, f) for f in prof.fitted},
        "tuning": dict(prof.tuning),
        "validation": val,
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
