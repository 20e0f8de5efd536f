"""Smoke test of the SpGEMM device path on a TPU: does the system start there?

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Every phase runs through the public entry points (``cached_plan`` /
``spgemm`` / ``plan.execute``) on integer-valued f32 operands made from
``--seed``, and is compared bit for bit with the host product stream.

(a) A·A on the ``Goodwin_013`` Table-1 stand-in (n=1,965, ~2M products): the
    XLA stream (``backend="jax"``), the fused Pallas kernel
    (``engine="fused"``), and the per-group Pallas kernels that compile for
    the chip (``backend="pallas"``: SPA, SPARS).  One plan miss, then hits.
    The HASH kernel must be refused by name.
(b) A² of a power-law matrix whose frozen stream puts ~0.7 GB of int32
    indices in HBM, on the XLA stream and the fused kernel.
(mesh, ``--chips 4``) A² of a larger power-law matrix whose stream is above
    one chip's plan-memory guard, on ``backend="mesh"``, with each chip
    holding only its own shard of the stream.

Every device execution must report ``device=True`` and no host fallback.
The script needs a TPU: on any other platform, or with an unknown device
kind, it exits non-zero without a result.  Its last line is one JSON object
naming the device.  Times printed are wall times of this process, labelled
as such; they are not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: device kinds this smoke knows (JAX's ``device_kind`` spellings of v5e)
KNOWN_KINDS = ("TPU v5 lite", "TPU v5e")

#: phase (b): power-law A² sized for ~6e7 products (n * avg**2)
POWERLAW = dict(n=150_000, avg=20.0, alpha=2.0)
#: mesh phase: the same generator, scaled past one chip's stream guard
MESH_SCALE = 3.5


def log(msg: str) -> None:
    print(msg, flush=True)


def int_valued(m, rng):
    """Same pattern, integer values 1..3 as f32: every partial sum is exact,
    so any summation order must reproduce the host stream bit for bit."""
    from repro.sparse.format import CSC

    vals = rng.integers(1, 4, m.nnz).astype("float32")
    return CSC(vals, m.row_indices, m.col_ptr, m.shape)


def host_reference(a, b, products: int):
    """The host product stream (numpy), planned on its own."""
    from repro.core import cached_plan

    plan = cached_plan(a, b, "expand", backend="host",
                       stream_limit=products + 1)
    return plan.execute(a, b, engine="stream")


def require(ok: bool, what) -> None:
    """A check that stays under ``python -O`` (unlike ``assert``)."""
    if not ok:
        raise AssertionError(what)


def check(name: str, got, ref, stats: dict) -> None:
    from repro.sparse.format import csc_bit_identical

    if stats.get("device") is not True or stats.get("fallback") is not None:
        raise AssertionError(f"{name}: ran off the device: {stats}")
    if not csc_bit_identical(got.to_host(), ref):
        raise AssertionError(f"{name}: result differs from the host stream")
    log(f"  {name}: OK (bit-identical to the host stream)")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def block(c):
    import jax

    jax.block_until_ready(c.values)
    return c


def phase_table1(seed: int) -> None:
    import numpy as np

    from repro import runtime
    from repro.core import api, cached_plan
    from repro.sparse.suitesparse import load_or_synthesize

    log("phase (a): Goodwin_013 stand-in, A·A")
    rng = np.random.default_rng(seed)
    a = int_valued(load_or_synthesize("Goodwin_013", seed=seed,
                                      cache_dir=None)[0], rng)
    products = int(np.diff(a.col_ptr)[a.row_indices].sum())
    ref = host_reference(a, a, products)
    log(f"  n={a.shape[0]} nnz={a.nnz} products={products} nnz_C={ref.nnz}")

    before = api.plan_cache_info()
    plan = cached_plan(a, a, "expand", backend="jax")            # miss
    for i in range(3):
        hit = cached_plan(a, a, "expand", backend="jax")         # hits
        require(hit is plan, "plan cache hit returned another plan")
        for engine in (None, "fused"):
            stats = {}
            c, t = timed(lambda: block(hit.execute(a, a, engine=engine,
                                                   stats=stats)))
            if i == 0:
                log(f"  jax engine={engine or 'stream'}: first execute "
                    f"{t:.3f}s wall (upload + compile)")
            check(f"jax engine={engine or 'stream'} [{i}]", c, ref, stats)
            if engine == "fused":
                require(stats["n_launches"] == 1, stats)
    after = api.plan_cache_info()
    require(after["misses"] - before["misses"] == 1, (before, after))
    require(after["hits"] - before["hits"] == 3, (before, after))

    for method in ("spa", "spars-40/40"):
        plan = cached_plan(a, a, method, backend="pallas")
        stats = {}
        c, t = timed(lambda: block(plan.execute(a, a, stats=stats)))
        log(f"  pallas {method}: {stats['n_launches']} launches, "
            f"{t:.3f}s wall first execute")
        check(f"pallas {method}", c, ref, stats)

    for method in sorted(runtime.TPU_REFUSED_KERNELS):
        try:
            cached_plan(a, a, f"{method}-256/256",
                        backend="pallas").execute(a, a)
        except NotImplementedError as e:
            log(f"  pallas {method}: refused as expected ({e})")
        else:
            raise AssertionError(f"the {method!r} kernel was not refused")


def powerlaw(seed: int, scale: float = 1.0):
    import numpy as np

    from repro.sparse.generate import random_powerlaw_csc

    n = int(POWERLAW["n"] * scale)
    a = random_powerlaw_csc(n, POWERLAW["avg"], POWERLAW["alpha"],
                            seed=seed, dtype=np.float32)
    a = int_valued(a, np.random.default_rng(seed + 1))
    products = int(np.diff(a.col_ptr)[a.row_indices].sum())
    return a, products


def phase_powerlaw(seed: int) -> None:
    import jax

    from repro.core import api, cached_plan, fast

    log("phase (b): power-law A²")
    a, products = powerlaw(seed)
    limit = fast.default_stream_limit(device=True)
    log(f"  n={a.shape[0]} nnz={a.nnz} products={products} "
        f"device guard={limit} products")
    require(products <= limit, "phase (b) must fit one chip's guard")

    plan, t_plan = timed(lambda: cached_plan(a, a, "expand", backend="jax"))
    s, t_sym = timed(lambda: plan.stream)
    log(f"  nnz_C={s.nnz}  plan {t_plan + t_sym:.3f}s wall "
        "(fingerprints + symbolic phase)")
    ref, t_ref = timed(lambda: host_reference(a, a, products))
    log(f"  host reference {t_ref:.3f}s wall")
    for engine in (None, "fused"):
        name = f"jax engine={engine or 'stream'}"
        stats = {}
        c, t_first = timed(lambda: block(plan.execute(a, a, engine=engine,
                                                      stats=stats)))
        check(name, c, ref, stats)
        reps = [timed(lambda: block(plan.execute(a, a, engine=engine)))[1]
                for _ in range(3)]
        log(f"  {name}: first execute {t_first:.3f}s wall, replay "
            f"{statistics.median(reps):.4f}s wall (median of 3)")
    info = api.plan_cache_info()
    mem = jax.devices()[0].memory_stats() or {}
    log(f"  stream bytes in HBM: xla={info['device_stream_bytes']} "
        f"fused={info['fused_stream_bytes']}  "
        f"peak_bytes_in_use={mem.get('peak_bytes_in_use')}")


def phase_mesh(seed: int, chips: int) -> None:
    from repro.core import cached_plan, fast, spgemm

    log(f"mesh phase: power-law A² on {chips} chips")
    a, products = powerlaw(seed, MESH_SCALE)
    limit = fast.default_stream_limit(device=True)
    log(f"  n={a.shape[0]} nnz={a.nnz} products={products} "
        f"one chip's guard={limit} products")
    require(products > limit, "the mesh phase must exceed one chip's guard")

    c, t_first = timed(lambda: block(spgemm(a, a, "expand", backend="mesh",
                                            shards=chips)))
    log(f"  plan + first execute {t_first:.3f}s wall")
    plan = cached_plan(a, a, "expand", backend="mesh", shards=chips)
    stats = {}
    reps = [timed(lambda: block(plan.execute(a, a, stats=stats)))[1]
            for _ in range(3)]
    log(f"  replay {statistics.median(reps):.4f}s wall (median of 3), "
        f"imbalance={plan.imbalance:.3f}, "
        f"per-device products={stats['per_device_products']}")
    check("mesh", c, host_reference(a, a, products), stats)

    # each chip holds exactly its own [1, Pmax] row of every stream array
    ss = plan.stream
    for arr in (ss.a_pos, ss.b_pos, ss.seg, ss.mask):
        shards = arr.addressable_shards
        require(len({sh.device for sh in shards}) == chips, shards)
        require(all(sh.data.shape == (1, arr.shape[1]) for sh in shards),
                [sh.data.shape for sh in shards])
    for sh in ss.a_pos.addressable_shards:
        mem = sh.device.memory_stats() or {}
        log(f"  {sh.device}: stream row {sh.index[0].start} of shape "
            f"{sh.data.shape}, peak_bytes_in_use="
            f"{mem.get('peak_bytes_in_use')}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro import runtime
    from repro.core import api

    runtime.enable_compile_cache()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    if kind not in KNOWN_KINDS:
        print(f"chip_smoke: unknown device kind {kind!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    require(not runtime.interpret_mode(), "Pallas would run interpreted")
    log(f"platform={platform} kind={kind!r} devices={len(devices)} "
        f"compile cache={runtime.compile_cache_dir()}")

    if args.chips == 4:
        phase_mesh(args.seed, args.chips)
    else:
        phase_table1(args.seed)
        phase_powerlaw(args.seed)
    fallbacks = api.plan_cache_info()["host_fallbacks"]
    require(fallbacks == 0, f"{fallbacks} host fallbacks")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
