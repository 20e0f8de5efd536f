"""E6 — Pallas kernel micro-benchmarks.

Two tiers (this container has no TPU):
 * wall-clock of the jit'd interpret-mode kernels on small shapes
   (regression tracking only — interpret mode is not TPU performance);
 * structural VMEM/FLOP accounting per kernel configuration: bytes of VMEM
   the BlockSpecs claim, MXU work, and the analytic arithmetic intensity that
   the §Roofline analysis consumes.

CSV: name,us_per_call,derived.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bsr_from_dense, bsr_spmm, spa_spgemm
from repro.kernels.vmem import VALUE_PLANES, row_planes
from repro.sparse import csc_to_padded_columns, random_uniform_csc


def _time(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / reps * 1e6


def run(csv=True):
    rows = []

    # SPA kernel wall-clock (interpret) + structural accounting
    for n, z, L in ((128, 2, 32), (128, 4, 32), (256, 4, 64)):
        a = random_uniform_csc(n, z, seed=z)
        r, v, c = csc_to_padded_columns(a)
        args = (jnp.asarray(r, jnp.int32), jnp.asarray(v, jnp.float32),
                jnp.asarray(c, jnp.int32)) * 2
        us = _time(spa_spgemm, *args, m=n, block_cols=L)
        vmem = (n * L * 4            # accumulator tile
                + (row_planes(n) + VALUE_PLANES) * n * z   # A's byte planes
                + 2 * L * z * 4)     # B block
        rows.append((f"spa_kernel_n{n}_z{z}_L{L}", us, f"vmem_bytes={vmem}"))

    # BSR kernel: structural roofline terms for a production shape
    rng = np.random.default_rng(0)
    for (mdim, kdim, ndim, bm, bk, bn, keep) in (
            (256, 256, 128, 32, 32, 64, 0.5),
            (512, 512, 128, 64, 64, 128, 0.25)):
        w = rng.normal(size=(mdim, kdim)).astype(np.float32)
        drop = rng.uniform(size=(mdim // bm, kdim // bk)) > keep
        for i in range(mdim // bm):
            for j in range(kdim // bk):
                if drop[i, j]:
                    w[i*bm:(i+1)*bm, j*bk:(j+1)*bk] = 0
        bi, bnnz, blocks = bsr_from_dense(w, bm, bk)
        x = rng.normal(size=(kdim, ndim)).astype(np.float32)
        us = _time(bsr_spmm, jnp.asarray(bi), jnp.asarray(bnnz),
                   jnp.asarray(blocks), jnp.asarray(x), bn=bn)
        flops = 2 * int(bnnz.sum()) * bm * bk * ndim
        dense_flops = 2 * mdim * kdim * ndim
        bytes_moved = (blocks.nbytes * (ndim // bn)  # blocks re-read per j
                       + x.nbytes * (mdim // bm)     # x tile per i
                       + mdim * ndim * 4)
        ai = flops / bytes_moved
        rows.append((
            f"bsr_kernel_{mdim}x{kdim}x{ndim}_b{bm}x{bk}_keep{keep}",
            us,
            f"flops={flops};dense_flops={dense_flops};"
            f"flop_savings={dense_flops/max(flops,1):.2f}x;"
            f"arith_intensity={ai:.1f}"))

    if csv:
        print("name,us_per_call,derived")
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}")
    return rows


if __name__ == "__main__":
    run()
