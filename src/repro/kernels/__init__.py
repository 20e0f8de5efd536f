"""Pallas TPU kernels for the paper's compute hot-spots.

- spa.py          SPA SpGEMM: dense [m, L] VMEM accumulator per column block
- spars.py        SPARS lock-step SpGEMM (cursor vectors, masked lanes)
- hash_spgemm.py  HASH lock-step SpGEMM (per-lane linear-probed VMEM tables)
- bsr_spmm.py     block-sparse x dense (production TPU re-targeting; SparseFFN)
- ref.py          pure-jnp oracles
- ops.py          jit'd wrappers + spgemm_pallas host API

All kernels are written for TPU (pl.pallas_call + BlockSpec VMEM tiling,
PrefetchScalarGridSpec for CSC pointer structure).  ``repro.runtime`` picks
the mode: interpreted on the CPU, compiled on a TPU, where the HASH kernel
is refused until it is ported.  Each SpGEMM kernel also has a
``*_batched`` variant that carries a leading batch axis on the value
operands only — B same-pattern multiplies in one launch (DESIGN.md §7).
"""

from repro.kernels.spa import spa_spgemm, spa_spgemm_batched
from repro.kernels.spars import spars_spgemm, spars_spgemm_batched
from repro.kernels.hash_spgemm import hash_spgemm, hash_spgemm_batched
from repro.kernels.bsr_spmm import bsr_spmm, bsr_from_dense
from repro.kernels.ops import spgemm_pallas

__all__ = [
    "spa_spgemm",
    "spa_spgemm_batched",
    "spars_spgemm",
    "spars_spgemm_batched",
    "hash_spgemm",
    "hash_spgemm_batched",
    "bsr_spmm",
    "bsr_from_dense",
    "spgemm_pallas",
]
