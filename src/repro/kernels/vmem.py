"""VMEM budget of the per-group Pallas kernels (DESIGN.md §2).

The SPA and SPARS kernels keep A's two lane-major tables (row ids and
values, ``[za, n_a]``) whole in VMEM for every grid step, where ``za`` is
A's largest column and ``n_a`` its column count.  Their block index never
changes, so each takes one buffer (:func:`whole`) and not the pipeline's
two; B's lane blocks and the output blocks move with the grid and keep
two.  Every launch asks the compiler for the chip's kernel budget
(:func:`compiler_params`, ``runtime.kernel_vmem_bytes``), and a plan whose
launches would need more is refused when it is built (:func:`check`),
never when the first kernel compiles.
"""

from __future__ import annotations

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """Bytes of a ``[rows, cols]`` VMEM buffer laid out in (8, 128) tiles."""
    return _round_up(max(rows, 1), 8) * _round_up(max(cols, 1), 128) \
        * itemsize


def table_rows(kind: str, za: int) -> int:
    """Rows of A's lane-major tables: ``za`` padded to 8, and for SPARS a
    spare row for A's column lengths where the padding leaves none."""
    z8 = _round_up(max(za, 1), 8)
    return z8 + 8 if kind == "spars" and z8 == za else z8


def vmem_bytes(kind: str, *, m: int, za: int, n_a: int, zb: int,
               block_cols: int) -> int:
    """VMEM bytes one launch of the ``kind`` (``"spa"``/``"spars"``)
    kernel takes, for C with ``m`` rows, A with ``n_a`` columns of at most
    ``za`` entries and B's columns of at most ``zb``, f32 throughout.

    A's two tables once; B's two lane blocks (SPARS: and its lengths) and
    the output blocks (SPARS: C and its flags) twice; the ``[rows, L]``
    scratch and loop values each kernel keeps.
    """
    L = block_cols
    zt = table_rows(kind, za)
    zb8 = _round_up(max(zb, 1), 8)
    tables = 2 * tile_bytes(zt, n_a)
    lane_n = tile_bytes(_round_up(n_a, 128), L)
    if kind == "spa":
        # blocks: B rows and values, C; scratch and values: the gathered
        # column pair twice, acc, the row iota, mask and select, the lane
        # iota and the one-hot in two dtypes
        moving = 2 * (2 * tile_bytes(zb8, L) + tile_bytes(m, L))
        local = 4 * tile_bytes(zt, L) + 4 * tile_bytes(m, L) + 3 * lane_n
    elif kind == "spars":
        # blocks: B rows, values and lengths, C and flags; values: acc,
        # flags, hit and the row iota; the lane iota and one-hot; the
        # gathered pair, its iota and selection; B's rows, values, iota
        # and selection
        moving = 2 * (2 * tile_bytes(zb8, L) + tile_bytes(1, L)
                      + 2 * tile_bytes(m, L))
        local = (4 * tile_bytes(m, L) + 2 * lane_n + 4 * tile_bytes(zt, L)
                 + 4 * tile_bytes(zb8, L))
    else:
        raise ValueError(f"no VMEM rule for a {kind!r} kernel")
    return tables + moving + local


def check(kind: str, need: int, budget: int | None) -> None:
    """Refuse a launch of ``need`` bytes over the chip's ``budget``
    (``None``: no budget applies, as on the CPU)."""
    if budget is not None and need > budget:
        raise ValueError(
            f"a {kind} kernel launch of this plan needs {need:,} bytes of "
            f"VMEM, over the chip's kernel budget of {budget:,} bytes "
            "(runtime.KERNEL_VMEM_BYTES): A's lane-major tables, whole in "
            "every launch, are too large; plan it on backend='jax'")


def whole(shape) -> pl.BlockSpec:
    """The block of a table every grid step reads whole: one buffer."""
    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape),
                        pipeline_mode=pl.Buffered(1))


def compiler_params() -> pltpu.CompilerParams:
    """The launch's compiler parameters: the chip's kernel budget."""
    return pltpu.CompilerParams(vmem_limit_bytes=runtime.kernel_vmem_bytes())
