"""VMEM budget of the per-group Pallas kernels (DESIGN.md §2).

The SPA and SPARS kernels keep A's lane-major gather table whole in VMEM
for every grid step: int8 byte planes ``[planes · zt, n_a]`` of A's row
codes and f32 value bits (:func:`table_rows`, :func:`row_planes`), where
``zt`` is A's largest column padded to an int8 tile and ``n_a`` its
column count.  Its block index never changes, so it takes one buffer
(:func:`whole`) and not the pipeline's two; B's lane blocks and the output
blocks move with the grid and keep two.  Every launch asks the compiler
for the chip's kernel budget (:func:`compiler_params`,
``runtime.kernel_vmem_bytes``), and a plan whose launches would need more
is refused when it is built (:func:`check`), never when the first kernel
compiles.
"""

from __future__ import annotations

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


#: byte planes of each of A's values: its f32 bit pattern, low byte first
VALUE_PLANES = 4


def tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """Bytes of a ``[rows, cols]`` VMEM buffer of ``itemsize``-byte
    elements, laid out in (8, 128) tiles of 32-bit words: (16, 128) for
    2 bytes, (32, 128) for 1."""
    return _round_up(max(rows, 1), 32 // itemsize) \
        * _round_up(max(cols, 1), 128) * itemsize


def table_rows(kind: str, za: int) -> int:
    """Rows of each byte plane of A's table: ``za`` padded to an int8
    tile (32), and for SPARS past a spare row ``za`` for A's column
    lengths."""
    return _round_up(max(za + (kind == "spars"), 1), 32)


def row_planes(m: int) -> int:
    """Byte planes of A's row codes for C with ``m`` rows: an entry holds
    its row id + 1 and padding 0, so the codes ``0..m`` take
    ``ceil(bits(m) / 8)`` bytes (1 up to m = 255, 2 up to 65,535, 3 up
    to 2**24 - 1).  A SPARS table's column lengths (at most ``m``) fit
    the same planes."""
    return max(1, -(-int(m).bit_length() // 8))


def vmem_bytes(kind: str, *, m: int, za: int, n_a: int, zb: int,
               block_cols: int) -> int:
    """VMEM bytes one launch of the ``kind`` (``"spa"``/``"spars"``)
    kernel takes, for C with ``m`` rows, A with ``n_a`` columns of at most
    ``za`` entries and B's columns of at most ``zb``, f32 values.

    A's byte-plane table once; B's two lane blocks (SPARS: and its
    lengths) and the output blocks (SPARS: C and its flags) twice; the
    int32 gather of every plane, ``[planes · zt, L]``, and the ``[rows,
    L]`` loop values each kernel keeps.
    """
    L = block_cols
    zt = table_rows(kind, za)
    zp = (row_planes(m) + VALUE_PLANES) * zt
    zb8 = _round_up(max(zb, 1), 8)
    table = tile_bytes(zp, n_a, 1)
    gather = tile_bytes(zp, L)
    n_a128 = _round_up(n_a, 128)
    # the lane iota and its compare, and the int8 one-hot
    lane_n = 2 * tile_bytes(n_a128, L) + tile_bytes(n_a128, L, 1)
    if kind == "spa":
        # blocks: B rows and values, C; scratch and values: the gather
        # twice (the dot's result, its scratch copy), acc, the row iota,
        # mask and select
        moving = 2 * (2 * tile_bytes(zb8, L) + tile_bytes(m, L))
        local = 2 * gather + 4 * tile_bytes(m, L) + lane_n
    elif kind == "spars":
        # blocks: B rows, values and lengths, C and flags; values: acc,
        # flags, hit and the row iota; the gather, the table-row iota,
        # its selection and one plane's select; B's rows, values, iota
        # and selection
        moving = 2 * (2 * tile_bytes(zb8, L) + tile_bytes(1, L)
                      + 2 * tile_bytes(m, L))
        local = (4 * tile_bytes(m, L) + lane_n + gather
                 + 3 * tile_bytes(zt, L) + 4 * tile_bytes(zb8, L))
    else:
        raise ValueError(f"no VMEM rule for a {kind!r} kernel")
    return table + moving + local


def check(kind: str, need: int, budget: int | None) -> None:
    """Refuse a launch of ``need`` bytes over the chip's ``budget``
    (``None``: no budget applies, as on the CPU)."""
    if budget is not None and need > budget:
        raise ValueError(
            f"a {kind} kernel launch of this plan needs {need:,} bytes of "
            f"VMEM, over the chip's kernel budget of {budget:,} bytes "
            "(runtime.KERNEL_VMEM_BYTES): A's lane-major table, whole in "
            "every launch, is too large; plan it on backend='jax'")


def whole(shape) -> pl.BlockSpec:
    """The block of a table every grid step reads whole: one buffer."""
    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape),
                        pipeline_mode=pl.Buffered(1))


def compiler_params() -> pltpu.CompilerParams:
    """The launch's compiler parameters: the chip's kernel budget."""
    return pltpu.CompilerParams(vmem_limit_bytes=runtime.kernel_vmem_bytes())
