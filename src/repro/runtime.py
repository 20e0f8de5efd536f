"""The platform the process runs on, and what follows from it.

Every Pallas call in this package asks :func:`interpret_mode` how to run:
interpreted on the CPU (the test suite), compiled on a TPU, refused on any
other platform.  Nothing takes an ``interpret=`` argument, so a TPU process
can never run a kernel interpreted by accident.

The module also sizes the default device plan-memory guard from the chip's
own memory (:func:`device_stream_limit`), says which value arrays XLA
copies into the chip's VMEM (:func:`prefetch_limits`) and how much VMEM
one per-group Pallas kernel may take (:func:`kernel_vmem_bytes`), and
points JAX's
persistent compilation cache at a fixed directory
(:func:`enable_compile_cache`).
Importing it has no side effects; entry points call what they need.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from pathlib import Path

import jax

#: repository root (``src/repro/runtime.py`` -> ``.``)
REPO_ROOT = Path(__file__).resolve().parents[2]

#: compile cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"

#: device bytes one product of a plan may pin: the XLA stream's three int32
#: index arrays (12 B); the other 36 B are the guard's margin for a replay's
#: transient gathered values and products.  Kept at 48 so the plan guard,
#: and with it every plan's device/host choice, does not move
DEVICE_BYTES_PER_PRODUCT = 48

#: share of the chip's memory one plan's stream may take by default; the
#: rest holds operands, results and the transient products of a replay
DEVICE_STREAM_SHARE = 0.5


@dataclasses.dataclass(frozen=True)
class PrefetchLimits:
    """What XLA moves into a chip's VMEM before a gather reads it.

    Facts of the chip and its compiler, not options, measured by compiling
    for a described chip (``tests/test_tpu_compile.py`` holds each at its
    edge).  Of an executable's entry parameters, one is copied across
    programs (cross-program prefetch, the largest that fits); any other
    gets an ordinary prefetch inside the program only if it is small and
    the gathers reading it are not too long.  The edges were found for
    float32 values only (``itemsize``); a wider or narrower value tiles
    differently, so the limits say nothing of it.
    """

    cross_program_bytes: int   # the one cross-program-prefetched parameter
    other_bytes: int           # any other prefetched parameter
    other_products: int        # longest gather whose operand is prefetched
    itemsize: int = 4          # bytes of the values the edges were found at


#: by device kind; a v5e (128 MiB of VMEM): 112 MiB across programs (an f32
#: table of 29,360,128 values is prefetched, one of 29,360,129 is not),
#: 1,044,480 bytes (255 tiles of 1,024 f32) otherwise, and that only for
#: gathers of at most 117,440,512 products, at any operand size
PREFETCH_LIMITS = {
    "TPU v5 lite": PrefetchLimits(112 * 2 ** 20, 1_044_480, 112 * 2 ** 20),
}

#: VMEM one launch of a per-group Pallas kernel (``kernels/spa.py``,
#: ``kernels/spars.py``) may take, by device kind: its ``vmem_limit_bytes``.
#: A v5e has 128 MiB; a kernel may take the 112 MiB that XLA's prefetch
#: across programs may (:data:`PREFETCH_LIMITS`), the rest is XLA's own
KERNEL_VMEM_BYTES = {
    "TPU v5 lite": 112 * 2 ** 20,
}

#: the compiler's default scoped VMEM limit, for a kind not measured yet
DEFAULT_KERNEL_VMEM_BYTES = 16 * 2 ** 20

#: Pallas kernels that do not compile for a TPU yet (the compiler aborts the
#: process on them, so they are refused before it is called)
TPU_REFUSED_KERNELS = frozenset({"hash"})


def platform() -> str:
    """The default JAX backend: ``"cpu"``, ``"tpu"``, ..."""
    return jax.default_backend()


def interpret_mode() -> bool:
    """Whether Pallas calls run in the interpreter: exactly on the CPU.

    Raises on a platform that is neither CPU nor TPU: the kernels are
    written for Mosaic and must not silently run anywhere else.
    """
    p = platform()
    if p == "cpu":
        return True
    if p == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpreted), "
        f"not on {p!r}")


def check_kernel(name: str) -> None:
    """Refuse a Pallas kernel the TPU compiler cannot build, before tracing.

    On the CPU every kernel runs interpreted.  On a TPU a kernel listed in
    :data:`TPU_REFUSED_KERNELS` raises ``NotImplementedError`` naming it —
    it never falls back to the interpreter or to a reference.
    """
    if name in TPU_REFUSED_KERNELS and not interpret_mode():
        raise NotImplementedError(
            f"the {name!r} Pallas kernel does not compile for "
            f"{platform()!r} yet; plan a method without it (e.g. 'spa' or "
            "'spars-*' on backend='pallas') or use backend='jax'")


@functools.cache
def device_stream_limit() -> int | None:
    """Default per-plan stream guard (products) for device plans, or None.

    On a TPU it is sized from the chip's memory: ``bytes_limit`` from the
    first device's memory statistics, times :data:`DEVICE_STREAM_SHARE`,
    over :data:`DEVICE_BYTES_PER_PRODUCT`.  Elsewhere (CPU) ``None``: the
    host guard ``fast.STREAM_MAX_PRODUCTS`` applies.
    """
    if platform() != "tpu":
        return None
    return _device_share_bytes() // DEVICE_BYTES_PER_PRODUCT


@functools.cache
def device_tile_bytes() -> int | None:
    """Device bytes the dense tiles and B operands of one per-group Pallas
    call may hold at once, or None where no limit applies.

    A plan whose call fits pads and compacts on the device
    (``core.planner.DeviceLayout``), so every tile and B operand of a call
    is alive until its gather.  On a TPU the limit is the share of the chip's memory a
    plan's stream may take (:data:`DEVICE_STREAM_SHARE` of
    ``bytes_limit``); elsewhere (CPU) ``None``.
    """
    if platform() != "tpu":
        return None
    return _device_share_bytes()


def _device_share_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        raise RuntimeError(
            "the TPU reports no memory bytes_limit; cannot size the device "
            "plan-memory guard")
    return int(limit * DEVICE_STREAM_SHARE)


@functools.cache
def prefetch_limits() -> PrefetchLimits | None:
    """The chip's :class:`PrefetchLimits`, or None where there is no VMEM.

    On a TPU, those of the first device's kind (:data:`PREFETCH_LIMITS`);
    a kind not measured yet gets all zeros, so nothing counts as
    prefetched.  Elsewhere (CPU) ``None``.
    """
    if platform() != "tpu":
        return None
    return PREFETCH_LIMITS.get(jax.devices()[0].device_kind,
                               PrefetchLimits(0, 0, 0))


@functools.cache
def kernel_vmem_bytes() -> int | None:
    """VMEM budget of one per-group Pallas kernel launch, or None.

    On a TPU, that of the first device's kind (:data:`KERNEL_VMEM_BYTES`),
    else the compiler's default (:data:`DEFAULT_KERNEL_VMEM_BYTES`).
    Elsewhere (CPU, kernels interpreted) ``None``: no budget applies.
    """
    if platform() != "tpu":
        return None
    return KERNEL_VMEM_BYTES.get(jax.devices()[0].device_kind,
                                 DEFAULT_KERNEL_VMEM_BYTES)


def compile_cache_dir() -> Path:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(env) if env else DEFAULT_CACHE_DIR


def enable_compile_cache() -> Path:
    """Keep JAX's persistent compilation cache in :func:`compile_cache_dir`.

    When the environment variable is set JAX already reads it; this sets
    no other directory then.  Otherwise the fixed in-repo path is used, so
    every run of the same checkout finds the same cache.
    """
    path = compile_cache_dir()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
