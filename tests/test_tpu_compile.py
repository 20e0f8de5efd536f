"""The device path compiles for a TPU v5e, at the widths ``chip_smoke.py``
drives: the fused stream kernel, the SPA / SPARS / BSR kernels and the XLA
stream, each compiled for a described (not attached) ``v5e:2x2`` chip.

Nothing runs: this catches what the interpreter cannot (unaligned blocks,
unsupported primitives, VMEM overruns) without chip time.  The HASH kernel
is absent on purpose — the v5e compiler aborts the whole process on it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import runtime
from repro.core import pallas_stream
from repro.core.jax_stream import _bilinear_contract

# Goodwin_013 stand-in (chip_smoke phase (a)): n, max column nnz, products
N, Z, P_SMALL = 1965, 62, 2_098_840
# power-law A² (phase (b)): products, operand nnz, output nnz
P_BIG, NNZ_BIG, NNZ_C_BIG = 59_948_811, 2_998_850, 59_000_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_mode(monkeypatch):
    """Lower kernels for Mosaic (not the interpreter), with JAX's
    persistent cache off: a compile for a described chip cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(runtime, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _spec(one_chip):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _fused_args(s, p):
    nb = -(-(-(-p // 128)) // pallas_stream.ROWS) * pallas_stream.ROWS
    return (s((p,), jnp.int32), s((p,), jnp.int32),
            s((nb, 128), jnp.int32), s((nb,), jnp.int32),
            s((nb,), jnp.int32))


@pytest.mark.parametrize("p,n_out,nnz", [
    (P_SMALL, 1_900_000, 56_059),
    (P_BIG, NNZ_C_BIG, NNZ_BIG),
], ids=["goodwin", "powerlaw"])
def test_fused_engine_compiles(one_chip, compiled_mode, p, n_out, nnz):
    s = _spec(one_chip)

    def run(view, x, y):
        v = pallas_stream.FusedView(*view, None, n_out, p, 128)
        return pallas_stream._fused_call(v, x, y)

    c = _compile(run, _fused_args(s, p), s((nnz,), jnp.float32),
                 s((nnz,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_fused_kernel_compiles_batched(one_chip, compiled_mode):
    """The vmapped launch (``execute_fused_batched``): batch as a grid axis."""
    s = _spec(one_chip)
    nb = 2048
    c = _compile(jax.vmap(pallas_stream._block_partials),
                 s((3, nb, 128), jnp.float32), s((3, nb, 128), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_xla_stream_compiles(one_chip, compiled_mode):
    s = _spec(one_chip)

    def run(idx, av, bv):
        return _bilinear_contract(NNZ_C_BIG)(idx, av, bv)

    c = _compile(run, tuple(s((P_BIG,), jnp.int32) for _ in range(3)),
                 s((NNZ_BIG,), jnp.float32), s((NNZ_BIG,), jnp.float32))
    # the stream indices are arguments, not constants of the executable
    assert c.memory_analysis().argument_size_in_bytes >= 3 * 4 * P_BIG


def test_spa_kernel_compiles(one_chip, compiled_mode):
    from repro.kernels.spa import spa_spgemm

    s = _spec(one_chip)
    nb = 2048
    c = _compile(lambda *a: spa_spgemm(*a, m=N, block_cols=128),
                 s((N, Z), jnp.int32), s((N, Z), jnp.float32),
                 s((N,), jnp.int32), s((nb, Z), jnp.int32),
                 s((nb, Z), jnp.float32), s((nb,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_spars_kernel_compiles(one_chip, compiled_mode):
    from repro.kernels.spars import spars_spgemm

    s = _spec(one_chip)
    nb = 2048
    c = _compile(lambda *a: spars_spgemm(*a, m=N, block_cols=128),
                 s((N, Z), jnp.int32), s((N, Z), jnp.float32),
                 s((N,), jnp.int32), s((nb, Z), jnp.int32),
                 s((nb, Z), jnp.float32), s((nb,), jnp.int32),
                 s((nb // 128,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_bsr_kernel_compiles(one_chip, compiled_mode):
    """qwen2-0.5b FFN up-projection width (d_ff 4864 x d_model 896) in
    128 x 128 blocks, 128 tokens."""
    from repro.kernels.bsr_spmm import bsr_spmm

    s = _spec(one_chip)
    n_rb, max_nb = 4864 // 128, 896 // 128
    c = _compile(lambda *a: bsr_spmm(*a, bn=128),
                 s((n_rb, max_nb), jnp.int32), s((n_rb,), jnp.int32),
                 s((n_rb, max_nb, 128, 128), jnp.bfloat16),
                 s((896, 128), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_hash_kernel_is_refused_before_compiling(monkeypatch):
    """On a TPU the HASH kernel raises by name before anything is traced
    (the compiler would abort the process)."""
    from repro.kernels.hash_spgemm import hash_spgemm

    monkeypatch.setattr(runtime, "interpret_mode", lambda: False)
    x = jnp.zeros((128, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="'hash'"):
        hash_spgemm(x, x.astype(jnp.float32), x[:, 0], x,
                    x.astype(jnp.float32), x[:, 0], x[:1, 0], m=128, h=8,
                    block_cols=128)
