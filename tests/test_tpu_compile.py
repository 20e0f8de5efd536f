"""The device path compiles for a TPU v5e, at the widths ``chip_smoke.py``
drives: the SPA / SPARS / BSR kernels (and SPA /
SPARS at Table 1's widest A tables, gathered by an int8 dot, under the
chip's kernel VMEM budget) and the XLA stream, each compiled for a
described (not attached) ``v5e:2x2`` chip.

Nothing runs: this catches what the interpreter cannot (unaligned blocks,
unsupported primitives, VMEM overruns) without chip time.  The HASH kernel
is absent on purpose — the v5e compiler aborts the whole process on it.
The execute path's one value table is checked in the compiled program:
both gathers read the one cross-program-prefetched VMEM copy, up to the
chip's prefetch limit and not past it.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import runtime
from repro.core import jax_stream
from repro.core.jax_stream import _bilinear_contract

# Goodwin_013 stand-in (chip_smoke phase (a)): n, max column nnz, products
N, Z, P_SMALL = 1965, 62, 2_098_840
# power-law A² (phase (b)): products, operand nnz, output nnz
P_BIG, NNZ_BIG, NNZ_C_BIG = 59_948_811, 2_998_850, 59_000_000
# the benchmark's A² cells: products, operand nnz, output nnz
KRON14 = (156_023_438, 425_666, 40_419_786)
TABLE1 = (149_059, 32_653, 100_000)


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


def test_spa_kernel_compiles_with_the_plans_trip_counts(one_chip,
                                                         compiled_mode):
    """As ``_execute_pallas`` launches it: the loop bounds a plan computed
    once (``KernelGroup.trips``) arrive as arguments."""
    from repro.kernels.spa import spa_spgemm

    s = _spec(one_chip)
    z8 = -(-Z // 8) * 8
    c = _compile(lambda *a: spa_spgemm(*a[:6], a[6:], m=N, block_cols=128),
                 s((N, Z), jnp.int32), s((N, Z), jnp.float32),
                 s((N,), jnp.int32), s((128, Z), jnp.int32),
                 s((128, Z), jnp.float32), s((128,), jnp.int32),
                 s((1,), jnp.int32), s((z8,), jnp.int32))
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


#: Table 1's widest A tables (n, largest column): A's byte-plane table
#: (six int8 planes each) takes 15.5 MB and 55.4 MB, the first with its
#: gathers already over the compiler's default 16 MiB
WIDE = {"adder_dcop_01": (1813, 1332), "iprob": (3001, 3000)}


def _kernel_dots(jaxpr) -> list:
    """``(operand dtypes, precision)`` of every ``dot_general`` inside the
    ``pallas_call`` bodies of ``jaxpr``, however deep in loops."""
    def walk(j, inside):
        for eqn in j.eqns:
            if inside and eqn.primitive.name == "dot_general":
                yield (tuple(str(v.aval.dtype) for v in eqn.invars),
                       eqn.params.get("precision"))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub, inside or
                                        eqn.primitive.name == "pallas_call")
    return list(walk(jaxpr.jaxpr, False))


@pytest.mark.parametrize("kind", ["spa", "spars"])
@pytest.mark.parametrize("name", sorted(WIDE))
def test_group_kernels_compile_at_the_widest_table1_tables(
        one_chip, compiled_mode, monkeypatch, kind, name):
    """One 128-column launch with A's byte-plane table whole in VMEM, one
    buffer, under a v5e's kernel budget (``runtime.KERNEL_VMEM_BYTES``);
    the kernel's one gather is an int8 dot with int32 accumulation, not
    an f32 one at ``HIGHEST``."""
    from repro.kernels.spa import spa_spgemm
    from repro.kernels.spars import spars_spgemm

    monkeypatch.setattr(runtime, "kernel_vmem_bytes",
                        lambda: runtime.KERNEL_VMEM_BYTES["TPU v5 lite"])
    s = _spec(one_chip)
    n, z = WIDE[name]
    args = [s((n, z), jnp.int32), s((n, z), jnp.float32), s((n,), jnp.int32),
            s((128, z), jnp.int32), s((128, z), jnp.float32),
            s((128,), jnp.int32)]
    if kind == "spa":
        fn = lambda *a: spa_spgemm(*a, m=n, block_cols=128)
    else:
        fn = lambda *a: spars_spgemm(*a, m=n, block_cols=128)
        args.append(s((1,), jnp.int32))
    assert _kernel_dots(jax.make_jaxpr(fn)(*args)) == [
        (("int8", "int8"), None)]
    c = _compile(fn, *args)
    assert "tpu_custom_call" in c.as_text()


def test_device_compaction_compiles_at_iprobs_tiles(one_chip,
                                                    compiled_mode):
    """One call's gather of C's values from its tiles, at ``iprob``'s
    size (24 tiles of [3001, 128], C full: 9,003,003 values), fits the
    chip (``ops.compact_tiles``)."""
    from repro.kernels.ops import compact_tiles

    s = _spec(one_chip)
    tiles = [s((3001, 128), jnp.float32)] * 24
    c = compact_tiles.lower(tiles, s((9_003_003,), jnp.int32)).compile()
    assert "gather" in c.as_text()


def test_device_padding_compiles_at_iprobs_operands(one_chip, compiled_mode):
    """One call's operands padded on the device at ``iprob``'s size (9,000
    values each; A's table [3001, 3000], 24 B operands of [128, 3000]) fit
    the chip (``ops.pad_operands``)."""
    from repro.kernels.ops import pad_operands

    s = _spec(one_chip)
    v, i = s((9_000,), jnp.float32), s((9_000,), jnp.int32)
    c = pad_operands.lower(v, v, i, i, i, i, a_shape=(3001, 3000),
                           widths=(128,) * 24, zb=3000).compile()
    assert "scatter" in c.as_text()


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


def _gathers(hlo: str, p: int) -> list:
    """Per gather fusion of ``p`` values in the entry computation: its
    value operand, and whether that operand is a cross-program-prefetched
    copy in VMEM (memory space ``S(1)``) of an entry parameter."""
    entry = hlo[hlo.index("\nENTRY"):]
    prefetched = set(re.findall(
        r"%(\S+) = \(\S+S\(1\)\}.* copy-start\(%\S+\), "
        r"cross_program_prefetch_index", entry))
    vmem_copies = {done for done, start in re.findall(
        r"%(\S+) = f32\[\d+\]\{\S*S\(1\)\} copy-done\(%(\S+)\)", entry)
        if start in prefetched}
    found = re.findall(
        rf"= f32\[{p}\]\{{\S+\}} fusion\(%([^,]+), "
        r".*op_name=\"[^\"]*/gather\"",
        entry)
    return [(operand, operand in vmem_copies) for operand in found]


def _one_table_program(s, p, na, nb, n_out):
    def run(idx, table):
        return jax_stream._one_table(_bilinear_contract(n_out), na)(idx,
                                                                    table)

    return _compile(run, tuple(s((p,), jnp.int32) for _ in range(3)),
                    s((na + nb,), jnp.float32)).as_text()


@pytest.mark.parametrize("p,nnz,n_out", [KRON14, TABLE1],
                         ids=["kron14", "table1"])
def test_execute_path_gathers_read_one_prefetched_table(one_chip,
                                                        compiled_mode, p,
                                                        nnz, n_out):
    """Both gathers of the one-table executable read the same
    cross-program-prefetched VMEM copy of the table."""
    hlo = _one_table_program(_spec(one_chip), p, nnz, nnz, n_out)
    (a, a_vmem), (b, b_vmem) = _gathers(hlo, p)
    assert a == b and a_vmem and b_vmem


def _two_table_program(s, p, na, nb, n_out):
    def run(idx, av, bv):
        return _bilinear_contract(n_out)(idx, av, bv)

    return _compile(run, tuple(s((p,), jnp.int32) for _ in range(3)),
                    s((na,), jnp.float32), s((nb,), jnp.float32)).as_text()


@pytest.fixture
def v5e_limits(topo, monkeypatch):
    """The described chip's prefetch limits, as the size rule reads them."""
    limits = runtime.PREFETCH_LIMITS[topo.devices[0].device_kind]
    monkeypatch.setattr(runtime, "prefetch_limits", lambda: limits)
    return limits


def test_two_tables_above_the_prefetch_limit(one_chip, compiled_mode,
                                             v5e_limits):
    """The cross-program limit is the chip's: a table of its size is
    prefetched and one value more is not, so both gathers would read HBM.
    Above it the size rule picks the two-table form, which still
    prefetches one operand's values (the larger's)."""
    s = _spec(one_chip)
    n = v5e_limits.cross_program_bytes // 4
    p, _, n_out = TABLE1
    na, nb = n // 2, n - n // 2
    hlo = _one_table_program(s, p, na, nb, n_out)
    assert all(vmem for _, vmem in _gathers(hlo, p))
    assert jax_stream.table_form(na, nb + 1, p, 4) == "two"
    hlo = _one_table_program(s, p, na, nb + 1, n_out)
    assert not any(vmem for _, vmem in _gathers(hlo, p))
    hlo = _two_table_program(s, p, na, nb + 1, n_out)
    assert [vmem for _, vmem in _gathers(hlo, p)].count(True) == 1


def _in_vmem(hlo: str, p: int) -> list:
    """Per gather of ``p`` values: does it read any VMEM copy (a
    cross-program or an ordinary prefetch)?"""
    entry = hlo[hlo.index("\nENTRY"):]
    copies = set(re.findall(
        r"%(\S+) = f32\[\d+\]\{\S*S\(1\)\} copy-done", entry))
    return [operand in copies for operand, _ in _gathers(hlo, p)]


@pytest.mark.parametrize("p,nnz,both", [
    (TABLE1[0], TABLE1[1], True),
    (TABLE1[0], 261_120, True),              # other_bytes of f32
    (TABLE1[0], 261_121, False),
    (117_440_512, TABLE1[1], True),          # other_products
    (117_440_513, TABLE1[1], False),
    (KRON14[0], KRON14[1], False),
], ids=["table1", "other_bytes", "other_bytes+1", "other_products",
        "other_products+1", "kron14"])
def test_one_table_where_two_leave_a_gather_in_hbm(one_chip, compiled_mode,
                                                   v5e_limits, p, nnz, both):
    """Two tables reach VMEM together only while the smaller operand fits
    the ordinary prefetch and the gathers are short enough: the size rule
    keeps two tables exactly there, and packs one table elsewhere."""
    hlo = _two_table_program(_spec(one_chip), p, nnz, nnz, 100_000)
    assert all(_in_vmem(hlo, p)) is both
    assert jax_stream.table_form(nnz, nnz, p, 4) == ("two" if both
                                                     else "one")
