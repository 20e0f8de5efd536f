"""The per-group kernels' gather of A's columns from int8 byte planes
(``kernels/spa.py``, DESIGN.md §2): exact to the bit, as many row-code
planes as C's row count needs, and SPA, SPARS and their batched forms
bit-equal to the f32 oracle (``kernels/ref.py``, interpret mode).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.kernels import spa, spars, vmem
from repro.kernels.ref import spars_ref, spgemm_padded_ref
from repro.sparse import csc_to_padded_columns
from repro.sparse.format import CSC


def _f32(bits):
    return np.asarray(bits, np.uint32).view(np.float32)


#: ±0, the smallest denormal, the largest finite, ±inf, a quiet NaN with a
#: payload, a signalling NaN, a negative NaN
SPECIAL = _f32([0x00000000, 0x80000000, 0x00000001, 0x80000001,
                0x7F7FFFFF, 0xFF7FFFFF, 0x7F800000, 0xFF800000,
                0x7FC12345, 0x7F800001, 0xFFC00000])


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@pytest.mark.parametrize("m,count", [
    (1, 1), (255, 1), (256, 2), (257, 2), (3001, 2), (65_535, 2),
    (65_536, 3), (65_537, 3), (2**24 - 1, 3), (2**24, 4)])
def test_row_planes_hold_every_row_code(m, count):
    """Row codes run from 0 (padding) to ``m`` (row id ``m - 1``), so a
    plane more is taken where ``m`` outgrows one, two and three bytes
    (m = 256, 65,536, 2**24); the codes come back from that many planes,
    and the largest not from one fewer."""
    assert vmem.row_planes(m) == count
    codes = jnp.array([[0, 1, m - 1, m]], jnp.int32)

    def round_trip(n):
        planes = spa.byte_planes(codes, n)
        assert planes.dtype == jnp.int8 and planes.shape == (n, 4)
        # the int8 dot reads each byte as the int32 it sign-extends to
        return np.asarray(spa.from_bytes(
            list(planes.astype(jnp.int32)[:, None, :])))

    assert np.array_equal(round_trip(count), np.asarray(codes))
    if count > 1:
        assert round_trip(count - 1)[0, 3] != m


@pytest.mark.parametrize("kind", ["spa", "spars"])
@pytest.mark.parametrize("m", [256, 3001, 65_536, 70_000])
def test_plane_gather_returns_the_table_to_the_bit(kind, m):
    """One int8 one-hot dot of A's table, put back together, is A's
    lane-major row ids and f32 values bit for bit: special values and
    row ids 0, 255, 256, 65,535 and ``m - 1`` included; padding reads
    row -1 and value +0, as does a B padding entry's all-zero one-hot;
    SPARS's spare row holds A's column lengths."""
    rng = np.random.default_rng(m)
    n, z = 40, 16
    rows = rng.integers(0, m, (n, z)).astype(np.int32)
    ids = [r for r in (0, 255, 256, 65_535, m - 1) if r < m]
    rows[: len(ids), 0] = ids
    vals = rng.standard_normal((n, z)).astype(np.float32)
    vals[: len(SPECIAL), 1] = SPECIAL
    vals[-len(SPECIAL):, z - 1] = SPECIAL
    nnz = rng.integers(0, z + 1, n).astype(np.int32)
    nnz[: len(SPECIAL)] = z                     # the special values count
    nnz[-1] = 0                                 # an empty column

    table = spa.a_table(jnp.asarray(rows), jnp.asarray(vals),
                        jnp.asarray(nnz), m=m, kind=kind)
    planes = vmem.row_planes(m)
    zt = vmem.table_rows(kind, z)
    assert table.dtype == jnp.int8
    assert table.shape == ((planes + vmem.VALUE_PLANES) * zt, 128)

    k = jnp.arange(-1, n)                       # -1: a B padding entry
    oh = (jax.lax.broadcasted_iota(jnp.int32, (128, n + 1), 0)
          == k).astype(jnp.int8)
    g = jnp.dot(table, oh, preferred_element_type=jnp.int32)
    r, v = spa.gathered([g[p * zt:(p + 1) * zt]
                         for p in range(planes + vmem.VALUE_PLANES)], planes)
    r, v = np.asarray(r), np.asarray(v)

    valid = (np.arange(z)[None, :] < nnz[:, None]).T
    assert np.array_equal(r[:z, 1:], np.where(valid, rows.T, -1))
    assert np.array_equal(_bits(v[:z, 1:]),
                          _bits(np.where(valid, vals.T, np.float32(0))))
    assert (r[z + (kind == "spars"):] == -1).all()
    assert (r[:, 0] == -1).all() and (_bits(v[:, 0]) == 0).all()
    if kind == "spars":
        lengths = spa.from_bytes([g[p * zt + z:p * zt + z + 1]
                                  for p in range(planes)])
        assert np.array_equal(np.asarray(lengths)[0, 1:], nnz)


def _arrow(n: int) -> sp.csc_matrix:
    """``iprob``'s shape: column 0 full, every other column its diagonal
    and row 0."""
    r = np.concatenate([np.arange(n), np.arange(1, n), np.zeros(n - 1)])
    c = np.concatenate([np.zeros(n), np.arange(1, n), np.arange(1, n)])
    return sp.csc_matrix((np.ones(len(r)), (r, c)), shape=(n, n))


def _random(n: int) -> sp.csc_matrix:
    return sp.random(n, n, density=0.04, format="csc",
                     random_state=np.random.default_rng(n))


PATTERNS = {"arrow300": lambda: _arrow(300), "random96": lambda: _random(96)}


def _operands(pattern: str, block: int, batch: int | None = None):
    """A's padded columns and B (= A's pattern) padded to whole blocks,
    values uniform of either sign from a seed (``batch`` sets of them),
    and SPARS's per-block steps."""
    m = PATTERNS[pattern]()
    m.sum_duplicates()
    m.sort_indices()
    n = m.shape[0]
    r, _, z = (np.asarray(x) for x in csc_to_padded_columns(CSC(
        np.ones(m.nnz, np.float32), m.indices.astype(np.int32),
        m.indptr.astype(np.int32), m.shape)))
    rng = np.random.default_rng(n)
    shape = (batch or 1, n, r.shape[1])
    vals = rng.uniform(-1.5, 1.5, shape).astype(np.float32) * (
        np.arange(r.shape[1])[None, None, :] < z[None, :, None])
    n_pad = -(-n // block) * block
    pad = ((0, n_pad - n), (0, 0))
    br, bn = np.pad(r, pad), np.pad(z, (0, n_pad - n))
    bv = np.pad(vals, ((0, 0),) + pad)
    ops = np.array([z[br[j, : bn[j]]].sum() for j in range(n_pad)])
    steps = ops.reshape(-1, block).max(axis=1).astype(np.int32)
    if batch is None:
        vals, bv = vals[0], bv[0]
    return n, (r.astype(np.int32), vals, z.astype(np.int32),
               br.astype(np.int32), bv, bn.astype(np.int32)), steps


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("kind", ["spa", "spars"])
def test_kernels_match_the_f32_oracle_to_the_bit(kind, pattern):
    """C, and SPARS's flags, bit for bit as ``kernels/ref.py`` computes
    them in f32, on an ``iprob``-like arrow (two planes of row codes) and
    a random pattern (one)."""
    n, args, steps = _operands(pattern, 32)
    args = tuple(jnp.asarray(x) for x in args)
    if kind == "spa":
        got = spa.spa_spgemm(*args, m=n, block_cols=32)
        want = spgemm_padded_ref(*args, n)
        assert np.array_equal(_bits(got), _bits(want))
    else:
        got, flags = spars.spars_spgemm(*args, jnp.asarray(steps), m=n,
                                        block_cols=32)
        want, want_flags = spars_ref(*args, n)
        assert np.array_equal(_bits(got), _bits(want))
        assert np.array_equal(np.asarray(flags), np.asarray(want_flags))


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("kind", ["spa", "spars"])
def test_batched_kernels_match_the_f32_oracle_to_the_bit(kind, pattern):
    """Each slice of a batched launch (two value sets) bit for bit as the
    oracle computes that set."""
    n, (ar, av, an, br, bv, bn), steps = _operands(pattern, 32, batch=2)
    pat = tuple(jnp.asarray(x) for x in (ar, an, br, bn))
    if kind == "spa":
        got = spa.spa_spgemm_batched(pat[0], jnp.asarray(av), pat[1],
                                     pat[2], jnp.asarray(bv), pat[3],
                                     m=n, block_cols=32)
    else:
        got, _ = spars.spars_spgemm_batched(
            pat[0], jnp.asarray(av), pat[1], pat[2], jnp.asarray(bv),
            pat[3], jnp.asarray(steps), m=n, block_cols=32)
    for b in range(2):
        want = spgemm_padded_ref(pat[0], jnp.asarray(av[b]), pat[1],
                                 pat[2], jnp.asarray(bv[b]), pat[3], n)
        assert np.array_equal(_bits(got[b]), _bits(want))
