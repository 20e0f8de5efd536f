"""CPU tests of the ``table1_dense18.hspa`` cell: the committed patterns
of the 18 denser Table-1 matrices, the harness finding every part of the
cell, and the readers of the per-group Pallas lowering
(``pallas_spans.py``) on a hand-built window."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "data"))

import harness  # noqa: E402
import make_table1_dense18  # noqa: E402
import pallas_spans  # noqa: E402
import program_spans  # noqa: E402
import work  # noqa: E402
from repro.core.spans import Span  # noqa: E402
from repro.sparse.suitesparse import SUITESPARSE_TABLE1  # noqa: E402

BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
CELL = "table1_dense18.hspa"
SPECS = SUITESPARSE_TABLE1[22:]
NEW = ["group_kernel_ms.replay", "spa_roofline.replay",
       "spars_roofline.replay", "tile_readback_ms.replay",
       "compact_ms.replay"]


@pytest.fixture(scope="module")
def matrices():
    cfg = json.loads((HERE / "configs/table1_dense18.json").read_text())
    return {m.name: m for m in harness.generate(cfg, 0)}


@pytest.mark.parametrize("spec", SPECS, ids=[s.name for s in SPECS])
def test_dense18_patterns_have_the_published_sizes(matrices, spec):
    m = matrices[spec.name]
    deg = np.diff(m.indptr)
    assert (m.n, m.nnz) == (spec.n, spec.nnz)
    assert (deg.min(), deg.max()) == (spec.nnz_min, spec.nnz_max)
    cols = np.repeat(np.arange(m.n), deg)
    assert np.all(np.diff(cols * m.n + m.indices) > 0)   # rows ascending
    assert m.indices.min() >= 0 and m.indices.max() < m.n


def test_dense18_round_is_the_published_products(matrices):
    assert list(matrices) == [s.name for s in SPECS]
    assert sum(work.products(m.indptr, m.indices)
               for m in matrices.values()) == 17_153_989


@pytest.mark.parametrize("spec", SPECS, ids=[s.name for s in SPECS])
def test_dense18_patterns_are_what_the_script_regenerates(matrices, spec):
    indptr, indices = make_table1_dense18.pattern(spec)
    m = matrices[spec.name]
    assert np.array_equal(indptr, m.indptr)
    assert np.array_equal(indices, m.indices)


def test_resolve_finds_every_part_of_the_cell():
    cell = harness.resolve(BENCH, CELL)
    assert cell.chips == 1
    assert cell.config["name"] == "table1_dense18"
    assert cell.config["reduced"] == []
    assert cell.mix == {"loop": "replay", "backend": "pallas",
                        "method": "h-spa-40/40", "value_sets": 4}
    assert {m["name"] for m in cell.end_to_end} == {
        "replay_products_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(NEW) | {
        "replay_roofline", "replay_device_ms", "host_ms_per_call.replay",
        "device_idle_pct.replay", "execute_ms.replay",
        "idle_in_execute_ms.replay", "idle_outside_program_ms.replay"}
    for m in cell.end_to_end + cell.per_layer:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()


# -- the readers, on a hand-built window of two calls ----------------------

T0 = 1000.0                     # the window's start, perf_counter seconds
LO = 5e9                        # bench.window's start on the trace clock
WINDOW_NS = 100_000
PRODUCTS = [70, 30]             # the two calls' multiplies


def _span(name, start, end, request, attrs=None):
    base = int(T0 * 1e9)
    return Span(name, base + start, base + end, 0, request, request,
                attrs or {})


def _group(start, request, kind, products):
    return _span("spgemm.pallas.group", start, start + 5_000, request,
                 {"kind": kind, "cols": 128, "zb": 8, "za": 8,
                  "products": products})


# call 1 (10-40 us): SPA 50 + SPARS 20 products; call 2 (50-80 us): SPA 30
RECORDS = [
    _span("spgemm.execute", 10_000, 40_000, 1),
    _group(12_000, 1, "spa", 50),
    _span("spgemm.pallas.readback", 17_000, 19_000, 1),
    _span("spgemm.pallas.compact", 19_000, 20_000, 1),
    _group(21_000, 1, "spars", 20),
    _span("spgemm.pallas.readback", 26_000, 27_000, 1),
    _span("spgemm.pallas.compact", 27_000, 30_000, 1),
    _span("spgemm.execute", 50_000, 80_000, 2),
    _group(52_000, 2, "spa", 30),
    _span("spgemm.pallas.readback", 57_000, 60_000, 2),
    _span("spgemm.pallas.compact", 60_000, 62_000, 2),
    _group(-9_000, 0, "spa", 99),                  # before the window
]
# device seconds by operation: the kernels' custom calls and others
OPS = {"spa_spgemm.1": 4e-6, "_spa_spgemm.3 = f32[8,128]": 2e-6,
       "spars_spgemm.1": 1e-6, "fusion.2": 5e-6}
LEAST = np.array([7e-8, 3e-8])  # the calls' least times


def _ctx(loop="replay", busy=True, least=LEAST):
    busy_iv = {"/device:TPU:0": LO + np.array([[15_000.0, 25_000.0]])} \
        if busy else {}
    trace = SimpleNamespace(busy=busy_iv, busy_s=1e-5 if busy else 0.0,
                            lo=LO, hi=LO + WINDOW_NS,
                            window_s=WINDOW_NS * 1e-9, op_time=OPS)
    window = SimpleNamespace(loop=loop, window_start=T0,
                             window_s=WINDOW_NS * 1e-9,
                             latencies=np.zeros(2),
                             products=np.array(PRODUCTS))
    return SimpleNamespace(window=window, trace=trace, least_time_s=least)


def _read(name, ctx, monkeypatch, records=RECORDS, dropped=0):
    monkeypatch.setattr(program_spans, "recorded",
                        lambda: None if records is None
                        else (records, dropped))
    return harness.load_module(HERE / "metrics" / f"{name}.py").read(ctx)


WANT = {
    # (4 + 2 + 1) us of kernels over 2 calls
    "group_kernel_ms.replay": 7e-6 / 2 * 1e3,
    # SPA: 7e-8 * 50/70 + 3e-8 * 30/30 over 6 us of SPA kernels
    "spa_roofline.replay": 100 * (7e-8 * 50 / 70 + 3e-8) / 6e-6,
    # SPARS: 7e-8 * 20/70 over 1 us
    "spars_roofline.replay": 100 * (7e-8 * 20 / 70) / 1e-6,
    "tile_readback_ms.replay": (2 + 1 + 3) * 1e-3 / 2,
    "compact_ms.replay": (1 + 3 + 2) * 1e-3 / 2,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_hand_computed_value(name, monkeypatch):
    got = _read(name, _ctx(), monkeypatch)
    assert got == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["no_busy", "other_loop", "no_spans",
                                  "dropped"])
def test_reader_gives_nothing_where_there_is_nothing_to_read(
        name, case, monkeypatch):
    records, dropped = RECORDS, 0
    if case == "no_spans":
        records = None
    elif case == "dropped":
        dropped = 1
    ctx = _ctx(loop="churn" if case == "other_loop" else "replay",
               busy=case != "no_busy")
    got = _read(name, ctx, monkeypatch, records, dropped)
    if name == "group_kernel_ms.replay" and case in ("no_spans", "dropped"):
        assert got == pytest.approx(WANT[name])   # the trace alone
    else:
        assert got is None


def test_roofline_is_none_without_a_launch_of_its_kind(monkeypatch):
    records = [r for r in RECORDS if r.attrs.get("kind") != "spars"]
    one_kind = [r if r.name != "spgemm.pallas.group" or r.start_ns
                != int(T0 * 1e9) + 12_000
                else r._replace(attrs={**r.attrs, "products": 70})
                for r in records]
    assert _read("spars_roofline.replay", _ctx(), monkeypatch,
                 one_kind) is None
    assert _read("spa_roofline.replay", _ctx(), monkeypatch,
                 one_kind) == pytest.approx(100 * 1e-7 / 6e-6)


def test_roofline_refuses_products_that_do_not_add_up(monkeypatch):
    short = [r._replace(attrs={**r.attrs, "products": 19})
             if r.attrs.get("kind") == "spars" else r for r in RECORDS]
    with pytest.raises(ValueError, match="add up to 69"):
        _read("spa_roofline.replay", _ctx(), monkeypatch, short)
    assert _read("spa_roofline.replay", _ctx(least=None), monkeypatch,
                 short) is None
