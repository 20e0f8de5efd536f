"""CPU tests of the benchmark's yardstick: work, trace reduction, the
reference and its comparison, the churn edit, and BENCHMARK.json."""

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())

# A = [[1, 0, 2], [0, 3, 0], [4, 0, 5]] in CSC
A_INDPTR = np.array([0, 2, 3, 5])
A_INDICES = np.array([0, 2, 1, 0, 2])
A_VALUES = np.array([1.0, 4.0, 3.0, 2.0, 5.0])


def test_work_of_a_hand_counted_product():
    # B = A: entries (0,0) (2,0) (1,1) (0,2) (2,2) meet columns of A with
    # 2, 2, 1, 2, 2 entries; C = A·A stores (0,0) (2,0) (1,1) (0,2) (2,2)
    assert work.products(A_INDPTR, A_INDICES) == 9
    w = work.multiply_work(A_INDPTR, A_INDICES, 5, 5, 5)
    assert w == {"products": 9, "flops": 18, "bytes": 8 * 15}
    t, bound = work.least_time_s(w, {"hbm_bytes_per_s": 120.0,
                                     "flops_per_s": 36.0})
    assert (t, bound) == (1.0, "bytes")
    t, bound = work.least_time_s(w, {"hbm_bytes_per_s": 240.0,
                                     "flops_per_s": 9.0})
    assert (t, bound) == (2.0, "flops")


def test_peaks_know_the_chip_and_refuse_other_kinds():
    assert work.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.load_peaks("cpu")


def test_reference_matches_a_dense_product():
    rng = np.random.default_rng(3)
    dense = np.where(rng.random((20, 20)) < 0.15,
                     rng.uniform(0.5, 1.5, (20, 20)), 0.0)
    cols = [np.nonzero(dense[:, j])[0] for j in range(20)]
    indptr = np.concatenate([[0], np.cumsum([len(c) for c in cols])])
    indices = np.concatenate(cols)
    values = np.concatenate([dense[c, j] for j, c in enumerate(cols)])
    pat = (indptr, indices, (20, 20))
    c_ptr, c_idx, c_val = reference.product(pat, pat, values, values)
    want = dense @ dense
    got = np.zeros((20, 20))
    for j in range(20):
        got[c_idx[c_ptr[j]:c_ptr[j + 1]], j] = c_val[c_ptr[j]:c_ptr[j + 1]]
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert (got != 0).sum() == (want != 0).sum() == c_ptr[-1]


def test_reference_of_the_hand_counted_product():
    pat = (A_INDPTR, A_INDICES, (3, 3))
    c_ptr, c_idx, c_val = reference.product(pat, pat, A_VALUES, A_VALUES)
    assert c_ptr.tolist() == [0, 2, 3, 5]
    assert c_idx.tolist() == [0, 2, 1, 0, 2]
    assert c_val.tolist() == [9.0, 24.0, 9.0, 12.0, 33.0]


def test_compare_counts_structure_and_relative_error():
    ref = (np.array([0, 2, 3]), np.array([0, 1, 1]), np.array([2.0, 4.0, 8.0]))
    same = reference.compare((ref[0], ref[1], np.array([2.0, 4.0, 8.0])),
                             ref, 2)
    assert same == {"structure_mismatches": 0, "max_rel_err": 0.0}
    off = reference.compare((ref[0], ref[1], np.array([2.0, 4.2, 8.0])),
                            ref, 2)
    assert off["max_rel_err"] == pytest.approx(0.05)
    # column 0 stores row 0 only: one entry missing, the rest compared
    short = reference.compare((np.array([0, 1, 2]), np.array([0, 1]),
                               np.array([2.0, 8.0])), ref, 2)
    assert short == {"structure_mismatches": 1, "max_rel_err": 0.0}


def test_reference_refuses_negative_values():
    pat = (A_INDPTR, A_INDICES, (3, 3))
    with pytest.raises(ValueError):
        reference.product(pat, pat, -A_VALUES, A_VALUES)


def test_bf16_control_rounds_to_eight_bits():
    v = reference.bf16_values(np.array([1.0 + 2.0 ** -10], np.float32))
    assert v[0] == 1.0


def test_merge_and_covered_on_hand_intervals():
    iv = np.array([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [7.0, 8.0]])
    merged = trace_reduce.merge(iv)
    assert merged.tolist() == [[0.0, 3.0], [5.0, 8.0]]
    lo = np.array([0.0, 2.0, 4.0, -1.0])
    hi = np.array([10.0, 6.0, 5.0, 0.5])
    assert trace_reduce.covered(merged, lo, hi).tolist() == [6.0, 2.0, 0.0,
                                                             0.5]


def _union_length(intervals):
    """Busy length by a plain sweep, independent of ``merge``."""
    total, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def test_reduce_a_recorded_chip_trace():
    fixture = json.loads((HERE / "fixtures" /
                          "trace_kron13_two_replays.json").read_text())
    events = [tuple(e) for e in fixture["events"]]
    r = trace_reduce.Reduced(events)
    (window,) = [e for e in events if e[2] == "bench.window"]
    assert r.window_s == pytest.approx(window[4] * 1e-9)
    ops = [(e[3], e[3] + e[4]) for e in events if e[1] == "XLA Ops"]
    assert r.busy_s == pytest.approx(_union_length(ops) * 1e-9)
    assert 0.99 < r.busy_s / r.window_s < 1.0   # one long program a call
    calls = r.span_s("bench.call")
    assert len(calls) == 2
    inside = r.busy_in("bench.call")
    assert np.all(inside <= calls) and np.all(inside > 0.99 * calls)
    gaps = r.idle_gaps()
    assert set(gaps) <= {"bench.call", "bench.window"}
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    # the device starts 1.2 ms into the first call: that gap is the call's
    assert gaps["bench.call"] > 1e-3
    b = r.breakdown()
    top = b["device_ops"][0]
    assert top[0].startswith("%fusion.2 = f32[13502979]")   # segment_sum
    assert top[1] == pytest.approx(r.op_time[next(
        n for n in r.op_time if n.startswith("%fusion.2 "))])
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_needs_one_window():
    with pytest.raises(RuntimeError):
        trace_reduce.Reduced([("/host:CPU", "python3", "bench.call", 0, 1)])


def test_churn_edit_keeps_column_degrees_and_changes_the_pattern():
    import harness

    (m,) = harness.generate({"generator": "table1", "params": {
        "file": "table1_sparse22.npz",
        "matrices": [{"name": "rajat03", "n": 7602, "nnz": 32653}]}}, 0)
    n = m.n
    e = traffic.churn_edit(m, 0.02, np.random.default_rng(1))
    assert np.array_equal(e.indptr, m.indptr)
    moved = 0
    for j in range(n):
        old = m.indices[m.indptr[j]:m.indptr[j + 1]]
        new = e.indices[e.indptr[j]:e.indptr[j + 1]]
        assert np.all(np.diff(new) > 0) and new.min(initial=0) >= 0
        assert new.max(initial=0) < n
        moved += len(np.setdiff1d(new, old))
    assert 0.9 * round(0.02 * m.nnz) <= moved <= round(0.02 * m.nnz)
    assert work.products(e.indptr, e.indices) != \
        work.products(m.indptr, m.indices)


def test_churn_edit_moves_at_least_one_entry():
    m = traffic.Matrix("a", A_INDPTR, A_INDICES, 3)
    e = traffic.churn_edit(m, 0.0, np.random.default_rng(0))
    assert not np.array_equal(e.indices, m.indices)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_names_files_that_exist():
    root = HERE.parents[1]
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert (root / BENCH["command"][1]).is_file()
    assert isinstance(BENCH["run_seconds"], int) \
        and 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and (root / c["file"]).is_file()
        assert c["file"].startswith("benchmarks/chip/")
        assert json.loads((root / c["file"]).read_text())["name"] == \
            c["name"]
        assert all(NAME.match(k) for k in c["reduced"])
    cells = set()
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cells.add(w["name"])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for cell in cells:
        reported = {m["name"] for m in BENCH["end_to_end"]
                    if cell in m.get("workloads", [cell])}
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])
        for m in BENCH["per_layer"]:
            if cell in m["workloads"]:
                assert m["moves"] in reported


def test_a_full_check_fits_its_time():
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (BENCH["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200
