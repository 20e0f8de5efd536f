"""CPU tests of whole runs: each traffic loop end to end at a tiny size
through the harness's internals, the control and the planted faults
coming out not correct, ``run.py`` refusing what is not a chip, and a
cell added from new files alone."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import controls  # noqa: E402
import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY_KRON = {"scale": 6, "edgefactor": 16, "A": 0.57, "B": 0.19,
             "C": 0.19, "D": 0.05}
SEED = 2 ** 40 + 17          # seeds may be wider than 32 bits
SECONDS = 0.3


@pytest.fixture
def bench(tmp_path):
    """BENCHMARK.json with both configurations cut to a tiny Kronecker
    graph, and a plan cache small enough for the churn pool."""
    from repro.core import api

    b = json.loads(json.dumps(BENCH))
    for c in b["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(generator="graph500", params=TINY_KRON)
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    size = api.plan_cache_info()["max_size"]
    api.plan_cache_resize(4)
    yield b
    api.plan_cache_resize(size)
    api.plan_cache_clear()


def run(bench, cell, program=None, trace=False):
    return harness.run_cell(bench, cell, SEED, SECONDS, trace,
                            time.perf_counter(), program=program,
                            require_tpu=False)


CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_each_cell_runs_and_is_correct(bench, cell, trace):
    out = run(bench, cell, trace=trace)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "check"
    assert out["check"]["structure_mismatches"]["value"] == 0
    assert out["check"]["max_rel_err"]["value"] < 1e-5
    names = set(out["metrics"])
    if trace:
        # the CPU has no device trace: only host-clock readers answer
        assert "busy_s" in out["device"] and "breakdown" in out
        assert names <= {"plan_build_ms.churn", "compile_ms.churn",
                         "executables_per_miss.churn"}
        if "executables_per_miss.churn" in names:
            # every new pattern gets an executable of its own today
            assert out["metrics"]["executables_per_miss.churn"]["value"] >= 1
    else:
        want = {m["name"] for m in BENCH["end_to_end"]
                if cell in m.get("workloads", [cell])}
        assert names == want
        assert all(v["value"] > 0 for v in out["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("make", [controls.Control, *controls.FAULTS.values()],
                         ids=["control", *controls.FAULTS])
def test_control_and_faults_are_not_correct(bench, cell, make):
    out = run(bench, cell, program=make)
    assert not out["correct"] and out["failed"] > 0


def _run_py(cwd, *extra, env=None):
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "benchmarks/chip/run.py"),
         "--workload", "table1_sparse22.replay", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env=env or dict(os.environ, JAX_PLATFORMS="cpu"))


def test_run_py_refuses_the_cpu():
    p = _run_py(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_py_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_cell_is_added_by_new_files_alone(tmp_path):
    """A new configuration, traffic mix and per-layer metric: new files
    and new BENCHMARK.json entries, no existing file edited."""
    shutil.copytree(HERE, tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    chip = tmp_path / "benchmarks/chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    b = json.loads(json.dumps(BENCH))
    (chip / "configs/kron6_dummy.json").write_text(json.dumps({
        "name": "kron6_dummy", "generator": "graph500", "params": TINY_KRON,
        "values": [1.0, 2.0], "check": {"structure_mismatches": 0,
                                        "max_rel_err": 1e-4}}))
    (chip / "traffic/replay_two_sets.json").write_text(json.dumps(
        {"loop": "replay", "backend": "jax", "method": "expand",
         "value_sets": 2}))
    (chip / "metrics/calls_dummy.py").write_text(
        "def read(ctx):\n    return len(ctx.window.latencies)\n")
    b["configs"].append({"name": "kron6_dummy", "source": "dummy",
                         "file": "benchmarks/chip/configs/kron6_dummy.json",
                         "reduced": ["scale"], "why": "dummy"})
    b["workloads"].append({"name": "kron6_dummy.replay_two_sets",
                           "config": "kron6_dummy",
                           "traffic": "replay_two_sets", "chips": 1,
                           "why": "dummy"})
    b["end_to_end"][0]["workloads"].append("kron6_dummy.replay_two_sets")
    b["per_layer"].append({"name": "calls_dummy", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "dummy", "moves": "setup_s",
                           "workloads": ["kron6_dummy.replay_two_sets"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    script = (
        "import json, sys, time\n"
        f"sys.path.insert(0, {str(chip)!r})\n"
        f"sys.path.insert(0, {str(tmp_path / 'src')!r})\n"
        "import harness\n"
        "b = json.load(open(harness.ROOT / 'BENCHMARK.json'))\n"
        "for trace in (False, True):\n"
        "    print(json.dumps(harness.run_cell(b, "
        "'kron6_dummy.replay_two_sets', 5, 0.2, trace, time.perf_counter(),"
        " require_tpu=False)))\n")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    plain, traced = [json.loads(x) for x in p.stdout.strip().splitlines()]
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"replay_products_per_s", "setup_s"}
    assert traced["metrics"]["calls_dummy"]["value"] == traced["attempted"]
    after = {p: p.read_bytes() for p in before}
    assert after == before
