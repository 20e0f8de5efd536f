"""Run one cell of the chip benchmark once and print its result line.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

- its configuration: the file the ``configs`` entry names, whose
  ``generator`` is a module ``data/<generator>.py`` with
  ``generate(params, seed)``;
- its traffic mix: ``traffic/<traffic>.json``, whose ``loop`` names a
  loop of :mod:`traffic` and whose ``backend`` and ``method`` name the
  program's entry point;
- each metric: ``metrics/<name>.py`` with ``read(ctx)``, returning the
  number or ``None`` when the run has nothing to read it from.

A run warms up in set-up, measures one window, reads the device's peak
memory, releases the program's plans, and then checks the answers it kept
against the plain reference (:mod:`reference`).  With ``--trace 0`` the
result line carries the cell's end-to-end metrics; with ``--trace 1`` the
window is traced and the line carries its per-layer metrics, the device's
busy and window seconds, and a ``breakdown``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: JAX's persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = ROOT / ".jax_cache" / "bench"


class Refused(Exception):
    """The run cannot be made here; it exits non-zero with no result."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError as e:
        raise Refused(f"missing {path}") from e


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path):
    """A benchmark file loaded by its path (names may hold dots)."""
    if not path.is_file():
        raise Refused(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def resolve(bench: dict, workload: str) -> Cell:
    """The cell ``workload`` of ``bench`` with its files loaded."""
    w = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], w["config"], "configuration")
    config = load_json(ROOT / entry["file"])
    mix = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(int(w["chips"]), config, mix, e2e, per_layer)


def generate(config: dict, seed: int) -> list:
    from traffic import Matrix

    gen = load_module(HERE / "data" / f"{config['generator']}.py")
    return [Matrix(*m) for m in gen.generate(config["params"], seed)]


def devices_for(chips: int, require_tpu: bool) -> list:
    import jax

    import work

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise Refused(f"needs a TPU, JAX found {devs[0].platform!r}")
        try:
            work.load_peaks(devs[0].device_kind)
        except KeyError as e:
            raise Refused(str(e)) from e
        if len(devs) < chips:
            raise Refused(f"the cell needs {chips} chips, JAX found "
                          f"{len(devs)}")
    return devs[:chips]


class Tracer:
    """Profiles the window into a temporary directory when ``on``."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None

    def start(self):
        if self.on:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def reduce(self):
        import trace_reduce

        try:
            return trace_reduce.Reduced(trace_reduce.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    window: object                        # traffic.Window
    setup_s: float
    trace: object = None                  # trace_reduce.Reduced
    least_time_s: np.ndarray | None = None   # per call, from work.py


def check(answers: list, limits: dict) -> tuple:
    """``(numbers, failed, nnz_c)``: each compared number's worst value
    over the answers, how many answers broke a limit, and the reference
    C's size of each matrix answered."""
    import reference

    refs = {}
    worst = {k: 0 for k in limits}
    failed = 0
    for a in answers:
        key = (id(a.matrix), id(a.values))
        if key not in refs:
            refs[key] = reference.product(a.matrix.pattern, a.matrix.pattern,
                                          a.values, a.values)
        got = reference.compare((a.c_indptr, a.c_indices, a.c_values),
                                refs[key], a.matrix.n)
        failed += any(got[k] > limits[k] for k in limits)
        for k in limits:
            worst[k] = max(worst[k], got[k])
    nnz_c = {id(a.matrix): len(refs[(id(a.matrix), id(a.values))][1])
             for a in answers}
    return worst, failed, nnz_c


def least_times(window, nnz_c: dict, kind: str) -> np.ndarray | None:
    """Least time of every call of the window at the chip's peaks, or
    ``None`` where a call's matrix has no reference C size."""
    import work

    try:
        peaks = work.load_peaks(kind)
    except KeyError:
        return None
    per_matrix = []
    for m in window.matrices:
        if id(m) not in nnz_c:
            return None
        w = work.multiply_work(m.indptr, m.indices, m.nnz, m.nnz,
                               nnz_c[id(m)])
        per_matrix.append(work.least_time_s(w, peaks)[0])
    return np.array(per_matrix)[window.call_matrix]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, program=None,
             require_tpu: bool = True) -> dict:
    """One run of one cell: its result line as a dict.  ``program`` is the
    class of the system under test, :class:`traffic.Program` unless a
    control or a fault stands in for it."""
    import traffic

    cell = resolve(bench, workload)
    program = (program or traffic.Program)(cell.mix["backend"],
                                           cell.mix["method"])
    devices = devices_for(cell.chips, require_tpu)
    loop = traffic.LOOPS[cell.mix["loop"]]
    tracer = Tracer(trace)
    window = loop(generate(cell.config, seed), cell.mix, cell.config, seed,
                  seconds, program,
                  traffic.CompileClock(), tracer)
    t_window = time.perf_counter()
    mem = memory_peak(devices)
    for a in window.answers:
        a.c_values = np.asarray(a.c_values)
    program.release()
    gc.collect()
    t_release = time.perf_counter()
    reduced = tracer.reduce() if trace else None
    t_reduce = time.perf_counter()
    numbers, failed, nnz_c = check(window.answers, cell.config["check"])
    lat = window.latencies * 1e3
    slow = np.argsort(lat)[::-1][:3]
    print(f"run.py: {len(lat)} calls, latency min {lat.min():.3f} median "
          f"{np.median(lat):.3f} max {lat.max():.3f} ms, slowest calls "
          f"{slow.tolist()} at {lat[slow].round(3).tolist()} ms",
          file=sys.stderr)
    print(f"run.py: set-up {window.window_start - t_start:.3f} s, window "
          f"{window.window_s:.3f} s, pull-back and release "
          f"{t_release - t_window:.3f} s, trace reduction "
          f"{t_reduce - t_release:.3f} s, check {time.perf_counter() - t_reduce:.3f} s of "
          f"{len(window.answers)} answers", file=sys.stderr)
    ctx = Context(window, window.window_start - t_start, reduced,
                  least_times(window, nnz_c, devices[0].device_kind))
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d = devices[0]
    out = {"correct": failed == 0, "attempted": int(len(window.latencies)),
           "failed": int(failed), "metrics": metrics,
           "device": {"platform": d.platform, "kind": d.device_kind,
                      "count": len(devices), "memory_peak_bytes": mem}}
    if reduced is not None:
        out["device"].update(busy_s=reduced.busy_s,
                             window_s=reduced.window_s)
        out["breakdown"] = reduced.breakdown()
    out["check"] = {k: {"value": numbers[k], "limit": cell.config["check"][k]}
                    for k in cell.config["check"]}
    return out


def parse(argv: list) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="run.py", description="Run one cell of the chip benchmark.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare() -> dict:
    """``BENCHMARK.json``, with the program importable and JAX's
    persistent compilation cache in the checkout."""
    bench = load_json(ROOT / "BENCHMARK.json")
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    # the program and JAX both take the cache directory from here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro import runtime

    runtime.enable_compile_cache()
    return bench


def main(argv: list, t_start: float) -> int:
    args = parse(argv)
    try:
        out = run_cell(prepare(), args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0
