"""``repro.runtime``: interpret mode, kernel refusal, the device guard and the
compile cache follow the platform; guarded device plans fall back loudly."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import runtime
from repro.core import api, cached_plan, fast, plan_spgemm
from repro.sparse import random_powerlaw_csc

REPO = Path(__file__).resolve().parents[1]


def test_cpu_runs_pallas_interpreted():
    assert runtime.platform() == "cpu"
    assert runtime.interpret_mode() is True
    for kernel in ("spa", "spars", "hash", "bsr"):
        runtime.check_kernel(kernel)        # nothing is refused on the CPU


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False)])
def test_interpret_mode_follows_platform(monkeypatch, platform, interpret):
    monkeypatch.setattr(runtime, "platform", lambda: platform)
    assert runtime.interpret_mode() is interpret


def test_pallas_refused_on_other_platforms(monkeypatch):
    monkeypatch.setattr(runtime, "platform", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        runtime.interpret_mode()


@pytest.mark.parametrize("kernel,refused", [("hash", True), ("spa", False),
                                            ("spars", False)])
def test_tpu_refuses_unported_kernels_by_name(monkeypatch, kernel, refused):
    monkeypatch.setattr(runtime, "platform", lambda: "tpu")
    if refused:
        with pytest.raises(NotImplementedError, match=f"'{kernel}'"):
            runtime.check_kernel(kernel)
    else:
        runtime.check_kernel(kernel)


def test_pallas_hash_plan_refused_before_any_launch(monkeypatch):
    """A pallas plan with a HASH group raises before its SPA group runs."""
    monkeypatch.setattr(runtime, "interpret_mode", lambda: False)
    launched = []
    from repro.kernels import ops

    monkeypatch.setattr(ops, "run_spa",
                        lambda *a, **k: launched.append("spa"))
    a = random_powerlaw_csc(40, 3.0, seed=2)
    plan = plan_spgemm(a, a, "h-hash-256/256", backend="pallas", t=8.0,
                       block_cols=8)
    assert {g.kind for g in plan.pallas.groups} >= {"spa", "hash"}
    with pytest.raises(NotImplementedError, match="'hash'"):
        plan.execute(a, a)
    assert launched == []


def test_device_guard_sized_from_chip_memory(monkeypatch):
    class Dev:
        def memory_stats(self):
            return {"bytes_limit": 16 * 2**30}

    runtime.device_stream_limit.cache_clear()
    monkeypatch.setattr(runtime, "platform", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    try:
        want = int(16 * 2**30 * runtime.DEVICE_STREAM_SHARE) \
            // runtime.DEVICE_BYTES_PER_PRODUCT
        assert runtime.device_stream_limit() == want
    finally:
        runtime.device_stream_limit.cache_clear()


def test_device_tile_limit_sized_from_chip_memory(monkeypatch):
    class Dev:
        def memory_stats(self):
            return {"bytes_limit": 16 * 2**30}

    runtime.device_tile_bytes.cache_clear()
    monkeypatch.setattr(runtime, "platform", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    try:
        assert runtime.device_tile_bytes() == int(
            16 * 2**30 * runtime.DEVICE_STREAM_SHARE)
    finally:
        runtime.device_tile_bytes.cache_clear()


@pytest.mark.parametrize("platform, kind, want", [
    ("cpu", None, None),
    ("tpu", "TPU v5 lite",
     runtime.PrefetchLimits(112 * 2**20, 1_044_480, 112 * 2**20)),
    ("tpu", "TPU v9 unmeasured", runtime.PrefetchLimits(0, 0, 0)),
])
def test_prefetch_limits_are_the_chips(monkeypatch, platform, kind, want):
    """No VMEM on the CPU; on a TPU its kind's measured limits, and
    nothing prefetched on a kind not measured."""
    class Dev:
        device_kind = kind

    runtime.prefetch_limits.cache_clear()
    monkeypatch.setattr(runtime, "platform", lambda: platform)
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    try:
        assert runtime.prefetch_limits() == want
    finally:
        runtime.prefetch_limits.cache_clear()


def test_device_plans_take_the_device_guard(monkeypatch):
    """jax/pallas plans (and their cache keys) default to the device guard;
    host plans keep the host knob."""
    monkeypatch.setattr(runtime, "device_stream_limit", lambda: 12_345)
    a = random_powerlaw_csc(30, 2.0, seed=5)
    assert fast.default_stream_limit(device=True) == 12_345
    assert fast.default_stream_limit() == fast.STREAM_MAX_PRODUCTS
    for backend, want in (("jax", 12_345), ("pallas", 12_345),
                          ("host", fast.STREAM_MAX_PRODUCTS)):
        method = "spa" if backend == "pallas" else "expand"
        assert plan_spgemm(a, a, method, backend=backend).stream_limit \
            == want, backend
        key = api.plan_cache_key(a, a, method, backend=backend)
        assert key[-1] == want, backend


def test_guarded_device_plan_falls_back_loudly():
    a = random_powerlaw_csc(30, 2.5, seed=3)
    api.plan_cache_clear()
    plan = cached_plan(a, a, "expand", backend="jax", stream_limit=1)
    stats = {}
    with pytest.warns(RuntimeWarning, match="host stream engine"):
        c = plan.execute(a, a, stats=stats)
    assert stats["fallback"] == "host" and stats["device"] is False
    assert api.plan_cache_info()["host_fallbacks"] == 1
    ref = plan_spgemm(a, a, "expand").execute(a, a)
    np.testing.assert_allclose(np.asarray(c.values)[: c.nnz],
                               np.asarray(ref.values)[: ref.nnz])
    api.plan_cache_clear()
    assert api.plan_cache_info()["host_fallbacks"] == 0


def test_compile_cache_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert runtime.compile_cache_dir() == REPO / ".jax_cache"
    was = jax.config.jax_compilation_cache_dir
    try:
        assert runtime.enable_compile_cache() == REPO / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(
            REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert runtime.compile_cache_dir() == Path("/x")


def test_compile_cache_written_under_env_dir(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, compiles land there (a
    subprocess: the cache directory is process-wide JAX state)."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro import runtime
        runtime.enable_compile_cache()
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "x"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert any((tmp_path / "x").iterdir())
