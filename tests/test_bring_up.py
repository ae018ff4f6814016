"""Bring-up guards that run on the CPU: the device is never hidden.

* kernel wrappers interpret only on the CPU platform;
* the JAX backend counts every payload path, including map payloads
  that fall back to NumPy, and never hands float64 to a Pallas kernel;
* the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or to
  the checkout's fixed ``.jax_cache/``;
* roofline peaks are looked up by device kind, never defaulted;
* the lint CLI stays off JAX while its children may need the chip;
* ``chip_smoke.py`` refuses to run without a TPU, and its phases pass
  at a tiny size in interpret mode.
"""
import inspect
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro
from repro.api import ExecutionPolicy, RuntimeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p
    )
    env.update(extra)
    return env


# ---------------------------------------------------------------------------
# interpret mode only on the CPU platform
# ---------------------------------------------------------------------------


def test_resolve_interpret_follows_platform():
    from repro.kernels import resolve_interpret

    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


@pytest.mark.parametrize("wrapper", [
    "repro.kernels.stencil:jacobi_sweep",
    "repro.kernels.stencil:stencil5_block",
    "repro.kernels.flash_attention:flash_attention",
    "repro.kernels.mamba2_scan:ssd_scan",
    "repro.kernels.rwkv6_wkv:wkv6",
])
def test_kernel_wrappers_default_to_platform_choice(wrapper):
    import importlib

    mod, name = wrapper.split(":")
    fn = getattr(importlib.import_module(mod), name)
    assert inspect.signature(fn).parameters["interpret"].default is None


# ---------------------------------------------------------------------------
# JaxBackend payload accounting
# ---------------------------------------------------------------------------


def _jax_runtime(**config):
    return repro.runtime(
        RuntimeConfig(nprocs=2, block_size=16, **config),
        ExecutionPolicy(flush="async", backend="jax"),
    )


def test_untranslatable_map_is_counted_as_host_fallback():
    host = np.linspace(0.0, 1.0, 32 * 32, dtype=np.float32).reshape(32, 32)
    with _jax_runtime() as rt:
        a = repro.array(host)
        np.asarray(a + 1.0)  # builds the backend
        before = rt.backend_stats()
        # this backend instance loses its jnp form of exp
        rt._exec_backend_obj._impls.pop("exp")
        got = np.asarray(np.exp(a))
        after = rt.backend_stats()
    np.testing.assert_allclose(got, np.exp(host), rtol=1e-6)
    assert before["n_host_untranslated"] == 0 and before["n_jit"] > 0
    assert after["n_host_untranslated"] >= 4  # one per 16x16 block
    assert after["interpret"] is True  # CPU platform


def test_float64_stencil_never_reaches_pallas():
    """Under x64 the fused stencil stays float64 on the jitted jnp path
    (Mosaic has no 64-bit types) and matches NumPy bit-for-bit."""
    import chip_smoke

    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        with _jax_runtime(fusion=True) as rt:
            full = repro.zeros((34, 34))
            full[0, :] = 1.0
            full[:, 0] = 1.0
            for _ in range(2):
                full[1:-1, 1:-1] = 0.2 * (
                    full[1:-1, 1:-1] + full[0:-2, 1:-1] + full[2:, 1:-1]
                    + full[1:-1, 0:-2] + full[1:-1, 2:]
                )
            got = np.asarray(full)
            counts = rt.backend_stats()
    finally:
        jax.config.update("jax_enable_x64", prev)
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got, chip_smoke.jacobi_reference(32, 2))
    assert counts["n_pallas"] == 0 and counts["n_jit"] > 0


def test_auto_backend_reports_jax_counters():
    with repro.runtime(
        RuntimeConfig(nprocs=2, block_size=128),
        ExecutionPolicy(flush="async", backend="auto"),
    ) as rt:
        a = repro.array(np.linspace(0.0, 1.0, 256 * 256).reshape(256, 256))
        np.asarray(np.exp(a) * 2.0)  # exp blocks clear the threshold
        counts = rt.backend_stats()
    assert counts["n_jax"] == 4 and counts["n_jit"] == counts["n_jax"]
    assert counts["n_numpy"] > 0
    assert counts["n_host_untranslated"] == 0


# ---------------------------------------------------------------------------
# compile cache directory
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_env_dir_is_left_alone(monkeypatch, cache_config):
    from repro.compile_cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None  # nothing set in code


def test_compile_cache_defaults_to_checkout_dir(monkeypatch, cache_config):
    from repro.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    path = enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs < 1.0
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------------
# roofline peaks
# ---------------------------------------------------------------------------


def test_roofline_peaks_are_keyed_by_device_kind():
    from repro.roofline import peaks_for

    v5e = peaks_for("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(ValueError, match="no peaks recorded"):
        peaks_for("cpu")


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------


def test_lint_cli_imports_no_jax_before_its_children():
    code = (
        "import sys, repro.analysis.__main__ as m; "
        "assert 'jax' not in sys.modules, 'lint parent imported jax'"
    )
    res = subprocess.run([sys.executable, "-c", code], env=_cpu_env(),
                         cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr


def test_lint_example_refuses_when_jax_is_loaded():
    from repro.analysis.__main__ import lint_example

    assert "jax" in sys.modules  # this test process
    with pytest.raises(RuntimeError, match="imported JAX"):
        lint_example(os.path.join(ROOT, "examples", "quickstart.py"))


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------


def _no_result(res) -> bool:
    return res.returncode != 0 and '"ok": true' not in res.stdout


def test_chip_smoke_fails_without_an_accelerator():
    res = subprocess.run([sys.executable, "chip_smoke.py"], env=_cpu_env(),
                         cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert _no_result(res), res.stdout
    assert "no TPU" in res.stderr


def test_chip_smoke_fails_outside_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    res = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert _no_result(res), res.stdout


def test_chip_smoke_phases_pass_at_tiny_size():
    """Every single-chip phase, shrunk, in interpret mode."""
    import chip_smoke

    chip_smoke.phase_kernels(n=64, block=16)
    counts = chip_smoke.phase_runtime(n=62, nprocs=4, block=16, iters=3)
    assert counts["n_pallas"] > 0 and counts["n_host_untranslated"] == 0
    counts = chip_smoke.phase_serve(requests=2, n=32, nprocs=2, block=16)
    assert counts["n_jit"] > 0


def test_chip_smoke_sharded_phase_on_four_cpu_devices():
    code = (
        "import chip_smoke; chip_smoke.phase_sharded(n=64); "
        "print('SHARDED-OK')"
    )
    res = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert "SHARDED-OK" in res.stdout, res.stdout + res.stderr
    assert "shards on [0, 1, 2, 3]" in res.stdout

