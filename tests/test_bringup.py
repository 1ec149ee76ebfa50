"""Platform bring-up and compile-cache placement (``ddl_tpu.bringup``).

A run that wants a TPU and finds none must stop with the reason — never
fall back and publish CPU numbers under device names — and a CPU run
has to be asked for by name.
"""

import os
import subprocess
import sys

import jax
import pytest

from ddl_tpu import bringup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """bring-up places the process-wide compile cache; put it back."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_wants_tpu_finds_none_exits_with_reason(cache_config):
    with pytest.raises(SystemExit) as exc:
        bringup.bring_up(None)  # this suite runs on the CPU backend
    assert "needs a TPU" in str(exc.value.code)
    assert "by name" in str(exc.value.code)


def test_cpu_by_name_is_granted(cache_config):
    assert bringup.bring_up("cpu") == "cpu"


def test_unknown_request_rejected():
    with pytest.raises(ValueError, match="cpu|tpu"):
        bringup.bring_up("gpu")


def test_cache_placement(cache_config, monkeypatch, tmp_path):
    """Placed from outside when the variable is set (nothing set in
    code); otherwise a FIXED path under the checkout."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert bringup.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert bringup.configure_compile_cache() == os.path.join(
        REPO, ".jax_cache"
    )
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache"
    )


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_entry_script_fails_without_a_tpu(script):
    """The whole entry point, as the driver runs it in a sandbox with no
    accelerator: non-zero exit, the reason on stderr, no result line."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("DDL_BENCH_PLATFORM", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout


def test_unknown_device_kind_has_no_peak():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_hbm("TPU v5 lite") == 819e9
    for peak in (bench._peak_flops, bench._peak_hbm, bench._peak_ici_link):
        with pytest.raises(LookupError, match="TPU v9"):
            peak("TPU v9 mega")
