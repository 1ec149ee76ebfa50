"""Test configuration: simulate an 8-device TPU mesh on CPU.

Mirrors SURVEY §8.1's test strategy: multi-chip behaviour is validated on a
virtual CPU mesh (``JAX_PLATFORMS=cpu`` plus
``--xla_force_host_platform_device_count=8``, both set here before jax is
imported); the real chip is reached only through ``chip_smoke.py``.
"""

import os

# DDL_TPU_ONCHIP=1 inverts the suite: the real accelerator backend stays
# active and ONLY tests marked `onchip` run (VERDICT r2 item 3) —
# everything else assumes the 8-device CPU sim and is deselected.
ONCHIP = os.environ.get("DDL_TPU_ONCHIP") == "1"

if not ONCHIP:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    skip_onchip = pytest.mark.skip(
        reason="on-chip test: set DDL_TPU_ONCHIP=1 (needs a real TPU)"
    )
    skip_sim = pytest.mark.skip(
        reason="CPU-sim test: not run under DDL_TPU_ONCHIP=1"
    )
    for item in items:
        if "onchip" in item.keywords:
            if not ONCHIP:
                item.add_marker(skip_onchip)
        elif ONCHIP:
            item.add_marker(skip_sim)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(params=["serial", "parallel"])
def crc_fold(request, monkeypatch):
    """Run a corruption test twice: as it stands (its KiB windows take
    the drain-time verify's one serial ``zlib.crc32``), and with the
    span floor lowered so the span-parallel fold finds the corrupt
    window, whatever machine runs the test.  Returns a check of a
    loader's ``Metrics``: every drain verify took that path."""
    from ddl_tpu import integrity

    parallel = request.param == "parallel"
    if parallel:
        monkeypatch.setattr(integrity, "SPAN_MIN_BYTES", 32)
        monkeypatch.setattr(integrity, "_usable_cores", lambda: 16)

    def check(m):
        verifies = m.timer("consumer.verify").count
        assert verifies > 0
        assert m.counter("consumer.verify_parallel_windows") == (
            verifies if parallel else 0
        )

    return check


@pytest.fixture(scope="session")
def eight_devices():
    assert len(jax.devices()) == 8, (
        f"expected 8 virtual CPU devices, got {jax.devices()}"
    )
    return jax.devices()
