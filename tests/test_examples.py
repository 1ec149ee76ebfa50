"""The example scripts stay runnable — each is an executable spec.

Mirrors the reference's test shape (spawn the program, assert exit 0
within a deadline — reference ``tests/test_ddl.py:9-28``) for every
shipped example, on the CPU backend.
"""

import os
import subprocess
import sys

import pytest

_EXAMPLES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"
)


def _run(script: str, *args: str, timeout_s: float = 420.0):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # examples pick their own device layout
    env["JAX_PLATFORMS"] = "cpu"  # the examples run on the CPU here
    proc = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES, script), *args],
        capture_output=True,
        text=True,
        timeout=timeout_s,
        env=env,
    )
    assert proc.returncode == 0, (
        f"{script} {args} rc={proc.returncode}\n{proc.stdout[-2000:]}"
        f"\n{proc.stderr[-2000:]}"
    )
    return proc.stdout


@pytest.mark.slow
def test_run_ddl_example():
    out = _run("run_ddl.py")
    assert "OK" in out


@pytest.mark.slow
def test_train_llama_example(tmp_path):
    out = _run("train_llama.py")
    assert "PASS" in out


@pytest.mark.slow
def test_train_llama_pp_example(tmp_path):
    """Pipeline-parallel training example: staged llama, window-streamed
    loader, loss decreases — and the tp-resident layout runs too."""
    out = _run("train_llama_pp.py", "pp_tp")
    assert "OK" in out
    assert "'tp': 2" in out


@pytest.mark.slow
def test_train_llama_pp_1f1b_example(tmp_path):
    """The interleaved 1F1B layout of the pp example: TrainConfig-driven
    schedule selection, chunked stage params, lower analytic bubble."""
    out = _run("train_llama_pp.py", "pp_1f1b")
    assert "OK" in out
    assert "schedule=1f1b" in out
    assert "bubble=0.111" in out


@pytest.mark.slow
def test_train_vit_example(tmp_path):
    out = _run("train_vit.py")
    assert "PASS" in out


@pytest.mark.slow
def test_generate_example():
    out = _run("generate.py")
    assert "OK" in out


@pytest.mark.slow
def test_global_shuffle_example():
    out = _run("global_shuffle.py")
    assert "PASS" in out
