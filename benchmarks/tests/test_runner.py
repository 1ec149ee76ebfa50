"""The runner end to end, rehearsed on the CPU at the tiny sizes each
cell's files keep under ``rehearsal`` (the four-chip cell on four virtual
devices).  Each run is a process of its own, as the driver's are: the
runner spawns producers and owns JAX for its lifetime."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import cells

RUN = os.path.join(cells.HERE, "run.py")


def run(*args, root=cells.ROOT, runner=RUN, **extra_env):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **extra_env)
    proc = subprocess.run(
        [sys.executable, runner, *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=600,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, [json.loads(ln) for ln in lines]


def test_a_run_without_a_tpu_prints_no_result():
    proc, lines = run("--workload", "mistral-7b.tokens-4k", "--seed", "0",
                      "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert lines == []
    assert "needs a TPU" in proc.stderr


def test_a_fault_is_for_the_rehearsal_only():
    proc, lines = run("--workload", "mistral-7b.tokens-4k", "--fault", "alter-row")
    assert proc.returncode != 0 and lines == []


@pytest.mark.parametrize("cell", [
    w["name"] for w in cells.benchmark_file()["workloads"]
])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_every_cell_rehearses_correct_and_prints_no_metric(cell, trace):
    proc, lines = run("--workload", cell, "--seed", "3", "--seconds", "0.5",
                      "--trace", trace, "--rehearsal", "cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = lines[-1]
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3 + 2 * 2  # steady + both edges, at least
    assert result["metrics"] == {}  # no number from a CPU run under a metric's name
    want = next(w["chips"] for w in cells.benchmark_file()["workloads"]
                if w["name"] == cell)
    assert result["device"] == {
        "platform": "cpu", "kind": "cpu", "count": want, "memory_peak_bytes": None,
    }
    steady = next(ln for ln in lines if ln.get("line") == "steady")
    assert steady["problems"] == [] and steady["rate"] == "not measured"
    assert steady["loss_rel_diff"] <= 0.002
    if trace == "1":
        found = next(ln for ln in lines if ln.get("line") == "rehearsal")
        assert "window_wait_host_share" in found["readers_with_data"]
        assert "fused_share" in found["readers_with_data"]


def test_the_four_chip_cell_rehearses_over_the_ici_tier():
    """On the chip ``auto`` takes the ICI fan-out; on the CPU it takes XLA's
    scatter unless asked.  Asked, the rehearsal's rows (193 float32, off
    the 128-lane tiling like the real 150,529) ride the interpreted
    kernel through the lane pack/unpack and still check out."""
    proc, lines = run("--workload", "vit-b16.images-224-dp4", "--seed", "4",
                      "--seconds", "0.3", "--trace", "1", "--rehearsal", "cpu",
                      DDL_TPU_DISTRIBUTE="ici")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True
    found = next(ln for ln in lines if ln.get("line") == "rehearsal")
    assert {"ici_share", "fanout_host_ms"} <= set(found["readers_with_data"])


@pytest.mark.parametrize("fault", ["alter-row", "swap-rows"])
def test_a_defective_row_reads_incorrect(fault):
    proc, lines = run("--workload", "vit-b16.images-224", "--seed", "3",
                      "--seconds", "0.5", "--trace", "0", "--rehearsal", "cpu",
                      "--fault", fault)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is False
    assert lines[-1]["failed"] == 1


def test_new_files_are_found_without_an_edit(tmp_path):
    """A later PR adds a configuration, a mix, a cell and a per-layer
    metric as files and entries of their own."""
    shutil.copytree(cells.HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(cells.ROOT, "ddl_tpu"), tmp_path / "ddl_tpu")
    bench = cells.benchmark_file()
    with open(os.path.join(cells.HERE, "configs", "vit-b16.json")) as f:
        config = json.load(f)
    config.update(name="vit-s16", hidden_size=384, num_attention_heads=6,
                  intermediate_size=1536)
    (tmp_path / "benchmarks/configs/vit-s16.json").write_text(json.dumps(config))
    with open(os.path.join(cells.HERE, "jobs", "images-224.json")) as f:
        mix = json.load(f)
    mix.update(name="images-224-p3", n_producers=3)
    (tmp_path / "benchmarks/jobs/images-224-p3.json").write_text(json.dumps(mix))
    (tmp_path / "benchmarks/layers/windows_served.py").write_text(
        "def read(m):\n    return m['counters'].get('consumer.windows')\n"
    )
    bench["configs"].append({
        "name": "vit-s16", "source": "arXiv:2106.10270",
        "file": "benchmarks/configs/vit-s16.json", "reduced": [], "why": "test",
    })
    bench["workloads"].append({
        "name": "vit-s16.images-224-p3", "config": "vit-s16",
        "traffic": "images-224-p3", "chips": 1, "why": "test",
    })
    bench["per_layer"].append({
        "name": "windows_served", "unit": "windows", "better": "higher",
        "source": "program_counter", "layer": "window rings", "moves": "mfu",
        "workloads": ["vit-s16.images-224-p3"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc, lines = run(
        "--workload", "vit-s16.images-224-p3", "--seed", "1", "--seconds", "0.5",
        "--trace", "1", "--rehearsal", "cpu",
        root=str(tmp_path), runner=str(tmp_path / "benchmarks/run.py"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True
    assert lines[0]["n_producers"] == 3
    found = next(ln for ln in lines if ln.get("line") == "rehearsal")
    assert "windows_served" in found["readers_with_data"]
    # ... and the cells that were there are untouched by the additions.
    assert "windows_served" not in [
        m["name"] for m in cells.load_cell("vit-b16.images-224", bench=bench).per_layer
    ]
