"""The idle-by-stage readers (``lib/gapshare.py`` and the eight
``layers/`` files that read the program's ``ddl.*`` stages and
``ddl_*`` kernel names): on a made-up ``measured``, and on the
reduction of the recorded v5e trace."""

import os

import pytest

from benchmarks.lib import cells, tracered

GAP_READERS = (
    "data_wait_share", "idle_unattributed_share", "ring_wait_idle_share",
    "transfer_wait_idle_share", "fanout_idle_share", "trainer_idle_share",
)
NEW = GAP_READERS + ("step_dispatch_host_ms", "flash_device_share")
RECORDED = os.path.join(cells.HERE, "testdata", "tpu_v5e_3steps.xplane.pb")


def read(name, measured):
    return cells.layer_reader(name)(measured)


def measured(idle_gaps, device_ops=(), window_s=2.0, counters=None):
    idle = sum(s for _, s in idle_gaps)
    return {
        "counters": counters or {},
        "window_s": 20.0, "n_windows": 97, "steps_per_window": 1,
        "trace": {
            "window_s": window_s,
            "idle_share_worst": idle / window_s,
            "idle_gaps": [list(g) for g in idle_gaps],
            "device_ops": [list(o) for o in device_ops],
        },
    }


#: A four-chip window as this PR's program should show it: every stage
#: under ``ddl.window_wait`` named, the trainer's own, the hook, the rest.
GAPS = [
    ("ddl.transfer_wait", 0.120), ("ddl.window_acquire", 0.060),
    ("ddl.ici_fanout", 0.040), ("ddl.staging_transfer", 0.020),
    ("ddl.window_wait", 0.010), ("ddl.step_dispatch", 0.016),
    ("ddl.loss_readback", 0.004), ("bench.window_hook", 0.008),
    ("unattributed", 0.002), ("launch gaps under 20 us", 0.0002),
]


def test_each_reader_sums_the_gaps_under_its_own_stages():
    m = measured(GAPS)
    assert read("ring_wait_idle_share", m) == pytest.approx(3.0)
    assert read("transfer_wait_idle_share", m) == pytest.approx(7.0)
    assert read("fanout_idle_share", m) == pytest.approx(2.0)
    assert read("data_wait_share", m) == pytest.approx(12.5)
    assert read("trainer_idle_share", m) == pytest.approx(1.0)
    assert read("idle_unattributed_share", m) == pytest.approx(0.1)
    # The data plane's parts, and what is left directly under the
    # enclosing ddl.window_wait, add up to the whole.
    assert read("data_wait_share", m) == pytest.approx(
        read("ring_wait_idle_share", m) + read("transfer_wait_idle_share", m)
        + read("fanout_idle_share", m) + 100 * 0.010 / 2.0
    )


def test_the_shares_add_up_to_the_device_idle_share():
    m = measured(GAPS)
    others = 100.0 * (0.008 + 0.0002) / 2.0  # the hook's and the launch gaps
    assert (
        read("data_wait_share", m) + read("trainer_idle_share", m)
        + read("idle_unattributed_share", m) + others
    ) == pytest.approx(read("device_idle_share", m))


@pytest.mark.parametrize("name", NEW)
def test_without_a_trace_and_counters_a_reader_returns_none(name):
    # The CPU rehearsal, and the parent's program under this PR's readers.
    assert read(name, {"counters": {}, "window_s": 20.0, "trace": None}) is None


@pytest.mark.parametrize("name", GAP_READERS)
def test_a_trace_with_no_gap_under_its_names_reads_zero(name):
    m = measured([("bench.window_hook", 0.5)])
    assert read(name, m) == 0.0


def test_the_parents_spans_still_read_as_data_wait():
    # Before this PR the program named two stages only.
    m = measured([("ddl.window_wait", 0.316), ("unattributed", 0.105)],
                 window_s=3.11)
    assert read("data_wait_share", m) == pytest.approx(10.16, abs=0.01)
    assert read("idle_unattributed_share", m) == pytest.approx(3.38, abs=0.01)
    assert read("transfer_wait_idle_share", m) == 0.0


def test_step_dispatch_is_a_mean_per_window():
    m = measured(GAPS, counters={
        "trainer.step_dispatch.total_s": 0.194,
        "trainer.step_dispatch.count": 97.0,
    })
    assert read("step_dispatch_host_ms", m) == pytest.approx(2.0)
    m["counters"]["trainer.step_dispatch.count"] = 0.0
    assert read("step_dispatch_host_ms", m) is None


def test_flash_share_sums_the_named_kernels_or_is_absent():
    ops = [("fusion", 0.7), ("ddl_flash_bwd_dkv", 0.3), ("ddl_flash_fwd", 0.16),
           ("ddl_flash_bwd_dq", 0.14), ("copy", 0.2), ("ddl_ici_scatter", 0.01)]
    assert read("flash_device_share", measured(GAPS, ops)) == pytest.approx(30.0)
    # XLA's accidental names (the parent's) are not the kernels' own.
    old = [("fusion", 0.7), ("transpose_jvp___", 0.44), ("jvp__", 0.16)]
    assert read("flash_device_share", measured(GAPS, old)) is None


def test_on_the_recorded_trace():
    """One chip, three rounds of hook -> scan -> a 20 ms wait inside
    ``ddl.window_wait``: the wait is all of the idle time."""
    r = tracered.reduce(tracered.load(RECORDED))
    m = {"counters": {}, "window_s": 1.0, "steps_per_window": 4, "trace": r}
    idle = read("device_idle_share", m)
    assert read("data_wait_share", m) == pytest.approx(idle, rel=0.05)
    assert read("data_wait_share", m) > 95.0
    for name in ("ring_wait_idle_share", "transfer_wait_idle_share",
                 "fanout_idle_share", "trainer_idle_share"):
        assert read(name, m) == 0.0  # recorded before these stages existed
    gaps = dict(r["idle_gaps"])
    others = 100.0 * sum(
        s for n, s in gaps.items()
        if n.startswith("bench.") or n.startswith("launch gaps")
    ) / r["window_s"]
    assert (
        read("data_wait_share", m) + read("trainer_idle_share", m)
        + read("idle_unattributed_share", m) + others
    ) == pytest.approx(idle, abs=1e-6)
    assert read("flash_device_share", m) is None  # a matmul scan, no flash
    assert read("step_dispatch_host_ms", m) is None


def test_every_new_metric_is_an_entry_with_a_reader():
    entries = {e["name"]: e for e in cells.benchmark_file()["per_layer"]}
    for name in NEW:
        assert name in entries and callable(cells.layer_reader(name))
    assert [e["name"] for e in cells.benchmark_file()["per_layer"]][-8:] == [
        "data_wait_share", "idle_unattributed_share", "ring_wait_idle_share",
        "transfer_wait_idle_share", "fanout_idle_share", "trainer_idle_share",
        "step_dispatch_host_ms", "flash_device_share",
    ]
