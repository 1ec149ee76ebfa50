"""The trace reduction on the recorded trace kept beside it
(``testdata/tpu_v5e_3steps.xplane.pb``: TPU v5 lite, three rounds of
hook -> a 4-step scan program ``jit(_run)`` -> a 20 ms annotated wait),
and its interval arithmetic on made-up intervals."""

import os

import pytest

from benchmarks.lib import cells, tracered as T

RECORDED = os.path.join(cells.HERE, "testdata", "tpu_v5e_3steps.xplane.pb")


def test_merge_clip_gaps():
    busy = T.merge([(0, 2), (1, 3), (5, 6), (5.5, 5.8), (9, 10)])
    assert busy == [(0, 3), (5, 6), (9, 10)]
    assert T.total(T.clip(busy, (2, 9.5))) == pytest.approx(1 + 1 + 0.5)
    assert T.gaps(T.clip(busy, (2, 9.5)), (2, 9.5)) == [(3, 5), (6, 9)]
    assert T.gaps([], (0, 1)) == [(0, 1)]


def test_self_time_takes_enclosed_events_out():
    events = [(0, 10, "%while = x"), (1, 4, "%a.1 = x"), (4, 9, "%a.2 = x"),
              (12, 13, "%b = x")]
    own = dict(T.self_times(events))
    assert own["%while = x"] == pytest.approx(2)
    assert own["%a.1 = x"] == 3 and own["%a.2 = x"] == 5 and own["%b = x"] == 1
    assert T.op_family(T.op_name("%a.12 = bf16[2] fusion()")) == "a"


def test_attribution_takes_the_innermost_covering_span():
    spans = [(0, 10, "ddl.window_wait"), (2, 5, "ddl.window_acquire")]
    assert T.attribute((2.5, 4.5), spans) == "ddl.window_acquire"
    assert T.attribute((6, 9), spans) == "ddl.window_wait"
    assert T.attribute((9, 20), spans) == "unattributed"


@pytest.fixture(scope="module")
def recorded():
    return T.load(RECORDED)


def test_recorded_trace_is_read(recorded):
    assert list(recorded.ops) == [0]
    assert [n.split("(")[0] for _, _, n in recorded.modules[0]] == [
        "jit_multiply", "jit__run"] * 3
    assert [n for _, _, n in recorded.spans] == [
        "bench.window_hook", "ddl.window_wait"] * 3


def test_recorded_trace_reduces(recorded):
    r = T.reduce(recorded)
    # Window: the first step program's start to the last one's, two
    # whole rounds on the device's clock.
    assert r["window_s"] == pytest.approx(0.090731 - 0.047903, rel=1e-4)
    # Two rounds of (26 us multiply + 403 us scan program) were busy.
    assert r["busy_s"] == pytest.approx(2 * (26e-6 + 403.4e-6), rel=0.01)
    assert r["idle_share_worst"] == pytest.approx(0.980, abs=1e-3)
    assert len(r["step_program_busy_s"]) == 2
    assert r["step_program_busy_s"][0] == pytest.approx(403.3e-6, rel=1e-3)
    # The scan body's matmul fusion leads; the enclosing %while adds nothing.
    ops = dict(r["device_ops"])
    assert r["device_ops"][0][0] == "convolution_tanh_fusion"
    assert ops["convolution_tanh_fusion"] == pytest.approx(8 * 90e-6, rel=0.01)
    assert ops["while"] < 1e-6
    assert sum(ops.values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert r["ops_own_time_s"] == pytest.approx(r["busy_s"], rel=1e-3)
    # All three executions give the device's period; two lie in the window.
    assert len(r["step_program_starts"][0]) == 3
    # The device sat idle while the host slept inside ddl.window_wait.
    name, secs = r["idle_gaps"][0]
    assert name == "ddl.window_wait" and secs > 0.95 * (r["window_s"] - r["busy_s"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_an_op_straddling_the_window_edge_is_cut_with_its_body(recorded):
    # 0.0481 s lies inside the first scan's %while (0.04793 to 0.04829).
    r = T.reduce(recorded, window=(0.0481, 0.0700))
    assert r["ops_own_time_s"] == pytest.approx(r["busy_s"], rel=1e-3)
    assert dict(r["device_ops"])["while"] < 1e-6


def test_without_step_programs_the_hook_spans_give_the_window(recorded):
    r = T.reduce(recorded, step_program="jit_no_such_program")
    assert r["window_s"] == pytest.approx(0.091271 - 0.048318, rel=1e-4)
    assert r["step_program_busy_s"] == []


def test_a_window_can_be_given(recorded):
    r = T.reduce(recorded, window=(0.0695, 0.0705))
    assert r["window_s"] == pytest.approx(1e-3)
    assert r["busy_s"] == pytest.approx(26e-6 + 403.4e-6, rel=0.01)
