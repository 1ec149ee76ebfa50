"""The reader of a share's full-width fallback
(``benchmarks/layers/moe_overflow_device_share.py``): own seconds whose
innermost ``ddl.`` scope is ``ddl.moe_overflow`` as a share of the window, on
tables built by hand from the path forms the step program writes
(``ddl_tpu/models/moe.py:_held_rows``)."""

import pytest

from benchmarks.layers import moe_overflow_device_share as reader
from benchmarks.lib import cells, scopes

CALL = "jit(_run)/while/body/closed_call/"
BOUNDED = {  # path: own seconds
    CALL + "jvp(ddl.moe)/ddl.moe_route/sort": 40e-6,
    CALL + "jvp(ddl.moe)/cond/branch_1_fun/ddl.moe_experts/jit(_take)/gather": 7e-6,
    CALL + "jvp(ddl.moe)/cond/branch_1_fun/ddl.moe_combine/gather": 5e-6,
    CALL + "transpose(jvp(jvp()))/checkpoint/ddl.moe/cond/branch_1_fun/"
    "transpose(jvp(ddl.moe_experts))/gather": 9e-6,
    CALL + "jvp(ddl.mlp)/dot_general": 200e-6,
}
FALLBACK = {
    CALL + "jvp(ddl.moe)/cond/branch_0_fun/ddl.moe_experts/ddl.moe_overflow/"
    "jit(_take)/gather": 26e-6,
    CALL + "transpose(jvp(jvp()))/checkpoint/rematted_computation/ddl.moe/cond/"
    "branch_0_fun/ddl.moe_combine/ddl.moe_overflow/jit(_take)/gather": 62e-6,
    CALL + "transpose(jvp(jvp()))/checkpoint/ddl.moe/cond/branch_0_fun/"
    "transpose(jvp(ddl.moe_combine))/ddl.moe_overflow/gather": 25e-6,
}
WINDOW_S = 1e-3


def table_of(paths, kernels_s=0.0):
    own = {scopes.classify(path) + ("fusion",): s for path, s in paths.items()}
    if kernels_s:  # XLA's grouped matmuls keep no path, in either branch
        own[(None, None, "forward", "ragged-dot-none")] = kernels_s
    return scopes.Table(
        window_s=WINDOW_S, step_own_s=sum(own.values()), own=own, flops={},
        bytes={}, peak_flops=None, peak_bytes=None, n_scoped_ops=len(paths),
    )


@pytest.fixture
def run_with(monkeypatch):
    def make(table):
        monkeypatch.setattr(scopes, "table_of_run", lambda m: table)
        return {"trace": {"window_s": WINDOW_S}}

    return make


def test_seconds_under_the_fallbacks_scope_are_the_share(run_with):
    m = run_with(table_of({**BOUNDED, **FALLBACK}, kernels_s=60e-6))
    assert reader.read(m) == pytest.approx(100.0 * (26 + 62 + 25) * 1e-6 / WINDOW_S)
    # ... which the neighbour does not see: the innermost frame decides.
    dispatch = cells.layer_reader("moe_dispatch_device_share")(m)
    assert dispatch == pytest.approx(100.0 * (40 + 7 + 5 + 9) * 1e-6 / WINDOW_S)
    # In no group of the table's: ``other``, and the sum stays whole.
    summary = scopes.table_of_run(m).summary()
    assert summary["other"] == pytest.approx(reader.read(m))
    assert sum(summary[k] for k in list(scopes.GROUPS) + [
        "other", "kernels", "unscoped"]) == pytest.approx(summary["step_own"])


def test_a_window_that_never_overflowed_reads_zero(run_with):
    m = run_with(table_of(BOUNDED, kernels_s=60e-6))
    assert reader.read(m) == 0.0


@pytest.mark.parametrize("table_names", [
    (), ("ddl.moe", "ddl.moe_route", "ddl.moe_experts", "ddl.moe_combine")],
    ids=["no_scope_table", "the_parents_table"])
def test_a_program_without_the_scope_reads_nothing(run_with, monkeypatch, table_names):
    """The parent of PR 40 under this PR's benchmark files: no fallback to
    time, no line - not 0.0, which would say "bounded in every layer"."""
    m = run_with(table_of(BOUNDED))
    monkeypatch.setattr(scopes, "_program_scopes", lambda: table_names)
    assert reader.read(m) is None


def test_no_trace_no_number(monkeypatch):
    monkeypatch.setattr(scopes, "_MEMO", {})
    assert reader.read({"trace": None}) is None


def test_the_entry_stands_beside_the_dispatch_share_in_the_two_share_cells():
    per_layer = {e["name"]: e for e in cells.benchmark_file()["per_layer"]}
    entry, dispatch = (per_layer[n] for n in (
        "moe_overflow_device_share", "moe_dispatch_device_share"))
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        dispatch["layer"], dispatch["moves"], dispatch["source"])
    assert entry["better"] == "lower" and entry["unit"] == "%"
    assert entry["workloads"] == [
        "trinity-mini.tokens-8k", "kanana-2-30b-a3b.tokens-8k"]
    assert set(entry["workloads"]) < set(dispatch["workloads"])
