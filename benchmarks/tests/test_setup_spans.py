"""The per-layer metrics under ``setup_s`` (PR 49): entries, readers on a
hand-made start-up record, and the inside reading of JAX's compile events
against the runner's outside one (``CompileLog``) on a CPU rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import cells

ROOT = cells.ROOT

#: In the order they were appended to ``BENCHMARK.json``.
SETUP = (
    "setup_backend_s", "setup_trace_s", "setup_lower_s", "setup_compile_s",
    "setup_cache_load_s", "setup_cache_miss_programs",
    "setup_trainer_build_s", "fit_start_s", "fit_stop_s",
)
NEW = SETUP + ("steady_build_s",)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"


def read(name, measured=None):
    return cells.layer_reader(name)(measured or {"counters": {}})


# -- the entries -------------------------------------------------------------


def test_the_new_entries_stand_after_every_entry_that_was_there():
    names = [e["name"] for e in cells.benchmark_file()["per_layer"]]
    assert tuple(names[-len(NEW):]) == NEW
    assert not set(names[:-len(NEW)]) & set(NEW)
    assert len(names) == len(set(names))


@pytest.mark.parametrize("name", NEW)
def test_a_new_entry_has_a_reader_a_unit_and_moves_what_its_cells_report(name):
    bench = cells.benchmark_file()
    entry = next(e for e in bench["per_layer"] if e["name"] == name)
    assert callable(cells.layer_reader(name))
    assert entry["better"] == "lower"
    assert entry["unit"] == (
        "programs" if name == "setup_cache_miss_programs" else "s"
    )
    assert entry["source"] == (
        "program_counter" if name == "setup_cache_miss_programs"
        else "program_span"
    )
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"][:-len(NEW)]}
    # No `workloads` key: every cell, and every cell reports what it moves.
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}
    assert entry["moves"] == ("mfu" if name == "steady_build_s" else "setup_s")
    moved = next(e for e in bench["end_to_end"] if e["name"] == entry["moves"])
    assert "workloads" not in moved
    for workload in bench["workloads"]:
        per_layer = cells.load_cell(workload["name"], bench=bench).per_layer
        assert name in [e["name"] for e in per_layer]


# -- the readers on a hand-made record ---------------------------------------


@pytest.fixture
def record(monkeypatch):
    from ddl_tpu import profiling

    fresh = profiling.StartupRecord()
    monkeypatch.setattr(profiling, "_RECORD", fresh)
    return fresh


def build(record, event, secs, program="p", cache=None, inner=()):
    record.build_opened(event)
    for args in inner:
        build(record, *args)
    if cache:
        record.cache_event(cache)
    record.build_closed(event, secs, program)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_record_reads_none(monkeypatch, name):
    from ddl_tpu import profiling

    monkeypatch.delattr(profiling, "startup_record")
    assert read(name) is None
    assert read(name, {"counters": {"trainer.step_dispatch.total_s": 1.0}}) is None


@pytest.mark.parametrize("name", SETUP)
def test_an_empty_phase_reads_zero_not_none(record, name):
    value = read(name)
    assert value == 0.0 and isinstance(value, float)


def test_the_readers_give_the_records_sums(record):
    from ddl_tpu import profiling
    from ddl_tpu.observability import Metrics

    m = Metrics()
    with profiling.stage("ddl.bring_up", m):
        pass
    # The harness's own programs: the weights, the reference.
    build(record, TRACE, 3.0, "init")
    build(record, LOWER, 1.0, "jit(init)", inner=[(TRACE, 0.5, "kernel")])
    build(record, COMPILE, 20.0, "jit(init)")
    build(record, COMPILE, 0.75, "jit(small)")
    build(record, COMPILE, 4.0, "jit(reference)", HIT)
    for ordinal in range(2):
        fit = record.begin_fit()
        with profiling.stage("ddl.state_init", m):
            build(record, COMPILE, 2.0, "jit(<lambda>)")
        with profiling.stage("ddl.window_wait", m):
            pass
        with profiling.stage("ddl.step_dispatch", m):
            if ordinal == 0:
                build(record, TRACE, 6.0, "_run")
                build(record, COMPILE, 1.5, "jit(_run)", HIT)
        record.stamp("last_readback")
        record.end_fit(fit)
    build(record, COMPILE, 9.0, "jit(late)")  # after the cut: not set-up
    spans = {s.name: s for s in record.spans}
    assert read("setup_backend_s") == spans["ddl.bring_up"].seconds
    assert read("setup_trace_s") == 9.0
    assert read("setup_lower_s") == 1.0  # the kernel body's 0.5 is inside it
    assert read("setup_compile_s") == 24.75
    assert read("setup_cache_load_s") == 5.5
    assert read("setup_cache_miss_programs") == 3.0  # 20 s, and init's 2 s twice
    assert read("setup_trainer_build_s") == 11.5
    fits = list(record.fits)
    assert read("fit_start_s") == pytest.approx(sum(
        f.first_window - f.entered - 2.0 for f in fits
    ) / 2)
    assert read("fit_stop_s") == pytest.approx(sum(
        f.returned - f.last_readback for f in fits
    ) / 2)
    kinds = sum(read(n) for n in (
        "setup_trace_s", "setup_lower_s", "setup_compile_s",
        "setup_cache_load_s",
    ))
    assert read("setup_trainer_build_s") <= kinds
    assert record.summary()["seconds"]["late_build"] == 9.0


@pytest.mark.parametrize("counters, want", [
    ({}, None),
    ({"trainer.step_dispatch.total_s": 0.4}, None),
    ({"build.trace.total_s": 0.0, "build.trace.count": 0.0}, 0.0),
    ({"build.trace.total_s": 0.0, "build.lower.total_s": 0.0,
      "build.compile.total_s": 0.0}, 0.0),
    ({"build.trace.total_s": 1.5, "build.lower.total_s": 0.5,
      "build.compile.total_s": 4.0, "build.cache_load.total_s": 0.25}, 6.25),
])
def test_steady_build_sums_the_four_timers_deltas(counters, want):
    assert read("steady_build_s", {"counters": counters}) == want


# -- inside against outside, on a rehearsal -----------------------------------

REHEARSAL = r"""
import json, sys
sys.path.insert(0, %r)
from benchmarks import run

logs = []

class Kept(run.CompileLog):
    def __init__(self):
        super().__init__()
        logs.append(self)

run.CompileLog = Kept
rc = run.main([
    "--workload", "mistral-7b.tokens-4k", "--seed", "11", "--seconds", "0.5",
    "--trace", "1", "--rehearsal", "cpu",
])
from benchmarks.lib import cells
from ddl_tpu import profiling

rec = profiling.startup_record()
cut = rec.fits[-1].first_dispatch_done
print("CHECK " + json.dumps({
    "rc": rc,
    "fits": len(rec.fits),
    "outside_s": sum(s for t, s, _ in logs[0].events if t <= cut),
    "outside_total_s": logs[0].total_s(),
    "outside_hits": logs[0].cache_hits,
    "inside_s": cells.layer_reader("setup_compile_s")({})
    + cells.layer_reader("setup_cache_load_s")({}),
    "nested_compiles": sum(
        b.parent is not None for b in rec.builds
        if b.kind in ("compile", "cache_load")
    ),
    "values": {
        name: cells.layer_reader(name)({"counters": {}})
        for name in %r
    },
    "first_dispatch_after_start_s": cut - rec.spans[0].start,
}))
""" % (ROOT, list(SETUP))


def test_the_inside_reading_of_compiles_is_the_runners_outside_one(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    # A compile cache of the rehearsal's own: cold, then its hits.
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    found = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", REHEARSAL], env=env, cwd=ROOT,
            capture_output=True, text=True, timeout=900,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = next(
            ln for ln in proc.stdout.splitlines() if ln.startswith("CHECK ")
        )
        found.append(json.loads(line[len("CHECK "):]))
    for check in found:
        assert check["rc"] == 0 and check["fits"] == 2
        assert check["nested_compiles"] == 0
        assert check["inside_s"] == pytest.approx(
            check["outside_s"], rel=1e-9
        )
        values = check["values"]
        assert all(v is not None and v >= 0.0 for v in values.values())
        kinds = sum(values[n] for n in (
            "setup_trace_s", "setup_lower_s", "setup_compile_s",
            "setup_cache_load_s",
        ))
        assert values["setup_trainer_build_s"] <= kinds
        assert (
            kinds + values["setup_backend_s"] + 2 * values["fit_start_s"]
            + values["fit_stop_s"]
        ) <= check["first_dispatch_after_start_s"]
    cold, warm = found
    assert cold["outside_hits"] == 0 and cold["values"]["setup_cache_load_s"] == 0.0
    # The CPU's programs compile in under jax's one-second floor for the
    # cache, so a second run may hit nothing: where it does, it is a load.
    assert (warm["values"]["setup_cache_load_s"] > 0.0) == (
        warm["outside_hits"] > 0
    )
