"""The start-up record (``ddl_tpu.profiling.startup_record``): stage spans,
fits and JAX's builds on one clock, fed by ``profiling.stage`` and ONE
``jax.monitoring`` listener.

No JAX import at module level: PROCESS-mode producers re-import this
module (``JaxProbeProducer``), and what they find in ``sys.modules`` is
what the library's own imports put there.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ringsupport import cross_process_ring

from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton, profiling
from ddl_tpu.observability import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACE, LOWER, COMPILE = profiling.BUILD_EVENTS
HIT, MISS = profiling.CACHE_EVENTS

# -- (a), (d), (f): one fresh process, bring-up and two fits -----------------

TWO_FITS = r"""
import json, sys
from ddl_tpu.bringup import bring_up
bring_up("cpu")
import jax, numpy as np, optax
from jax._src import monitoring
from jax.sharding import PartitionSpec as P
from ddl_tpu import profiling
from ddl_tpu.ingest import north_star_report
from ddl_tpu.models import pointnet
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.parallel.mesh import make_mesh
from ddl_tpu.readers import ArrayProducer
from ddl_tpu.trainer import Trainer

m = Metrics()
cfg = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
trainer = Trainer(
    loss_fn=lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
    optimizer=optax.adam(1e-2), mesh=make_mesh({"dp": 1}),
    param_specs=pointnet.param_specs(cfg),
    init_params=pointnet.init_params(cfg, jax.random.key(0)),
    batch_spec=P(("dp",)), metrics=m,
)
profiling.listen_for_builds()
profiling.listen_for_builds()
data = np.random.default_rng(0).random((256, 6)).astype(np.float32)
snaps = []

def hook(win):
    snaps.append({
        k: v for k, v in m.snapshot().items() if k.startswith("build.")
    })
    return win

for _ in range(2):
    trainer.fit(
        ArrayProducer(data, window_size=64, splits=(3, 2, 1)),
        batch_size=16, n_epochs=5, n_producers=2, mode="thread",
        output="jax", window_stream=True, window_hook=hook,
    )
rec = profiling.startup_record()
print("RECORD " + json.dumps({
    "spans": [s._asdict() for s in rec.spans],
    "fits": [f.as_dict() for f in rec.fits],
    "builds": [b._asdict() for b in rec.builds],
    "summary": rec.summary(),
    "hook_snaps": snaps,
    "trainer_timers": {
        k: v for k, v in m.snapshot().items()
        if k.startswith(("build.", "startup."))
    },
    "default_timers": {
        k: v for k, v in default_metrics().snapshot().items()
        if k.startswith(("build.", "startup."))
    },
    "duration_listeners": sum(
        cb is profiling._on_duration
        for cb in monitoring.get_event_duration_listeners()
    ),
    "report": north_star_report(m)["startup"],
}))
"""


@pytest.fixture(scope="module")
def two_fits():
    """``bring_up("cpu")`` and two ``Trainer.fit(window_stream=True)`` of
    one Trainer (THREAD producers, a tiny model) in a fresh process."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)  # conftest's eight devices: one will do
    proc = subprocess.run(
        [sys.executable, "-c", TWO_FITS], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(
        ln for ln in proc.stdout.splitlines() if ln.startswith("RECORD ")
    )
    return json.loads(line[len("RECORD "):])


def test_one_bring_up_row_before_everything_else(two_fits):
    rows = [s for s in two_fits["spans"] if s["name"] == "ddl.bring_up"]
    assert len(rows) == 1 and rows[0]["fit"] is None
    assert rows[0]["seconds"] > 0.0
    # Back-dated to the function's entry: `import jax` is inside it.
    assert rows[0]["end"] - rows[0]["start"] == pytest.approx(
        rows[0]["seconds"]
    )
    assert all(s["start"] >= rows[0]["start"] for s in two_fits["spans"])
    assert two_fits["default_timers"]["startup.bring_up.count"] == 1.0
    assert two_fits["default_timers"][
        "startup.bring_up.total_s"
    ] == pytest.approx(rows[0]["seconds"], abs=5e-3)


@pytest.mark.parametrize("ordinal", [0, 1])
def test_a_fit_has_its_five_stamps_in_order(two_fits, ordinal):
    assert [f["ordinal"] for f in two_fits["fits"]] == [0, 1]
    fit = two_fits["fits"][ordinal]
    stamps = [fit[name] for name in profiling.FIT_STAMPS]
    assert all(isinstance(t, float) for t in stamps)
    assert stamps == sorted(stamps)
    if ordinal:
        assert fit["entered"] >= two_fits["fits"][0]["returned"]


@pytest.mark.parametrize("ordinal", [0, 1])
@pytest.mark.parametrize(
    "name",
    ["ddl.pool_start", "ddl.state_init", "ddl.loader_attach",
     "ddl.pool_stop", "ddl.window_wait", "ddl.step_dispatch"],
)
def test_each_start_up_stage_is_one_row_a_fit(two_fits, name, ordinal):
    rows = [
        s for s in two_fits["spans"]
        if (s["name"], s["fit"]) == (name, ordinal)
    ]
    assert len(rows) == 1
    fit = two_fits["fits"][ordinal]
    assert fit["entered"] <= rows[0]["start"] <= rows[0]["end"]
    assert rows[0]["end"] <= fit["returned"]
    # The hot path's two stages: a fit's FIRST emission, its end the stamp.
    stamp = profiling.STAGES[name].startup
    if stamp != "span":
        assert rows[0]["end"] == fit[stamp]
    if name == "ddl.pool_stop":
        # The trainer's part and the wrapper's: one row, both inside.
        assert rows[0]["start"] >= fit["last_readback"]
        assert rows[0]["seconds"] <= rows[0]["end"] - rows[0]["start"]


@pytest.mark.parametrize("kind", ["trace", "lower", "compile"])
def test_the_step_program_is_built_in_fit_0_and_held_after(two_fits, kind):
    caused = [
        b for b in two_fits["builds"]
        if b["cause"] == "ddl.step_dispatch" and b["kind"] == kind
        and b["parent"] is None
    ]
    by_fit = {
        f: [b for b in caused if b["fit"] == f and "_run" in b["program"]]
        for f in (0, 1)
    }
    assert by_fit[0], "fit 0 built no step program under its dispatch"
    fit0 = two_fits["fits"][0]
    first = by_fit[0][0]
    assert fit0["first_window"] <= first["start"]
    assert first["end"] <= fit0["first_dispatch_done"]
    # The multistep cache holds the program: fit 1 builds nothing there.
    assert [b for b in caused if b["fit"] == 1] == []


def test_the_init_program_is_state_inits(two_fits):
    rows = [
        b for b in two_fits["builds"] if b["cause"] == "ddl.state_init"
    ]
    assert {b["fit"] for b in rows} == {0, 1}
    assert {"trace", "lower", "compile"} <= {b["kind"] for b in rows}
    spans = {
        s["fit"]: s for s in two_fits["spans"]
        if s["name"] == "ddl.state_init"
    }
    for b in rows:
        assert spans[b["fit"]]["start"] <= b["start"]
        assert b["end"] <= spans[b["fit"]]["end"] + 1e-3
    # Self time: the spans less the builds they caused.
    caused = sum(
        b["end"] - b["start"] for b in rows if b["parent"] is None
    )
    total = sum(s["seconds"] for s in spans.values())
    own = two_fits["summary"]["stages"]["ddl.state_init"]["self_seconds"]
    assert own == pytest.approx(total - caused)
    assert 0.0 <= own < total


def test_builds_outside_a_stage_have_no_cause(two_fits):
    # The test's own weights (`jax.random`), before any fit.
    assert any(
        b["cause"] is None and b["fit"] is None for b in two_fits["builds"]
    )
    assert all(
        b["cause"] in (None, "ddl.state_init", "ddl.step_dispatch")
        for b in two_fits["builds"]
    )


def test_the_summary_adds_up(two_fits):
    s = two_fits["summary"]
    assert s["fits"] == 2 and s == two_fits["report"]
    sec = s["seconds"]
    kinds = sum(sec[k] for k in profiling.BUILD_KINDS)
    assert 0.0 < sec["trainer_build"] <= kinds
    assert sec["cache_load"] == 0.0 and sec["late_build"] == 0.0
    assert sec["bring_up"] > 0.0 and sec["fit_start"] > 0.0
    assert sec["fit_stop"] >= 0.0
    last = two_fits["fits"][-1]
    wall = last["first_dispatch_done"] - two_fits["spans"][0]["start"]
    assert (
        kinds + sec["bring_up"] + 2 * sec["fit_start"] + sec["fit_stop"]
        <= wall
    )
    assert len(s["slowest_programs"]) == 5
    assert s["slowest_programs"][0]["kind"] in profiling.BUILD_KINDS


def test_registering_again_keeps_one_listener(two_fits):
    # bring_up, Trainer.__init__ and two more calls in the script.
    assert two_fits["duration_listeners"] == 1


def test_build_timers_are_the_trainers_and_still_in_a_later_window(two_fits):
    timers = two_fits["trainer_timers"]
    for kind in ("trace", "lower", "compile"):
        assert timers[f"build.{kind}.total_s"] > 0.0
    assert not [k for k in two_fits["default_timers"] if "build." in k]
    for name in ("state_init", "loader_attach", "pool_stop"):
        assert timers[f"startup.{name}.count"] == 2.0
    assert two_fits["default_timers"]["startup.pool_start.count"] == 2.0
    # Ten hook calls (two fits of five windows); between two calls lies
    # one whole window: wait, dispatch, read-back.
    snaps = two_fits["hook_snaps"]
    assert len(snaps) == 10
    for before, after in ((snaps[3], snaps[4]), (snaps[8], snaps[9])):
        assert before and set(after) == set(before)
        assert all(after[k] - before[k] == 0.0 for k in before)
    # ...and the first window's dispatch is where they come from.
    assert snaps[1]["build.compile.total_s"] > snaps[0].get(
        "build.compile.total_s", 0.0
    )


# -- (b), (c), (e): the listener on synthetic events -------------------------


@pytest.fixture
def record(monkeypatch):
    """A fresh record in the process's place; JAX's own events reach it
    through the one listener."""
    fresh = profiling.StartupRecord()
    monkeypatch.setattr(profiling, "_RECORD", fresh)
    profiling.listen_for_builds()
    return fresh


def emit(event, secs, program="prog", cache=None, inner=()):
    """One build extent as JAX emits it: the scalar on entry, what
    happens inside, the duration on exit."""
    import jax.monitoring as mon

    mon.record_scalar(event, time.time(), fun_name=program)
    for args in inner:
        emit(*args)
    if cache:
        mon.record_event(cache)
    mon.record_event_duration_secs(event, secs, fun_name=program)


@pytest.mark.parametrize(
    "cache, kind, verdict",
    [(HIT, "cache_load", "hit"), (MISS, "compile", "miss"),
     (None, "compile", None)],
)
def test_the_cache_event_inside_a_compile_names_its_kind(
    record, cache, kind, verdict
):
    emit(COMPILE, 2.5, "jit(step)", cache)
    emit(COMPILE, 0.25, "jit(next)")  # the verdict does not leak
    first, second = record.builds
    assert (first.kind, first.cache, first.program) == (
        kind, verdict, "jit(step)"
    )
    assert (second.kind, second.cache) == ("compile", None)
    assert first.end - first.start == pytest.approx(2.5)
    assert first.end <= time.monotonic()
    totals = record.totals()
    assert totals.by_kind[kind] >= 2.5
    assert totals.slow_compiles == (1 if kind == "compile" else 0)
    assert record.summary()["slow_compiles"] == totals.slow_compiles


def test_a_verdict_left_outside_a_compile_is_not_the_next_ones(record):
    import jax.monitoring as mon

    # The cache answered and no compile extent closed over it (a compile
    # that never reported, a lookup outside one).
    mon.record_event(HIT)
    emit(COMPILE, 2.0, "jit(next)")
    (row,) = record.builds
    assert (row.kind, row.cache, row.program) == ("compile", None, "jit(next)")
    assert record.totals().by_kind["cache_load"] == 0.0


def test_a_nested_slow_compile_counts_where_its_seconds_do(record):
    # A compile inside a trace's extent adds no seconds of its own, so it
    # is no program of `setup_cache_miss_programs` either: the count and
    # `setup_compile_s` cannot disagree.
    emit(TRACE, 5.0, "outer", inner=[(COMPILE, 3.0, "jit(inner)", MISS)])
    totals = record.totals()
    assert totals.by_kind == {
        "trace": 5.0, "lower": 0.0, "compile": 0.0, "cache_load": 0.0,
    }
    assert totals.slow_compiles == 0
    inner = next(b for b in record.builds if b.program == "jit(inner)")
    assert inner.parent is not None and inner.cache == "miss"
    emit(COMPILE, 3.0, "jit(own)", MISS)
    assert record.totals().slow_compiles == 1


@pytest.mark.parametrize(
    "event, kind", [(TRACE, "trace"), (LOWER, "lower"), (COMPILE, "compile")]
)
def test_a_build_carries_the_stage_open_on_its_thread(record, event, kind):
    m = Metrics()
    done = threading.Event()

    def elsewhere():
        emit(event, 0.5, "other")
        done.set()

    with profiling.stage("ddl.step_dispatch", m):
        emit(event, 0.25, "mine")
        worker = threading.Thread(target=elsewhere)
        worker.start()
        worker.join(10)
    assert done.is_set()
    emit(event, 0.125, "after")
    by_program = {b.program: b for b in record.builds}
    assert by_program["mine"].cause == "ddl.step_dispatch"
    assert by_program["other"].cause is None  # another thread's
    assert by_program["after"].cause is None  # the stage has closed
    assert all(b.kind == kind for b in record.builds)
    assert m.timer(f"build.{kind}").total_s == pytest.approx(0.25)
    assert m.timer(f"build.{kind}").count == 1
    totals = record.totals()
    assert totals.by_cause["ddl.step_dispatch"] == pytest.approx(0.25)
    assert totals.by_cause[None] == pytest.approx(0.625)


@pytest.mark.parametrize(
    "name", [n for n, row in profiling.STAGES.items() if not row.builds]
)
def test_a_stage_that_builds_nothing_is_no_cause(record, name):
    with profiling.stage(name, Metrics()):
        emit(TRACE, 0.25)
    assert record.builds[-1].cause is None


def test_nested_events_count_once(record):
    m = Metrics()
    with profiling.stage("ddl.state_init", m):
        emit(TRACE, 1.0, "outer", inner=[
            (TRACE, 0.25, "helper"),
            (TRACE, 1e-5, "add"),  # too short for a row of its own
        ])
        emit(LOWER, 2.0, "jit(outer)", inner=[
            (TRACE, 0.5, "kernel_body", None, [(TRACE, 0.125, "deeper")]),
        ])
    rows = {b.program: b for b in record.builds}
    assert set(rows) == {
        "outer", "helper", "jit(outer)", "kernel_body", "deeper"
    }
    assert rows["outer"].parent is None and rows["jit(outer)"].parent is None
    assert rows["helper"].parent == rows["outer"].id
    # The OUTERMOST extent is the parent, however deep the row.
    assert rows["kernel_body"].parent == rows["jit(outer)"].id
    assert rows["deeper"].parent == rows["jit(outer)"].id
    totals = record.totals()
    assert totals.by_kind == {
        "trace": 1.0, "lower": 2.0, "compile": 0.0, "cache_load": 0.0
    }
    assert totals.seconds() == 3.0
    assert m.timer("build.trace").total_s == 1.0
    assert m.timer("build.lower").total_s == 2.0


def test_a_stages_self_time_is_its_span_less_its_builds(record):
    with profiling.stage("ddl.state_init", Metrics()):
        time.sleep(0.02)
        emit(COMPILE, 0.015, "jit(init)")
    (span,) = record.spans
    assert span.name == "ddl.state_init" and span.seconds >= 0.02
    stages = record.summary()["stages"]["ddl.state_init"]
    assert stages["count"] == 1
    assert stages["self_seconds"] == pytest.approx(span.seconds - 0.015)


def test_an_inner_jitted_function_is_traced_inside_its_callers_extent(record):
    import jax
    import jax.numpy as jnp

    def slow_helper(x):
        time.sleep(0.005)  # while TRACING: long enough for a row
        return x * 2 + 1

    inner = jax.jit(slow_helper)

    def calls_inner(x):
        return inner(x).sum()

    t0 = time.monotonic()
    jax.block_until_ready(jax.jit(calls_inner)(jnp.ones(8)))
    wall = time.monotonic() - t0
    traces = [b for b in record.builds if b.kind == "trace"]
    outer = next(b for b in traces if b.program == "calls_inner")
    helper = next(b for b in traces if b.program == "slow_helper")
    assert outer.parent is None and helper.parent == outer.id
    assert outer.start <= helper.start and helper.end <= outer.end
    # The helper's seconds are its caller's: counted once, inside the wall.
    totals = record.totals()
    assert totals.by_kind["trace"] == pytest.approx(
        sum(b.end - b.start for b in traces if b.parent is None)
    )
    assert outer.end - outer.start >= helper.end - helper.start >= 0.005
    assert totals.seconds() <= wall
    assert {b.kind for b in record.builds} >= {"trace", "lower", "compile"}


def test_the_record_is_bounded_and_loses_no_second(monkeypatch):
    small = profiling.StartupRecord(max_rows=8)
    monkeypatch.setattr(profiling, "_RECORD", small)
    profiling.listen_for_builds()
    for i in range(20):
        emit(COMPILE, 1.5, f"jit(p{i})")
        with profiling.stage("ddl.pool_start", None):
            pass
    assert len(small.builds) == 8 and len(small.spans) == 8
    assert small.builds.maxlen == small.spans.maxlen == small.fits.maxlen == 8
    assert [b.program for b in small.builds][-1] == "jit(p19)"
    totals = small.totals()
    assert totals.by_kind["compile"] == 30.0
    assert totals.slow_compiles == 20
    assert small.summary()["stages"]["ddl.pool_start"]["count"] == 20


def test_an_extent_open_before_the_listener_closes_as_outermost(record):
    import jax.monitoring as mon

    mon.record_event_duration_secs(TRACE, 0.5, fun_name="half_seen")
    emit(TRACE, 0.25, "whole")
    assert [(b.program, b.parent) for b in record.builds] == [
        ("half_seen", None), ("whole", None)
    ]
    assert record.totals().by_kind["trace"] == 0.75


def test_other_events_are_not_rows(record):
    import jax.monitoring as mon

    mon.record_scalar("/jax/other", 1.0)
    mon.record_event("/jax/compilation_cache/compile_requests_use_cache")
    mon.record_event_duration_secs("/jax/other_duration", 3.0)
    assert not record.builds and record.totals().seconds() == 0.0


# -- the record's own arithmetic ---------------------------------------------


def test_a_summary_without_a_fit_is_cut_now(record):
    emit(TRACE, 0.5)
    emit(COMPILE, 2.0, "jit(w)", MISS)
    s = record.summary()
    assert s["fits"] == 0
    assert s["seconds"] == {
        "bring_up": 0.0, "trace": 0.5, "lower": 0.0, "compile": 2.0,
        "cache_load": 0.0, "trainer_build": 0.0, "fit_start": 0.0,
        "fit_stop": 0.0, "late_build": 0.0,
    }
    assert s["slowest_programs"][0]["program"] == "jit(w)"
    assert s["slowest_programs"][0]["cache"] == "miss"


def test_set_up_ends_at_the_last_fits_first_dispatch(record):
    m = Metrics()
    emit(COMPILE, 4.0, "jit(reference)")
    for ordinal in range(2):
        fit = record.begin_fit()
        assert fit.ordinal == ordinal and fit.entered is not None
        with profiling.stage("ddl.state_init", m):
            emit(COMPILE, 0.5, "jit(init)")
        for window in range(3):
            with profiling.stage("ddl.window_wait", m):
                pass
            with profiling.stage("ddl.step_dispatch", m):
                if window == 0 and ordinal == 0:
                    emit(TRACE, 1.0, "_run")
                if window == 2 and ordinal == 1:
                    emit(COMPILE, 8.0, "jit(_run)")  # a late rebuild
        record.stamp("last_readback")
        with profiling.stage("ddl.pool_stop", m):
            pass
        with profiling.stage("ddl.pool_stop", None):
            pass
        record.end_fit(fit)
        assert fit.first_window <= fit.first_dispatch_done
        assert fit.first_dispatch_done <= fit.last_readback <= fit.returned
    s = record.summary()
    sec = s["seconds"]
    assert (sec["trace"], sec["compile"]) == (1.0, 5.0)
    assert sec["trainer_build"] == 2.0  # init twice, the step's trace
    assert sec["late_build"] == 8.0 and s["slow_compiles"] == 1
    assert record.totals().slow_compiles == 2
    assert m.timer("build.compile").total_s == 9.0
    # One row a fit for each stage, the hot path's first emissions only.
    names = [(r.name, r.fit) for r in record.spans]
    assert names == [
        (name, ordinal) for ordinal in range(2) for name in (
            "ddl.state_init", "ddl.window_wait", "ddl.step_dispatch",
            "ddl.pool_stop",
        )
    ]
    assert s["stages"]["ddl.pool_stop"]["count"] == 2
    assert s["stages"]["ddl.step_dispatch"]["count"] == 2
    # `fit_start` leaves the builds inside entered -> first_window out.
    starts = [
        f.first_window - f.entered - 0.5 for f in record.fits
    ]
    assert sec["fit_start"] == pytest.approx(sum(starts) / 2)
    assert m.timer("trainer.step_dispatch").count == 6


def test_stamping_outside_a_fit_does_nothing(record):
    record.stamp("last_readback")
    with profiling.stage("ddl.window_wait", Metrics()):
        pass
    assert not record.fits and not record.spans


def test_started_back_dates_the_timer_and_the_row(record):
    m = Metrics()
    entered = time.monotonic() - 1.5
    with profiling.stage("ddl.bring_up", m, started=entered):
        pass
    (row,) = record.spans
    assert row.start == entered and row.seconds >= 1.5
    assert m.timer("startup.bring_up").total_s == pytest.approx(
        row.seconds, abs=5e-3
    )


# -- the table -----------------------------------------------------------------


def test_the_table_says_which_stages_the_record_sees():
    assert {
        name: (row.startup, row.builds)
        for name, row in profiling.STAGES.items() if row.startup or row.builds
    } == {
        "ddl.bring_up": ("span", False),
        "ddl.pool_start": ("span", False),
        "ddl.state_init": ("span", True),
        "ddl.loader_attach": ("span", False),
        "ddl.pool_stop": ("span", False),
        "ddl.window_wait": ("first_window", False),
        "ddl.step_dispatch": ("first_dispatch_done", True),
    }
    for row in profiling.STAGES.values():
        assert row.startup in (None, "span") + profiling.FIT_STAMPS
        if row.startup:
            assert row.timer, "a start-up stage has a Metrics timer"
            assert row.timer.startswith(("startup.", "trainer."))


def test_the_records_lock_is_a_leaf():
    from ddl_tpu.concurrency import LOCK_ORDER

    assert LOCK_ORDER.index("obs.startup") > LOCK_ORDER.index("obs.metrics")


# -- (g): producers stay off JAX -----------------------------------------------


class JaxProbeProducer(ProducerFunctionSkeleton):
    """Every value of its windows says whether ``jax`` was imported in
    the producer's process when the window was filled."""

    def on_init(self, producer_idx=0, **kw) -> DataProducerOnInitReturn:
        return DataProducerOnInitReturn(
            nData=32, nValues=2, shape=(32, 2), splits=(1, 1)
        )

    def post_init(self, my_ary, **kw):
        my_ary[:] = float("jax" in sys.modules)

    def execute_function(self, my_ary, iteration=0, **kw):
        my_ary[:] = float("jax" in sys.modules)


@cross_process_ring
def test_spawned_producers_never_import_jax():
    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader

    @distributed_dataloader(n_producers=2, mode="process")
    def main(env):
        loader = DistributedDataLoader(
            JaxProbeProducer(), batch_size=8, connection=env.connection,
            n_epochs=3, output="numpy",
        )
        seen = []
        for _ in range(3):
            for batch in loader:
                seen.append(np.concatenate([np.asarray(c) for c in batch], 1))
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)
        return np.concatenate(seen)

    before = len(profiling.startup_record().spans)
    values = main()
    assert values.size and not values.any(), "a producer had imported jax"
    # The wrapper's two stages ran here, in the consumer.
    names = [s.name for s in list(profiling.startup_record().spans)[before:]]
    assert names == ["ddl.pool_start", "ddl.pool_stop"]


def test_importing_the_package_leaves_jax_alone():
    code = (
        "import sys; import ddl_tpu.env, ddl_tpu.profiling, "
        "ddl_tpu.datapusher; "
        "ddl_tpu.profiling.startup_record().summary(); "
        "assert 'jax' not in sys.modules, 'jax imported'"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
