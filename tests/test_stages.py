"""``profiling.stage``: the one emission point of the data plane's host
stages (profiler annotation + ``Metrics`` timer + ``SpanLog`` span), and
the one table that names them (``profiling.STAGES``)."""

import glob
import os
import re

import jax
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddl_tpu import (
    DistributedDataLoader, Marker, distributed_dataloader, profiling,
)
from ddl_tpu import obs
from ddl_tpu.obs import spans as obs_spans
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.profiling import STAGES, stage
from ddl_tpu.readers import ArrayProducer

DOCS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs", "OBSERVABILITY.md",
)


def host_annotations(trace_dir):
    """Names of the ``ddl.*`` events on the profiler's host plane."""
    files = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    assert files, f"no trace under {trace_dir}"
    data = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    return {
        ev.name
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines
        for ev in line.events if ev.name.startswith("ddl.")
    }


# -- the emission point ----------------------------------------------------


class TestStage:
    def test_emits_annotation_timer_and_keyed_span(self, tmp_path):
        m = Metrics()
        with obs_spans.tracing() as slog, profiling.trace(str(tmp_path)):
            with stage("ddl.staging_copy", m, key=(2, 7)):
                pass
        assert "ddl.staging_copy" in host_annotations(str(tmp_path))
        t = m.timer("ingest.stage_copy")
        assert t.count == 1 and t.total_s > 0.0
        (ev,) = slog.events()
        t0, t1, name, pidx, seq, pid = ev
        assert (name, pidx, seq, pid) == ("staging.copy", 2, 7, os.getpid())
        # One extent for timer and span: the same two clock reads.
        assert t1 - t0 == pytest.approx(t.total_s)

    def test_key_can_be_set_late_and_defaults_to_the_threads_window(self):
        with obs_spans.tracing() as slog:
            with stage("ddl.window_acquire", Metrics()) as st:
                st.key = (1, 3)  # the seq is known only post-acquire
            obs_spans.set_window(4, 9)
            try:
                with stage("ddl.ingest_put_window"):
                    pass
            finally:
                obs_spans.clear_window()
        assert [(e[2], e[3], e[4]) for e in slog.events()] == [
            ("consumer.acquire", 1, 3), ("ingest.transfer", 4, 9),
        ]

    def test_disarmed_records_nothing_and_reads_no_clock_for_the_log(
        self, monkeypatch
    ):
        reads = []
        m = Metrics()  # (its constructor reads the clock)
        real = profiling.time.perf_counter

        def counting():
            reads.append(1)
            return real()

        monkeypatch.setattr(profiling.time, "perf_counter", counting)
        assert not obs_spans.armed()
        # A stage without a timer touches no clock at all disarmed ...
        with stage("ddl.staging_transfer", m, key=(1, 1)):
            pass
        assert reads == []
        # ... and one with a timer reads it for the timer alone.
        with stage("ddl.ici_fanout", m):
            pass
        assert len(reads) == 2 and m.timer("ici.fanout").count == 1
        # No metrics registry, no timer: nothing to read it for.
        with stage("ddl.ici_fanout"):
            pass
        assert len(reads) == 2

    def test_a_block_that_raised_is_timed_but_leaves_no_span(self):
        m = Metrics()
        with obs_spans.tracing() as slog:
            with pytest.raises(RuntimeError):
                with stage("ddl.staging_copy", m, key=(1, 0)):
                    raise RuntimeError("torn copy")
        assert m.timer("ingest.stage_copy").count == 1
        assert slog.events() == []

    def test_an_unknown_stage_is_refused(self):
        with pytest.raises(KeyError):
            stage("ddl.no_such_stage")


# -- the table -------------------------------------------------------------


class TestStageTable:
    def test_names_are_well_formed_and_spans_are_known_lanes(self):
        timers = [r.timer for r in STAGES.values() if r.timer]
        assert len(set(timers)) == len(timers)
        for name, row in STAGES.items():
            # Plain strings: tracered.attribute groups by the whole name.
            assert re.fullmatch(r"ddl\.[a-z_]+", name), name
            assert row.span is None or row.span in obs.STAGES, name
            assert row.where

    def test_frozen_names_are_unchanged(self):
        """What the benchmark's readers, tracered.py and
        north_star_report read by name."""
        frozen = {
            "ddl.window_wait": ("trainer.window_wait", None),
            "ddl.window_acquire": ("consumer.wait", "consumer.acquire"),
            "ddl.release_wait": ("ingest.release_wait", None),
            "ddl.staging_copy": ("ingest.stage_copy", "staging.copy"),
            "ddl.staging_transfer": (None, "staging.transfer"),
            "ddl.ingest_put_window": (None, "ingest.transfer"),
            "ddl.ingest_put": (None, None),
            "ddl.ici_fanout": ("ici.fanout", "ici.fanout"),
            "ddl.step_dispatch": ("trainer.step_dispatch", "trainer.consume"),
        }
        for name, (timer, span) in frozen.items():
            assert (STAGES[name].timer, STAGES[name].span) == (timer, span)

    def test_docs_table_is_the_table(self):
        """docs/OBSERVABILITY.md's stage table, row for row."""
        with open(DOCS) as f:
            text = f.read()
        rows = re.findall(
            r"^\| `(ddl\.[a-z_]+)` \| (`[^`]+`|—) \| (`[^`]+`|—) \| (.+) \|$",
            text, flags=re.M,
        )
        assert {
            name: (timer.strip("`"), span.strip("`"), where)
            for name, timer, span, where in rows
        } == {
            name: (row.timer or "—", row.span or "—", row.where)
            for name, row in STAGES.items()
        }


# -- every row is reachable ------------------------------------------------


def _data():
    return np.arange(64 * 6, dtype=np.float32).reshape(64, 6)


def _stream(m, n_producers=2, pin_attached=False, batches=False,
            window_size=8, batch_size=2, **loader_kw):
    """A THREAD-mode loader drained as a window stream (or by batches)."""

    @distributed_dataloader(n_producers=n_producers, mode="thread")
    def main(env):
        loader = DistributedDataLoader(
            ArrayProducer(_data(), window_size=window_size, splits=(5, 1)),
            batch_size=batch_size, connection=env.connection, n_epochs=4,
            output="jax", metrics=m, **loader_kw,
        )
        if pin_attached:
            # The accelerator's inline discipline: the transfer sources
            # the ring slot, so releases ride the deferred backlog.
            loader._ingestor.window_source_detached = lambda: False
        if batches:
            for _ in range(4):
                for cols in loader:
                    jax.block_until_ready(cols)
                loader.mark(Marker.END_OF_EPOCH)
        else:
            for win in loader.windows():
                jax.block_until_ready(win)
                loader.mark(Marker.END_OF_EPOCH)
        loader.shutdown()

    main()


def _fit(m):
    from ddl_tpu.models import pointnet
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.trainer import Trainer

    cfg = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
    trainer = Trainer(
        loss_fn=lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
        optimizer=optax.adam(1e-2), mesh=make_mesh({"dp": 8}),
        param_specs=pointnet.param_specs(cfg),
        init_params=pointnet.init_params(cfg, jax.random.key(0)),
        batch_spec=P(("dp",)), metrics=m,
    )
    data = np.random.default_rng(0).random((256, 6)).astype(np.float32)
    res = trainer.fit(
        ArrayProducer(data, window_size=64, splits=(3, 2, 1)),
        batch_size=16, n_epochs=3, n_producers=2, mode="thread",
        output="jax", window_stream=True,
    )
    assert len(res.losses) == 3


#: The stages whose sites have no registry of their own (`bringup.py`,
#: `env.py`'s wrapper before the user's main): they time into the
#: process default.  Every other row honours the `metrics` it is handed.
ON_THE_DEFAULT_REGISTRY = ("ddl.bring_up", "ddl.pool_start")


@pytest.fixture(scope="module")
def tour(tmp_path_factory):
    """One THREAD-mode tour of the data plane — staged stream, inline
    stream with attached sources, batch iteration, an ICI-distributed
    stream on the virtual mesh, a fused ``Trainer.fit`` (and with it the
    start-up stages) — under an armed SpanLog and a CPU ``jax.profiler``
    trace."""
    from ddl_tpu import bringup

    trace_dir = str(tmp_path_factory.mktemp("stages"))
    m = Metrics()
    before = {
        name: default_metrics().timer(STAGES[name].timer).count
        for name in ON_THE_DEFAULT_REGISTRY
    }
    dp8 = NamedSharding(Mesh(np.array(jax.devices()), ("dp",)), P("dp"))
    cache_dir = jax.config.jax_compilation_cache_dir
    with obs_spans.tracing() as slog, profiling.trace(trace_dir):
        # A process's first call; here only for its stage (the process-
        # wide compile-cache placement is put back at once).
        bringup.bring_up("cpu")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        _stream(m, staged=True)
        _stream(m, staged=False, pin_attached=True)
        _stream(m, batches=True)
        # (batches per window, batch, features): one batch-block a device.
        _stream(m, n_producers=1, window_size=32, batch_size=4,
                sharding=dp8, distribute="ici")
        _fit(m)
    return {
        "annotations": host_annotations(trace_dir),
        "metrics": m,
        "default_before": before,
        "spans": {e[2] for e in slog.events()},
        "keyed": {e[2] for e in slog.events() if e[3] is not None},
    }


@pytest.mark.parametrize("name", sorted(STAGES))
def test_every_stage_row_is_reachable(tour, name):
    row = STAGES[name]
    assert name in tour["annotations"], "not on the profiler's host plane"
    if name in ON_THE_DEFAULT_REGISTRY:
        # Shared by every test of the process: the TOUR has to have timed.
        count = default_metrics().timer(row.timer).count
        assert count > tour["default_before"][name]
    elif row.timer:
        assert tour["metrics"].timer(row.timer).count >= 1
    if row.span:
        assert row.span in tour["spans"]
        assert row.span in tour["keyed"], "no span carried a window key"
