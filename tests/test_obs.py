"""ddl_tpu.obs: end-to-end data-plane tracing (ISSUE 15).

Covers the four tentpole pieces and their satellites:

- Metrics histograms (fixed log-spaced bounded buckets, quantile
  accuracy, snapshot/state transport, reset semantics) and the
  gauge-companion lifecycle (``clear_gauge`` retiring ``.max`` with its
  base — the between-bench-reps staleness fix);
- SpanLog window-lifecycle spans: bounded buffer, zero-cost disarmed,
  THREAD e2e stage coverage keyed on the integrity-trailer identity,
  Chrome/Perfetto export with cross-process flow stitching;
- cross-process aggregation: a PROCESS-mode run whose worker
  registries surface under ``producer.<idx>.*`` in the consumer
  registry AND whose stitched Chrome trace carries one window's spans
  across the producer→consumer process boundary (the ISSUE 15
  acceptance row), plus report fencing;
- the flight recorder: bounded ring, atomic parseable dumps, the
  seeded-corruption artifact naming the faulted (producer_idx, seq),
  and the ``python -m ddl_tpu.obs dump`` CLI;
- the north_star_report percentile contract: the admission-wait p99
  agrees with an independently recorded distribution, and every name
  family documented in docs/OBSERVABILITY.md has an emitting site
  (the reflection test — documented-but-never-emitted names rot).
"""

import json
import os
import re
import zlib
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

from ddl_tpu import obs
from ddl_tpu.obs import aggregate as obs_aggregate
from ddl_tpu.obs import recorder as obs_recorder
from ddl_tpu.obs import spans as obs_spans
from ddl_tpu.observability import (
    HIST_MAX,
    HIST_MIN,
    Histogram,
    Metrics,
    hist_bounds,
)

REPO_ROOT = Path(__file__).resolve().parent.parent


# -- histograms (tentpole piece 2) ----------------------------------------


class TestHistogram:
    def test_quantiles_track_numpy_within_one_bucket(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(-4.0, 1.5, 4000)
        h = Histogram()
        for v in vals:
            h.observe(v)
        for q in (0.1, 0.5, 0.9, 0.99):
            est = h.quantile(q)
            ref = float(np.quantile(vals, q))
            # One log-spaced bucket is x10^(1/6) ~= 1.47.
            assert ref / 1.5 <= est <= ref * 1.5, (q, est, ref)

    def test_bounded_by_construction(self):
        h = Histogram()
        for v in (-1.0, 0.0, HIST_MIN / 10, HIST_MAX, HIST_MAX * 100):
            h.observe(v)
        assert h.count == 5
        assert len(h.counts) == len(hist_bounds()) + 2
        assert h.counts[0] == 3  # underflow incl. zero/negatives
        assert h.counts[-1] == 2  # overflow

    def test_quantile_clamps_to_observed_extremes(self):
        h = Histogram()
        h.observe(0.5)
        assert h.quantile(0.0) == 0.5
        assert h.quantile(1.0) == 0.5

    def test_empty_quantile_is_zero(self):
        assert Histogram().quantile(0.99) == 0.0
        assert Metrics().quantile("never.observed", 0.5) == 0.0

    def test_state_roundtrip(self):
        h = Histogram()
        for v in (1e-3, 2e-3, 5.0):
            h.observe(v)
        h2 = Histogram.from_state(h.state())
        assert h2.counts == h.counts
        assert h2.quantile(0.5) == h.quantile(0.5)

    def test_metrics_snapshot_carries_percentile_keys(self):
        m = Metrics()
        m.observe("lat", 0.01)
        snap = m.snapshot()
        assert snap["lat.count"] == 1.0
        assert snap["lat.p50"] == pytest.approx(0.01)
        assert snap["lat.p50"] <= snap["lat.p99"]

    def test_reset_clears_histograms(self):
        m = Metrics()
        m.observe("lat", 0.01)
        m.reset()
        assert m.quantile("lat", 0.5) == 0.0
        assert "lat.p50" not in m.snapshot()


# -- gauge .max companions (satellite: reset/clear staleness) --------------


class TestGaugeCompanions:
    def test_clear_gauge_retires_max_companion(self):
        m = Metrics()
        m.set_gauge("q.depth", 9.0)
        m.set_gauge("q.depth", 1.0)
        assert m.snapshot()["q.depth.max"] == 9.0
        m.clear_gauge("q.depth")
        snap = m.snapshot()
        assert "q.depth" not in snap and "q.depth.max" not in snap

    def test_reset_clears_max_with_base(self):
        m = Metrics()
        m.set_gauge("q.depth", 9.0)
        m.reset()
        snap = m.snapshot()
        assert "q.depth.max" not in snap
        # Re-seeding after reset starts a FRESH high-water, not the
        # stale pre-reset peak.
        m.set_gauge("q.depth", 2.0)
        assert m.snapshot()["q.depth.max"] == 2.0

    def test_tenant_unregister_clears_stall_gauges(self):
        """The shipped fix site: a departed tenant must not leave a
        phantom ``serve.stall.<t>``/``.max`` pair between bench reps."""
        from ddl_tpu.serve import AdmissionController, FairShareScheduler
        from ddl_tpu.serve import TenantSpec

        m = Metrics()
        ctl = AdmissionController(
            scheduler=FairShareScheduler(quantum_bytes=1024, metrics=m),
            metrics=m,
        )
        t = ctl.register(TenantSpec("ghost"))
        ctl.report()  # publishes serve.stall.ghost
        assert "serve.stall.ghost" in m.snapshot()
        t.close()
        snap = m.snapshot()
        assert "serve.stall.ghost" not in snap
        assert "serve.stall.ghost.max" not in snap
        from ddl_tpu.ingest import north_star_report

        assert "ghost" not in north_star_report(m)["serve_tenant_stall"]


# -- SpanLog (tentpole piece 1) --------------------------------------------


class TestSpanLog:
    def test_disarmed_is_a_noop(self):
        assert obs_spans.log() is None
        assert obs_spans.t0() == 0.0  # no clock read disarmed
        obs_spans.record("x", 1, 2, 0.0)  # must not raise
        obs_spans.mark("x", 1, 2)
        obs_spans.set_window(1, 2)
        assert obs_spans.current_window() == (None, None)

    def test_bounded_ring_drops_oldest(self):
        slog = obs_spans.SpanLog(capacity=4)
        for i in range(10):
            slog.record("s", 1, i, 0.0, 1.0)
        assert len(slog.events()) == 4
        assert slog.appended == 10
        assert [e[4] for e in slog.events()] == [6, 7, 8, 9]

    def test_drain_new_cursor(self):
        slog = obs_spans.SpanLog(capacity=16)
        slog.record("s", 1, 0, 0.0, 1.0)
        assert len(slog.drain_new()) == 1
        assert slog.drain_new() == []
        slog.record("s", 1, 1, 0.0, 1.0)
        slog.record("s", 1, 2, 0.0, 1.0)
        assert [e[4] for e in slog.drain_new()] == [1, 2]

    def test_tracing_ctx_arms_and_restores(self):
        assert not obs_spans.armed()
        with obs_spans.tracing(export=True) as slog:
            assert obs_spans.armed() and obs_spans.log() is slog
            assert os.environ.get(obs_spans.TRACE_ENV)
            t = obs_spans.t0()
            assert t > 0.0
            obs_spans.record("stage", 3, 7, t)
        assert not obs_spans.armed()
        assert obs_spans.TRACE_ENV not in os.environ
        (ev,) = slog.events()
        assert ev[2:5] == ("stage", 3, 7)

    def test_stage_totals(self):
        slog = obs_spans.SpanLog()
        slog.record("a", 1, 0, 0.0, 0.25)
        slog.record("a", 1, 1, 1.0, 1.25)
        slog.record("b", 1, 0, 0.0, None)  # instant: no duration
        totals = slog.stage_totals()
        assert totals["a"] == pytest.approx(0.5)
        assert "b" not in totals


class TestChromeTrace:
    def _events(self):
        # Two windows; window (1, 5) crosses two pids.
        return [
            (0.0, 0.1, "producer.fill", 1, 5, 100),
            (0.1, 0.2, "producer.commit", 1, 5, 100),
            (0.25, 0.3, "consumer.acquire", 1, 5, 200),
            (0.31, None, "consumer.yield", 1, 5, 200),
            (0.0, 0.1, "consumer.acquire", 2, 0, 200),
        ]

    def test_lanes_spans_and_instants(self):
        tr = obs.chrome_trace(self._events())
        evs = tr["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        instants = [e for e in evs if e["ph"] == "i"]
        assert len(xs) == 4 and len(instants) == 1
        names = {
            e["args"]["name"]
            for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert {"producer.fill", "consumer.acquire"} <= names
        # Lane order follows the documented waterfall.
        lane = {
            (e["pid"], e["args"]["name"]): e["tid"]
            for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert lane[(100, "producer.fill")] < lane[(200, "consumer.acquire")]

    def test_flow_stitch_only_for_cross_pid_windows(self):
        tr = obs.chrome_trace(self._events())
        flows = [e for e in tr["traceEvents"] if e["ph"] in ("s", "f")]
        assert len(flows) == 2
        s, f = sorted(flows, key=lambda e: e["ph"], reverse=True)
        assert s["ph"] == "s" and s["pid"] == 100
        assert f["ph"] == "f" and f["pid"] == 200
        assert s["id"] == f["id"] == (1 << 32) | 5

    def test_write_chrome_trace_parses(self, tmp_path):
        path = str(tmp_path / "trace.json")
        obs.write_chrome_trace(self._events(), path)
        with open(path) as fh:
            data = json.load(fh)
        assert data["traceEvents"]


# -- flight recorder (tentpole piece 4) ------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = obs_recorder.FlightRecorder(capacity=8)
        for i in range(100):
            rec.note("counter", "x", float(i))
        assert len(rec.events()) == 8
        assert rec.noted == 100

    def test_metric_tap_feeds_ring(self, tmp_path):
        with obs_recorder.armed(directory=str(tmp_path)) as rec:
            m = Metrics()
            m.incr("a.b")
            m.set_gauge("c.d", 2.0)
            m.observe("e.f", 0.5)
            m.add_time("g.h", 0.1)
        kinds = {e[1] for e in rec.events()}
        assert kinds == {"counter", "gauge", "observe", "timer"}
        # Disarmed again: taps removed.
        m.incr("a.b")
        assert len(rec.events()) == 4

    def test_dump_parses_and_names_window(self, tmp_path):
        with obs_recorder.armed(directory=str(tmp_path)) as rec:
            m = Metrics()
            m.incr("integrity.corrupt_windows")
            path = obs_recorder.flight_dump(
                "unit.test", producer_idx=3, seq=11, metrics=m,
                extra={"note": "hi"},
            )
        assert path and os.path.exists(path)
        with open(path) as fh:
            record = json.load(fh)
        assert record["version"] == obs_recorder.DUMP_VERSION
        assert record["window"] == {"producer_idx": 3, "seq": 11}
        assert record["metrics"]["integrity.corrupt_windows"] == 1.0
        assert record["extra"]["note"] == "hi"

    def test_dump_budget(self, tmp_path):
        rec = obs_recorder.FlightRecorder(directory=str(tmp_path))
        paths = [
            rec.dump("r", metrics=Metrics())
            for _ in range(obs_recorder.MAX_DUMPS + 3)
        ]
        assert sum(p is not None for p in paths) == obs_recorder.MAX_DUMPS

    def test_disarmed_flight_dump_is_noop(self, tmp_path):
        assert obs_recorder.flight_dump("x") is None

    def test_cli_dump_renders(self, tmp_path, capsys):
        with obs_recorder.armed(directory=str(tmp_path)) as rec:
            rec.note("span", "consumer.acquire", 0.012,
                     producer_idx=1, seq=4)
            rec.note("counter", "integrity.replays", 1.0)
            path = obs_recorder.flight_dump(
                "integrity.corrupt_window", producer_idx=1, seq=4,
                metrics=Metrics(),
            )
        from ddl_tpu.obs.__main__ import main as cli_main

        assert cli_main(["dump", path]) == 0
        out = capsys.readouterr().out
        assert "producer_idx=1 seq=4" in out
        assert "consumer.acquire" in out  # the waterfall rendered

    def test_cli_refuses_newer_version(self, tmp_path):
        p = tmp_path / "future.json"
        p.write_text(json.dumps({"version": 999, "events": []}))
        from ddl_tpu.obs.__main__ import main as cli_main

        with pytest.raises(SystemExit):
            cli_main(["dump", str(p)])


# -- report merging / fencing (tentpole piece 3) ---------------------------


class TestReportMerger:
    def _report(self, idx, report_idx, counters, pid=1):
        from ddl_tpu.types import ObsReport

        m = Metrics()
        for k, v in counters.items():
            m.incr(k, v)
        return ObsReport(
            producer_idx=idx, report_idx=report_idx, pid=pid,
            snapshot=m.snapshot(), hists=m.hist_state(), spans=[],
        )

    def test_adopt_and_fence(self):
        m = Metrics()
        merger = obs.ReportMerger(m)
        assert merger.apply(self._report(0, 1, {"producer.windows": 4}))
        assert m.counter("producer.0.producer.windows") == 4
        # Newer cumulative report replaces.
        assert merger.apply(self._report(0, 2, {"producer.windows": 9}))
        assert m.counter("producer.0.producer.windows") == 9
        # Stale/duplicate report is dropped, never regresses the merge.
        assert not merger.apply(self._report(0, 1, {"producer.windows": 4}))
        assert m.counter("producer.0.producer.windows") == 9
        assert m.counter("obs.reports_stale") == 1
        assert m.counter("obs.reports_applied") == 2

    def test_respawned_incarnation_resets_the_fence(self):
        """Elastic recovery: a respawned producer restarts report
        numbering in a fresh process — the pid change resets the
        fence, so its reports are never dropped as 'stale'."""
        m = Metrics()
        merger = obs.ReportMerger(m)
        assert merger.apply(
            self._report(0, 5, {"producer.windows": 20}, pid=111)
        )
        assert merger.apply(
            self._report(0, 1, {"producer.windows": 2}, pid=222)
        )
        assert m.counter("producer.0.producer.windows") == 2
        assert m.counter("obs.reports_stale") == 0

    def test_adopted_keys_surface_in_prefixed_and_snapshot(self):
        m = Metrics()
        merger = obs.ReportMerger(m)
        merger.apply(self._report(1, 1, {"shuffle.degraded": 2}))
        assert m.prefixed("producer.1.")["shuffle.degraded"] == 2
        assert m.snapshot()["producer.1.shuffle.degraded"] == 2


# -- e2e: THREAD spans + byte identity -------------------------------------


def _run_stream(metrics, n_epochs=4, crcs=None, mode="thread",
                producers=2):
    from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
    from ddl_tpu.readers import ArrayProducer

    data = np.arange(64 * 6, dtype=np.float32).reshape(64, 6)

    @distributed_dataloader(n_producers=producers, mode=mode)
    def main(env):
        loader = DistributedDataLoader(
            ArrayProducer(data, window_size=8, splits=(5, 1)),
            batch_size=2, connection=env.connection, n_epochs=n_epochs,
            output="jax", metrics=metrics,
        )
        for win in loader.windows():
            if crcs is not None:
                crcs.append(zlib.crc32(np.asarray(win).tobytes()))
            loader.mark(Marker.END_OF_EPOCH)
        loader.drain_obs_reports(
            timeout_s=2.0 if mode == "process" else 0.0
        )
        loader.shutdown()

    main()


class TestThreadE2E:
    def test_armed_stream_records_keyed_lifecycle_spans(self):
        with obs_spans.tracing() as slog:
            _run_stream(Metrics())
        stages = {e[2] for e in slog.events()}
        assert {
            "producer.fill", "producer.commit", "consumer.acquire",
            "ingest.transfer", "consumer.yield", "consumer.release",
        } <= stages
        # Spans key on the integrity-trailer identity: every producer
        # contributed every seq.
        keys = defaultdict(set)
        for e in slog.events():
            if e[2] == "producer.commit":
                keys[e[3]].add(e[4])
        assert set(keys) == {1, 2}
        # 4 epochs over 2 producers: each SERVES seqs {0, 1} (commits
        # may run ahead of service by the ring depth).
        assert {0, 1} <= keys[1] and {0, 1} <= keys[2]
        # Acquire spans carry the SAME identities the producers stamped.
        acq = {
            (e[3], e[4]) for e in slog.events()
            if e[2] == "consumer.acquire"
        }
        assert {(1, 0), (1, 1), (2, 0), (2, 1)} <= acq

    def test_arming_never_changes_bytes(self):
        crc_armed, crc_plain = [], []
        with obs_spans.tracing():
            with obs_recorder.armed():
                _run_stream(Metrics(), crcs=crc_armed)
        _run_stream(Metrics(), crcs=crc_plain)
        assert crc_armed and crc_armed == crc_plain

    def test_window_latency_histogram_feeds_report(self):
        from ddl_tpu.ingest import north_star_report

        m = Metrics()
        _run_stream(m)
        r = north_star_report(m)
        assert r["window_latency_p99"] >= r["window_latency_p50"] > 0.0
        assert r["stage_breakdown"]["acquire_wait"] >= 0.0


# -- e2e: PROCESS-mode stitched trace + aggregation (acceptance row) -------


@pytest.fixture
def forced_py_ring(monkeypatch):
    monkeypatch.setenv("DDL_TPU_FORCE_PY_RING", "1")
    monkeypatch.setenv("DDL_TPU_OBS_SHIP_EVERY", "2")


class TestProcessStitched:
    def test_process_spans_stitch_and_registries_merge(
        self, forced_py_ring, tmp_path
    ):
        m = Metrics()
        with obs_spans.tracing(export=True) as slog:
            _run_stream(m, n_epochs=8, mode="process")
        evs = slog.events()
        pids = {e[5] for e in evs}
        assert len(pids) >= 2, "no producer-process spans arrived"
        # At least one window's spans cross the process boundary.
        by_window = defaultdict(set)
        stages_by_window = defaultdict(set)
        for e in evs:
            if e[3] is not None:
                by_window[(e[3], e[4])].add(e[5])
                stages_by_window[(e[3], e[4])].add(e[2])
        crossing = [k for k, v in by_window.items() if len(v) >= 2]
        assert crossing, "no window's spans crossed the process boundary"
        k = crossing[0]
        assert "producer.commit" in stages_by_window[k]
        assert "consumer.acquire" in stages_by_window[k]
        # The exported Chrome trace parses and carries the stitch.
        path = str(tmp_path / "stitched.json")
        obs.write_chrome_trace(evs, path)
        with open(path) as fh:
            trace = json.load(fh)["traceEvents"]
        starts = [e for e in trace if e["ph"] == "s"]
        finishes = [e for e in trace if e["ph"] == "f"]
        assert starts and finishes
        assert {e["id"] for e in starts} & {e["id"] for e in finishes}
        flow_pids = {e["pid"] for e in starts} | {
            e["pid"] for e in finishes
        }
        assert len(flow_pids) >= 2
        # Cross-process metric aggregation: the consumer registry now
        # carries each worker's counters under producer.<idx>.* — the
        # documented PROCESS-mode blind spot is closed.
        assert m.counter("obs.reports_applied") >= 1
        assert m.adopted_prefixes() == ["producer.0.", "producer.1."]
        for idx in (0, 1):
            assert m.counter(f"producer.{idx}.producer.windows") > 0
        assert m.prefixed("producer.0.")["producer.bytes"] > 0


# -- chaos: corruption leaves a named flight record ------------------------


class TestChaosFlightRecord:
    def test_seeded_corruption_dumps_artifact(self, tmp_path):
        from ddl_tpu import faults
        from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec

        m = Metrics()
        crcs = []
        plan = FaultPlan(
            [FaultSpec("producer.commit", FaultKind.RING_CORRUPTION,
                       at=2, param=8)],
            seed=3,
        )
        with obs_recorder.armed(directory=str(tmp_path)) as rec:
            with faults.armed(plan):
                _run_stream(m, n_epochs=4, crcs=crcs)
        assert plan.fired
        assert m.counter("integrity.corrupt_windows") >= 1
        assert len(crcs) == 4  # quarantine+replay kept the stream whole
        # The consumer-side dump names the faulted window's identity.
        named = []
        for path in rec.dumped_paths:
            with open(path) as fh:
                record = json.load(fh)
            if record["window"]["seq"] is not None:
                named.append(record)
        assert named, "no artifact named the faulted window"
        record = named[0]
        assert record["reason"].startswith("integrity.")
        assert isinstance(record["window"]["producer_idx"], int)
        assert isinstance(record["window"]["seq"], int)
        assert record["metrics"]["integrity.corrupt_windows"] >= 1.0

    def test_preemption_notice_dumps_at_poll_not_in_notify(self, tmp_path):
        """notify() may run inside the SIGTERM handler, where a dump
        (registry lock + file IO) could deadlock against the
        interrupted main thread — the artifact is deferred to the next
        main-thread poll()/drain()."""
        from ddl_tpu.resilience import PreemptionGuard

        m = Metrics()
        with obs_recorder.armed(directory=str(tmp_path)) as rec:
            guard = PreemptionGuard(deadline_s=5.0, metrics=m)
            guard.notify("unit")
            assert rec.dumps == 0  # NOT in the (possibly-signal) frame
            assert guard.poll() is True
            assert rec.dumps == 1
            guard.poll()
            assert rec.dumps == 1  # once per notice
        with open(rec.dumped_paths[0]) as fh:
            record = json.load(fh)
        assert record["reason"] == "resilience.preemption_notice"
        assert record["extra"]["grace_s"] == 5.0


# -- admission p99 agreement (acceptance row) ------------------------------


class TestAdmissionP99Agreement:
    def test_report_p99_matches_independent_distribution(self):
        """north_star_report's admission_wait_p99 must agree with an
        independently recorded wait distribution through the REAL
        admit path (a throttled tenant, waits in the ms range)."""
        import time as _time

        from ddl_tpu.ingest import north_star_report
        from ddl_tpu.serve import FairShareScheduler, TenantSpec

        m = Metrics()
        sched = FairShareScheduler(quantum_bytes=1 << 16, metrics=m)
        # 4 MiB/s budget, 64 KiB windows -> ~16 ms steady-state wait
        # once the bucket's initial one-second burst allowance is gone;
        # one oversized charge burns it up front so every measured
        # admit is genuinely throttled.
        sched.register(TenantSpec("t0", byte_budget_per_s=1 << 22))
        sched.admit("t0", timeout_s=10.0)
        sched.note_served("t0", 1 << 22)
        waits = []
        for _ in range(25):
            t0 = _time.perf_counter()
            sched.admit("t0", timeout_s=10.0)
            waits.append(_time.perf_counter() - t0)
            sched.note_served("t0", 1 << 16)
        p99_np = float(np.percentile(waits, 99))
        r = north_star_report(m)
        p99_hist = r["admission_wait_p99"]
        p99_tenant = r["serve_tenant_admission_p99"]["t0"]
        assert p99_np > 1e-3, "tenant was never throttled"
        # One log bucket (x1.47) + interpolation margin.
        assert p99_np / 1.8 <= p99_hist <= p99_np * 1.8
        assert p99_np / 1.8 <= p99_tenant <= p99_np * 1.8


# -- reflection: documented names must have emitting sites -----------------


class TestDocReflection:
    """Every metric name documented in docs/OBSERVABILITY.md's
    name-family tables must appear as an emission-site string literal
    somewhere in the tree (grep-the-tree style) — a new subsystem
    cannot document names it never emits (ISSUE 15 satellite).

    Dynamic components (``<tenant>``, ``<idx>``, ``<leg>``) map to
    f-string ``{...}`` holes.  ``ddl.*`` names are jax.profiler
    annotation lanes, matched the same way.
    """

    #: Name-shaped backticked tokens inside table rows.
    _ROW = re.compile(r"^\|\s*`([a-z][a-z_.<>]*(?:`[^|]*`)*)`")
    _NAME = re.compile(r"`([a-z][a-z_]*(?:\.[a-z_<>]+)+)`")

    def _documented_names(self):
        doc = (REPO_ROOT / "docs" / "OBSERVABILITY.md").read_text()
        names = set()
        for line in doc.splitlines():
            if not line.startswith("|"):
                continue
            first_cell = line.split("|")[1]
            for name in self._NAME.findall(first_cell):
                names.add(name)
        return sorted(names)

    def _source_blob(self):
        blobs = []
        for path in (REPO_ROOT / "ddl_tpu").rglob("*.py"):
            blobs.append(path.read_text())
        return "\n".join(blobs)

    def test_tables_were_parsed(self):
        names = self._documented_names()
        assert len(names) > 80, names  # the table is the real one
        assert "consumer.windows" in names
        assert "serve.stall.<tenant>" in names

    def test_every_documented_name_has_an_emitting_site(self):
        blob = self._source_blob()
        missing = []
        for name in self._documented_names():
            # <placeholder> -> an f-string hole of any expression.
            pat = re.escape(name).replace(
                r"<tenant>", r"\{[^}]+\}"
            ).replace(r"<idx>", r"\{[^}]+\}").replace(
                r"<leg>", r"\{[^}]+\}"
            ).replace(r"<src>", r"\{[^}]+\}")
            if not re.search(f"[\"']f?.*{pat}", blob) and not re.search(
                pat, blob
            ):
                missing.append(name)
        assert not missing, (
            "documented in docs/OBSERVABILITY.md but no emitting "
            f"site in the tree: {missing}"
        )

    def test_north_star_percentiles_documented(self):
        doc = (REPO_ROOT / "docs" / "OBSERVABILITY.md").read_text()
        for key in (
            "window_latency_p50", "admission_wait_p99",
            "stage_breakdown", "obs_flight_dumps",
        ):
            assert key in doc, f"{key} missing from the reference page"
