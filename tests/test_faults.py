"""Chaos suite: seeded fault injection against full loader pipelines.

Tier-1 runs a DETERMINISTIC single-fault matrix — for every fault kind
the pipeline must either recover with byte-identical, exactly-once
delivery of the window stream, or degrade along the documented ladder
(docs/ROBUSTNESS.md).  It must never deadlock, and never silently drop
or duplicate a window.  ``@pytest.mark.slow`` adds a randomized
multi-fault soak (``make chaos``).

The producer serves a fully deterministic pattern (window ``it`` has
every element derived from ``(producer, it, position)``), so "recovered"
is asserted at byte granularity, not just by count.
"""

import os
import time

import numpy as np
import pytest

from ddl_tpu import (
    DataProducerOnInitReturn,
    DistributedDataLoader,
    Marker,
    ProducerFunctionSkeleton,
    distributed_dataloader,
)
from ddl_tpu import faults, integrity
from ddl_tpu.exceptions import (
    IntegrityError,
    InjectedFault,
    ShutdownRequested,
    TransportError,
)
from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec, fault_point
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.watchdog import Watchdog

N_DATA, N_VALUES = 16, 4
SHAPE = (N_DATA, N_VALUES)


def pattern(it: int, producer_idx: int = 1) -> np.ndarray:
    """Byte-deterministic content of window ``it`` (1-based)."""
    base = producer_idx * 100_000 + it * 1_000
    return (
        base + (np.arange(N_DATA * N_VALUES, dtype=np.float32) % 997)
    ).reshape(SHAPE).astype(np.float32)


class PatternProducer(ProducerFunctionSkeleton):
    """Windows 1, 2, 3, ... of :func:`pattern` — replayable by the default
    ``fast_forward`` (state advances only through ``execute_function``)."""

    def on_init(self, producer_idx=1, **kw):
        self.idx = producer_idx
        self.it = 0
        return DataProducerOnInitReturn(
            nData=N_DATA, nValues=N_VALUES, shape=SHAPE,
            splits=(N_VALUES - 1, 1),
        )

    def post_init(self, my_ary, **kw):
        my_ary[:] = 0.0

    def execute_function(self, my_ary, **kw):
        self.it += 1
        my_ary[:] = pattern(self.it, self.idx)


class InplacePatternProducer(PatternProducer):
    """The same deterministic pattern stream, FORCED write-once: every
    fill lands straight in the live ring slot (module-level so PROCESS
    chaos tests can pickle it across the spawn boundary)."""

    inplace_fill = True


def drain_numpy(plan, n_epochs=6, metrics=None, stall_budget_s=60.0,
                producer_cls=PatternProducer):
    """Run a 1-producer THREAD pipeline under ``plan``; return the window
    arrays served, the watchdog, and the metrics registry."""
    m = metrics or Metrics()

    @distributed_dataloader(n_producers=1, mode="thread")
    def main(env):
        wd = Watchdog(
            env.workers, poll_interval_s=0.1, stall_budget_s=stall_budget_s,
            respawn=True, metrics=m,
        ).start()
        try:
            loader = DistributedDataLoader(
                producer_cls(), batch_size=N_DATA,
                connection=env.connection, n_epochs=n_epochs,
                output="numpy", timeout_s=60.0, metrics=m,
            )
            windows = []
            for _ in range(n_epochs):
                for cols in loader:
                    windows.append(np.hstack([np.asarray(c) for c in cols]))
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
        finally:
            wd.stop()
        return windows, wd

    with faults.armed(plan):
        windows, wd = main()
    return windows, wd, m


def drain_windows_jax(plan, n_epochs=5, metrics=None, pace_s=0.0):
    """Run the staged ``windows()`` stream (engine forced on) under
    ``plan``; return served window arrays, the metrics, and the loader's
    engine-faulted flag.  ``pace_s`` holds each window that long, so
    the producer runs ahead and the stream's lookahead finds a
    committed window to probe."""
    m = metrics or Metrics()

    @distributed_dataloader(n_producers=1, mode="thread")
    def main(env):
        loader = DistributedDataLoader(
            PatternProducer(), batch_size=N_DATA,
            connection=env.connection, n_epochs=n_epochs, output="jax",
            timeout_s=60.0, metrics=m, staged=True,
        )
        windows = []
        for win in loader.windows():
            windows.append(np.asarray(win).reshape(SHAPE).copy())
            time.sleep(pace_s)
            loader.mark(Marker.END_OF_EPOCH)
        engine = loader._ingestor._engine
        return windows, bool(engine is not None and engine.faulted)

    with faults.armed(plan):
        windows, faulted = main()
    return windows, faulted, m


def expected(n_epochs):
    return [pattern(it) for it in range(1, n_epochs + 1)]


def assert_byte_identical(got, n_epochs):
    want = expected(n_epochs)
    assert len(got) == len(want), (len(got), len(want))
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g, w, err_msg=f"window {i + 1}")


# ---------------------------------------------------------------------------
# The deterministic single-fault matrix (tier-1).  One test per fault
# kind; each asserts exactly-once byte-identical delivery or the
# documented degradation — never a deadlock, drop, or duplicate.
# ---------------------------------------------------------------------------


class TestFaultMatrix:
    def test_producer_crash_respawned_byte_identical(self):
        plan = FaultPlan(
            [FaultSpec("producer.fill", FaultKind.PRODUCER_CRASH, at=3)]
        )
        windows, wd, m = drain_numpy(plan)
        assert_byte_identical(windows, 6)
        assert list(wd.respawns) == [1]
        assert list(wd.failures) == []
        assert m.counter("watchdog.respawns") == 1
        assert plan.fired and plan.fired[0][1] == "producer_crash"

    def test_producer_slowdown_recovers_unassisted(self):
        plan = FaultPlan(
            [FaultSpec("producer.fill", FaultKind.PRODUCER_SLOWDOWN,
                       at=2, count=2, param=0.3)]
        )
        windows, wd, m = drain_numpy(plan)
        assert_byte_identical(windows, 6)
        assert list(wd.respawns) == []
        assert list(wd.failures) == []
        assert len(plan.fired) == 2

    def test_spurious_shutdown_respawned_byte_identical(self):
        """A spurious ShutdownRequested kills one producer incarnation
        cleanly; the watchdog tells a spurious signal (rings still live)
        from real teardown and respawns into the exact position."""
        plan = FaultPlan(
            [FaultSpec("producer.fill", FaultKind.SPURIOUS_SHUTDOWN, at=2)]
        )
        windows, wd, m = drain_numpy(plan)
        assert_byte_identical(windows, 6)
        assert list(wd.respawns) == [1]
        assert list(wd.failures) == []

    def test_ring_corruption_quarantined_and_replayed(self, crc_fold):
        """Flipped slot bytes after commit: drain-time CRC verification
        quarantines the window and the producer replays it — the served
        stream is byte-identical, with the corruption visible in
        metrics, not in data.  Found by the serial CRC and by the
        span-parallel fold alike (``crc_fold``)."""
        plan = FaultPlan(
            [FaultSpec("producer.commit", FaultKind.RING_CORRUPTION,
                       at=2, param=4)]
        )
        windows, wd, m = drain_numpy(plan)
        assert_byte_identical(windows, 6)
        assert m.counter("integrity.corrupt_windows") == 1
        assert m.counter("integrity.replays") == 1
        assert list(wd.failures) == []
        crc_fold(m)

    def test_corrupt_lookahead_window_waits_for_the_head(
        self, crc_fold, monkeypatch
    ):
        """A corrupt window met while the ``windows()`` stream deepens
        its lookahead is not quarantined out of FIFO order
        (``_CorruptAhead``): it is counted once and replayed when a
        blocking acquire reaches it at the head, before anything of it
        is submitted downstream."""
        from ddl_tpu import dataloader

        ahead_errors = []
        real = dataloader._CorruptAhead

        class Spy(real):
            def __init__(self, *a):
                ahead_errors.append(a)
                super().__init__(*a)

        monkeypatch.setattr(dataloader, "_CorruptAhead", Spy)
        plan = FaultPlan(
            [FaultSpec("producer.commit", FaultKind.RING_CORRUPTION,
                       at=3, param=4)]
        )
        windows, faulted, m = drain_windows_jax(plan, n_epochs=6, pace_s=0.05)
        assert_byte_identical(windows, 6)
        assert ahead_errors, "the lookahead never met the corrupt window"
        assert m.counter("integrity.corrupt_windows") == 1
        assert m.counter("integrity.replays") == 1
        assert not faulted
        crc_fold(m)

    def test_persistent_corruption_escalates_to_integrity_error(self):
        """Corruption that survives every replay exhausts the budget and
        raises IntegrityError — loudly, instead of serving bad bytes or
        spinning forever (the documented terminal rung)."""
        plan = FaultPlan(
            [FaultSpec("producer.commit", FaultKind.RING_CORRUPTION,
                       at=2, count=50, param=4)]
        )
        m = Metrics()

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                PatternProducer(), batch_size=N_DATA,
                connection=env.connection, n_epochs=6,
                output="numpy", timeout_s=15.0, metrics=m,
            )
            with pytest.raises(IntegrityError, match="still corrupt"):
                for _ in range(6):
                    for cols in loader:
                        loader.mark(Marker.END_OF_BATCH)
                    loader.mark(Marker.END_OF_EPOCH)
            loader.shutdown()

        with faults.armed(plan):
            main()
        assert m.counter("integrity.replays") == 2  # DDL_TPU_MAX_REPLAYS
        assert m.counter("integrity.corrupt_windows") >= 3

    def test_inplace_crash_mid_fill_respawned_byte_identical(self):
        """PRODUCER_CRASH at the ``pusher.inplace_fill`` site: the ring
        slot is fully WRITTEN but not yet stamped/committed — the torn
        slot (new payload under the previous occupant's stale trailer)
        must never reach the consumer.  Write-once ordering (stamp AFTER
        fill, commit after stamp) guarantees it is never committed; the
        respawned incarnation rejoins the surviving ring, reads the last
        COMMITTED slot's header for its exact position, and re-fills the
        torn slot from scratch.  Byte-identical, exactly once, zero
        corrupt windows observed."""
        plan = FaultPlan(
            [FaultSpec("pusher.inplace_fill", FaultKind.PRODUCER_CRASH,
                       at=3)]
        )
        windows, wd, m = drain_numpy(
            plan, producer_cls=InplacePatternProducer
        )
        assert_byte_identical(windows, 6)
        assert list(wd.respawns) == [1]
        assert list(wd.failures) == []
        assert m.counter("integrity.corrupt_windows") == 0
        assert plan.fired and plan.fired[0][0] == "pusher.inplace_fill"

    def test_inplace_torn_commit_quarantined_and_replayed(self, crc_fold):
        """A torn COMMITTED slot on the write-once path (bytes flipped
        after the trailer stamp — what a real shared-memory scribble
        looks like): the drain-time CRC quarantines it, and the replay
        rewinds the inplace producer THROUGH ITS LIVE SLOT VIEW
        (on_init → post_init → fast_forward all write into the acquired
        slot).  Served stream byte-identical, exactly once."""
        plan = FaultPlan(
            [FaultSpec("producer.commit", FaultKind.RING_CORRUPTION,
                       at=2, param=4)]
        )
        windows, wd, m = drain_numpy(
            plan, producer_cls=InplacePatternProducer
        )
        assert_byte_identical(windows, 6)
        assert m.counter("integrity.corrupt_windows") == 1
        assert m.counter("integrity.replays") == 1
        assert list(wd.failures) == []
        crc_fold(m)

    def test_staging_copy_fault_retried(self):
        """A transient staging-copy failure is retried with backoff; the
        stream stays byte-identical and the retry is metered."""
        plan = FaultPlan(
            [FaultSpec("staging.copy", FaultKind.STAGING_COPY_FAIL, at=2)]
        )
        windows, faulted, m = drain_windows_jax(plan)
        assert_byte_identical(windows, 5)
        assert m.counter("staging.retries") >= 1
        assert not faulted

    def test_staged_transfer_fault_falls_back_inline(self):
        """Persistent staged-transfer failure: bounded retries, then the
        salvaged staging buffer rides the sanctioned inline path and the
        engine is latched faulted — every window still arrives
        byte-identical, exactly once."""
        plan = FaultPlan(
            [FaultSpec("staging.transfer", FaultKind.STAGED_TRANSFER_FAIL,
                       at=1, count=999)]
        )
        windows, faulted, m = drain_windows_jax(plan)
        assert_byte_identical(windows, 5)
        assert m.counter("staging.retries") >= 1
        assert m.counter("staging.inline_fallbacks") >= 1
        assert faulted

    def test_staged_transfer_timeout_recovers(self):
        """An injected transfer delay stalls, never corrupts: the
        bounded waits absorb it and the stream is byte-identical."""
        plan = FaultPlan(
            [FaultSpec("staging.transfer",
                       FaultKind.STAGED_TRANSFER_TIMEOUT, at=2, param=0.4)]
        )
        windows, faulted, m = drain_windows_jax(plan)
        assert_byte_identical(windows, 5)
        assert m.counter("integrity.corrupt_windows") == 0
        assert not faulted

    def test_ici_dma_fail_mid_fused_stream_latches_sync_fallback(self):
        """ICI_DMA_FAIL fired mid-fused-stream (the two-slot ingest
        tier active on the virtual mesh): the distributor latches the
        synchronous xla fallback for the rest of the run WITHOUT
        stranding the in-flight landing slot (already-dispatched
        windows resolve on their own semaphores) or the consumer's
        release backlog, and the served stream stays byte-identical —
        the fused protocol's degradation rung."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        m = Metrics()
        n_epochs = 6
        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        sharding = NamedSharding(mesh, P(None, "dp"))
        plan = FaultPlan(
            [FaultSpec("ici.fanout", FaultKind.ICI_DMA_FAIL, at=3)]
        )

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                PatternProducer(), batch_size=N_DATA,
                connection=env.connection, n_epochs=n_epochs,
                output="jax", timeout_s=60.0, metrics=m,
                sharding=sharding, distribute="ici",
            )
            windows = []
            for win in loader.windows():
                windows.append(np.asarray(win).reshape(SHAPE).copy())
                loader.mark(Marker.END_OF_EPOCH)
            assert not loader._release_backlog  # nothing stranded
            return windows, loader._ingestor._ici

        with faults.armed(plan):
            windows, dist = main()
        assert_byte_identical(windows, n_epochs)
        assert plan.fired and plan.fired[0][1] == "ici_dma_fail"
        assert dist.faulted  # latched: the rest of the run rode xla
        assert m.counter("ici.fallbacks") == 1
        # Exactly the pre-fault windows rode the fused ICI tier; the
        # fault window and every later one took the synchronous path.
        assert m.counter("ici.windows") == 2
        assert m.counter("ici.fused_windows") == 2
        # The latch cleared the landing-slot tracking (no phantom
        # occupancy), while the high-water proves slots were used.
        assert m.gauge("ici.slots_in_flight") == 0.0

    def test_shuffle_peer_loss_degrades_to_local(self):
        """Exchange partner lost: the round degrades to a node-local
        shuffle (loud warning + metric) instead of stalling; after
        max_peer_losses consecutive losses the exchange is disabled and
        the run COMPLETES.  Row multiset per window is preserved."""
        from ddl_tpu.env import WorkerSet
        from ddl_tpu.shuffle import Rendezvous, ThreadExchangeShuffler
        from ddl_tpu.types import RunMode, Topology

        class TaggedShuffleProducer(ProducerFunctionSkeleton):
            """Tagged rows, locally shuffled in place per refill — so
            served content is a row PERMUTATION, and loss/duplication is
            visible as a multiset change."""

            def on_init(self, producer_idx=1, **kw):
                self._rng = np.random.default_rng(0)
                return DataProducerOnInitReturn(
                    nData=N_DATA, nValues=N_VALUES, shape=SHAPE,
                    splits=(N_VALUES - 1, 1),
                )

            def post_init(self, my_ary, **kw):
                my_ary[:] = (
                    np.arange(N_DATA, dtype=np.float32)[:, None]
                    + np.arange(N_VALUES, dtype=np.float32)[None, :] * 100
                )

            def execute_function(self, my_ary, **kw):
                self._rng.shuffle(my_ary)

        plan = FaultPlan(
            [FaultSpec("shuffle.exchange", FaultKind.SHUFFLE_PEER_LOSS,
                       at=1, count=999)]
        )
        n_epochs = 5
        before = default_metrics().counter("shuffle.degraded")
        # Instance 0 of a declared 2-instance topology, with NO instance
        # 1 running: every exchange round has a lost peer by construction.
        topo = Topology(
            n_instances=2, instance_idx=0, n_producers=1,
            mode=RunMode.THREAD,
        )
        ws = WorkerSet(
            topo, nslots=2,
            shuffler_factory=ThreadExchangeShuffler.factory(
                rendezvous=Rendezvous(),  # private: no cross-test leaks
                exchange_timeout_s=5.0, max_peer_losses=2,
            ),
        )
        t0 = time.monotonic()
        with faults.armed(plan):
            loader = DistributedDataLoader(
                TaggedShuffleProducer(), batch_size=N_DATA,
                connection=ws.connection, n_epochs=n_epochs,
                output="numpy", global_shuffle_fraction_exchange=0.5,
                timeout_s=60.0,
            )
            windows = []
            try:
                for _ in range(n_epochs):
                    for cols in loader:
                        windows.append(
                            np.hstack([np.asarray(c) for c in cols])
                        )
                        loader.mark(Marker.END_OF_BATCH)
                    loader.mark(Marker.END_OF_EPOCH)
            finally:
                loader.shutdown()
                ws.abort()
                ws.join(30.0)
        assert len(windows) == n_epochs
        # Degraded rounds permute rows locally: the original row tags are
        # conserved as a multiset in EVERY served window — peer loss cost
        # global mixing, never data.
        tags = (
            np.arange(N_DATA, dtype=np.float32)[:, None]
            + np.arange(N_VALUES, dtype=np.float32)[None, :] * 100
        )
        for i, win in enumerate(windows):
            np.testing.assert_array_equal(
                np.sort(win, axis=0), np.sort(tags, axis=0),
                err_msg=f"window {i + 1} lost/duplicated rows",
            )
        assert default_metrics().counter("shuffle.degraded") - before >= 2
        # Never stalled out a full exchange timeout, let alone one per
        # round: the injection fails fast and the latch disables the rest.
        assert time.monotonic() - t0 < 30.0

    def test_handshake_crash_fails_fast_with_typed_error(self):
        """A producer crashing during its handshake ships the failure to
        the consumer — construction raises TransportError promptly
        instead of stalling until the handshake timeout."""
        plan = FaultPlan(
            [FaultSpec("producer.handshake", FaultKind.PRODUCER_CRASH)]
        )

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            with pytest.raises(TransportError, match="handshake"):
                DistributedDataLoader(
                    PatternProducer(), batch_size=N_DATA,
                    connection=env.connection, n_epochs=1, output="numpy",
                )

        t0 = time.monotonic()
        with faults.armed(plan):
            main()
        assert time.monotonic() - t0 < 30.0

    @pytest.mark.parametrize("tenant_burst", [False, True])
    def test_host_loss_repartitions_byte_identical(self, tenant_burst):
        """HOST_LOSS at cluster.heartbeat (ISSUE 10): the injected loss
        of a whole mock host drives the epoch-fenced view change — pool
        shrink, shard adoption — and the stream recovers byte-identical
        full-shard coverage (the runner lives in tests/test_cluster.py;
        the matrix row wires it into the tier-1 chaos sweep).  With
        ``tenant_burst`` the loader is a serve-tier tenant and a
        TENANT_BURST lands on its admissions in the same run: both
        faults fire and the tenant's stream stays byte-correct."""
        from test_cluster import (
            assert_full_coverage_byte_identical,
            drain_cluster,
        )

        from ddl_tpu.serve import AdmissionController, TenantSpec

        specs = [
            # at=8: past bootstrap sweeps, mid-stream (50 ms cadence).
            FaultSpec("cluster.heartbeat", FaultKind.HOST_LOSS,
                      at=8, producer_idx=1)
        ]
        m, tenant = Metrics(), None
        if tenant_burst:
            tenant = AdmissionController(metrics=m).register(
                TenantSpec("burst-me")
            )
            specs.append(
                FaultSpec("serve.admit", FaultKind.TENANT_BURST,
                          at=4, producer_idx=0, param=float(16 << 20))
            )
        plan = FaultPlan(specs)
        seen, m, sup = drain_cluster(
            plan=plan, n_epochs=24, pace_s=0.05, metrics=m, tenant=tenant,
        )
        fired = {kind for _, kind, *_ in plan.fired}
        assert "host_loss" in fired, "HOST_LOSS spec never fired"
        if tenant_burst:
            assert "tenant_burst" in fired
            assert m.counter("serve.tenant_bursts") == 1.0
        assert m.counter("cluster.host_losses") == 1.0
        assert m.counter("cluster.view_changes") == 1.0
        assert m.counter("watchdog.failures") == 0.0
        assert_full_coverage_byte_identical(seen)

    def test_tenant_burst_mid_stream_byte_identical(self):
        """TENANT_BURST at serve.admit (ISSUE 11): an injected demand
        spike lands on a tenant's 4th admission mid-stream — the
        fair-share scheduler absorbs it as phantom bytes charged to the
        burster's own share (replenish rounds pay it down), and the
        stream completes byte-identical with every window served (the
        scheduler/runner live in ddl_tpu/serve + tests/test_serve.py;
        the matrix row wires the kind into the tier-1 chaos sweep)."""
        from test_serve import (
            ROWS,
            PatternProducer,
            assert_pattern_windows,
        )

        from ddl_tpu import DistributedDataLoader, distributed_dataloader
        from ddl_tpu.observability import Metrics
        from ddl_tpu.serve import AdmissionController, TenantSpec

        m = Metrics()
        ctl = AdmissionController(metrics=m)
        tenant = ctl.register(TenantSpec("burst-me"))
        plan = FaultPlan(
            [FaultSpec("serve.admit", FaultKind.TENANT_BURST,
                       at=4, producer_idx=0, param=float(16 << 20))]
        )
        n_epochs = 8

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                PatternProducer(), batch_size=ROWS,
                connection=env.connection, n_epochs=n_epochs,
                output="numpy", timeout_s=30.0, metrics=m,
            )
            tenant.bind(loader)
            wins = []
            for _ in range(n_epochs):
                for (win,) in loader:
                    wins.append(win.copy())
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return wins

        with faults.armed(plan):
            wins = main()
        assert plan.fired and plan.fired[0][1] == "tenant_burst"
        assert len(wins) == n_epochs
        assert_pattern_windows(wins)
        assert m.counter("serve.tenant_bursts") == 1.0
        assert m.counter("ingest.burst-me.windows") == n_epochs
        # The spike was paid down by replenish rounds, not a timeout.
        assert m.counter("serve.rounds") >= 1.0

    def test_scale_decision_delay_defers_but_preserves_the_decision(self):
        """SCALE_DECISION_DELAY at serve.scale (ISSUE 11): the policy
        loop's decision lands ``param`` seconds late and is the SAME
        decision — reaction time degrades, membership correctness never
        (the policy machine lives in ddl_tpu/serve/autoscaler.py; the
        runner idiom mirrors tests/test_serve.py's)."""
        from test_serve import FakeCluster, make_scaler

        from ddl_tpu.cluster import HostInfo

        clock = [0.1]
        sig = {"stall_fraction": 0.9}
        fc = FakeCluster([0])
        sc = make_scaler(fc, sig, clock, sustain_s=0.0, cooldown_s=0.0,
                         standby=[HostInfo(1, loader_ranks=(2,))])
        plan = FaultPlan(
            [FaultSpec("serve.scale", FaultKind.SCALE_DECISION_DELAY,
                       at=1, param=0.1)]
        )
        t0 = time.perf_counter()
        with faults.armed(plan):
            out = sc.step()
        assert time.perf_counter() - t0 >= 0.1
        assert out == "up" and fc.rejoins == [1]
        assert plan.fired[0][1] == "scale_decision_delay"
        # The next (undelayed) step sees the grown pool and is a no-op
        # within cooldown semantics — the delayed action was complete.
        assert len(fc.supervisor.view.hosts) == 2

    def test_heartbeat_drop_expires_lease_then_recovers(self):
        """Persistent HEARTBEAT_DROP at cluster.heartbeat: single drops
        are absorbed (only the lease ages), but a host whose every beat
        is lost expires and leaves the view — the stream re-partitions
        and completes with full coverage."""
        from test_cluster import (
            assert_full_coverage_byte_identical,
            drain_cluster,
        )

        plan = FaultPlan(
            [FaultSpec("cluster.heartbeat", FaultKind.HEARTBEAT_DROP,
                       producer_idx=1, count=100_000)]
        )
        seen, m, sup = drain_cluster(
            plan=plan, n_epochs=24, lease_s=0.4, pace_s=0.05
        )
        assert plan.fired
        assert m.counter("cluster.heartbeats_dropped") > 1.0
        assert m.counter("cluster.host_losses") == 1.0
        assert m.counter("watchdog.failures") == 0.0
        assert_full_coverage_byte_identical(seen)


# ---------------------------------------------------------------------------
# Engine mechanics: determinism, matching, serialization, zero-cost.
# ---------------------------------------------------------------------------


class TestFaultEngine:
    def test_disarmed_fault_point_is_a_noop(self):
        assert faults.armed_plan() is None
        fault_point("producer.fill", producer_idx=1)
        fault_point("nonexistent.site", view=np.zeros(4, np.uint8))

    def test_plan_json_roundtrip(self):
        plan = FaultPlan(
            [FaultSpec("producer.fill", FaultKind.PRODUCER_CRASH, at=3,
                       count=2, producer_idx=1, param=0.5)],
            seed=7,
        )
        back = FaultPlan.from_json(plan.to_json())
        assert back.seed == 7
        assert back.specs == plan.specs

    def test_at_and_count_hit_windows(self):
        plan = FaultPlan(
            [FaultSpec("s", FaultKind.PRODUCER_CRASH, at=2, count=2)]
        )
        with faults.armed(plan):
            fault_point("s")  # hit 1: below `at`
            with pytest.raises(InjectedFault):
                fault_point("s")  # hit 2
            with pytest.raises(InjectedFault):
                fault_point("s")  # hit 3
            fault_point("s")  # hit 4: past the window
        assert [f[3] for f in plan.fired] == [2, 3]

    def test_producer_idx_narrowing(self):
        plan = FaultPlan(
            [FaultSpec("s", FaultKind.PRODUCER_CRASH, producer_idx=2)]
        )
        with faults.armed(plan):
            fault_point("s", producer_idx=1)  # other producer: no match
            fault_point("s")  # no producer context: no match
            with pytest.raises(InjectedFault):
                fault_point("s", producer_idx=2)

    def test_armed_context_restores_previous_plan_and_env(self):
        outer = FaultPlan([FaultSpec("a", FaultKind.PRODUCER_CRASH)])
        inner = FaultPlan([FaultSpec("b", FaultKind.PRODUCER_CRASH)])
        with faults.armed(outer):
            with faults.armed(inner, export=True):
                assert faults.armed_plan() is inner
                assert faults.PLAN_ENV in os.environ
            assert faults.armed_plan() is outer
            assert faults.PLAN_ENV not in os.environ
        assert faults.armed_plan() is None

    def test_corruption_is_seed_deterministic(self):
        def corrupted(seed):
            buf = np.zeros(64, np.uint8)
            plan = FaultPlan(
                [FaultSpec("s", FaultKind.RING_CORRUPTION, param=4)],
                seed=seed,
            )
            with faults.armed(plan):
                fault_point("s", view=buf)
            return buf

        np.testing.assert_array_equal(corrupted(3), corrupted(3))
        assert not np.array_equal(corrupted(3), corrupted(4))

    def test_hang_observes_abort(self):
        plan = FaultPlan(
            [FaultSpec("s", FaultKind.PRODUCER_HANG, param=30.0)]
        )
        t0 = time.monotonic()
        flag = {"down": False}

        import threading

        def aborter():
            time.sleep(0.2)
            flag["down"] = True

        threading.Thread(target=aborter, daemon=True).start()
        with faults.armed(plan):
            with pytest.raises(ShutdownRequested):
                fault_point("s", should_abort=lambda: flag["down"])
        assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# Integrity layer units: header codec, drain verification, TFRecord CRCs.
# ---------------------------------------------------------------------------


class TestIntegrityLayer:
    def _slot(self, payload_val=7, payload_bytes=128):
        slot = np.zeros(payload_bytes + integrity.HEADER_BYTES, np.uint8)
        slot[:payload_bytes] = payload_val
        integrity.write_header(
            slot, payload_bytes, seq=5, producer_idx=2,
            crc=integrity.window_crc(slot[:payload_bytes]),
        )
        return slot, payload_bytes

    def test_header_roundtrip_and_verify_ok(self):
        slot, n = self._slot()
        hdr = integrity.read_header(slot, n)
        assert hdr.valid_magic and hdr.seq == 5 and hdr.producer_idx == 2
        assert integrity.verify_window(slot, n, 5, 2) is None

    def test_verify_catches_flipped_byte(self):
        slot, n = self._slot()
        slot[17] ^= 0xFF
        err = integrity.verify_window(slot, n, 5, 2)
        assert err is not None and "crc32" in err

    def test_verify_catches_seq_and_producer_mismatch(self):
        slot, n = self._slot()
        assert "seq" in integrity.verify_window(slot, n, 6, 2)
        assert "producer" in integrity.verify_window(slot, n, 5, 3)

    def test_verify_catches_unstamped_header(self):
        slot = np.zeros(128 + integrity.HEADER_BYTES, np.uint8)
        assert "magic" in integrity.verify_window(slot, 128, 0, 1)

    def test_enable_gate(self, monkeypatch):
        monkeypatch.delenv("DDL_TPU_INTEGRITY", raising=False)
        assert integrity.integrity_enabled()
        monkeypatch.setenv("DDL_TPU_INTEGRITY", "0")
        assert not integrity.integrity_enabled()
        assert integrity.integrity_enabled(override=True)

    def test_pipeline_with_integrity_disabled(self, monkeypatch):
        """The DDL_TPU_INTEGRITY=0 escape hatch serves the PR 2 byte
        path: no headers, no verification, identical data."""
        monkeypatch.setenv("DDL_TPU_INTEGRITY", "0")
        windows, wd, m = drain_numpy(None, n_epochs=3)
        assert_byte_identical(windows, 3)
        assert m.counter("integrity.corrupt_windows") == 0


class TestTFRecordCRC:
    def _write(self, tmp_path, valid=True):
        import sys

        sys.path.insert(0, os.path.dirname(__file__))
        from datagen import encode_example_int64, write_tfrecord

        payloads = [
            encode_example_int64("input_ids", list(range(10 * i, 10 * i + 8)))
            for i in range(4)
        ]
        path = str(tmp_path / "rec.tfrecord")
        write_tfrecord(path, payloads, valid_crc=valid)
        return path, payloads

    def test_crc32c_check_vector(self):
        from ddl_tpu.readers import crc32c

        # The spec's check vector, plus length edge cases around the
        # 8-byte slicing boundary.
        assert crc32c(b"123456789") == 0xE3069283
        assert crc32c(b"") == 0
        import zlib

        data = bytes(range(256)) * 3 + b"tail"
        # Cross-check slicing-by-8 against a per-byte reference.
        ref = 0xFFFFFFFF
        from ddl_tpu.readers import _make_crc32c_tables

        t0 = _make_crc32c_tables()[0]
        for b in data:
            ref = int(t0[(ref ^ b) & 0xFF]) ^ (ref >> 8)
        assert crc32c(data) == ref ^ 0xFFFFFFFF
        assert crc32c(data) != (zlib.crc32(data) & 0xFFFFFFFF)  # crc32c != crc32

    def test_valid_file_reads_with_verification(self, tmp_path):
        from ddl_tpu.readers import iter_tfrecords

        path, payloads = self._write(tmp_path)
        assert list(iter_tfrecords(path, verify_crc=True)) == payloads

    def test_corrupt_payload_raises_with_context(self, tmp_path):
        from ddl_tpu.readers import iter_tfrecords

        path, _ = self._write(tmp_path)
        data = bytearray(open(path, "rb").read())
        data[20] ^= 0xFF  # inside record 0's payload
        open(path, "wb").write(bytes(data))
        with pytest.raises(IntegrityError, match="offset 0"):
            list(iter_tfrecords(path, verify_crc=True))

    def test_corrupt_length_crc_raises(self, tmp_path):
        from ddl_tpu.readers import iter_tfrecords

        path, _ = self._write(tmp_path, valid=False)  # zeroed CRCs
        with pytest.raises(IntegrityError, match="length-crc"):
            list(iter_tfrecords(path, verify_crc=True))

    def test_opt_out_knob_skips_validation(self, tmp_path, monkeypatch):
        from ddl_tpu.readers import iter_tfrecords

        path, payloads = self._write(tmp_path, valid=False)
        assert list(iter_tfrecords(path, verify_crc=False)) == payloads
        monkeypatch.setenv("DDL_TPU_TFRECORD_CRC", "0")
        assert list(iter_tfrecords(path)) == payloads
        monkeypatch.setenv("DDL_TPU_TFRECORD_CRC", "1")
        with pytest.raises(IntegrityError):
            list(iter_tfrecords(path))


# ---------------------------------------------------------------------------
# Randomized multi-fault soak (make chaos).
# ---------------------------------------------------------------------------


def _random_plan(seed: int) -> FaultPlan:
    """2 seeded faults drawn from the locally-replayable matrix."""
    rng = np.random.default_rng(seed)
    kinds = [
        (FaultKind.PRODUCER_CRASH, "producer.fill", 0.0),
        (FaultKind.PRODUCER_SLOWDOWN, "producer.fill", 0.3),
        (FaultKind.SPURIOUS_SHUTDOWN, "producer.fill", 0.0),
        (FaultKind.RING_CORRUPTION, "producer.commit", 3.0),
    ]
    specs = []
    for pick in rng.choice(len(kinds), size=2, replace=False):
        kind, site, param = kinds[int(pick)]
        specs.append(
            FaultSpec(site, kind, at=int(rng.integers(2, 6)), param=param)
        )
    return FaultPlan(specs, seed=seed)


@pytest.mark.slow
class TestChaosSoak:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_multi_fault_byte_identical(self, seed):
        plan = _random_plan(seed)
        windows, wd, m = drain_numpy(plan, n_epochs=8)
        assert_byte_identical(windows, 8)
        assert list(wd.failures) == []
        assert plan.fired, "no scheduled fault ever fired"

    @pytest.mark.parametrize("producer_cls,site", [
        (PatternProducer, "producer.fill"),
        # Write-once producers: the crash fires mid-inplace-fill with a
        # torn shm slot behind it — the respawn must re-fill it, never
        # serve it (tier-1 has the THREAD twin; this one crosses the
        # real spawn boundary over the native shm ring).
        (InplacePatternProducer, "pusher.inplace_fill"),
    ])
    def test_process_mode_crash_respawn_with_exported_plan(
        self, producer_cls, site
    ):
        """PROCESS mode: the plan crosses the spawn boundary via
        DDL_TPU_FAULT_PLAN and the spawned producer injects its own
        crash; elastic recovery still delivers the exact stream."""
        plan = FaultPlan(
            [FaultSpec(site, FaultKind.PRODUCER_CRASH, at=3)]
        )
        m = Metrics()

        @distributed_dataloader(n_producers=1, mode="process")
        def main(env):
            wd = Watchdog(
                env.workers, poll_interval_s=0.2, stall_budget_s=60.0,
                respawn=True, metrics=m,
            ).start()
            try:
                loader = DistributedDataLoader(
                    producer_cls(), batch_size=N_DATA,
                    connection=env.connection, n_epochs=6,
                    output="numpy", timeout_s=120.0, metrics=m,
                )
                windows = []
                for _ in range(6):
                    for cols in loader:
                        windows.append(
                            np.hstack([np.asarray(c) for c in cols])
                        )
                        loader.mark(Marker.END_OF_BATCH)
                    loader.mark(Marker.END_OF_EPOCH)
            finally:
                wd.stop()
            return windows, wd

        with faults.armed(plan, export=True):
            windows, wd = main()
        assert_byte_identical(windows, 6)
        # Each spawned incarnation re-arms the plan from the env with a
        # fresh hit counter, so late incarnations may crash (and heal)
        # again — the count is timing-dependent, the DATA never is.
        assert len(wd.respawns) >= 1 and set(wd.respawns) == {1}
        assert list(wd.failures) == []
