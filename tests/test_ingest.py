"""Ingest path tests: DeviceIngestor, PrefetchIterator, epoch resync."""

import os

import numpy as np
import pytest

from ddl_tpu import (
    DataProducerOnInitReturn,
    DistributedDataLoader,
    Marker,
    ProducerFunctionSkeleton,
    distributed_dataloader,
)
from ddl_tpu.ingest import DeviceIngestor, PrefetchIterator


class SeqProducer(ProducerFunctionSkeleton):
    def on_init(self, producer_idx=0, **kw):
        return DataProducerOnInitReturn(
            nData=32, nValues=4, shape=(32, 4), splits=(3, 1)
        )

    def post_init(self, my_ary, **kw):
        my_ary[:, -1] = np.arange(32)


class InplaceSeqProducer(ProducerFunctionSkeleton):
    """Module-level (picklable for PROCESS mode), zero-copy slot fill."""

    inplace_fill = True

    def on_init(self, producer_idx=0, **kw):
        self.iteration = 0
        return DataProducerOnInitReturn(
            nData=32, nValues=4, shape=(32, 4), splits=(3, 1)
        )

    def post_init(self, my_ary, **kw):
        my_ary[:] = 0.0

    def execute_function(self, my_ary, **kw):
        self.iteration += 1
        my_ary[:] = self.iteration * 100.0


class TaggedWindowProducer(ProducerFunctionSkeleton):
    """Each window uniformly tagged producer_idx*1000 + iteration."""

    inplace_fill = True

    def on_init(self, producer_idx=0, **kw):
        self.idx = producer_idx
        self.iteration = 0
        return DataProducerOnInitReturn(
            nData=32, nValues=4, shape=(32, 4), splits=(3, 1)
        )

    def post_init(self, my_ary, **kw):
        my_ary[:] = self.idx * 1000

    def execute_function(self, my_ary, **kw):
        self.iteration += 1
        my_ary[:] = self.idx * 1000 + self.iteration


class TestDeviceIngestor:
    def test_put_returns_device_arrays(self):
        import jax

        ing = DeviceIngestor()
        cols = (np.ones((4, 3), np.float32), np.zeros((4, 1), np.float32))
        a, b = ing.put(cols)
        assert isinstance(a, jax.Array)
        np.testing.assert_array_equal(np.asarray(a), cols[0])

    def test_sharded_put(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("dp",))
        sharding = NamedSharding(mesh, P("dp"))
        ing = DeviceIngestor(sharding=sharding)
        (a,) = ing.put((np.ones((16, 3), np.float32),))
        assert a.sharding == sharding
        assert len(a.addressable_shards) == len(jax.devices())


class TestPrefetchIterator:
    def test_order_and_exhaustion(self):
        batches = [(np.full((2, 2), i, np.float32),) for i in range(7)]
        out = list(PrefetchIterator(iter(batches), DeviceIngestor(), depth=3))
        assert len(out) == 7
        for i, (a,) in enumerate(out):
            assert float(np.asarray(a)[0, 0]) == i

    def test_empty_iterator(self):
        assert list(PrefetchIterator(iter([]), DeviceIngestor())) == []


class TestEarlyEpochEnd:
    def test_mid_window_epoch_end_resyncs(self):
        """Breaking an epoch early must not re-serve the stale window."""

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=8, connection=env.connection,
                n_epochs=2, output="numpy",
            )
            # Epoch 0: consume only 2 of 4 batches, then end the epoch.
            for i in range(2):
                loader[i]
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)
            # Epoch 1: a full drain must start at a fresh window boundary.
            count = 0
            for _ in loader:
                loader.mark(Marker.END_OF_BATCH)
                count += 1
            loader.mark(Marker.END_OF_EPOCH)
            assert loader._batches_in_window == 0
            return count

        assert main() == 4


class TestGlobalArray:
    def test_make_global_array_sharded(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddl_tpu.ingest import make_global_array
        from ddl_tpu.parallel import data_parallel_mesh

        mesh = data_parallel_mesh()
        sharding = NamedSharding(mesh, P("dp"))
        batch = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
        g = make_global_array(batch, sharding)
        assert g.shape == (16, 3)
        assert len(g.addressable_shards) == len(jax.devices())
        np.testing.assert_array_equal(np.asarray(g), batch)


class TestLoaderShardedIngest:
    def test_loader_jax_output_with_sharding(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddl_tpu import (
            DistributedDataLoader,
            Marker,
            distributed_dataloader,
        )
        from ddl_tpu.parallel import data_parallel_mesh

        mesh = data_parallel_mesh()
        sharding = NamedSharding(mesh, P("dp"))

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=32, connection=env.connection,
                n_epochs=1, output="jax", sharding=sharding,
            )
            feats, tag = loader[0]
            assert feats.sharding == sharding
            assert len(feats.addressable_shards) == len(jax.devices())
            loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)

        main()


class TestNorthStarReport:
    def test_report_keys(self):
        from ddl_tpu.ingest import north_star_report
        from ddl_tpu.observability import Metrics

        m = Metrics()
        m.incr("consumer.samples", 100)
        m.add_time("consumer.wait", 0.1)
        r = north_star_report(m)
        assert set(r) == {
            "samples_per_sec", "stall_fraction", "ingest_bytes_per_sec",
            "windows", "elapsed_s",
            # staged-ingest extras (ddl_tpu.staging)
            "stage_copy_s", "transfer_s", "stall_s",
            "pool_hits", "pool_misses", "queue_depth_max",
            "alias_windows", "alias_fallbacks",
            # robustness extras (ISSUE 3: watchdog + integrity + ladder)
            "respawns", "watchdog_failures", "corrupt_windows",
            "replays", "shuffle_degraded", "staging_retries",
            "inline_fallbacks",
            # shard-cache extras (ISSUE 4: ddl_tpu.cache tiers)
            "cache_hits", "cache_misses", "cache_evictions",
            "cache_spills", "cache_spill_hits", "cache_quarantined",
            "cache_resident_bytes", "cache_resident_bytes_max",
            # training hot-path extras (ISSUE 5: overlap health +
            # pipeline-schedule gauges)
            "window_wait_s", "release_wait_s", "pp_bubble", "pp_chunks",
            # ICI ingest tier extras (ISSUE 7: ddl_tpu/parallel/ici)
            "ici_bytes", "ici_windows", "ici_fallbacks",
            "ici_fanout_s", "ici_redistribute_s", "ici_peak_bytes",
            # fused compute/ingest step extras (ISSUE 12: overlap
            # proof + two-slot landing occupancy)
            "ingest_overlap_s", "fused_windows", "slots_in_flight",
            # distributed-optimizer extras (ISSUE 8:
            # ddl_tpu/parallel/optimizer)
            "opt_state_bytes_per_replica", "opt_state_bytes_total",
            "opt_grad_comm_bytes_raw", "opt_grad_comm_bytes_quantized",
            "opt_gather_s", "opt_scatter_s",
            # multi-host control plane extras (ISSUE 10:
            # ddl_tpu/cluster — membership churn + ladder actions)
            "view_changes", "host_losses", "host_rejoins",
            "heartbeats_dropped", "shard_adoptions",
            "cluster_cache_adoptions", "pool_updates",
            # multi-tenant ingest service extras (ISSUE 11:
            # ddl_tpu.serve — admission + autoscaler)
            "serve_tenants", "serve_scale_ups", "serve_scale_downs",
            "serve_admission_waits_s", "serve_tenant_stall",
            # data-plane wire format extras (ISSUE 13: ddl_tpu.wire —
            # honest encoded/raw byte pair + ladder counters)
            "wire_encoded_bytes", "wire_payload_bytes",
            "wire_decoded_windows", "wire_decode_fails",
            "wire_fallbacks",
            # preemption tolerance extras (ISSUE 14: ddl_tpu.resilience
            # — notice/drain events, async-checkpoint stall split,
            # restore-ladder health, serve-plane revocations)
            "resilience_notices", "resilience_drains",
            "resilience_drain_s", "resilience_ckpts",
            "resilience_final_ckpts", "resilience_ckpt_submit_s",
            "resilience_ckpt_write_s", "resilience_ckpt_quarantined",
            "resilience_ckpt_cold_starts", "serve_revocations",
            # end-to-end tracing extras (ISSUE 15: ddl_tpu.obs —
            # histogram percentiles, per-stage breakdown,
            # cross-process aggregation + flight-recorder health)
            "window_latency_p50", "window_latency_p99",
            "admission_wait_p99", "serve_tenant_admission_p99",
            "stage_breakdown", "obs_reports_applied",
            "obs_reports_stale", "obs_flight_dumps",
            # self-tuning extras (ISSUE 20: ddl_tpu/tune —
            # calibration/controller decision counts + provenance)
            "tune_decisions", "tune_reverts", "tune_cost_source",
            # start-up (PR 49: profiling.startup_record().summary())
            "startup",
        }
        assert r["samples_per_sec"] > 0
        assert set(r["startup"]) == {
            "fits", "seconds", "slow_compiles", "stages", "slowest_programs"
        }
        # The per-tenant stall block is a DICT keyed by tenant name
        # (empty when no tenancy ran), not a flat float.
        assert isinstance(r["serve_tenant_stall"], dict)
        # So are the per-tenant admission p99s and the stage breakdown.
        assert isinstance(r["serve_tenant_admission_p99"], dict)
        assert isinstance(r["stage_breakdown"], dict)
        assert "acquire_wait" in r["stage_breakdown"]

    def test_report_serve_block_reflects_tenancy(self):
        """The serve_* keys chart real scheduler/autoscaler activity."""
        from ddl_tpu.ingest import north_star_report
        from ddl_tpu.observability import Metrics
        from ddl_tpu.serve import AdmissionController, TenantSpec

        m = Metrics()
        m.incr("consumer.samples", 1)
        ctl = AdmissionController(metrics=m)
        a = ctl.register(TenantSpec("alpha"))
        a.admit(1.0)
        a.note_served(4096)
        m.incr("serve.scale_ups")
        ctl.report()  # refreshes the serve.stall.<tenant> gauges
        r = north_star_report(m)
        assert r["serve_tenants"] == 1
        assert r["serve_scale_ups"] == 1
        assert r["serve_admission_waits_s"] >= 0
        # Keyed by tenant NAME only: set_gauge's ".max" companions are
        # filtered, or consumers would see a phantom tenant "alpha.max".
        assert set(r["serve_tenant_stall"]) == {"alpha"}


class TestFusedGatedRelease:
    """``gate_release_on``: the fused-step protocol's loader half —
    ring-slot release gated on the CONSUMING step's done-future, not
    the bare transfer (ISSUE 12).  Exercised with a controllable fake
    future and the accelerator-style inline path forced (the CPU
    client's detached source releases at yield, where gating is a
    documented no-op)."""

    class _Future:
        """Duck-typed device future: non-blocking ``is_ready`` probe +
        a ``block_until_ready`` the forced flush path may call."""

        def __init__(self):
            self.ready = False
            self.forced = False

        def is_ready(self):
            return self.ready

        def block_until_ready(self):
            self.forced = True
            self.ready = True
            return self

    def _run(self, body):
        from ddl_tpu.observability import Metrics

        m = Metrics()

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=32, connection=env.connection,
                n_epochs=3, output="jax", metrics=m,
            )
            # Force the accelerator-style inline discipline: treat the
            # transfer as sourcing the ring slot, so releases ride the
            # probe-gated backlog instead of happening at yield.
            loader._ingestor.window_source_detached = lambda: False
            try:
                return body(loader, m)
            finally:
                loader.shutdown()

        return main()

    def test_release_waits_for_consuming_step(self):
        def body(loader, m):
            ring = loader.connection.rings[0]
            stream = loader.windows(lookahead=0)
            fut = self._Future()
            next(stream)
            assert len(loader._release_backlog) == 1
            loader.gate_release_on(fut)
            assert m.counter("ingest.fused_gated") == 1
            # The transfer itself is long done (CPU), but the consuming
            # step is not: the sweep at the next acquire must NOT free
            # the slot.
            next(stream)
            assert ring.stats()["released"] == 0
            assert len(loader._release_backlog) >= 1
            # Step completes -> the very next sweep frees the slot.
            fut.ready = True
            next(stream)
            assert ring.stats()["released"] >= 1
            assert not fut.forced  # released by the probe, not a flush

        self._run(body)

    def test_pending_step_future_cannot_deadlock(self):
        """A gated slot with its step future still pending when the
        ring runs dry: the forced flush block_until_ready's the
        COMBINED (transfer, step) future — the stream keeps moving and
        shutdown drains everything; the protocol can never strand a
        slot."""

        def body(loader, m):
            ring = loader.connection.rings[0]
            stream = loader.windows(lookahead=0)
            fut = self._Future()
            next(stream)
            loader.gate_release_on(fut)
            # Drain the remaining windows WITHOUT ever resolving the
            # future ourselves: the ring (nslots=2) exhausts and the
            # stream's forced flush must wait out the step future.
            for _ in stream:
                pass
            assert fut.forced  # the flush waited on the step, not a spin
            assert ring.stats()["released"] >= 1
            # Teardown drains the remaining backlog: every acquired
            # slot comes back, nothing stranded (idempotent with the
            # harness's own shutdown).
            loader.shutdown()
            assert ring.stats()["released"] == 3
            assert not loader._release_backlog

        self._run(body)

    def test_gate_is_noop_when_slot_released_at_yield(self):
        """On the CPU client (detached source) the slot is back with
        the producer at yield — gating must be a harmless no-op, so a
        fused trainer runs unchanged on any client."""
        from ddl_tpu.observability import Metrics

        m = Metrics()

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=32, connection=env.connection,
                n_epochs=2, output="jax", metrics=m,
            )
            stream = loader.windows()
            next(stream)
            loader.gate_release_on(self._Future())
            assert m.counter("ingest.fused_gated") == 0
            assert loader._last_stream_entry is None
            loader.shutdown()

        main()


class TestLoaderPrefetch:
    """loader.prefetch(): lookahead device iteration (VERDICT r2 item 5)."""

    def test_prefetch_matches_plain_iteration(self):
        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=8, connection=env.connection,
                n_epochs=4, output="jax",
            )
            plain_epochs, pf_epochs = [], []
            for epoch in range(4):
                use_pf = epoch % 2 == 1
                it = loader.prefetch(2) if use_pf else loader
                got = [np.asarray(y).ravel().tolist() for _, y in it]
                (pf_epochs if use_pf else plain_epochs).append(got)
                for _ in got:
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return plain_epochs, pf_epochs

        plain, pf = main()
        # Same producers, deterministic windows: prefetch epochs must see
        # exactly the same batches plain epochs saw (4 batches of 8 rows).
        assert plain == pf, (plain, pf)
        assert all(len(ep) == 4 for ep in plain + pf)

    def test_windows_streaming(self):
        """windows(): whole-window zero-copy streaming, content + rotation
        + epoch accounting match per-batch iteration semantics."""

        class CountingProducer(ProducerFunctionSkeleton):
            inplace_fill = True

            def on_init(self, producer_idx=0, **kw):
                self.idx = producer_idx
                self.iteration = 0
                return DataProducerOnInitReturn(
                    nData=32, nValues=4, shape=(32, 4), splits=(3, 1)
                )

            def post_init(self, my_ary, **kw):
                my_ary[:] = self.idx * 1000

            def execute_function(self, my_ary, **kw):
                # inplace_fill contract: fully rewrite the window.
                self.iteration += 1
                my_ary[:] = self.idx * 1000 + self.iteration

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                CountingProducer(), batch_size=8, connection=env.connection,
                n_epochs=6, output="jax",
            )
            tags = []
            for win in loader.windows():
                assert win.shape == (4, 8, 4)  # (bpw, batch, values)
                vals = np.unique(np.asarray(win))
                assert len(vals) == 1  # each window uniform by design
                tags.append(float(vals[0]))
                loader.mark(Marker.END_OF_EPOCH)
            assert loader.epoch == 6
            return tags

        tags = main()
        # Round-robin producers (1-based idx, like the reference's shm
        # ranks), each window freshly rewritten in place: producer 1
        # serves 1001,1002,..., producer 2 serves 2001,2002,...
        assert tags == [
            1001.0, 2001.0, 1002.0, 2002.0, 1003.0, 2003.0,
        ], tags

    def test_windows_bytes_counted_at_completion(self):
        """Stream byte accounting lands at transfer COMPLETION (finish),
        not dispatch: across a mid-stream registry reset — exactly what
        the bench's steady-state window does — ingest.bytes and
        consumer.samples must cover identical windows, so their ratio is
        exactly bytes-per-sample.  Dispatch-time accounting would lose
        the lookahead window in flight at the reset (VERDICT r4 Weak #3)."""
        from ddl_tpu.observability import Metrics

        metrics = Metrics()

        @distributed_dataloader(n_producers=2, mode="thread", nslots=2)
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=8, connection=env.connection,
                n_epochs=8, output="jax", metrics=metrics,
            )
            for seen, win in enumerate(loader.windows()):
                if seen == 2:
                    metrics.reset()  # steady-state span, lookahead in flight
                loader.mark(Marker.END_OF_EPOCH)
            return metrics.counter("ingest.bytes"), metrics.counter(
                "consumer.samples"
            ), metrics.counter("ingest.windows")

        nbytes, samples, windows = main()
        bytes_per_sample = 4 * 4  # SeqProducer: 4 f32 values per row
        assert samples > 0 and windows > 0
        assert nbytes == samples * bytes_per_sample, (nbytes, samples)

    def test_windows_double_buffer_holds_two_slots(self):
        """Double-buffered streaming (VERDICT r3 item 3): before window k
        is yielded, window k+1 must already be acquired — a recording
        proxy over the single producer's ring observes TWO concurrently
        held slots, and the lookahead acquisition precedes the previous
        slot's release.  Runs INLINE (staged=False): early slot release
        is the staged engine's whole point and deliberately breaks the
        held-until-transfer-complete property asserted here; the staged
        counterpart lives in tests/test_staging.py."""
        import time

        class RecordingRing:
            def __init__(self, inner):
                self._inner = inner
                self.events = []
                self.held = 0
                self.max_held = 0

            def acquire_drain_ahead(self, ahead, timeout_s=300.0):
                slot = self._inner.acquire_drain_ahead(ahead, timeout_s)
                self.held += 1
                self.max_held = max(self.max_held, self.held)
                self.events.append(("acquire", slot, ahead))
                return slot

            def acquire_drain(self, timeout_s=300.0):
                return self.acquire_drain_ahead(0, timeout_s)

            def release(self, slot):
                self.held -= 1
                self.events.append(("release", slot))
                self._inner.release(slot)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        @distributed_dataloader(n_producers=1, mode="thread", nslots=2)
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=8, connection=env.connection,
                n_epochs=4, output="jax", staged=False,
            )
            rec = RecordingRing(env.connection.rings[0])
            env.connection.rings[0] = rec
            # Let the producer run ahead so the non-blocking lookahead
            # try-acquire deterministically finds window k+1 committed.
            deadline = time.time() + 10
            while rec.stats()["committed"] < 2 and time.time() < deadline:
                time.sleep(0.01)
            n = 0
            for win in loader.windows():
                assert win.shape == (4, 8, 4)
                n += 1
                loader.mark(Marker.END_OF_EPOCH)
            assert n == 4
            return rec

        rec = main()
        assert rec.max_held == 2, rec.events
        first_release = rec.events.index(("release", 0))
        lookaheads = [
            i for i, e in enumerate(rec.events)
            if e[0] == "acquire" and e[2] == 1
        ]
        assert lookaheads and lookaheads[0] < first_release, rec.events

    def test_windows_break_resumes_at_next_unserved(self):
        """Abandoning the stream with a lookahead window in flight must
        not lose data: acquisition has no ring side effect, so a resumed
        stream serves exactly the next unserved window (code-review
        finding on the double-buffer change)."""

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                TaggedWindowProducer(), batch_size=8,
                connection=env.connection, n_epochs=6, output="jax",
            )
            tags = []
            for win in loader.windows():
                tags.append(float(np.unique(np.asarray(win))[0]))
                loader.mark(Marker.END_OF_EPOCH)
                if len(tags) == 2:
                    break  # abandon mid-stream, lookahead likely held
            for win in loader.windows():
                tags.append(float(np.unique(np.asarray(win))[0]))
                loader.mark(Marker.END_OF_EPOCH)
            return tags

        tags = main()
        assert tags == [
            1001.0, 2001.0, 1002.0, 2002.0, 1003.0, 2003.0,
        ], tags

    def test_windows_stale_generator_finalize_harmless(self):
        """A dead generator finalized LATE — after a new stream started —
        must not corrupt the live rotation (review finding: an earlier
        version rewound shared loader state in the generator's finally,
        which fires at GC time, not at abandonment time)."""

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                TaggedWindowProducer(), batch_size=8,
                connection=env.connection, n_epochs=6, output="jax",
            )
            it1 = loader.windows()
            tags = [float(np.unique(np.asarray(next(it1)))[0])]
            loader.mark(Marker.END_OF_EPOCH)
            it2 = loader.windows()  # it1 abandoned but still referenced
            tags.append(float(np.unique(np.asarray(next(it2)))[0]))
            loader.mark(Marker.END_OF_EPOCH)
            it1.close()  # stale generator finalizes only NOW
            for win in it2:
                tags.append(float(np.unique(np.asarray(win))[0]))
                loader.mark(Marker.END_OF_EPOCH)
            return tags

        tags = main()
        assert tags == [
            1001.0, 2001.0, 1002.0, 2002.0, 1003.0, 2003.0,
        ], tags

    def test_windows_concurrent_streams_rejected(self):
        """Interleaving two live windows() streams would double-release
        ring slots (review finding): the superseded stream must raise,
        not corrupt the counters."""
        import pytest

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                TaggedWindowProducer(), batch_size=8,
                connection=env.connection, n_epochs=6, output="jax",
            )
            it1 = loader.windows()
            next(it1)
            loader.mark(Marker.END_OF_EPOCH)
            it2 = loader.windows()
            next(it2)  # supersedes it1
            loader.mark(Marker.END_OF_EPOCH)
            with pytest.raises(RuntimeError, match="superseded"):
                next(it1)
            # The live stream keeps working.
            next(it2)
            loader.mark(Marker.END_OF_EPOCH)
            loader.shutdown()

        main()

    def test_windows_deep_lookahead(self):
        """lookahead > 1 genuinely deepens the pipeline (not capped at
        one): with nslots=4 and lookahead=3 the consumer holds more than
        two slots at once mid-stream.  Inline mode (staged=False): the
        staged engine releases slots at copy-completion, so held-count
        depth is asserted on the path that holds slots for the whole
        transfer."""
        import time

        class HeldCounter:
            def __init__(self, inner):
                self._inner = inner
                self.held = 0
                self.max_held = 0

            def acquire_drain_ahead(self, ahead, timeout_s=300.0):
                slot = self._inner.acquire_drain_ahead(ahead, timeout_s)
                self.held += 1
                self.max_held = max(self.max_held, self.held)
                return slot

            def acquire_drain(self, timeout_s=300.0):
                return self.acquire_drain_ahead(0, timeout_s)

            def release(self, slot):
                self.held -= 1
                self._inner.release(slot)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        @distributed_dataloader(n_producers=1, mode="thread", nslots=4)
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=8, connection=env.connection,
                n_epochs=8, output="jax", staged=False,
            )
            rec = HeldCounter(env.connection.rings[0])
            env.connection.rings[0] = rec
            deadline = time.time() + 10
            while rec.stats()["committed"] < 4 and time.time() < deadline:
                time.sleep(0.01)
            n = 0
            for win in loader.windows(lookahead=3):
                n += 1
                loader.mark(Marker.END_OF_EPOCH)
            assert n == 8
            return rec

        rec = main()
        assert rec.max_held >= 3, rec.max_held

    def test_windows_ragged_tail_unserved(self):
        """nData not a batch multiple: windows() serves the same batches
        the per-batch path serves, dropping the ragged tail rows."""

        class RaggedProducer(ProducerFunctionSkeleton):
            def on_init(self, producer_idx=0, **kw):
                return DataProducerOnInitReturn(
                    nData=33, nValues=4, shape=(33, 4), splits=(3, 1)
                )

            def post_init(self, my_ary, **kw):
                my_ary[:, -1] = np.arange(33)

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                RaggedProducer(), batch_size=8, connection=env.connection,
                n_epochs=1, output="jax",
            )
            (win,) = list(loader.windows())
            loader.mark(Marker.END_OF_EPOCH)
            return np.asarray(win)

        win = main()
        assert win.shape == (4, 8, 4)
        np.testing.assert_array_equal(win[..., -1].ravel(), np.arange(32))

    def test_inplace_fill_rejects_global_shuffle(self):
        """Exchange on nslots-stale slots would be silently wrong data —
        the producer constructor must reject the combination."""
        import pytest

        from ddl_tpu.datapusher import DataPusher
        from ddl_tpu.exceptions import DoesNotMatchError
        from ddl_tpu.shuffle import ThreadExchangeShuffler
        from ddl_tpu.transport.connection import (
            ProducerConnection,
            ThreadChannel,
        )
        from ddl_tpu.types import (
            MetaData_Consumer_To_Producer,
            RunMode,
            Topology,
        )

        topo = Topology(
            n_instances=2, instance_idx=0, n_producers=1,
            mode=RunMode.THREAD,
        )
        cons_end, prod_end = ThreadChannel.pair()
        cons_end.send(
            MetaData_Consumer_To_Producer(
                data_producer_function=InplaceSeqProducer(), batch_size=8,
                n_epochs=1, global_shuffle_fraction_exchange=0.5,
                exchange_method="sendrecv_replace",
            )
        )
        with pytest.raises(DoesNotMatchError, match="inplace_fill"):
            DataPusher(
                ProducerConnection(prod_end, 1, cross_process=False),
                topo, 1,
                shuffler_factory=ThreadExchangeShuffler.factory(),
            )

    def test_windows_requires_jax_output(self):
        import pytest

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=8, connection=env.connection,
                n_epochs=1, output="numpy",
            )
            with pytest.raises(RuntimeError, match="windows"):
                next(loader.windows())
            for _ in loader:
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)

        main()

    def test_inplace_fill_process_mode(self):
        """inplace_fill writes land in shm ring slots across processes."""

        @distributed_dataloader(n_producers=1, mode="process")
        def main(env):
            loader = DistributedDataLoader(
                InplaceSeqProducer(), batch_size=8,
                connection=env.connection, n_epochs=2, output="numpy",
            )
            seen = []
            for _ in range(2):
                for x, y in loader:
                    seen.append(float(y[0, 0]))
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return seen

        seen = main()
        # Window 0: iteration 1 tags batches 1.x; window 1: iteration 2.
        assert seen == [100.0, 100.0, 100.0, 100.0,
                        200.0, 200.0, 200.0, 200.0], seen

    def test_prefetch_requires_jax_output(self):
        import pytest

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                SeqProducer(), batch_size=8, connection=env.connection,
                n_epochs=1, output="numpy",
            )
            with pytest.raises(RuntimeError, match="prefetch"):
                loader.prefetch()
            for _ in loader:
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)

        main()


class TestDeferredSlotRelease:
    """Accelerator-shaped inline streams (the transfer sources the ring
    slot): slot release is gated on a transfer-completion probe instead
    of a per-window host ``block_until_ready`` (ISSUE 5 — the old sync
    serialized window k+1's H2D against window k's scanned steps).  The
    CPU client detaches sources in ``put_window``, so the attached path
    is exercised by pinning ``window_source_detached`` False — data
    stays correct either way (the alias-guard copy still runs)."""

    def _pin_attached(self, monkeypatch):
        from ddl_tpu.ingest import DeviceIngestor

        monkeypatch.setattr(
            DeviceIngestor, "window_source_detached", lambda self: False
        )

    def test_stream_correct_and_backlog_drained(self, monkeypatch):
        self._pin_attached(monkeypatch)

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                TaggedWindowProducer(), batch_size=8,
                connection=env.connection, n_epochs=6, output="jax",
                staged=False,
            )
            tags = []
            backlog_seen = 0
            for win in loader.windows():
                tags.append(float(np.unique(np.asarray(win))[0]))
                backlog_seen = max(
                    backlog_seen, len(loader._release_backlog)
                )
                loader.mark(Marker.END_OF_EPOCH)
            # The final mark shut the loader down: every deferred slot
            # must have been flushed back to its ring.
            return tags, backlog_seen, len(loader._release_backlog)

        tags, backlog_seen, backlog_left = main()
        assert tags == [
            1001.0, 2001.0, 1002.0, 2002.0, 1003.0, 2003.0,
        ], tags
        # The deferral actually engaged (at least one window released
        # via the probe path), and nothing leaked past shutdown.
        assert backlog_seen >= 1
        assert backlog_left == 0

    def test_break_then_new_stream_inherits_backlog(self, monkeypatch):
        """A new stream must account for the old stream's yielded-but-
        unreleased slots (they are still held on the ring) — the
        drain-lookahead bookkeeping starts from the backlog instead of
        re-acquiring served windows."""
        self._pin_attached(monkeypatch)

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                TaggedWindowProducer(), batch_size=8,
                connection=env.connection, n_epochs=6, output="jax",
                staged=False,
            )
            tags = []
            for win in loader.windows():
                tags.append(float(np.unique(np.asarray(win))[0]))
                loader.mark(Marker.END_OF_EPOCH)
                if len(tags) == 2:
                    break  # abandon with deferred releases pending
            for win in loader.windows():
                tags.append(float(np.unique(np.asarray(win))[0]))
                loader.mark(Marker.END_OF_EPOCH)
            return tags

        tags = main()
        assert tags == [
            1001.0, 2001.0, 1002.0, 2002.0, 1003.0, 2003.0,
        ], tags

    def test_batch_path_flushes_backlog(self, monkeypatch):
        """Switching from a stream to batch iteration flushes deferred
        releases first — the batch-path drain must not re-serve a slot
        the stream already yielded."""
        self._pin_attached(monkeypatch)

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                TaggedWindowProducer(), batch_size=8,
                connection=env.connection, n_epochs=3, output="jax",
                staged=False,
            )
            it = loader.windows()
            first = float(np.unique(np.asarray(next(it))))
            loader.mark(Marker.END_OF_EPOCH)
            # Batch-iterate the next epoch: backlog must flush, and the
            # window served is the next UNSERVED one.
            seen = []
            for cols in loader:
                seen.append(float(np.asarray(cols[0])[0, 0]))
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)
            assert len(loader._release_backlog) == 0
            loader.shutdown()
            return first, seen

        first, seen = main()
        assert first == 1001.0
        assert seen and all(v == 2001.0 for v in seen), seen


class AutoInplaceProducer(ProducerFunctionSkeleton):
    """Capability-advertising producer: every fill fully rewrites, so
    the pusher MAY hand it a live slot view (but must not when a global
    shuffle needs a persistent my_ary)."""

    supports_inplace_fill = True

    def on_init(self, producer_idx=0, **kw):
        self.iteration = 0
        return DataProducerOnInitReturn(
            nData=32, nValues=4, shape=(32, 4), splits=(3, 1)
        )

    def post_init(self, my_ary, **kw):
        my_ary[:] = 0.0

    def execute_function(self, my_ary, **kw):
        self.iteration += 1
        my_ary[:] = self.iteration


class TestAutoInplaceFill:
    """The extended inplace contract (write-once producers): a
    ``supports_inplace_fill`` producer fills live ring slots by default,
    degrades to the copying fill when a shuffler owns my_ary, and obeys
    the ``DDL_TPU_INPLACE`` escape hatch — which never overrides a
    producer that FORCES ``inplace_fill``."""

    def _pusher(self, producer, shuffle=0.0, n_instances=1, factory=None):
        from ddl_tpu.datapusher import DataPusher
        from ddl_tpu.transport.connection import (
            ProducerConnection,
            ThreadChannel,
        )
        from ddl_tpu.types import (
            MetaData_Consumer_To_Producer,
            RunMode,
            Topology,
        )

        topo = Topology(
            n_instances=n_instances, instance_idx=0, n_producers=1,
            mode=RunMode.THREAD,
        )
        cons_end, prod_end = ThreadChannel.pair()
        cons_end.send(
            MetaData_Consumer_To_Producer(
                data_producer_function=producer, batch_size=8,
                n_epochs=1, global_shuffle_fraction_exchange=shuffle,
                exchange_method="sendrecv_replace",
            )
        )
        return DataPusher(
            ProducerConnection(prod_end, 1, cross_process=False),
            topo, 1, shuffler_factory=factory,
        )

    def test_builtin_readers_advertise_capability(self):
        from ddl_tpu.readers import (
            ArrayProducer,
            FileShardProducer,
            TFRecordTokenProducer,
            TokenStreamProducer,
            WebDatasetProducer,
        )

        for cls in (
            ArrayProducer, FileShardProducer, WebDatasetProducer,
            TokenStreamProducer, TFRecordTokenProducer,
        ):
            assert cls.supports_inplace_fill is True
            assert cls.inplace_fill is False  # opt-in stays the pusher's

    def test_auto_inplace_gets_live_slot_view(self):
        p = self._pusher(AutoInplaceProducer())
        assert p.inplace_fill is True
        assert p._fill_slot is not None
        assert np.shares_memory(
            p.my_ary, p.ring.slot_view(p._fill_slot)
        )

    def test_env_escape_hatch_restores_copy_fill(self, monkeypatch):
        monkeypatch.setenv("DDL_TPU_INPLACE", "0")
        p = self._pusher(AutoInplaceProducer())
        assert p.inplace_fill is False
        assert not any(
            np.shares_memory(p.my_ary, p.ring.slot_view(s))
            for s in range(p.ring.nslots)
        )

    def test_env_escape_hatch_never_overrides_forced(self, monkeypatch):
        monkeypatch.setenv("DDL_TPU_INPLACE", "0")
        p = self._pusher(InplaceSeqProducer())
        assert p.inplace_fill is True  # forced = contract, not preference

    def test_auto_degrades_under_global_shuffle(self):
        """Unlike FORCED inplace (rejected — see
        test_inplace_fill_rejects_global_shuffle), a capability
        advertisement quietly keeps the private my_ary the exchange
        needs."""
        from ddl_tpu.shuffle import ThreadExchangeShuffler

        p = self._pusher(
            AutoInplaceProducer(), shuffle=0.5, n_instances=2,
            factory=ThreadExchangeShuffler.factory(),
        )
        assert p.shuffler is not None
        assert p.inplace_fill is False


class TestWriteOnceByteIdentity:
    """PROCESS inplace stream ≡ THREAD stream ≡ the old copying PROCESS
    path (``DDL_TPU_INPLACE=0``), cache-on and cache-off, for every
    built-in shard reader: the write-once refactor must change copy
    counts, never bytes."""

    def _drain(self, make_producer, mode, batch_size, n_epochs=3):
        @distributed_dataloader(n_producers=1, mode=mode)
        def main(env):
            loader = DistributedDataLoader(
                make_producer(), batch_size=batch_size,
                connection=env.connection, n_epochs=n_epochs,
                output="numpy",
            )
            out = []
            for _ in range(n_epochs):
                for cols in loader:
                    out.append(
                        np.hstack([np.asarray(c) for c in cols]).copy()
                    )
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return np.stack(out)

        return main()

    #: label -> (run mode, DDL_TPU_INPLACE, cache on).  The THREAD
    #: cache-off run is the reference stream.
    MATRIX = {
        "thread": ("thread", "1", False),
        "thread_cache": ("thread", "1", True),
        "process_inplace": ("process", "1", False),
        "process_inplace_cache": ("process", "1", True),
        "process_copy": ("process", "0", False),
        "process_copy_cache": ("process", "0", True),
    }

    def _assert_matrix_identical(
        self, make_producer, batch_size, monkeypatch, tmp_path
    ):
        runs = {}
        for label, (mode, inplace, cache_on) in self.MATRIX.items():
            monkeypatch.setenv("DDL_TPU_INPLACE", inplace)
            if cache_on:
                monkeypatch.setenv("DDL_TPU_CACHE", "1")
                monkeypatch.setenv(
                    "DDL_TPU_CACHE_SPILL_DIR",
                    str(tmp_path / f"spill_{label}"),
                )
            else:
                monkeypatch.delenv("DDL_TPU_CACHE", raising=False)
                monkeypatch.delenv(
                    "DDL_TPU_CACHE_SPILL_DIR", raising=False
                )
            runs[label] = self._drain(make_producer, mode, batch_size)
        ref = runs["thread"]
        for label, got in runs.items():
            np.testing.assert_array_equal(
                got, ref,
                err_msg=f"{label} stream diverged from the THREAD "
                "cache-off reference",
            )

    def test_fileshard_matrix(self, tmp_path, monkeypatch):
        rng = np.random.default_rng(0)
        for i in range(2):
            np.save(
                tmp_path / f"shard_{i}.npy",
                rng.standard_normal((8, 6)).astype(np.float32),
            )
        pattern = str(tmp_path / "shard_*.npy")

        from ddl_tpu.readers import FileShardProducer

        self._assert_matrix_identical(
            lambda: FileShardProducer(pattern, seed=0, warm=False),
            batch_size=4, monkeypatch=monkeypatch, tmp_path=tmp_path,
        )

    def test_tfrecord_matrix(self, tmp_path, monkeypatch):
        import sys

        sys.path.insert(0, os.path.dirname(__file__))
        from datagen import encode_example_int64, write_tfrecord

        payloads = [
            encode_example_int64(
                "input_ids", list(range(20 * i, 20 * i + 20))
            )
            for i in range(4)
        ]
        path = str(tmp_path / "toks.tfrecord")
        write_tfrecord(path, payloads)

        from ddl_tpu.readers import TFRecordTokenProducer

        self._assert_matrix_identical(
            lambda: TFRecordTokenProducer(
                str(tmp_path / "toks.tfrecord"), seq_len=8,
                window_rows=4, warm=False,
            ),
            batch_size=4, monkeypatch=monkeypatch, tmp_path=tmp_path,
        )

    def test_webdataset_matrix(self, tmp_path, monkeypatch):
        pytest.importorskip("PIL")
        import sys

        sys.path.insert(0, os.path.dirname(__file__))
        from datagen import write_image_shard

        write_image_shard(
            str(tmp_path / "imgs.tar"),
            [(f"s{i:03d}", i % 3) for i in range(4)],
            size=8,
        )

        from ddl_tpu.readers import WebDatasetProducer

        self._assert_matrix_identical(
            lambda: WebDatasetProducer(
                str(tmp_path / "imgs.tar"), image_size=8,
                window_rows=4, warm=False,
            ),
            batch_size=4, monkeypatch=monkeypatch, tmp_path=tmp_path,
        )
