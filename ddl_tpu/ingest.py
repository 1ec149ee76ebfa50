"""Device ingest: host windows → TPU HBM.

The reference stopped at host memory — GPU transfer was left to the user
(commented out in its harness, reference ``tests/run_ddl.py:233-235``).  On
TPU the HBM hop is mandatory, so hiding it is a core feature
(SURVEY §8.3 "hard part #3"):

- :class:`DeviceIngestor` — async ``device_put`` of host batches onto a
  device or a sharded mesh (``jax.device_put`` returns immediately; the
  transfer overlaps subsequent host work).  This backs the loader's
  ``output="jax"`` mode.
- :class:`PrefetchIterator` — keeps N transfers in flight ahead of
  compute; used by training loops and the benchmark harness around any
  host-batch iterator.
"""

from __future__ import annotations

import collections
import os
import time
from typing import Any, Optional, Sequence, Tuple

import numpy as np

from ddl_tpu import envspec
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.profiling import stage
from ddl_tpu.staging import StagedTransfer, staged_enabled


class DeviceIngestor:
    """Puts host batches onto a device (or a sharded mesh) asynchronously.

    With ``sharding`` set (a ``jax.sharding.Sharding``), batches land
    sharded across the mesh — the data-parallel ingest path.  Otherwise
    they land on ``device`` (default: first local device).

    ``staged`` (default: the ``DDL_TPU_STAGED`` env gate, on) routes
    staging copies through a recycled-buffer pool and, for the lookahead
    consumers (:class:`PrefetchIterator`, ``DistributedDataLoader.
    windows``), through a background copy/transfer executor
    (:mod:`ddl_tpu.staging`).  ``staged=False`` is the inline escape
    hatch: fresh ``copy=True`` staging on the caller thread, exactly the
    pre-engine behavior.
    """

    def __init__(
        self,
        device: Any = None,
        sharding: Any = None,
        metrics: Optional[Metrics] = None,
        staged: Optional[bool] = None,
        distribute: Optional[str] = None,
    ):
        import jax

        self._jax = jax
        self.sharding = sharding
        self.device = device
        if sharding is None and device is None:
            self.device = jax.local_devices()[0]
        self.metrics = metrics or default_metrics()
        self.staged = staged_enabled(staged)
        #: Explicit constructor intent (None = env default) — the window
        #: stream distinguishes "forced on" from "default on" (below).
        self._staged_arg = staged
        self._engine: Any = None
        #: Post-H2D distribution tier: "ici" routes the device-side hop
        #: through the Pallas fan-out + redistribution planner
        #: (ddl_tpu/parallel/ici.py), "xla" keeps the pre-existing
        #: sharded device_put, "auto" (the default, also via the
        #: DDL_TPU_DISTRIBUTE env) routes by the window's plan on
        #: accelerator meshes (ring where a byte must reach more than
        #: one chip, one direct sharded put for a pure split —
        #: parallel/ici.py:choose_route) and is xla on the CPU client
        #: (where there is no ICI to control) — DDL_TPU_ICI_INGEST=0 is
        #: the auto-mode kill switch.
        distribute = distribute or envspec.get("DDL_TPU_DISTRIBUTE")
        if distribute not in ("ici", "xla", "auto"):
            raise ValueError(
                f"distribute must be ici|xla|auto, got {distribute!r}"
            )
        self.distribute = distribute
        self._ici: Any = None  # lazily-built IciDistributor

    @property
    def stream_staged(self) -> bool:
        """Should the WINDOW STREAM route through the staging engine?

        The batch paths always staged a host copy, so pooling/offloading
        them is strictly-no-worse everywhere.  The stream is different:
        inline ``put_window`` is the ZERO-COPY path (transfer straight
        from the ring slot), so staging it adds a whole host memcpy per
        window.  That trade buys early slot release — worth it where the
        transfer is a genuine DMA the slot would otherwise sit acquired
        behind (accelerators), and a pure loss on the CPU client, which
        can alias host buffers into "device" arrays (measured ~2x slower
        staged).  Default: staged on accelerators, inline on CPU;
        ``staged=True`` passed explicitly forces the engine everywhere
        (tests, experiments).
        """
        if not self.staged:
            return False
        if self._staged_arg is True:
            return True
        return self._target_platform() != "cpu"

    @property
    def batch_staged(self) -> bool:
        """Should the BATCH paths (``put``/``put_batch``/prefetch
        offload) stage through the recycled pool + background executor?

        The CPU PJRT client zero-copy-aliases 64-byte-aligned host
        buffers — which pooled ``np.empty`` staging buffers are — so on
        CPU every buffer is alias-dropped after its first transfer: an
        ALL-MISS pool whose per-transfer pointer walk, sweep, and gauge
        bookkeeping are pure ceremony on top of the same fresh
        allocation the inline path does plainly.  Accelerator puts
        genuinely copy, the pool recycles, and the executor buys
        overlap.  ``staged=True`` passed explicitly forces the engine
        everywhere (tests, A/B measurement).

        The decision predicate is deliberately the stream's (does this
        client's put genuinely copy?), so it DELEGATES — two copies of
        the same gate would drift."""
        return self.stream_staged

    @property
    def stream_alias(self) -> bool:
        """Should staged window-stream jobs ALIAS the ring slot (skip the
        slot→staging memcpy)?  True on accelerators under the
        ``DDL_TPU_SHM_STAGING`` gate: their ``device_put`` is a genuine
        host→HBM copy, so once the transfer completes nothing reads the
        slot and it can be released with ZERO host memcpys between
        producer fill and HBM.  The CPU client may zero-copy-alias host
        pages into "device" arrays, so it stays on the copying pool —
        and the executor's per-transfer ``unsafe_buffer_pointer`` check
        latches a fallback if an unrecognized client aliases anyway."""
        from ddl_tpu.staging import shm_staging_enabled

        return (
            self.stream_staged
            and shm_staging_enabled()
            and self._target_platform() != "cpu"
        )

    @property
    def ici_active(self) -> bool:
        """Does the ICI tier's distributor (redistribution planner +
        fan-out kernels) own the host→mesh hop, instead of a plain
        XLA-scattered ``device_put``?

        Requires a multi-device ``NamedSharding`` target and a single
        JAX process (the multihost assembly path owns its own
        distribution).  ``distribute="ici"`` forces the kernels for
        every geometry, anywhere — including the CPU virtual mesh, where
        they run in interpret mode (that is how tier-1 proves byte
        identity); ``"auto"`` engages the distributor only on
        accelerator meshes, gated by ``DDL_TPU_ICI_INGEST`` (default on
        — the distributor latches an xla fallback on any DMA failure, so
        auto cannot strand a run), and there the distributor picks each
        window's route from its plan: the ring where the anchor hop
        saves host-link crossings (replicated windows, splits with
        replication axes left), one direct sharded put for a pure split
        (``ici.direct_windows``).
        """
        if self.distribute == "xla" or self.sharding is None:
            return False
        if getattr(self.sharding, "mesh", None) is None:
            return False  # ici needs a named mesh to plan over
        if len(self.sharding.device_set) <= 1:
            return False
        if self._jax.process_count() > 1:
            return False
        if self.distribute == "ici":
            return True
        return (
            self._target_platform() != "cpu"
            and envspec.flag("DDL_TPU_ICI_INGEST")
        )

    def ici(self):
        """The lazily-built ICI distributor (plan + kernel caches)."""
        if self._ici is None:
            from ddl_tpu.parallel.ici import IciDistributor

            self._ici = IciDistributor(
                self.sharding, metrics=self.metrics
            )
        return self._ici

    # -- staging engine ----------------------------------------------------

    def engine(self):
        """The lazily-built staging engine (pool + background executor).

        Built on first use so host-output loaders and ``staged=False``
        ingestors never pay for a worker thread.  Buffer-recycling
        safety against CPU zero-copy puts is checked per transfer by the
        pool itself (see :func:`ddl_tpu.staging._may_alias`).
        """
        if self._engine is None:
            from ddl_tpu.staging import StagedIngestEngine

            self._engine = StagedIngestEngine(metrics=self.metrics)
        return self._engine

    def close(self) -> None:
        """Stop the background executor and flush pooled buffers."""
        if self._engine is not None:
            self._engine.close()

    def _stage(self, arr: np.ndarray) -> np.ndarray:
        """Copy ``arr`` into a pooled staging buffer (timed)."""
        pool = self.engine().pool
        buf = pool.acquire(arr.shape, arr.dtype)
        t0 = time.perf_counter()
        np.copyto(buf, arr, casting="no")
        self.metrics.add_time(
            "ingest.stage_copy", time.perf_counter() - t0
        )
        return buf

    def put(self, cols: Sequence[np.ndarray]) -> Tuple[Any, ...]:
        """Transfer a tuple of column arrays; returns JAX arrays.

        ``device_put`` is async — the returned arrays are futures whose
        transfers overlap subsequent host work.  Columns are copied out of
        the ring slot first: the transfer source must stay valid after the
        slot is released back to the producer, so an explicit copy is
        mandatory (``ascontiguousarray`` would pass an already-contiguous
        slot view through uncopied and the producer would overwrite it
        mid-transfer).  Staged mode stages into recycled pool buffers;
        inline mode allocates fresh.
        """
        with stage("ddl.ingest_put"):
            if self.batch_staged:
                pool = self.engine().pool
                out = []
                for c in cols:
                    buf = self._stage(c)
                    dev = self._transfer(buf)
                    pool.recycle_when_ready(buf, dev)
                    out.append(dev)
                out = tuple(out)
                pool.sweep()
            else:
                # Inline fresh copy: the DDL_TPU_STAGED=0 escape hatch
                # AND the CPU-client default (an aliasing client makes
                # the pool all-miss ceremony — see batch_staged).
                out = tuple(
                    self._transfer(
                        np.array(c, copy=True)  # ddl-lint: disable=DDL011
                    )
                    for c in cols
                )
        self.metrics.incr(
            "ingest.bytes", float(sum(int(c.nbytes) for c in cols))
        )
        self.metrics.incr("ingest.batches")
        return out

    def put_batch(
        self, batch: np.ndarray, splits: Sequence[int]
    ) -> Tuple[Any, ...]:
        """Transfer one unsplit batch, splitting into columns ON DEVICE.

        One copy + one transfer instead of one of each per column: narrow
        columns (a label column is ~KiB) otherwise pay the link's fixed
        per-transfer cost for a few bytes.  The device-side column slices
        are sub-microsecond XLA ops.
        """
        with stage("ddl.ingest_put"):
            if self.batch_staged:
                pool = self.engine().pool
                buf = self._stage(batch)
                dev = self._transfer(buf)
                pool.recycle_when_ready(buf, dev)
                pool.sweep()
            else:
                # Inline fresh copy (DDL_TPU_STAGED=0, and the CPU-client
                # default — see batch_staged).
                dev = self._transfer(
                    np.array(batch, copy=True)  # ddl-lint: disable=DDL011
                )
        self.metrics.incr("ingest.bytes", float(batch.nbytes))
        self.metrics.incr("ingest.batches")
        return _device_split(dev, splits)

    def batch_transfer_fn(self, splits: Sequence[int]):
        """A :data:`~ddl_tpu.staging.TransferFn` running this ingestor's
        single-transfer batch put from an already-staged buffer — what
        the background executor runs after its slot→staging copy."""

        def transfer(buf: np.ndarray):
            dev = self._transfer(buf)
            self.metrics.incr("ingest.bytes", float(buf.nbytes))
            self.metrics.incr("ingest.batches")
            return _device_split(dev, splits), dev

        return transfer

    def _transfer(self, arr: np.ndarray) -> Any:
        """One host→device transfer honouring the multihost case: with
        multiple JAX processes each host contributes its local shard of
        the global array (same assembly as :func:`make_global_array`).

        With the ICI tier active the distributor routes the buffer by
        its plan and by whether the tier was forced (``self.distribute``
        is passed through).  Ring route: H2D lands the whole buffer on
        the plan's anchor device (one link crossing), then the fan-out
        kernel + redistribution legs move it to the target sharding
        entirely over ICI.  Direct route (``"auto"``, pure split): one
        sharded put, each chip fed its block straight from ``arr`` — the
        returned array IS the transfer, so the staged alias path's
        slot-release wait ends when the H2D legs land, not behind five
        queued device programs.  The distributor owns its own failure
        ladder (latched xla fallback), so this seam stays
        exception-free."""
        target = self.sharding if self.sharding is not None else self.device
        if self.sharding is not None and self._jax.process_count() > 1:
            return self._jax.make_array_from_process_local_data(
                self.sharding, arr
            )
        if self.ici_active:
            return self.ici().put(
                arr, self._jax.device_put, tier=self.distribute
            )
        return self._jax.device_put(arr, target)

    def put_window(
        self, window: np.ndarray, defer_metrics: bool = False
    ) -> Any:
        """Transfer a whole window WITHOUT a host copy.

        The source may be a live ring-slot view: the caller must keep the
        slot acquired until the returned array is ready
        (``jax.block_until_ready``) — that is what
        ``DistributedDataLoader.windows`` does.  One large transfer per
        window beats per-batch/per-column puts wherever the link has fixed
        per-transfer cost.

        ``defer_metrics=True`` skips the ``ingest.bytes``/``ingest.windows``
        accounting here so the caller can record it when the transfer
        *completes* — the window stream does this so bytes-arrived and
        samples-served counters cover identical windows over any
        measurement span (a dispatch-time count leads completion by the
        whole lookahead depth).
        """
        if self._target_platform() == "cpu":
            # The CPU PJRT client may *alias* a compatible host buffer
            # instead of copying — the returned array would then observe
            # the producer's next refill through the released slot.  On an
            # accelerator the put is a genuine transfer and the zero-copy
            # path is safe.
            window = np.array(window, copy=True)
        # Dispatch span, keyed on the thread's current-window context
        # (set by the stream / staging executor) — the transfer itself
        # is async; completion shows up as the consumer.release mark.
        with stage("ddl.ingest_put_window"):
            out = self._transfer(window)
        if not defer_metrics:
            self.metrics.incr("ingest.bytes", float(window.nbytes))
            self.metrics.incr("ingest.windows")
        return out

    def window_source_detached(self) -> bool:
        """Does :meth:`put_window` detach the transfer from its host
        source?  True on the CPU client, whose alias-guard copy means
        the returned array never reads the ring slot again — the caller
        may release the slot immediately at yield.  On accelerators the
        transfer sources the slot directly (zero-copy), so release must
        wait for transfer completion (``DistributedDataLoader``'s
        readiness-gated backlog)."""
        return self._target_platform() == "cpu"

    def _target_platform(self) -> str:
        if self.sharding is not None:
            dev = next(iter(self.sharding.device_set))
        else:
            dev = self.device
        return getattr(dev, "platform", "cpu")


def make_global_array(
    local_batch: np.ndarray, sharding: Any, axis: str = "dp"
) -> Any:
    """Assemble a process-local host batch into a global dp-sharded array.

    Multihost ingest: every host's loader drains its own producers'
    windows (the per-host shard of the global batch), and this stitches
    them into one global ``jax.Array`` without gathering — the TPU analog
    of the reference's per-instance window ownership
    (reference ``ddl/ddl_env.py:45-50``: each trainer only ever reads its
    own block's producers).

    Single-process (including the 8-device CPU sim), the local batch IS the
    global batch and this is a sharded ``device_put``.
    """
    import jax

    # Copy before the async transfer: the input is typically a view of a
    # ring slot that the producer will refill once the caller releases it.
    local_batch = np.array(local_batch, copy=True)
    if jax.process_count() == 1:
        return jax.device_put(local_batch, sharding)
    return jax.make_array_from_process_local_data(sharding, local_batch)


def measure_h2d_bandwidth(
    nbytes: int = 1 << 26, device: Any = None, trials: int = 3
) -> float:
    """Measured host→device link capability in bytes/sec.

    The denominator for BASELINE.md's "≥90% bandwidth utilization" target.
    Measured, not quoted from a spec sheet, so it is honest on whatever
    link the host reaches its chip over.
    """
    import time

    import jax

    if device is None:
        device = jax.local_devices()[0]
    buf = np.ones(nbytes, np.uint8)
    jax.block_until_ready(jax.device_put(buf, device))  # warmup
    best = 0.0
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(buf, device))
        best = max(best, nbytes / (time.perf_counter() - t0))
    return best


def north_star_report(
    metrics: Optional[Metrics] = None,
    link_bytes_per_sec: Optional[float] = None,
) -> dict:
    """The BASELINE.md metric set, computed from the shared registry.

    Note ``ingest_bytes_per_sec`` counts *device transfers* only — it stays
    zero in host-output (numpy/torch) runs by design.  Pass
    ``link_bytes_per_sec`` (e.g. from :func:`measure_h2d_bandwidth`) to get
    ``bandwidth_utilization`` — achieved ingest over link capability.
    """
    m = metrics or default_metrics()
    # Metrics.rates() computes every rate over ONE elapsed snapshot, so
    # bytes/s and samples/s agree exactly when their counters cover
    # identical windows (they do on the stream path — completion-time
    # accounting in DistributedDataLoader.windows).
    report = dict(m.rates())
    report["windows"] = m.counter("consumer.windows")
    # Staged-ingest observability (ddl_tpu.staging): where the engine's
    # time went (staging memcpy, observed transfer spans, consumer pop
    # stalls) and whether the buffer pool is actually recycling.
    report["stage_copy_s"] = m.timer("ingest.stage_copy").total_s
    report["transfer_s"] = m.timer("ingest.transfer").total_s
    report["stall_s"] = m.timer("ingest.stall").total_s
    report["pool_hits"] = m.counter("staging.pool_hits")
    report["pool_misses"] = m.counter("staging.pool_misses")
    report["queue_depth_max"] = m.gauge("staging.queue_depth.max")
    # Shm-backed (zero-copy) staging: windows whose transfer sourced the
    # ring slot directly (no slot→staging memcpy), and jobs the
    # per-transfer alias check bounced back to the copying pool.
    report["alias_windows"] = m.counter("staging.alias_windows")
    report["alias_fallbacks"] = m.counter("staging.alias_fallbacks")
    # Training hot-path observability (ISSUE 5): time the trainer's
    # stream loop spent waiting for the next window (overlap health —
    # near zero when H2D hides behind the scans), time the loader spent
    # in FORCED transfer-completion waits before slot release, and the
    # analytic bubble/chunking of the last-compiled pipeline schedule.
    report["window_wait_s"] = m.timer("trainer.window_wait").total_s
    report["release_wait_s"] = m.timer("ingest.release_wait").total_s
    # The pp gauges are PROCESS-level trace-time facts (pipeline_apply
    # records them once per compilation, on the default registry — it
    # cannot see a run's private registry), so read them from the
    # default registry even when reporting a private one; otherwise
    # every private-registry run reports 0.0 for a schedule that ran.
    report["pp_bubble"] = default_metrics().gauge("pp.bubble")
    report["pp_chunks"] = default_metrics().gauge("pp.chunks")
    # Robustness observability (ISSUE 3): recovery events must be visible
    # in the report and the bench JSON trajectories, not just in logs —
    # a "passing" run that silently replayed half its windows is a
    # regression the BENCH_* history should show.
    report["respawns"] = m.counter("watchdog.respawns")
    report["watchdog_failures"] = m.counter("watchdog.failures")
    report["corrupt_windows"] = m.counter("integrity.corrupt_windows")
    report["replays"] = m.counter("integrity.replays")
    report["shuffle_degraded"] = m.counter("shuffle.degraded")
    report["staging_retries"] = m.counter("staging.retries")
    report["inline_fallbacks"] = m.counter("staging.inline_fallbacks")
    # Shard-cache observability (ddl_tpu.cache, ISSUE 4): the warm tier's
    # effectiveness (hit ratio), pressure (evictions/spills), and health
    # (quarantines = corrupt disk entries healed by refetch) belong in
    # the same report the bench JSON charts — a run whose "warm" epochs
    # quietly missed every shard is a perf regression, and one that
    # quarantined entries deserves a look even when throughput held.
    report["cache_hits"] = m.counter("cache.hits")
    report["cache_misses"] = m.counter("cache.misses")
    report["cache_evictions"] = m.counter("cache.evictions")
    report["cache_spills"] = m.counter("cache.spills")
    report["cache_spill_hits"] = m.counter("cache.spill_hits")
    report["cache_quarantined"] = m.counter("cache.quarantined")
    report["cache_resident_bytes"] = m.gauge("cache.resident_bytes")
    report["cache_resident_bytes_max"] = m.gauge("cache.resident_bytes.max")
    # ICI ingest tier (ddl_tpu/parallel/ici.py, ISSUE 7): wire bytes the
    # device-side fan-out moved, dispatch time split between the Pallas
    # kernel and the redistribution legs, the plan's asserted per-device
    # peak, and fallback latches (a nonzero ici_fallbacks on a run that
    # "passed" means the tier degraded to the xla path mid-stream).
    report["ici_bytes"] = m.counter("ici.bytes")
    report["ici_windows"] = m.counter("ici.windows")
    report["ici_fallbacks"] = m.counter("ici.fallbacks")
    report["ici_fanout_s"] = m.timer("ici.fanout").total_s
    report["ici_redistribute_s"] = m.timer("ici.redistribute").total_s
    report["ici_peak_bytes"] = m.gauge("ici.peak_bytes")
    # Fused compute/ingest step (ISSUE 12): how much of the data plane
    # actually hid under the train step.  ``ingest_overlap_s`` is the
    # trainer-measured lower bound on hidden ingest time (acquire spans
    # that ran while the previous scan was still computing),
    # ``fused_windows`` counts windows driven through the fused loop
    # (``trainer.*``; the distributor's own two-slot dispatches ride
    # ``ici.fused_windows`` inside the ici counters above), and
    # ``slots_in_flight`` is the HIGH-WATER landing-slot occupancy —
    # 2 means the double-buffer genuinely had both slots carrying
    # unresolved windows at once.
    report["ingest_overlap_s"] = m.timer("trainer.ingest_overlap").total_s
    report["fused_windows"] = m.counter("trainer.fused_windows")
    report["slots_in_flight"] = m.gauge("ici.slots_in_flight.max")
    # Distributed optimizer (ddl_tpu/parallel/optimizer.py, ISSUE 8):
    # optimizer-state bytes actually STORED per dp replica (shrinks ~dp×
    # under zero1), the per-step gradient-communication payload raw vs
    # quantized, and the measured collective-leg times.  The byte gauges
    # are trace-time facts recorded on the default registry (the
    # pp.bubble pattern — ShardedOptimizer.update cannot see a private
    # registry from inside a trace); the leg timers come from
    # ShardedOptimizer.measure_legs on whichever registry ran it.
    report["opt_state_bytes_per_replica"] = default_metrics().gauge(
        "opt.state_bytes_per_replica"
    )
    report["opt_state_bytes_total"] = default_metrics().gauge(
        "opt.state_bytes_total"
    )
    report["opt_grad_comm_bytes_raw"] = default_metrics().gauge(
        "opt.grad_comm_bytes_raw"
    )
    report["opt_grad_comm_bytes_quantized"] = default_metrics().gauge(
        "opt.grad_comm_bytes_quantized"
    )
    report["opt_gather_s"] = m.timer("opt.gather").total_s
    report["opt_scatter_s"] = m.timer("opt.scatter").total_s
    # Multi-host control plane (ddl_tpu.cluster, ISSUE 10): membership
    # churn (view changes / host losses / rejoins) and the recovery
    # ladder's cross-host actions (shard adoptions, cache warm-start
    # adoptions, consumer pool updates).  A "passing" run that silently
    # lost a host and re-partitioned mid-stream must be visible in the
    # BENCH_* trajectories, exactly like respawns and replays.
    report["view_changes"] = m.counter("cluster.view_changes")
    report["host_losses"] = m.counter("cluster.host_losses")
    report["host_rejoins"] = m.counter("cluster.rejoins")
    report["heartbeats_dropped"] = m.counter("cluster.heartbeats_dropped")
    report["shard_adoptions"] = m.counter("producer.shard_adoptions")
    report["cluster_cache_adoptions"] = m.counter("cluster.cache_adoptions")
    report["pool_updates"] = m.counter("consumer.pool_updates")
    # Multi-tenant ingest service (ddl_tpu.serve, ISSUE 11): how many
    # tenants share the fabric, how the autoscaler moved the pool
    # (scale-ups via rejoin_host, scale-downs via drain-then-release),
    # total time tenants spent parked at the fair-share admission gate,
    # and each tenant's admission-stall fraction (the serve.stall.<t>
    # gauges AdmissionController.report refreshes) — a "fair" run whose
    # smallest tenant quietly waited out every round must be visible in
    # the BENCH_* trajectories, exactly like replays and view changes.
    report["serve_tenants"] = m.gauge("serve.tenants")
    report["serve_scale_ups"] = m.counter("serve.scale_ups")
    report["serve_scale_downs"] = m.counter("serve.scale_downs")
    report["serve_admission_waits_s"] = m.timer(
        "serve.admission_wait"
    ).total_s
    # Keyed by TENANT NAME only: set_gauge's ``.max`` high-water
    # companions are dropped, or a consumer iterating the dict would
    # see a phantom tenant "<name>.max".
    report["serve_tenant_stall"] = {
        k: v
        for k, v in m.prefixed("serve.stall.").items()
        if not k.endswith(".max")
    }
    # Data-plane wire format (ddl_tpu.wire, ISSUE 13): bytes that
    # actually traveled an encode-engaged wire (slot commits, exchange
    # envelopes, the ICI fan-out) vs the logical raw bytes the same
    # windows represent — the honest numerator/denominator pair for
    # every "the wire got smaller" claim — plus the consumer-edge
    # decode counter and the degradation-ladder counters.  SCOPE: like
    # every producer.* counter, the EXCHANGE wire's ladder events are
    # counted in the shuffler's own registry — consumer-visible in
    # THREAD mode (shared default registry), per-worker-process in
    # PROCESS mode (read them from the producer logs / the bench wire
    # mode's own shuffler registries); the slot-path decode counters
    # below are consumer-side and surface in every mode.
    report["wire_encoded_bytes"] = m.counter("wire.encoded_bytes")
    report["wire_payload_bytes"] = m.counter("wire.payload_bytes")
    report["wire_decoded_windows"] = m.counter("wire.decoded_windows")
    report["wire_decode_fails"] = m.counter("wire.decode_fails")
    report["wire_fallbacks"] = m.counter("wire.fallbacks")
    # Preemption tolerance (ISSUE 14: ddl_tpu.resilience): notices
    # absorbed and drains run, the async checkpoint tier's hot-path
    # stall (the submit timer — the ONLY stall the step loop pays) vs
    # its hidden write time, and the restore ladder's health
    # (quarantined generations / cold starts are incidents the BENCH_*
    # trajectories must chart even when the run "passed").  The
    # revocation counter is the serve-plane half of the drain ladder.
    report["resilience_notices"] = m.counter("resilience.notices")
    report["resilience_drains"] = m.counter("resilience.drains")
    report["resilience_drain_s"] = m.timer("resilience.drain").total_s
    report["resilience_ckpts"] = m.counter("resilience.ckpts")
    report["resilience_final_ckpts"] = m.counter("resilience.final_ckpts")
    report["resilience_ckpt_submit_s"] = m.timer(
        "resilience.ckpt_submit"
    ).total_s
    report["resilience_ckpt_write_s"] = m.timer(
        "resilience.ckpt_write"
    ).total_s
    report["resilience_ckpt_quarantined"] = m.counter(
        "resilience.ckpt_quarantined"
    )
    report["resilience_ckpt_cold_starts"] = m.counter(
        "resilience.ckpt_cold_starts"
    )
    report["serve_revocations"] = m.counter("serve.revocations")
    # End-to-end tracing layer (ISSUE 15: ddl_tpu.obs).  Percentiles
    # come from the bounded log-spaced histograms Metrics.observe
    # feeds: window latency (time a blocking head acquire waited for
    # its committed window) and the fair-share admission wait — the
    # p99s the tenancy/preempt benches previously computed ad hoc.
    report["window_latency_p50"] = m.quantile(
        "consumer.window_latency", 0.5
    )
    report["window_latency_p99"] = m.quantile(
        "consumer.window_latency", 0.99
    )
    report["admission_wait_p99"] = m.quantile("serve.admission_wait", 0.99)
    # Per-tenant admission p99s.  Tenants are discovered from the
    # histogram names themselves (every admit observes into
    # ingest.<tenant>.admission_wait), so the dict is complete even
    # when no AdmissionController.report() refreshed the stall gauges.
    _suffix = ".admission_wait"
    report["serve_tenant_admission_p99"] = {
        name[len("ingest."):-len(_suffix)]: m.quantile(name, 0.99)
        for name in m.hist_names("ingest.")
        if name.endswith(_suffix)
    }
    # Where the per-window time went, by pipeline stage: the curated
    # always-on timers every mode records, plus (when span tracing is
    # armed) the SpanLog's measured per-stage totals under their lane
    # names — one dict the bench JSON charts instead of ten scattered
    # *_s keys.
    breakdown = {
        "acquire_wait": m.timer("consumer.wait").total_s,
        "stage_copy": m.timer("ingest.stage_copy").total_s,
        "transfer": m.timer("ingest.transfer").total_s,
        "release_wait": m.timer("ingest.release_wait").total_s,
        "window_wait": m.timer("trainer.window_wait").total_s,
        "admission_wait": m.timer("serve.admission_wait").total_s,
        "ici_fanout": m.timer("ici.fanout").total_s,
    }
    from ddl_tpu.obs import spans as _obs_spans

    _slog = _obs_spans.log()
    if _slog is not None:
        for stage, total in _slog.stage_totals().items():
            breakdown[f"span.{stage}"] = total
    report["stage_breakdown"] = breakdown
    # Cross-process aggregation health: reports merged vs dropped
    # stale, and the flight recorder's dump count — zero in THREAD
    # mode / disarmed runs by construction.
    report["obs_reports_applied"] = m.counter("obs.reports_applied")
    report["obs_reports_stale"] = m.counter("obs.reports_stale")
    report["obs_flight_dumps"] = m.counter("obs.flight_dumps")
    # Self-tuning audit (ISSUE 20: ddl_tpu.tune).  How many knob
    # decisions the Calibrator/KnobController made, how many the
    # never-worse guard took back, and what evidence drove them — the
    # cost_source histogram (measured / declared / default) that tells
    # an operator whether this run was tuned from probes or from
    # guesses.  Zeros in untuned runs by construction.
    report["tune_decisions"] = m.counter("tune.decisions")
    report["tune_reverts"] = m.counter("tune.reverts")
    report["tune_cost_source"] = {
        src: m.counter(f"tune.cost_source.{src}")
        for src in ("measured", "declared", "default")
    }
    # Start-up (ddl_tpu.profiling.startup_record, PROCESS-wide like the
    # pp gauges: a build has no registry to land in): seconds by phase
    # up to the last fit's first dispatch — backend bring-up, the four
    # build kinds, the Trainer's share of them, a fit's mean time to its
    # first window and to stop — and the slowest programs with their
    # kind and the compile cache's verdict.  "My first step takes
    # minutes" is answered here (docs/OBSERVABILITY.md).
    from ddl_tpu.profiling import startup_record

    report["startup"] = startup_record().summary()
    if link_bytes_per_sec:
        report["link_bytes_per_sec"] = link_bytes_per_sec
        report["bandwidth_utilization"] = (
            report["ingest_bytes_per_sec"] / link_bytes_per_sec
        )
    return report


class PrefetchIterator:
    """Wrap a batch iterator, keeping ``depth`` device transfers in flight.

    The standard TPU input recipe: while step k computes, batch k+1 is
    already crossing PCIe/DMA into HBM.

    Two operating modes:

    - **Staged** (``transfer`` given and the ingestor is staged): host
      batches are *enqueued* to the background executor, which stages
      them into pooled buffers and dispatches the transfers off-thread —
      ``__next__`` never copies; it only pops ready device values (pop
      wait time accumulates into ``ingest.stall``).  Offload is
      ADAPTIVE: when the worker demonstrably loses every claim to the
      consumer's work-stealing (a GIL/core-saturated host, where
      per-batch handoffs cost without buying overlap), fills switch to
      direct pooled puts — dispatch-now, recycled buffers — and
      periodically re-probe the executor in case cores free up.
    - **Inline** (default): each fill calls ``put`` on the caller thread
      — the pre-engine behavior, and the path for tuple-shaped host
      batches the single-buffer executor does not model.
    """

    #: Consecutive consumer-stolen jobs before concluding the worker is
    #: starved, and the direct-put span to run before probing it again.
    #: Each probe miss costs one handoff's ceremony (~ms on a saturated
    #: host), so conclude fast and re-probe sparsely.
    PROBE_MISSES = 2
    DIRECT_SPAN = 256

    def __init__(
        self,
        it: Any,
        ingestor: DeviceIngestor,
        depth: Optional[int] = None,
        put: Any = None,
        transfer: Any = None,
    ):
        """``put`` overrides the inline transfer call (default
        ``ingestor.put``) — e.g. a bound ``put_batch`` for
        single-transfer column batches.  ``transfer`` (a staged
        :data:`~ddl_tpu.staging.TransferFn`, e.g. from
        ``ingestor.batch_transfer_fn``) selects staged mode instead;
        staged direct-mode fills use ``put``, so pass both for the
        adaptive fallback to stay on the pooled path.  ``depth=None``
        reads ``DDL_TPU_PREFETCH_DEPTH`` (the tunable seam)."""
        self._it = iter(it)
        self._ingestor = ingestor
        self._put = put or ingestor.put
        # Gated on batch_staged: on the aliasing CPU client the executor
        # handoff costs without buying overlap (all-miss pool), so fills
        # go straight through `put` there.
        self._transfer = transfer if ingestor.batch_staged else None
        if depth is None:
            depth = envspec.get("DDL_TPU_PREFETCH_DEPTH")
        self._depth = max(1, depth)
        self._queue: collections.deque = collections.deque()

    def set_depth(self, depth: int) -> None:
        """Retune the in-flight transfer count live (ddl_tpu.tune).

        Takes effect on the next ``__next__`` fill: a shrink simply
        stops refilling until the queue drains below the new depth —
        already-dispatched transfers are never cancelled."""
        self._depth = max(1, int(depth))

    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self) -> Any:
        engine = (
            self._ingestor.engine() if self._transfer is not None else None
        )
        while len(self._queue) < self._depth:
            try:
                host_batch = next(self._it)
            except StopIteration:
                break
            if (
                engine is not None
                and not engine.faulted
                and engine.direct_left == 0
            ):
                self._queue.append(
                    engine.submit(host_batch, self._transfer)
                )
            else:
                if engine is not None and not engine.faulted:
                    engine.direct_left -= 1
                self._queue.append(self._put(host_batch))
        if not self._queue:
            raise StopIteration
        head = self._queue.popleft()
        if isinstance(head, StagedTransfer):
            # Work-stealing pop: an unstarted head job runs inline here
            # (never slower than the inline path); a worker-claimed one
            # is a genuine wait, counted as ingest.stall.  On transfer-
            # retry exhaustion the engine salvages the verified staging
            # copy down the inline path (degradation ladder; no loss,
            # no dup — `engine.faulted` routes later batches inline).
            value = engine.complete_or_salvage(head, self._put)
            if head.worker_executed:
                engine.stolen_streak = 0
            else:
                engine.stolen_streak += 1
                if engine.stolen_streak >= self.PROBE_MISSES:
                    # The worker lost PROBE_MISSES claims in a row: it is
                    # starved for CPU and each handoff is pure overhead.
                    # Run direct pooled puts for a span, then probe again.
                    engine.stolen_streak = 0
                    engine.direct_left = self.DIRECT_SPAN
            return value
        return head


def _device_split(dev: Any, splits: Sequence[int]) -> Tuple[Any, ...]:
    """Column-split a transferred (B, sum(splits)) batch ON DEVICE."""
    out, off = [], 0
    for w in splits:
        out.append(dev[:, off : off + w])
        off += w
    return tuple(out)
