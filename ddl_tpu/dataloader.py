"""Consumer API: the distributed dataloader.

Parity with reference ``ddl/mpi_dataloader.py`` — ``DistributedDataLoader``
with ``__len__`` / ``__getitem__`` / ``mark`` (``mpi_dataloader.py:107-241``):

- ``__len__`` is ``batches_per_window`` — an "epoch" in the user loop is one
  window of the current producer (Q7 semantics preserved for API compat;
  dataset coverage comes from round-robin rotation across epochs).
- ``__getitem__`` returns a zero-copy tuple of column-split tensors from the
  current window (reference ``mpi_dataloader.py:179-198``).
- The user MUST call ``mark(Marker.END_OF_BATCH)`` after every step and
  ``mark(Marker.END_OF_EPOCH)`` after every epoch; rotation and shutdown
  are driven off the marks (reference ``mpi_dataloader.py:89-102``).

Fixes over the reference: unequal ``batches_per_window`` across producers is
SERVED (weighted rotation — each turn drains the whole current window, so
``len(loader)`` tracks the rotation) where the reference left mixed sizes
as an unfinished deadlocking ToDo (Q6, ``mpi_dataloader.py:223``);
single-process THREAD mode is first-class rather than a silent empty
loader (Q9, ``mpi_dataloader.py:173-174``); output can be numpy views,
torch tensors, or JAX device arrays (device ingest).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ddl_tpu import envspec
from ddl_tpu import integrity
from ddl_tpu.datasetwrapper import ProducerFunctionSkeleton
from ddl_tpu.exceptions import (
    DoesNotMatchError,
    IntegrityError,
    LoaderStateError,
    ShutdownRequested,
    StallTimeoutError,
)
from ddl_tpu.obs import spans as obs_spans
from ddl_tpu.obs.recorder import flight_dump
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.profiling import stage
from ddl_tpu.transport.connection import NOTHING, ConsumerConnection
from ddl_tpu.types import (
    ControlAck,
    Marker,
    MetaData_Consumer_To_Producer,
    ObsReport,
)
from ddl_tpu.utils import for_all_methods, with_logging

logger = logging.getLogger("ddl_tpu")


def _transfer_ready(dev: Any) -> bool:
    """Non-blocking transfer-completion probe on a device value (a jax
    array or tuple/pytree of them).  Leaves without ``is_ready`` (older
    jax) report not-ready — the caller's forced flush still blocks
    correctly, the fast path just never triggers."""
    from ddl_tpu.utils import value_ready

    return value_ready(dev, default=False)


class _CorruptAhead(Exception):
    """Internal: integrity verification failed on a LOOKAHEAD acquire.

    Held earlier slots make out-of-FIFO quarantine impossible, so the
    stream stops deepening instead; the corrupt window re-verifies (and
    enters quarantine-and-replay) when it reaches the head.  Never
    escapes the loader.
    """


class _TargetRevoked(Exception):
    """Internal: the target being acquired left the loader pool (a
    cluster view change dropped its host mid-acquire).  The acquire
    paths re-normalise onto the published pool and retry; never escapes
    the loader."""


# Rank-tagged DEBUG call tracing on every method, as the reference wrapped
# its three core classes (reference ``mpi_dataloader.py:106``); the hot
# per-batch path (``__getitem__`` via dunder skip, ``_host_cols``
# explicitly) stays quiet, mirroring the reference's ``__getitem__``
# exclusion (``mpi_dataloader.py:104-106``).
@for_all_methods(with_logging, exclude=("_host_cols", "_host_batch"))
class DistributedDataLoader:
    """Map-style loader over producer window rings.

    Construction performs the consumer half of the handshake
    (reference ``mpi_dataloader.py:127-172``): broadcast the pickled
    producer function + batch geometry, gather per-producer window specs,
    attach rings, and acquire the first window.
    """

    def __init__(
        self,
        data_producer_function: ProducerFunctionSkeleton,
        batch_size: int,
        connection: ConsumerConnection,
        n_epochs: int = 1,
        global_shuffle_fraction_exchange: float = 0.0,
        exchange_method: str = "sendrecv_replace",
        output: str = "torch",
        device: Any = None,
        sharding: Any = None,
        metrics: Optional[Metrics] = None,
        timeout_s: float = 300.0,
        staged: Optional[bool] = None,
        distribute: Optional[str] = None,
        cluster: Any = None,
    ):
        if output not in ("torch", "numpy", "jax"):
            raise ValueError(f"output must be torch|numpy|jax, got {output!r}")
        self.batch_size = batch_size
        self.n_epochs = n_epochs
        self.connection = connection
        self.output = output
        self.metrics = metrics or default_metrics()
        # The acked control seam's delivery counters (ctrl.*) land in
        # this loader's registry (ddl_tpu.transport.envelope).
        connection.control_metrics = self.metrics
        self.timeout_s = timeout_s
        self._epoch = 0
        self._batches_in_window = 0
        self._served_in_epoch = 0
        self._target = 0  # index into connection.rings, round-robin
        self._cur_slot: Optional[int] = None
        self._cur_array: Optional[np.ndarray] = None
        self._stream_token: Optional[object] = None  # active windows() stream
        self._finalized = False
        self._ingestor = None
        # Staged windows whose ring slots were released early (copy done)
        # but which no stream has yielded yet — an abandoned stream's
        # lookahead survives here, so the next stream serves it instead
        # of losing it (the break-resume contract, kept under staging).
        self._staged_orphans: "list" = []
        # Inline-stream windows already YIELDED whose ring slots are
        # still held pending transfer completion: [target, slot, dev]
        # in yield (== per-ring FIFO) order.  The old stream blocked the
        # host on every window's transfer before yielding it
        # (``jax.block_until_ready``), serializing window k+1's H2D
        # against window k's scanned optimizer steps (VERDICT r5 weak
        # #4); release is now gated on a non-blocking readiness probe,
        # with forced (blocking) flushes only where the ring actually
        # needs the slot back.
        self._release_backlog: "list" = []
        # Fused-step protocol seam: the most recently yielded stream
        # window's backlog entry, so ``gate_release_on`` can re-gate its
        # slot release on the CONSUMING step's done-future instead of
        # the bare transfer (ddl_tpu.trainer._fused_stream_loop).
        self._last_stream_entry: Any = None
        # Loader-pool decoupling seam (ddl_tpu.cluster): the APPLIED
        # LoaderPool this loader rotates over (members filtered to
        # local ring targets).  None = every ring (the static topology
        # the handshake reported).  Pool updates arrive asynchronously
        # (cluster supervisor thread) as _pending_pool and are APPLIED
        # on the consumer thread at window boundaries — rotation state
        # is single-threaded by construction.
        self._pool: Any = None
        self._pool_generation = -1
        self._pending_pool: Any = None
        self._cluster = cluster
        # Multi-tenant admission seam (ddl_tpu.serve): when bound, every
        # window acquisition passes the fair-share gate before touching
        # a ring, and charges its byte size after — see bind_admission.
        self._admission: Any = None
        # Per-job integrity namespace (ddl_tpu.serve.jobs): producers
        # stamp trailer seqs at seq_base + iteration and this consumer
        # expects exactly that slice, so a window leaking across jobs
        # fails seq verification.  Rides the producer function — the
        # wire_dtype handshake pattern — so both sides always agree.
        self._seq_base = int(
            getattr(data_producer_function, "seq_base", 0) or 0
        )
        # Cross-process observability (ddl_tpu.obs): PROCESS workers
        # ship ObsReports over the control channel; the merger fences
        # and folds them into this registry under producer.<idx>.*.
        # Built lazily on the first cross-process report poll.
        self._obs_merger: Any = None
        # Logical seq of the most recent successful head acquire — the
        # window-identity key the span/staging instrumentation stitches
        # on (consumer thread only, like the rotation state).
        self._last_acquired_seq: Optional[int] = None
        # Identity key of the most recently YIELDED stream window (the
        # trainer's consume spans read it — see last_window_key).
        self._last_window_key: Any = None
        if output == "jax":
            from ddl_tpu.ingest import DeviceIngestor

            # ``staged=None`` defers to the DDL_TPU_STAGED env gate;
            # ``distribute=None`` to DDL_TPU_DISTRIBUTE (default "auto":
            # on accelerator meshes each window is routed by its plan —
            # the ICI fan-out tier where a byte must reach more than one
            # chip, one direct sharded put for a pure split — the XLA
            # scatter elsewhere; ddl_tpu/parallel/ici).
            self._ingestor = DeviceIngestor(
                device=device, sharding=sharding, metrics=self.metrics,
                staged=staged, distribute=distribute,
            )

        # -- handshake -----------------------------------------------------
        connection.send_metadata(
            MetaData_Consumer_To_Producer(
                data_producer_function=data_producer_function,
                batch_size=batch_size,
                n_epochs=n_epochs,
                global_shuffle_fraction_exchange=global_shuffle_fraction_exchange,
                exchange_method=exchange_method,
            )
        )
        replies = connection.recv_metadata_as_consumer()
        if not replies:
            raise DoesNotMatchError(0, "no producers connected")
        self.replies = replies
        # Per-producer epoch lengths: UNEQUAL batches_per_window is
        # served by weighted rotation — each producer's turn serves its
        # WHOLE window, so a bigger window simply makes a longer epoch
        # (len(self) tracks the current target).  The reference left
        # mixed sizes as an unfinished ToDo that deadlocked its token
        # protocol (Q6, reference mpi_dataloader.py:223); rotation has
        # no tokens to mismatch.
        self._lens = [r.batches_per_window for r in replies]
        # End-to-end integrity (ddl_tpu.integrity): every producer that
        # advertised header stamping gets drain-time verification; the
        # quarantine-and-replay budget bounds how often one logical
        # window may be re-requested before the corruption is declared
        # unrecoverable.  Replay rewinds the producer function, which is
        # only sound without cross-instance exchange (peer-contributed
        # rows are not locally regenerable, whichever transport carried
        # them — host rendezvous or the device tier's ICI exchange) —
        # with shuffle active a corrupt slot escalates straight to
        # IntegrityError.
        self._integrity = all(getattr(r, "integrity", False) for r in replies)
        # Wire format per producer (ddl_tpu.wire): slots from a
        # wire-encoded producer carry the bf16/int8 payload + trailer
        # scales; the consumer edge decodes them back to the logical
        # shape/dtype the handshake reported (``_slot_array``).
        self._wire_dtypes = [
            getattr(r, "wire_dtype", "raw") or "raw" for r in replies
        ]
        self._shuffle_fraction = global_shuffle_fraction_exchange
        self._max_replays = envspec.get("DDL_TPU_MAX_REPLAYS")
        # Per-target count of DISCARDED ring commits (quarantined slots +
        # stale in-flight successors dropped while waiting for a replay):
        # logical window seq = ring.released + held - skew.
        self._seq_skew = [0] * len(replies)
        # Geometry is per-producer: heterogeneous column layouts are served
        # correctly rather than silently mis-split with producer 0's spec.
        self.splits_per_producer = [tuple(r.splits) for r in replies]
        self.shapes = [tuple(r.shape) for r in replies]
        self.dtypes = [np.dtype(r.dtype) for r in replies]
        connection.attach_rings()
        # Cluster decoupling seam: consume from whatever loader pool the
        # view publishes.  ``cluster`` may be the full recovery ladder
        # (ElasticCluster — attach_loader wires pool-following + rung-2
        # actions) or a bare ClusterSupervisor (pool-following only).
        if cluster is not None:
            if hasattr(cluster, "attach_loader"):
                cluster.attach_loader(self)
            else:
                cluster.add_listener(
                    lambda _old, new, _dead: self.apply_pool(
                        new.loader_pool()
                    )
                )
                self.apply_pool(cluster.view.loader_pool())
        # First window is acquired lazily on first __getitem__: acquiring
        # here (as the reference did, mpi_dataloader.py:172) would also make
        # the FINAL mark of a run block on a whole extra window that
        # shutdown immediately discards.

    # -- iteration protocol ------------------------------------------------

    @property
    def n_producers(self) -> int:
        return self.connection.n_producers

    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def batches_per_window(self) -> int:
        """Epoch length of the CURRENT target producer (Q7: one epoch ==
        one window).  With mixed window sizes this changes as the
        rotation advances — read it per epoch, as ``Trainer.fit`` does
        for its per-geometry scan cache."""
        return self._lens[self._target]

    def __len__(self) -> int:
        return self._lens[self._target]

    def _host_batch(self, idx: int) -> np.ndarray:
        """Zero-copy view of batch ``idx`` in the current window."""
        if not isinstance(idx, (int, np.integer)):
            raise ValueError(f"index must be int, got {type(idx)}")
        if (
            self._cur_array is None
            and self._batches_in_window == 0
            and self._served_in_epoch
        ):
            # This epoch's window has been fully served and released
            # (marks rotated the target); the next window belongs to the
            # NEXT epoch (Q7: one epoch == one window).  Ending
            # iteration here is what bounds a `for` loop when the NEXT
            # producer's window is longer than the one just served —
            # with equal windows the idx bound below fired at the same
            # point, with mixed windows it would keep indexing into the
            # rotated-to window mid-epoch.
            raise IndexError(idx)
        if idx < 0 or idx >= self._lens[self._target]:
            raise IndexError(idx)
        if self._finalized:
            raise LoaderStateError("loader is finalized")
        if self._cur_array is None:
            self._acquire_current()
        assert self._cur_array is not None
        start = self.batch_size * idx
        batch = self._cur_array[start : start + self.batch_size]
        self.metrics.incr("consumer.samples", self.batch_size)
        self._served_in_epoch += 1
        return batch

    def _host_cols(self, idx: int) -> Tuple[np.ndarray, ...]:
        """Zero-copy column views of batch ``idx`` in the current window."""
        return _split_columns(
            self._host_batch(idx), self.splits_per_producer[self._target]
        )

    def __getitem__(self, idx: int) -> Tuple[Any, ...]:
        # IndexError terminates Python's implicit iteration protocol in the
        # user's `for` loop (reference mpi_dataloader.py:180-183).
        if self.output == "jax":
            # One transfer per batch, column split ON device (narrow
            # columns otherwise pay the link's fixed per-transfer cost).
            assert self._ingestor is not None
            return self._ingestor.put_batch(
                self._host_batch(idx), self.splits_per_producer[self._target]
            )
        cols = self._host_cols(idx)
        if self.output == "numpy":
            return cols
        # torch.from_numpy is zero-copy over the ring slot, exactly as
        # the reference's view over the MPI shared window
        # (mpi_dataloader.py:192-193).
        import torch

        return tuple(torch.from_numpy(c) for c in cols)

    def prefetch(self, depth: Optional[int] = None):
        """Iterate one epoch's device batches with ``depth`` transfers in
        flight (``output="jax"`` only) — while step k computes, batch k+1
        is already crossing into HBM (the standard TPU input recipe;
        VERDICT r2 item 5 wired this into the training path).
        ``depth=None`` reads ``DDL_TPU_PREFETCH_DEPTH`` (the
        config-mirrored seam the boot-time Calibrator retunes).

        Reads ahead *within the current window*: all ``len(self)`` batches
        of an epoch live in one window, and each batch is copied out of
        the slot before the window is released — at enqueue time on the
        inline path, and no later than the slot-release barrier
        (``TransferExecutor.flush_copies`` in ``_release_current``) on
        the staged path — so lookahead never outlives the slot.
        ``mark()`` stays the caller's job, exactly as with plain
        iteration.
        """
        if self._ingestor is None:
            raise LoaderStateError("prefetch requires output='jax'")
        from ddl_tpu.ingest import PrefetchIterator

        splits = self.splits_per_producer[self._target]

        def host_iter():
            for idx in range(self._lens[self._target]):
                yield self._host_batch(idx)

        # Staged ingestors enqueue slot views to the background executor
        # (copy + dispatch off-thread) and pop ready device tuples; the
        # put fn serves inline ingestors AND the staged adaptive direct
        # mode (pooled, dispatch now) on hosts where the worker starves.
        # PrefetchIterator itself gates `transfer` on ingestor.staged.
        return PrefetchIterator(
            host_iter(), self._ingestor, depth,
            put=lambda b: self._ingestor.put_batch(b, splits),
            transfer=self._ingestor.batch_transfer_fn(splits),
        )

    def windows(self, lookahead: int = 1):
        """Stream whole windows into HBM, one per epoch (``output="jax"``).

        Two ingest disciplines, selected by the ``DDL_TPU_STAGED`` gate
        and the target platform (``DeviceIngestor.stream_staged``):

        - **Staged** (default on accelerators; forced by
          ``staged=True``): the background executor copies each window
          slot→pooled-staging-buffer and dispatches its transfer
          off-thread; the SLOT is released back to the producer as soon
          as the staging copy completes — one host memcpy of hold time
          instead of the whole H2D transfer, so producers refill sooner
          and the same ``nslots`` sustains a deeper in-flight pipeline.
        - **Inline** (``DDL_TPU_STAGED=0``, and the default on the CPU
          client): each window's transfer sources the ring slot directly
          (no host memcpy anywhere between producer fill and HBM).  The
          slot is still owned until the transfer completes, but the
          HOST never blocks on that: windows yield as async device
          values and slot release is gated on a transfer-completion
          probe (forced only when the ring runs out of slots), so
          window k+1's H2D overlaps window k's compute instead of
          serializing behind a per-window ``block_until_ready``.  (On
          the CPU client ``put_window`` detaches the source with its
          alias-guard copy, so slots release at yield.)

        Either way the next window's transfer streams while the caller's
        compute on the current one runs.  This is the TPU analog of the
        reference's zero-copy shared-window reads
        (reference ``mpi_dataloader.py:192-193``) extended across the
        host→device boundary.

        ``lookahead`` (default 1) double-buffers the stream: before window
        k is yielded, window k+1 is acquired — holding a second slot —
        and its transfer started, so H2D overlaps the caller's compute BY
        CONSTRUCTION rather than by async-dispatch timing.  The reference
        double-buffered the host-side analog only as a ToDo sketch
        (reference ``mpi_dataloader.py:21-28``); here it spans the
        host→device boundary.  The lookahead acquire is a NON-BLOCKING
        try: when the producer has not committed window k+1 yet, window k
        yields immediately and the wait happens where it always did — the
        stream never lets producer slowness delay compute it could not
        have hidden anyway.  Needs ``nslots >= 2`` (or >= 2 producers) to
        take effect; ``lookahead=0`` restores strict alternation.

        Yields device arrays of shape ``(batches_per_window, batch_size,
        *features)``.  The caller still calls ``mark(Marker.END_OF_EPOCH)``
        after each window (Q7: one epoch == one window); batch-level
        ``__getitem__``/``END_OF_BATCH`` iteration must not be mixed with
        ``windows()`` inside the same epoch.  Pair with producer functions
        that set ``inplace_fill`` for a fully copy-free pipeline.
        """
        if self._ingestor is None:
            raise LoaderStateError("windows() requires output='jax'")
        import collections

        import jax

        from ddl_tpu.staging import StagedTransfer

        # Staged engine: the window is copied slot→pooled-staging-buffer
        # by the background executor, and the SLOT is released as soon as
        # that copy completes — the producer refills while the H2D
        # transfer (sourcing the staging buffer, not the slot) is still
        # in flight.  Inline (DDL_TPU_STAGED=0, and the default on the
        # CPU client, where the stream is zero-copy — see
        # DeviceIngestor.stream_staged): the transfer sources the slot
        # directly and the slot is held until the bytes are on device.
        engine = (
            self._ingestor.engine() if self._ingestor.stream_staged else None
        )

        held: collections.Counter = collections.Counter()
        # A previous stream's yielded-but-unreleased windows still hold
        # ring slots; count them so this stream's drain-lookahead
        # accounting (acquire_drain_ahead(held)) skips past them, and
        # sweep them out as their transfers complete.
        for _entry in self._release_backlog:
            held[_entry[0]] += 1
        # FIFO of [slot, target, payload, samples, slot_released] with
        # transfers in flight; at most 1 + lookahead entries.  payload is
        # a device array (inline) or a StagedTransfer handle (staged).
        pending: collections.deque = collections.deque()
        # GENERATOR-LOCAL rotation cursor.  ``self._target`` stays the
        # authoritative next-UNSERVED pointer and only advances when a
        # window is actually yielded (see finish) — so abandoning this
        # generator needs no state rollback, and a stale generator
        # finalized by GC long after a new stream started cannot corrupt
        # the live rotation.  Acquired-but-unyielded windows need no ring
        # cleanup either: acquisition has no ring side effect (only
        # release() moves the counter), so a later stream re-acquires
        # exactly the same windows.  In-flight transfers on abandonment
        # are harmless — the producer cannot overwrite an unreleased
        # slot, and slot mappings outlive close().
        cursor = self._target
        # ONE live stream at a time: two concurrently-iterated streams
        # would acquire the same slot (cursor and held counts are
        # per-generator) and double-release it, silently corrupting the
        # ring counters.  Starting a new stream therefore invalidates
        # the previous one — its next iteration raises instead.
        token = object()
        self._stream_token = token

        def check_live():
            if self._stream_token is not token:
                raise LoaderStateError(
                    "this windows() stream was superseded by a newer "
                    "windows() call on the same loader; iterate one "
                    "stream at a time"
                )

        def start_one(timeout_s: float):
            """Acquire the next window at the local cursor, start its
            transfer, advance the cursor.  With ``held[target] > 0`` the
            ring's drain-lookahead primitive acquires PAST the still-held
            slot (release order stays FIFO).  Acquisition is integrity-
            verified: a corrupt head window is quarantined and replayed
            before anything is submitted downstream.  A cluster view
            change revoking the target mid-acquire rotates onto the
            published pool and retries — the cross-host ladder's
            consumer-side edge."""
            nonlocal cursor
            self._apply_pending_pool()
            cursor = self._next_target(cursor, include=True)
            target = cursor
            with stage("ddl.window_acquire", self.metrics) as st:
                while True:
                    try:
                        slot = self._acquire_verified(
                            target, held[target], timeout_s
                        )
                        break
                    except _TargetRevoked:
                        self._apply_pending_pool()
                        cursor = self._next_target(cursor, include=True)
                        target = cursor
                st.key = (target + 1, self._last_acquired_seq)
            ring = self.connection.rings[target]
            # Window identity (the integrity trailer's (producer_idx,
            # seq)) — the key every downstream span of THIS window
            # stitches on (staging copy/transfer, H2D, ICI fan-out,
            # trainer consume, slot release).
            wkey = (target + 1, self._last_acquired_seq)
            arr = self._slot_array(target, slot)
            # Ragged tail rows (nData not a batch multiple) are unserved,
            # exactly as in batch iteration.  bpw is per-TARGET: mixed
            # window sizes yield differently-shaped windows as the
            # rotation advances.
            bpw = self._lens[target]
            served = bpw * self.batch_size
            window = arr[:served].reshape(
                bpw, self.batch_size, *self.shapes[target][1:]
            )
            # Byte accounting is deferred to finish(): counting bytes at
            # yield keeps ingest.bytes and consumer.samples covering
            # identical windows over any measurement span (dispatch leads
            # the yield by the lookahead depth).  An engine that faulted
            # (staged transfers exhausted their retry budget) is skipped:
            # the degradation ladder routes every later window straight
            # down the sanctioned inline path.
            if engine is not None and not engine.faulted:
                ingestor = self._ingestor
                # Shm-backed staging (write-once pipeline): on clients
                # whose device_put genuinely copies, the staged transfer
                # sources the slot DIRECTLY — no slot→staging memcpy —
                # and copy_done (the release edge) fires at transfer
                # completion.  The slot is held for the DMA, so the
                # early-release torn-read hazard the staged CRC re-check
                # guards does not exist on this path.
                alias = (
                    ingestor.stream_alias
                    and not engine.executor.alias_unsafe
                )
                # Post-copy re-verify (ddl_tpu.integrity): when the
                # served rows span the whole payload, the committed CRC
                # also certifies the staging copy — the executor checks
                # it after its slot→buffer memcpy, catching a producer
                # overwriting a not-yet-copied slot.
                expected_crc = None
                if not alias and self._integrity and window.nbytes == int(
                    ring.slot_payload(slot)
                ):
                    expected_crc = integrity.read_header(
                        ring.slot_view(slot), ring.slot_payload(slot)
                    ).crc
                payload = engine.submit(
                    window,
                    lambda buf: (ingestor._transfer(buf),) * 2,
                    expected_crc=expected_crc,
                    alias_src=alias,
                    span_key=wkey,
                )
            else:
                # Identity context for the nested transfer/fan-out
                # spans (put_window, IciDistributor) — they run on this
                # thread and cannot see the window key otherwise.
                obs_spans.set_window(*wkey)
                try:
                    payload = self._ingestor.put_window(
                        window, defer_metrics=True
                    )
                finally:
                    obs_spans.clear_window()
            held[target] += 1
            cursor = self._next_target(cursor)
            return [slot, target, payload, served, False, wkey]

        def release_early():
            """Staged mode: hand back the slots of every pending window
            whose staging copy has completed — in pending (FIFO) order,
            stopping at the first incomplete copy so per-ring release
            order stays FIFO.  This is what shrinks slot-hold time from
            'whole H2D transfer' to 'one host memcpy': the producer can
            refill while the transfer is still crossing the link.

            A released-but-unyielded window's data lives only in its
            staging buffer, so it is recorded on the LOADER
            (``_staged_orphans``): if this stream is abandoned, the next
            stream inherits and serves it — the break-resume contract
            survives early release."""
            for entry in pending:
                slot, target, payload, _served, released = entry[:5]
                if released:
                    continue
                if not isinstance(payload, StagedTransfer):
                    # Inline-fallback window (engine faulted mid-stream):
                    # its transfer sources the slot directly, so the slot
                    # is held until finish() — and release order is FIFO,
                    # so nothing behind it may release early either.
                    break
                if not payload.copy_done.is_set():
                    break
                self.connection.rings[target].release(slot)
                obs_spans.mark("consumer.release", *entry[5])
                held[target] -= 1
                entry[4] = True
                self._staged_orphans.append(entry)

        def finish(entry):
            slot, target, payload, served, released, wkey = entry
            if isinstance(payload, StagedTransfer):
                # Wait only for the staging copy + dispatch (the slot's
                # last reader), not the whole transfer — the device value
                # is an async future exactly like the batch path's.
                # Work-stealing: an unstarted job runs inline here.  On
                # transfer-retry exhaustion the engine salvages the
                # verified staging copy down the sanctioned inline path
                # (degradation ladder rung 2 — no loss, no duplicate;
                # `engine.faulted` routes later windows inline up front).
                def inline_put(buf):
                    dev = self._ingestor.put_window(buf, defer_metrics=True)
                    jax.block_until_ready(dev)
                    return dev

                with stage("ddl.transfer_wait", self.metrics):
                    dev = engine.complete_or_salvage(
                        payload, inline_put, self.timeout_s
                    )
            else:
                dev = payload
            self.metrics.incr("ingest.bytes", float(dev.nbytes))
            self.metrics.incr("ingest.windows")
            self.metrics.incr("consumer.windows")
            self.metrics.incr("consumer.samples", served)
            self._last_stream_entry = None
            if not released:
                if not isinstance(payload, StagedTransfer) and (
                    not self._ingestor.window_source_detached()
                ):
                    # Inline on an accelerator: the transfer sources the
                    # ring slot, so the slot must outlive the DMA — but
                    # the HOST need not wait for it.  The old
                    # ``block_until_ready`` here serialized window k+1's
                    # H2D against window k's scanned optimizer steps
                    # (VERDICT r5 weak #4); release is instead deferred
                    # onto the transfer-completion probe
                    # (``_sweep_release_backlog``), forced only when the
                    # ring runs out of slots.  The entry is remembered
                    # so a fused-step consumer can re-gate it on the
                    # consuming step's done-future (gate_release_on).
                    # (Named distinctly from the enclosing ``entry``
                    # parameter — the pending-queue 5-tuple — which the
                    # staged-orphan branch below still reads.)
                    backlog_entry = [target, slot, dev, wkey]
                    self._release_backlog.append(backlog_entry)
                    self._last_stream_entry = backlog_entry
                else:
                    # Staged payload (copy+dispatch already awaited) or
                    # inline with a DETACHED source (the CPU client's
                    # alias-guard copy in ``put_window``): nothing reads
                    # the slot anymore, hand it back now.
                    self.connection.rings[target].release(slot)
                    obs_spans.mark("consumer.release", *wkey)
                    held[target] -= 1
            elif self._staged_orphans and self._staged_orphans[0] is entry:
                # Yielded after its early release: no longer an orphan.
                self._staged_orphans.pop(0)
            # This window is now SERVED: commit the rotation.
            self._target = self._next_target(target)
            self._last_window_key = wkey
            obs_spans.mark("consumer.yield", *wkey)
            return dev

        # Inherit a superseded/abandoned stream's early-released windows:
        # their slots are gone from the ring (data lives in staging
        # buffers / in flight to HBM) and they are, by FIFO construction,
        # exactly the next unserved windows in rotation order — serve
        # them first, then continue acquiring after them.
        for entry in self._staged_orphans:
            pending.append(entry)
        if pending:
            cursor = self._next_target(pending[-1][1])

        # Yield-bounded up front: the generator serves exactly the
        # epochs left, so exhausting it eagerly (e.g. list()) before
        # the marks terminates rather than streaming past the run.
        remaining = self.n_epochs - self._epoch
        for i in range(remaining):
            check_live()
            if self._finalized:
                break
            # Cross-process observability: fold any pending worker
            # ObsReports in at the window boundary (no-op in THREAD).
            self._poll_obs()
            if self._release_backlog:
                # Free completed-transfer slots (non-blocking probe)
                # before acquiring or deepening.
                self._sweep_release_backlog(held)
            if not pending:
                if (
                    held[cursor]
                    >= self.connection.rings[cursor].nslots
                ):
                    # Every slot of the head ring is either in flight or
                    # awaiting its transfer-gated release: the blocking
                    # acquire below could never be satisfied (the
                    # producer has no free slot to commit into) — wait
                    # out the OLDEST deferred transfer on that ring.
                    self._flush_release_backlog(held, target=cursor)
                pending.append(start_one(self.timeout_s))
            if engine is not None:
                # Free completed-copy slots BEFORE deepening: an early
                # release lowers held[cursor], so the same nslots admits
                # a deeper in-flight pipeline.
                release_early()
            # Deepen the pipeline up to `lookahead` extra windows, each
            # a non-blocking try: the first not-yet-committed (or
            # capacity-exhausted) window ends the deepening round.
            while (
                len(pending) <= lookahead
                and i + len(pending) < remaining
                and not self._finalized
                and held[cursor]
                < self.connection.rings[cursor].nslots
                # A full executor queue would park start_one inside
                # submit's backpressure wait — deepening is lookahead,
                # never a place to block.  A faulted engine routes
                # inline, so its queue no longer gates deepening.
                and (
                    engine is None
                    or engine.faulted
                    or engine.executor.has_capacity()
                )
            ):
                # Cheap counter peek first: a not-yet-committed window
                # must not register a wait event in the stall accounting
                # (it is lookahead, not a stall).
                if not self.connection.rings[cursor].poll_drain_ready(
                    held[cursor]
                ):
                    break
                try:
                    pending.append(start_one(0.0))
                except StallTimeoutError:
                    break  # not committed yet; wait at next iter
                except _CorruptAhead:
                    # Corrupt window discovered during lookahead: held
                    # slots forbid out-of-FIFO quarantine, so stop
                    # deepening — it re-verifies (and replays) when it
                    # reaches the head at ahead == 0.
                    break
                except NotImplementedError:
                    # Ring without drain lookahead (a custom WindowRing
                    # on the base-class fallback): degrade to strict
                    # alternation instead of dying mid-stream.
                    lookahead = 0
                    break
            yield finish(pending.popleft())

    # -- cross-process observability drain (ddl_tpu.obs) -------------------

    def _poll_obs(self) -> None:
        """Drain pending producer ObsReports (non-blocking, once per
        window boundary) and merge them into this registry under
        ``producer.<idx>.*``.  THREAD-mode channels never carry reports
        (the worker registry IS this one), so the poll is a cheap
        per-window no-op there."""
        self._drain_obs_once()

    def _obs_reports_possible(self) -> bool:
        """Could this loader's producers ship ObsReports at all?
        Cross-process channels with shipping enabled — THREAD loaders
        (in-process queues, shared registry) never wait on teardown."""
        from ddl_tpu.obs import ship_every
        from ddl_tpu.transport.connection import ThreadChannel

        return ship_every() > 0 and any(
            not isinstance(ch, ThreadChannel)
            for ch in self.connection.channels
        )

    def drain_obs_reports(
        self, timeout_s: float = 0.0, wait_for_all: bool = False
    ) -> int:
        """Drain producer ObsReports, optionally waiting up to
        ``timeout_s`` for stragglers (a PROCESS worker's FINAL report
        races teardown) — the shutdown/bench/test hook; the per-window
        poll is :meth:`_poll_obs`.  ``wait_for_all`` exits EARLY once a
        FRESH report (one applied after this call started) has arrived
        from every producer — a clean teardown pays only the real
        straggler latency, never the whole deadline; crashed producers
        never report, so the deadline stays the upper bound.  Returns
        reports applied."""
        import threading

        deadline = time.monotonic() + timeout_s
        waiter = threading.Event()
        applied = 0
        start_state = (
            self._obs_merger.fence_state()
            if self._obs_merger is not None
            else {}
        )
        targets = set(range(self.n_producers))
        while True:
            applied += self._drain_obs_once()
            if wait_for_all and self._obs_merger is not None:
                state = self._obs_merger.fence_state()
                if all(
                    t in state and state[t] != start_state.get(t)
                    for t in targets
                ):
                    return applied
            if timeout_s <= 0 or time.monotonic() >= deadline:
                return applied
            waiter.wait(0.02)

    def _drain_obs_once(self) -> int:
        # Retry due unacked control envelopes first (the acked seam,
        # ddl_tpu.transport.envelope): this drain runs once per window
        # boundary and from every teardown/straggler wait, so it is the
        # consumer's natural delivery heartbeat.
        self.connection.pump_control()
        applied = 0
        for target in range(self.n_producers):
            while True:
                msg = self.connection.try_recv_control(target)
                if msg is NOTHING:
                    break
                if isinstance(msg, ObsReport):
                    if self._obs_merger is None:
                        from ddl_tpu.obs import ReportMerger

                        self._obs_merger = ReportMerger(
                            self.metrics, obs_spans.log
                        )
                    if self._obs_merger.apply(msg):
                        applied += 1
                elif isinstance(msg, ControlAck):
                    # Producer acked an enveloped command: clear the
                    # sender's pending retry (dedup/fence verdicts land
                    # as ctrl.* counters inside the sender).
                    self.connection.note_ack(msg)
                else:
                    logger.warning(
                        "consumer: ignoring unexpected producer "
                        "message %r on channel %d",
                        type(msg).__name__, target,
                    )
        return applied

    # -- loader-pool decoupling seam (ddl_tpu.cluster) ---------------------

    def apply_pool(self, pool: Any) -> None:
        """Adopt a published :class:`~ddl_tpu.cluster.pool.LoaderPool`.

        Thread-safe entry point (called from the cluster supervisor's
        sweep thread): the pool is only RECORDED here; rotation state
        changes on the consumer thread at the next window boundary
        (``_apply_pending_pool``), and a consumer blocked on a ring the
        new pool drops is unblocked by target revocation inside the
        sliced acquire.  Stale generations (<= the applied one) are
        ignored — the epoch fence.
        """
        cur = self._pending_pool
        if cur is not None and cur.generation >= pool.generation:
            return  # a newer pool is already pending; keep the fence
        if pool.generation <= self._pool_generation:
            return  # stale relative to what was already applied
        self._pending_pool = pool

    def _apply_pending_pool(self) -> None:
        """Consumer-thread half of :meth:`apply_pool`."""
        pool = self._pending_pool
        if pool is None:
            return
        self._pending_pool = None
        if pool.generation <= self._pool_generation:
            return  # stale fence: view N must never undo view N+1
        from ddl_tpu.cluster.pool import LoaderPool

        members = tuple(
            m for m in pool.members if 0 <= m < self.n_producers
        )
        if not members:
            raise LoaderStateError(
                "loader pool update left no local ring targets "
                f"(pool={pool.members}, rings={self.n_producers})"
            )
        self._pool = LoaderPool(members=members, generation=pool.generation)
        self._pool_generation = pool.generation
        self.metrics.incr("consumer.pool_updates")
        self.metrics.set_gauge("consumer.pool_size", len(members))
        if self._target not in self._pool:
            # The current target's host left: drop any partially-served
            # window (its remaining batches are re-partitioned to the
            # survivors by shard adoption) and rotate onto the pool.
            self._batches_in_window = 0
            self._release_current()
            self._target = self._next_target(self._target)

    def gate_release_on(self, done: Any) -> None:
        """Fused-step protocol: gate the most recently yielded stream
        window's deferred slot release on the CONSUMING step's
        done-future, not the bare transfer.

        ``done`` is any device value (or pytree of them) produced by
        the step that consumed the window — e.g. the scanned
        multistep's per-step losses.  The window's backlog entry grows
        the future as an ADDITIONAL release condition: the
        non-blocking sweep (``_sweep_release_backlog``) then frees the
        slot only once both the transfer AND the consuming step have
        completed, which is the two-slot ring discipline — the
        producer may overwrite a slot only when the step that read its
        window is done, so a re-fill can never race a still-running
        scan's device reads (on clients that alias host pages the
        transfer-done edge alone is not that guarantee).

        No-op when the window's slot was already released at yield
        (staged early release, or a detached CPU-client source): gating
        is only ever an extra condition on an entry that exists, so a
        consumer that never calls this keeps the plain transfer-probe
        behavior, and the protocol cannot deadlock — the blocking flush
        paths ``block_until_ready`` the combined future, and the step
        completes independently of any slot.  One window at a time: the
        gate applies to the LAST yielded window and is consumed by the
        call (the fused trainer loop calls it once per step dispatch).
        """
        entry = self._last_stream_entry
        self._last_stream_entry = None
        if entry is None:
            return
        for e in self._release_backlog:
            if e is entry:
                # Tuple pytree: both the transfer value and the step
                # future must probe ready before the sweep releases.
                e[2] = (e[2], done)
                self.metrics.incr("ingest.fused_gated")
                return

    def last_window_key(self) -> Any:
        """Identity ``(producer_idx, seq)`` of the most recently yielded
        stream window — the trainer's consume spans key on it
        (``ddl_tpu.obs``).  None before the first yield."""
        return self._last_window_key

    def bind_admission(self, admission: Any) -> None:
        """Attach a multi-tenant admission gate (``ddl_tpu.serve``).

        ``admission`` speaks the two-method protocol of
        :class:`~ddl_tpu.serve.tenancy.Tenant`: ``admit(timeout_s)``
        blocks (deadline-bounded) until the fair-share scheduler grants
        this tenant its next window — raising
        :class:`~ddl_tpu.exceptions.StallTimeoutError` on a
        non-blocking probe (``timeout_s <= 0``, the lookahead-deepening
        path) exactly like a not-yet-committed window — and
        ``note_served(nbytes)`` charges the acquired window's bytes
        against the tenant's share and budgets.  The hook lives in
        ``_acquire_verified``, the one choke point every window
        acquisition (batch, stream, lookahead, replay) already passes
        through, so tenancy cannot be bypassed by any iteration style —
        the same bypass-proof property the pool seam's
        :meth:`~ddl_tpu.cluster.pool.LoaderPool.next_member` rotation
        rule has.  ``None`` unbinds.
        """
        self._admission = admission

    def _next_target(self, t: int, include: bool = False) -> int:
        """The next ACTIVE ring target cyclically after ``t`` (or ``t``
        itself when ``include`` and it is active) — all rotation goes
        through here, delegating to the applied pool's
        :meth:`~ddl_tpu.cluster.pool.LoaderPool.next_member` (ONE
        implementation of the rotation rule), so the pool seam has one
        bypass-proof gate."""
        if self._pool is None:
            return t % self.n_producers if include else (
                (t + 1) % self.n_producers
            )
        return self._pool.next_member(t, include=include)

    def _target_revoked(self, target: int) -> bool:
        """True when ``target`` is outside the active pool or about to
        be dropped by a pending one — the sliced acquire polls this so
        a consumer blocked on a dead host's ring unblocks at the view
        change instead of its full timeout."""
        if self._pool is not None and target not in self._pool:
            return True
        pool = self._pending_pool
        return (
            pool is not None
            and pool.generation > self._pool_generation
            and target not in pool
        )

    # -- progress marks ------------------------------------------------------

    def mark(self, marker: Marker) -> None:
        """Report progress (reference ``mpi_dataloader.py:236-241``)."""
        if marker is Marker.END_OF_BATCH:
            self._on_batch_end()
        elif marker is Marker.END_OF_EPOCH:
            self._on_epoch_end()
        else:
            raise ValueError(f"unknown marker {marker!r}")

    def _on_batch_end(self) -> None:
        self._batches_in_window += 1
        if self._batches_in_window >= self._lens[self._target]:
            self._batches_in_window = 0
            self._release_current()
            self._advance_to_next_producer()
            # Next window is acquired lazily by the next __getitem__.

    def _on_epoch_end(self) -> None:
        self._served_in_epoch = 0
        if self._batches_in_window:
            # Epoch ended mid-window (user broke out early): discard the
            # partially consumed window so the next epoch starts on a fresh
            # window boundary instead of silently re-serving stale batches.
            self._batches_in_window = 0
            self._release_current()
            self._advance_to_next_producer()
        self._epoch += 1
        if self._epoch >= self.n_epochs:
            self.shutdown()

    # -- window rotation (reference mpi_dataloader.py:200-234) -------------

    def _ring(self):
        return self.connection.rings[self._target]

    def _advance_to_next_producer(self) -> None:
        self._apply_pending_pool()
        self._target = self._next_target(self._target)

    def _slot_array(self, target: int, slot: int) -> np.ndarray:
        """Window array of an acquired slot, shaped for ``target``.

        Raw producers: a zero-copy view of the slot payload.  Wire-
        encoded producers (``ddl_tpu.wire``): the slot holds the
        bf16/int8 payload + trailer scales; this is the CONSUMER EDGE
        decode — a fresh array per acquire (never a shared scratch:
        lookahead holds several of one target's windows live at once),
        after which nothing downstream reads the slot.  A decode
        failure (the ``wire.decode`` chaos site's ``DECODE_FAIL``, or
        real bit rot the CRC somehow missed) retries once, then
        escalates to :class:`IntegrityError` — by then the bytes are
        provably undecodable, the same terminal rung a persistent
        backend failure reaches.
        """
        ring = self.connection.rings[target]
        nbytes = ring.slot_payload(slot)
        if self._wire_dtypes[target] == "raw":
            return (
                ring.slot_view(slot)[:nbytes]
                .view(self.dtypes[target])
                .reshape(self.shapes[target])
            )
        from ddl_tpu import wire
        from ddl_tpu.exceptions import DecodeError
        from ddl_tpu.faults import fault_point

        view = ring.slot_view(slot)
        hdr = integrity.read_header(view, nbytes)
        scales = (
            integrity.read_scales(view, nbytes, hdr.scale_bytes)
            if hdr.scale_bytes
            else None
        )
        _span_t0 = obs_spans.t0()
        for attempt in (1, 2):
            try:
                fault_point("wire.decode", view=view[:nbytes])
                dec = wire.decode_window(
                    np.array(view[:nbytes]), scales,
                    self.shapes[target], self.dtypes[target],
                    hdr.wire_dtype,
                )
                break
            except DecodeError as e:
                self.metrics.incr("wire.decode_fails")
                if attempt == 2:
                    flight_dump(
                        "wire.undecodable",
                        producer_idx=target + 1, seq=hdr.seq,
                        metrics=self.metrics,
                        extra={"wire_dtype": hdr.wire_dtype},
                    )
                    raise IntegrityError(
                        f"window from producer {target + 1} undecodable "
                        f"after retry ({hdr.wire_dtype} wire): {e}"
                    ) from e
        obs_spans.record("wire.decode", target + 1, hdr.seq, _span_t0)
        self.metrics.incr("wire.decoded_windows")
        # The wire accounting pair (encoded bytes that traveled the
        # slot vs the logical raw bytes served) — counted HERE, the one
        # registry every run mode shares.
        self.metrics.incr(
            "wire.encoded_bytes", float(nbytes + hdr.scale_bytes)
        )
        self.metrics.incr("wire.payload_bytes", float(dec.nbytes))
        return dec

    # -- deferred (transfer-gated) slot release ----------------------------

    def _sweep_release_backlog(self, held=None) -> None:
        """Release yielded inline-stream windows whose transfers have
        COMPLETED (non-blocking ``is_ready`` probe), in per-ring FIFO
        order — a not-yet-ready transfer blocks only later entries of
        the same ring.  ``held`` (the live stream's per-target hold
        counter) is decremented alongside each release."""
        blocked: set = set()
        remaining = []
        for entry in self._release_backlog:
            target, slot, dev = entry[:3]
            if target not in blocked and _transfer_ready(dev):
                self.connection.rings[target].release(slot)
                if len(entry) > 3:
                    obs_spans.mark("consumer.release", *entry[3])
                if held is not None:
                    held[target] -= 1
            else:
                blocked.add(target)
                remaining.append(entry)
        self._release_backlog = remaining

    def _flush_release_backlog(self, held=None, target=None) -> None:
        """BLOCKING release of backlog entries: all of them (stream
        teardown / path switches), or only the oldest entry of
        ``target`` (a ring out of free slots).  The wait is the
        transfer completing — accounted as ``ingest.release_wait`` so
        a stream losing its overlap shows up in the north-star report
        instead of hiding inside opaque wall time."""
        import jax

        remaining = []
        done = False
        for entry in self._release_backlog:
            t, slot, dev = entry[:3]
            if done or (target is not None and t != target):
                remaining.append(entry)
                continue
            with stage("ddl.release_wait", self.metrics):
                jax.block_until_ready(dev)
            self.connection.rings[t].release(slot)
            if len(entry) > 3:
                obs_spans.mark("consumer.release", *entry[3])
            if held is not None:
                held[t] -= 1
            if target is not None:
                done = True
        self._release_backlog = remaining

    # -- end-to-end integrity (ddl_tpu.integrity) --------------------------

    def _expected_seq(self, target: int, ahead: int) -> int:
        """Logical window number of the slot ``acquire_drain_ahead(ahead)``
        returns on ``target``: released count plus lookahead, minus the
        commits discarded by past quarantine replays — offset into this
        job's integrity namespace (``seq_base``)."""
        ring = self.connection.rings[target]
        return (
            self._seq_base
            + int(ring.stats()["released"]) + ahead
            - self._seq_skew[target]
        )

    def _verify_slot(
        self, target: int, slot: int, expect_seq: int
    ) -> Optional[str]:
        """Drain-time header check; None when the window is intact.

        ``consumer.verify`` times every check and
        ``consumer.verify_parallel_windows`` counts the windows large
        enough for the span-parallel CRC fold — ``consumer.*``, not
        ``integrity.*``: those counters read zero on a healthy run, and
        the benchmark holds runs to that."""
        ring = self.connection.rings[target]
        payload_bytes = ring.slot_payload(slot)
        t0 = time.perf_counter()
        err = integrity.verify_window(
            ring.slot_view(slot),
            payload_bytes,
            expect_seq=expect_seq,
            expect_producer=target + 1,
        )
        self.metrics.add_time("consumer.verify", time.perf_counter() - t0)
        if integrity.fold_spans(payload_bytes) > 1:
            self.metrics.incr("consumer.verify_parallel_windows")
        return err

    def _acquire_verified(self, target: int, ahead: int, timeout_s: float):
        """Acquire the next committed slot on ``target`` and verify its
        integrity header — behind the fair-share admission gate when a
        tenant is bound (``bind_admission``).

        Admission runs FIRST (ddl_tpu.serve): no ring wait may start
        before the tenant's turn is granted — otherwise a slot could be
        held hostage while the scheduler throttles the holder.
        Non-blocking probes (``timeout_s <= 0``) raise
        :class:`StallTimeoutError` when not grantable, which the
        lookahead deepening treats as "not committed yet".  The
        admission wait SPENDS FROM the same budget the ring acquire
        gets: one acquisition, one ``timeout_s`` — a throttled tenant
        must not silently double the documented stall budget.  A grant
        whose ring acquire then FAILS (stall timeout, revoked target,
        shutdown) is released via ``note_aborted`` — a leaked in-flight
        grant would make every later ``revoke_inflight`` burn its full
        SLO on a phantom window.
        """
        if self._admission is None:
            return self._acquire_observed(target, ahead, timeout_s)
        t_admit = time.monotonic()
        _span_t0 = obs_spans.t0()
        self._admission.admit(timeout_s)
        admit_wait = time.monotonic() - t_admit
        if timeout_s > 0:
            timeout_s = max(0.0, timeout_s - admit_wait)
        try:
            slot = self._acquire_observed(target, ahead, timeout_s)
        except BaseException:
            abort = getattr(self._admission, "note_aborted", None)
            if abort is not None:
                abort()
            raise
        # Admission observability: the span is keyed on the window the
        # grant actually bought (seq known only post-acquire), and the
        # wait lands in the bounded consumer.admission_wait histogram —
        # the first-class home of the p99 the tenancy bench previously
        # computed ad hoc (per-tenant histograms ride
        # ingest.<tenant>.admission_wait in ddl_tpu.serve).
        obs_spans.record(
            "consumer.admission", target + 1, self._last_acquired_seq,
            _span_t0, _span_t0 + admit_wait if _span_t0 else None,
        )
        self.metrics.observe("consumer.admission_wait", admit_wait)
        # The charge-after half of the fair-share gate: the window's
        # actual byte size is only known post-acquire.
        self._admission.note_served(
            int(self.connection.rings[target].slot_payload(slot))
        )
        return slot

    def _acquire_observed(
        self, target: int, ahead: int, timeout_s: float
    ):
        """The acquire choke point's observability shim: stashes the
        logical seq for downstream keying (the enclosing
        ``ddl.window_acquire`` stage's span, staging jobs, yields,
        releases), and feeds the bounded ``consumer.window_latency``
        histogram — head acquires only, so the percentile measures
        "time to obtain the next committed window" and non-blocking
        lookahead probes cannot dilute it."""
        t0 = time.perf_counter() if ahead == 0 and timeout_s > 0 else 0.0
        slot = self._acquire_slot_verified(target, ahead, timeout_s)
        # The logical window number, by the same arithmetic the
        # integrity verify pins (valid with integrity off too: the skew
        # term is only ever advanced by quarantine replays).
        seq = self._expected_seq(target, ahead)
        self._last_acquired_seq = seq
        if t0:
            self.metrics.observe(
                "consumer.window_latency", time.perf_counter() - t0
            )
        return slot

    def _acquire_slot_verified(
        self, target: int, ahead: int, timeout_s: float
    ):
        """The admission-free acquire: next committed slot on
        ``target``, integrity-verified.  A corrupt head slot (``ahead
        == 0``) enters quarantine-and-replay; corruption discovered
        during lookahead deepening (``ahead > 0``) raises
        :class:`_CorruptAhead` — held slots make out-of-FIFO quarantine
        impossible, so the caller stops deepening and the window
        re-verifies when it reaches the head."""
        ring = self.connection.rings[target]
        pool_managed = (
            self._cluster is not None
            or self._pool is not None
            or self._pending_pool is not None
        )
        if not pool_managed:
            slot = (
                ring.acquire_drain_ahead(ahead, timeout_s)
                if ahead
                else ring.acquire_drain(timeout_s)
            )
        else:
            # Cluster-attached acquire (head AND lookahead): sliced so
            # a view change that drops THIS target mid-wait revokes the
            # acquire promptly (the dead host's producer will never
            # commit again; waiting out the full timeout would stall
            # recovery by minutes).  A shut-down ring below a pending
            # view change is the same revocation, not run teardown.
            deadline = time.monotonic() + timeout_s
            while True:
                if self._target_revoked(target):
                    raise _TargetRevoked(target)
                try:
                    remaining = min(
                        0.25, max(0.0, deadline - time.monotonic())
                    )
                    slot = (
                        ring.acquire_drain_ahead(ahead, remaining)
                        if ahead
                        else ring.acquire_drain(remaining)
                    )
                    break
                except StallTimeoutError:
                    if time.monotonic() >= deadline:
                        raise
                except ShutdownRequested:
                    if self._target_revoked(target):
                        raise _TargetRevoked(target)
                    raise
        if self._integrity:
            expect = self._expected_seq(target, ahead)
            err = self._verify_slot(target, slot, expect)
            if err is not None:
                if ahead or timeout_s <= 0:
                    # Deferred, NOT counted yet: held slots forbid
                    # out-of-FIFO quarantine, and a non-blocking
                    # deepening probe (timeout_s == 0) must not run a
                    # replay wait under a zero-second budget — either
                    # way the same corrupt window re-verifies when a
                    # BLOCKING head acquire reaches it, which is where
                    # it is counted once and replayed under the
                    # loader's real timeout.
                    raise _CorruptAhead(err)
                self.metrics.incr("integrity.corrupt_windows")
                # Post-mortem artifact (ddl_tpu.obs): the corrupt
                # window is THE event a chaos row or chip-run anomaly
                # needs explained — dump the flight ring naming the
                # faulted window's trailer identity (no-op disarmed).
                flight_dump(
                    "integrity.corrupt_window",
                    producer_idx=target + 1, seq=expect,
                    metrics=self.metrics, extra={"verify_error": err},
                )
                slot = self._quarantine_and_replay(
                    target, expect, err, timeout_s
                )
        return slot

    def _quarantine_and_replay(
        self, target: int, seq: int, err: str, timeout_s: float
    ) -> int:
        """The corrupt-slot recovery ladder (docs/ROBUSTNESS.md).

        The head slot of ``target`` failed verification as logical
        window ``seq``.  Re-request ``seq`` from the producer (which
        rewinds via the deterministic-replay contract), discard the
        quarantined slot plus any stale in-flight successors, and serve
        the re-committed window — byte-identical, exactly once.  Rungs:

        1. up to ``DDL_TPU_MAX_REPLAYS`` replay attempts per window;
        2. cross-instance exchange active → no local replay is possible
           → :class:`IntegrityError` immediately;
        3. budget exhausted (persistent corruption) → IntegrityError.

        The caller's acquired head slot is owned by this method from
        entry: every discard releases it and acquires the next commit.
        """
        ring = self.connection.rings[target]
        for attempt in range(1, self._max_replays + 1):
            if self._shuffle_fraction > 0.0:
                raise IntegrityError(
                    f"corrupt window {seq} from producer {target + 1} "
                    f"({err}); not replayable: cross-instance exchange "
                    "contributed rows no local rewind can regenerate"
                )
            logger.error(
                "ddl_tpu: corrupt window %d from producer %d (%s) — "
                "quarantined; replay attempt %d/%d",
                seq, target + 1, err, attempt, self._max_replays,
            )
            self.metrics.incr("integrity.replays")
            self.connection.request_replay(target, seq)
            deadline = time.monotonic() + max(timeout_s, 1.0)
            last_request = time.monotonic()
            reattempt = False
            while not reattempt:
                # Discard the head (quarantined or stale) and take the
                # next commit; the producer is re-committing seq, seq+1,
                # ... behind us, so this loop is bounded by the in-flight
                # depth plus one replayed window.
                ring.release(int(ring.stats()["released"]) % ring.nslots)
                self._seq_skew[target] += 1
                while True:
                    now = time.monotonic()
                    if now >= deadline:
                        raise IntegrityError(
                            f"replayed window {seq} from producer "
                            f"{target + 1} never arrived within "
                            f"{timeout_s}s"
                        )
                    if now - last_request >= 2.0:
                        # Re-send periodically: the original request is
                        # LOST if the producer died (or was respawned —
                        # fresh channel) before reading it; requests are
                        # idempotent rewinds, and a respawned replacement
                        # polls its new channel like any incarnation.
                        # Rides the acked seam (request_replay wraps in
                        # an envelope), so a merely-DROPPED wire attempt
                        # is retried by pump below long before this
                        # coarse 2s incarnation-loss backstop fires.
                        self.connection.request_replay(target, seq)
                        last_request = now
                    self.connection.pump_control(now)
                    try:
                        slot = ring.acquire_drain(
                            min(2.0, deadline - now)
                        )
                        break
                    except StallTimeoutError:
                        continue  # wake to re-send, then wait again
                hdr = integrity.read_header(
                    ring.slot_view(slot), ring.slot_payload(slot)
                )
                if not hdr.valid_magic or hdr.seq != seq:
                    continue  # stale in-flight successor: discard too
                err = self._verify_slot(target, slot, seq)
                if err is None:
                    # The replayed commit is served (and later released)
                    # through the normal path — skew already counts
                    # exactly the discarded commits before it.
                    logger.warning(
                        "ddl_tpu: window %d from producer %d recovered "
                        "by replay", seq, target + 1,
                    )
                    return slot
                # Replayed copy is corrupt AGAIN: burn a replay attempt.
                self.metrics.incr("integrity.corrupt_windows")
                reattempt = True
        flight_dump(
            "integrity.replay_exhausted",
            producer_idx=target + 1, seq=seq,
            metrics=self.metrics, extra={"verify_error": err},
        )
        raise IntegrityError(
            f"window {seq} from producer {target + 1} still corrupt "
            f"after {self._max_replays} replay(s): {err}"
        )

    def _acquire_current(self) -> None:
        if self._release_backlog:
            # Batch-path acquire tracks no per-stream hold counter, so a
            # stream's deferred releases must land first — otherwise the
            # drain-head acquire below would re-serve their slots.
            self._flush_release_backlog()
        if self._staged_orphans:
            # The next unserved windows live in staging buffers (an
            # abandoned staged stream released their slots early); the
            # batch path serves host slot views and cannot reach them.
            raise LoaderStateError(
                "an abandoned windows() stream left staged windows in "
                "flight; drain them with a new windows() stream before "
                "batch iteration"
            )
        # The annotation makes window-wait stalls visible on the profiler
        # timeline next to the XLA ops (SURVEY §5.1 TPU-native tracing).
        self._apply_pending_pool()
        self._poll_obs()
        with stage("ddl.window_acquire", self.metrics) as st:
            while True:
                try:
                    slot = self._acquire_verified(
                        self._target, 0, self.timeout_s
                    )
                    break
                except _TargetRevoked:
                    # The target's host left the view mid-acquire:
                    # adopt the published pool and retry on a survivor.
                    self._apply_pending_pool()
                    self._target = self._next_target(
                        self._target, include=True
                    )
            st.key = (self._target + 1, self._last_acquired_seq)
        self._cur_slot = slot
        self._cur_array = self._slot_array(self._target, slot)
        self.metrics.incr("consumer.windows")

    def fast_forward(self, n_windows: int) -> None:
        """Discard ``n_windows`` windows without serving them (resume
        support): producers regenerate their window sequence
        deterministically from their seeds, so skipping the windows the
        pre-checkpoint run consumed puts the pipeline at the exact data
        position where it stopped (one window per epoch — Q7 semantics)."""
        if self._release_backlog:
            self._flush_release_backlog()
        # Resume replay is bookkeeping, not service: the discarded
        # windows are never delivered to the tenant, so they must not
        # pass (or be charged at) the fair-share admission gate — a
        # byte-budgeted tenant would otherwise spend ~history/budget
        # wall time (and its counters) replaying windows it never sees.
        admission, self._admission = self._admission, None
        try:
            self._fast_forward_unadmitted(n_windows)
        finally:
            self._admission = admission

    def _fast_forward_unadmitted(self, n_windows: int) -> None:
        for _ in range(n_windows):
            if self._staged_orphans:
                # Early-released staged window: already off the ring;
                # discarding it is dropping the handle.
                self._staged_orphans.pop(0)
                self._advance_to_next_producer()
                self.metrics.incr("consumer.windows_skipped")
                continue
            self._acquire_current()
            self._release_current()
            self._advance_to_next_producer()
            self.metrics.incr("consumer.windows_skipped")

    def _release_current(self) -> None:
        if self._cur_slot is not None:
            if self._ingestor is not None and self._ingestor._engine is not None:
                # Slot-safety barrier: a staged prefetch may still hold
                # queued jobs whose sources VIEW this window (a mid-epoch
                # break abandons lookahead batches before their copies
                # ran).  Their staging copies must land before the
                # producer may overwrite the slot.  O(1) when all copies
                # already completed — the steady-state case.
                self._ingestor._engine.executor.flush_copies()
            self._ring().release(self._cur_slot)
            self._cur_slot = None
            self._cur_array = None

    # -- shutdown (reference mpi_dataloader.py:229-234, §3.5) --------------

    def shutdown(self) -> None:
        if self._finalized:
            return
        self._finalized = True
        # Deferred stream releases first: their transfers must complete
        # (and their slots return) before the rings go away.
        self._flush_release_backlog()
        self._release_current()
        if self._ingestor is not None:
            # Stop the staging executor BEFORE the rings go away: pending
            # jobs error with ShutdownRequested instead of racing teardown,
            # and completed staging buffers flush back to their pool.
            self._ingestor.close()
        # No window is verified past this point: stop the span-parallel
        # CRC fold's threads (a later loader in this process starts its
        # own at its first large window).
        integrity.close_fold_pool()
        self.connection.shutdown_operation()
        # Final observability drain: PROCESS workers ship a last
        # cumulative ObsReport on their way out — give stragglers a
        # short bounded window before the channels close, exiting
        # early once every producer's final report landed (a run
        # SHORTER than the periodic ship cadence has its whole
        # aggregation riding on exactly this drain, so the gate is
        # "could reports exist at all", not "did one arrive already";
        # a crashed worker never ships and the deadline bounds it).
        if self._obs_reports_possible():
            self.drain_obs_reports(timeout_s=0.5, wait_for_all=True)
        else:
            self._drain_obs_once()
        self.connection.finalize()
        logger.debug("consumer: shutdown complete after epoch %d", self._epoch)

    def __del__(self) -> None:  # pragma: no cover - best effort
        try:
            self.shutdown()
        except ShutdownRequested:
            # Raced a concurrent teardown: the shutdown flag is already
            # set, which is all this finalizer wanted.  Handled BY NAME
            # (DDL007) rather than re-raised — PEP 442 means nothing can
            # propagate out of a finalizer anyway; an accidental broad
            # swallow and a deliberate no-op must not look alike.
            pass
        except Exception:
            # GC-time shutdown may run after interpreter state this
            # loader depends on is already gone; anything else is
            # best-effort by construction.
            pass


def _split_columns(
    batch: np.ndarray, splits: Sequence[int]
) -> Tuple[np.ndarray, ...]:
    """Split a (B, sum(splits)) window slice into column views.

    The analog of ``torch.split(..., dim=1)`` in the reference consumer
    (``mpi_dataloader.py:195-197``) — plain numpy slicing, still zero-copy.
    """
    out: List[np.ndarray] = []
    off = 0
    for w in splits:
        out.append(batch[:, off : off + w])
        off += w
    return tuple(out)
