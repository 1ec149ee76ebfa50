"""Producer runtime: the worker-side window-fill loop.

Parity with reference ``ddl/datapusher.py``: construction performs the
metadata handshake and first fill (``datapusher.py:46-124``), then
``push_data`` runs the hot loop (``datapusher.py:147-170``):
``global_shuffle`` → ``execute_function`` → offer window → wait for it back.

TPU-native differences:

- The window the user fills (``my_ary``) is a private array; a committed
  copy lands in the next free ring slot.  With ``nslots>=2`` the producer
  refills while the consumer drains — the double-buffering the reference
  sketched but never built (reference ``ddl/mpi_dataloader.py:21-28``).
  Producer functions with ``inplace_fill = True`` skip the private array
  and write straight into ring slots (zero-copy fill); functions
  advertising ``supports_inplace_fill`` get the same slot view whenever
  no global shuffle needs a persistent ``my_ary`` and ``DDL_TPU_INPLACE``
  allows (write-once producers — acquire before fill, integrity trailer
  stamped strictly AFTER the fill, so a mid-fill crash can never commit
  a torn slot).
- The callback chain actually runs every callback (SURVEY Q1 fixed), so a
  registered global shuffler really executes.
- Shutdown arrives as :class:`ShutdownRequested` out of any blocked ring
  wait — the analog of the reference's Waitany-vs-Ibarrier race
  (reference ``ddl/connection.py:161-182``).
"""

from __future__ import annotations

import logging
from typing import Any, List, Optional

import numpy as np

from ddl_tpu import integrity
from ddl_tpu.datasetwrapper import DataProducerOnInitReturn
from ddl_tpu.exceptions import DoesNotMatchError, ShutdownRequested
from ddl_tpu.faults import armed_plan, fault_point
from ddl_tpu.obs import aggregate as obs_aggregate
from ddl_tpu.obs import spans as obs_spans
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.transport.connection import NOTHING, ProducerConnection
from ddl_tpu.types import (
    ControlEnvelope,
    MetaData_Consumer_To_Producer,
    MetaData_Producer_To_Consumer,
    ReplayRequest,
    RunMode,
    ShardAdoption,
    Topology,
    normalize_splits,
)
from ddl_tpu.utils import execute_callbacks, for_all_methods, with_logging

logger = logging.getLogger("ddl_tpu")

#: Default ring depth. 2 = double buffering; 1 = reference-style strict
#: alternation (one window per producer, consumer and producer ping-pong).
DEFAULT_NSLOTS = 2


def inplace_enabled(override: bool = None) -> bool:
    """The ``DDL_TPU_INPLACE`` gate (default ON): lets producers that
    advertise ``supports_inplace_fill`` write straight into ring slots.
    ``0`` is the escape hatch back to the private-array + commit-memcpy
    fill (debugging, byte-identity A/B) — it never affects producers
    that FORCE ``inplace_fill = True`` (that is their contract, not a
    preference)."""
    from ddl_tpu.utils import env_flag

    return env_flag("DDL_TPU_INPLACE", override)


def _abort_sentinel() -> str:
    """The consumer's ABORT broadcast string (lazy: env imports this
    module inside its spawn target, so a top-level import would cycle)."""
    from ddl_tpu.env import ABORT

    return ABORT


# DEBUG call tracing on every method, as the reference did
# (``for_all_methods(with_logging)``, reference ``datapusher.py:44``);
# ``_commit_window`` (per-window hot path) stays quiet.
@for_all_methods(
    with_logging,
    exclude=("_commit_window", "_stage_next_fill", "_stamp_and_commit",
             "_encode_and_commit", "_slot_array", "_poll_control"),
)
class DataPusher:
    """One producer worker: handshake, then fill windows until shutdown.

    Parity: reference ``ddl/datapusher.py:45-170``.
    """

    def __init__(
        self,
        connection: ProducerConnection,
        topology: Topology,
        producer_idx: int,
        nslots: int = DEFAULT_NSLOTS,
        metrics: Optional[Metrics] = None,
        shuffler_factory: Any = None,
        rejoin_ring: Any = None,
    ):
        """``rejoin_ring`` (elastic recovery): attach to a predecessor's
        surviving ring (shm name or in-process ring object) instead of
        creating one, and fast-forward the producer function to the data
        position the ring's committed count records."""
        self.connection = connection
        self.topology = topology
        self.producer_idx = producer_idx
        self.nslots = nslots
        self.metrics = metrics or default_metrics()
        self._iteration = 0
        # Last applied cluster view epoch (ShardAdoption fence).
        self._view_epoch = -1
        # Acked control-envelope unwrap (ddl_tpu.transport.envelope):
        # dedup by (incarnation, seq) + command fencing, with an ack
        # back per envelope so the consumer's retry loop terminates.
        from ddl_tpu.transport.envelope import EnvelopeReceiver

        self._envelope_rx = EnvelopeReceiver(producer_idx=producer_idx)
        # Cross-process observability shipping (ddl_tpu.obs): PROCESS
        # workers periodically send cumulative Metrics snapshots (+
        # armed-span deltas) back over this control channel; THREAD
        # producers share the consumer registry and never ship.
        self._obs_ship_every = (
            obs_aggregate.ship_every() if connection.cross_process else 0
        )
        self._obs_report_idx = 0

        # End-to-end window integrity (ddl_tpu.integrity): slots carry a
        # checksummed trailer header past the payload; the flag rides the
        # handshake reply so the consumer always agrees on slot layout.
        self._integrity = integrity.integrity_enabled()

        # -- handshake (reference datapusher.py:46-124) --------------------
        meta: MetaData_Consumer_To_Producer = connection.recv_metadata_as_producer()
        self.batch_size = meta.batch_size
        # The user's producer function is callbacks[0], exactly as in the
        # reference (datapusher.py:64); further callbacks append after it.
        self.callbacks: List[Any] = [meta.data_producer_function]
        # Per-job integrity namespace (ddl_tpu.serve.jobs): trailer
        # seqs are stamped at seq_base + iteration.  Rides the producer
        # function — the wire_dtype handshake pattern — so the base
        # crosses the spawn boundary with the function itself and the
        # consumer reads the identical attribute.
        self.seq_base = int(
            getattr(meta.data_producer_function, "seq_base", 0) or 0
        )

        init_ret = execute_callbacks(
            self.callbacks,
            "on_init",
            producer_idx=producer_idx,
            n_producers=topology.n_producers,
            instance_idx=topology.instance_idx,
            n_instances=topology.n_instances,
            batch_size=meta.batch_size,
        )
        if not isinstance(init_ret, DataProducerOnInitReturn):
            raise DoesNotMatchError(
                init_ret, "on_init must return DataProducerOnInitReturn"
            )
        self.shape = tuple(int(s) for s in init_ret.shape)
        self.dtype = np.dtype(init_ret.dtype)
        self.splits = normalize_splits(init_ret.splits, init_ret.nValues)
        if self.shape[0] != init_ret.nData:
            raise DoesNotMatchError(
                self.shape, f"shape[0] must equal nData={init_ret.nData}"
            )
        self.batches_per_window = init_ret.nData // meta.batch_size
        if self.batches_per_window < 1:
            raise DoesNotMatchError(
                meta.batch_size,
                f"batch_size {meta.batch_size} exceeds window nData "
                f"{init_ret.nData}",
            )
        self.window_nbytes = int(np.prod(self.shape)) * self.dtype.itemsize
        # Wire format (ddl_tpu.wire): the reader's per-capability
        # wire_dtype (env-overridable) selects what BYTES the slot
        # commit carries — raw, or the blockwise bf16/int8 encoding
        # with scales in the integrity trailer extension.  Lossy wire
        # needs the trailer (scales have nowhere else to travel) and a
        # float window; both are validated at handshake, not mid-run.
        from ddl_tpu import wire

        self.wire_dtype = wire.resolve_wire_dtype(
            getattr(meta.data_producer_function, "wire_dtype", "raw")
        )
        if self.wire_dtype != "raw":
            if not self._integrity:
                raise DoesNotMatchError(
                    self.wire_dtype,
                    "lossy wire_dtype needs DDL_TPU_INTEGRITY on (the "
                    "quantization scales travel in the slot trailer "
                    "extension next to the CRC)",
                )
            if not wire.lossy_supported(self.dtype):
                raise DoesNotMatchError(
                    self.dtype.name,
                    f"lossy wire_dtype {self.wire_dtype!r} needs a float "
                    "window dtype (use the lossless codec tier for "
                    "token/image shards)",
                )
        self._enc_nbytes = wire.encoded_nbytes(
            self.shape, self.dtype, self.wire_dtype
        )
        self._scale_nbytes = wire.scale_bytes_for(
            self.shape, self.wire_dtype
        )
        # Fill discipline: ``inplace_fill = True`` on the producer
        # function FORCES slot-view fills (the original contract);
        # ``supports_inplace_fill = True`` advertises write-once
        # capability and lets the pusher decide — in place whenever no
        # global shuffle needs a persistent private array and the
        # ``DDL_TPU_INPLACE`` gate is on.  Resolved AFTER the shuffler
        # below exists, since the shuffler is what forbids it.
        self._forced_inplace = bool(
            getattr(meta.data_producer_function, "inplace_fill", False)
        )
        self._auto_inplace = bool(
            getattr(meta.data_producer_function, "supports_inplace_fill", False)
        )
        self.inplace_fill = self._forced_inplace
        self._fill_slot: Optional[int] = None

        # Global shuffler: registered as an additional callback when the
        # topology and config ask for it (reference datapusher.py:89-108) —
        # and unlike the reference, it will actually run (Q1 fixed).
        self.shuffler = None
        if (
            topology.n_instances > 1
            and meta.global_shuffle_fraction_exchange > 0.0
            and shuffler_factory is not None
        ):
            num_exchange = int(
                init_ret.nData * meta.global_shuffle_fraction_exchange
            )
            if num_exchange > 0:
                if self.inplace_fill:
                    # The exchange would operate on nslots-stale slot
                    # content and its result would then be destroyed by
                    # the contractually required full rewrite — silently
                    # wrong data distribution, so reject the combination.
                    raise DoesNotMatchError(
                        type(meta.data_producer_function).__name__,
                        "global shuffle is incompatible with "
                        "inplace_fill producers (the exchange needs a "
                        "persistent my_ary; use the default copy fill)",
                    )
                self.shuffler = shuffler_factory(
                    topology=topology,
                    producer_idx=producer_idx,
                    num_exchange=num_exchange,
                    exchange_method=meta.exchange_method,
                )
                # Degradation events must land in THIS pipeline's
                # registry (factories stay picklable, so the registry
                # cannot ride through them — it is injected post-hoc).
                if hasattr(self.shuffler, "metrics"):
                    self.shuffler.metrics = self.metrics
                if rejoin_ring is not None:
                    # Rejoining a LIVE exchange needs POSITIVE capability:
                    # a replay-capable shuffler (round re-entry over a
                    # retention fabric — ThreadExchangeShuffler over
                    # Rendezvous/ShmRendezvous advertises it) and a ring
                    # deep enough that the last committed window cannot
                    # share a slot with the predecessor's in-flight
                    # (possibly torn) fill.  Anything else fails HERE, at
                    # handshake — as the pre-replay code did — instead of
                    # timing out at runtime or desyncing the schedule.
                    if not getattr(
                        self.shuffler, "supports_elastic_replay", False
                    ) or not callable(
                        getattr(self.shuffler, "rejoin", None)
                    ):
                        raise DoesNotMatchError(
                            type(self.shuffler).__name__,
                            "elastic respawn with global shuffle needs a "
                            "replay-capable shuffler (consumed-box "
                            "retention + a rejoin(round) re-entry "
                            "method); this one does not advertise "
                            "supports_elastic_replay / rejoin",
                        )
                    # (The matching nslots >= 2 torn-fill guard runs
                    # after ring attach, against the ATTACHED ring's
                    # real geometry — the ctor arg may disagree with
                    # what the predecessor created.)
                # Fail LOUDLY at handshake when the shuffler's fabric
                # declares a span too narrow to reach its exchange
                # partners, instead of every producer stalling against a
                # board its peers can't see (the reference's exchange ran
                # between OS processes via MPI, reference
                # shuffle.py:92-108 — host-side fabrics here have
                # narrower spans and must be matched).  Custom shufflers
                # WITHOUT a span attribute pass through unchecked — the
                # guard only rejects spans it positively knows are too
                # narrow, so pre-existing user fabrics keep working.
                span = getattr(self.shuffler, "span", None)
                if topology.mode is RunMode.MULTIHOST and span in (
                    "thread", "process",
                ):
                    raise DoesNotMatchError(
                        span,
                        "host-side global shuffle cannot span hosts "
                        "(exchange partners are other instances' "
                        "producer processes); use the trainer-side "
                        "device exchange (ddl_tpu.parallel."
                        "DeviceGlobalShuffler over the instance mesh "
                        "axis) for MULTIHOST runs — the producer-side "
                        "DeviceExchangeShuffler resolves its device "
                        "tier off outside THREAD topologies",
                    )
                if connection.cross_process and span == "thread":
                    raise DoesNotMatchError(
                        span,
                        "an in-process Rendezvous cannot reach producers "
                        "in other processes (each process waits on its "
                        "own private board until timeout); pass "
                        "ThreadExchangeShuffler.factory(rendezvous="
                        "ShmRendezvous(session)) with a shared session "
                        "string — DeviceExchangeShuffler.factory "
                        "accepts the same and runs the host exchange "
                        "over it across processes — or use the "
                        "trainer-side device exchange",
                    )
                self.callbacks.append(self.shuffler)

        # Wire-encoded commits need a RAW source array distinct from the
        # slot (the encode reads the float window and writes the int8/
        # bf16 payload — encoding a slot in place would destroy its own
        # input), so the lossy wire keeps the private-array fill: auto
        # inplace is silently skipped (the shuffle precedent), forced
        # inplace is a contract conflict and fails at handshake.
        if self.wire_dtype != "raw" and self._forced_inplace:
            raise DoesNotMatchError(
                type(meta.data_producer_function).__name__,
                "inplace_fill producers cannot use a lossy wire_dtype "
                "(the encode needs the raw window as its source; use "
                "the default copy fill or wire_dtype='raw')",
            )
        # Auto inplace (write-once producers): a shuffler needs my_ary to
        # persist across iterations (the exchange mutates it between
        # fills), so capability-advertising producers silently keep the
        # copying fill when one is active; otherwise they write straight
        # into ring slots unless DDL_TPU_INPLACE=0 opts out.
        if (
            self._auto_inplace
            and not self.inplace_fill
            and self.shuffler is None
            and self.wire_dtype == "raw"
            and inplace_enabled()
        ):
            self.inplace_fill = True
        if not self.inplace_fill:
            # Private window the user fills; commits copy it into ring slots.
            self.my_ary = np.zeros(self.shape, dtype=self.dtype)

        # Integrity slots are one trailer header larger than the payload;
        # geometry (shape/splits/payload) is untouched.  Wire-encoded
        # commits use strictly LESS of the slot (encoded payload +
        # header + scales < raw payload for every supported float
        # dtype), so slots stay raw-sized: a replayed/rejoined producer
        # never depends on the wire setting for its ring geometry.
        slot_bytes = self.window_nbytes + (
            integrity.HEADER_BYTES if self._integrity else 0
        )
        if self.wire_dtype != "raw" and (
            self._enc_nbytes + integrity.HEADER_BYTES + self._scale_nbytes
            > slot_bytes
        ):
            # Degenerate geometries CAN overflow: int8 with 1 value per
            # row pays a 4-byte scale per 1-byte payload (scales are
            # per-row-block), so "encoded < raw" does not hold for
            # every shape — refuse at handshake like every other
            # invalid wire config, never mid-run.
            raise DoesNotMatchError(
                self.shape,
                f"wire_dtype {self.wire_dtype!r} does not shrink this "
                f"window geometry (encoded {self._enc_nbytes} + trailer "
                f"{integrity.HEADER_BYTES + self._scale_nbytes} exceeds "
                f"the {slot_bytes}-byte slot); use wire_dtype='raw' for "
                "windows this narrow",
            )
        if rejoin_ring is not None:
            self.ring = connection.attach_ring(rejoin_ring)
            if self._integrity and self.ring.slot_bytes < slot_bytes:
                # The predecessor created this ring without integrity
                # headroom: the incarnations disagree on DDL_TPU_INTEGRITY
                # (env drift across a respawn) — fail at handshake rather
                # than stamping headers over the next slot's payload.
                raise DoesNotMatchError(
                    self.ring.slot_bytes,
                    "surviving ring has no integrity-header headroom; "
                    "respawned producer must run with the same "
                    "DDL_TPU_INTEGRITY setting as its predecessor",
                )
            if self.shuffler is not None and self.ring.nslots < 2:
                # Checked against the ATTACHED ring's REAL geometry (the
                # ctor arg may disagree with what the predecessor
                # created): with one slot the last committed window
                # shares the slot the predecessor was filling when it
                # died, so the state restore could read a torn fill.
                raise DoesNotMatchError(
                    self.ring.nslots,
                    "elastic respawn with global shuffle needs "
                    "nslots >= 2: with one slot the last committed "
                    "window shares the slot the predecessor was "
                    "filling when it died, so the state restore could "
                    "read a torn fill",
                )
        else:
            self.ring = connection.create_ring(nslots, slot_bytes)
        if self.inplace_fill:
            # Zero-copy fill: the user writes straight into ring slots.
            # (On a fresh ring the first slot is free immediately; on a
            # rejoined ring this waits for a free slot like any fill.)
            self._fill_slot = self.ring.acquire_fill()
            self.my_ary = self._slot_array(self._fill_slot)
        connection.send_metadata(
            MetaData_Producer_To_Consumer(
                producer_idx=producer_idx,
                n_data=init_ret.nData,
                n_values=init_ret.nValues,
                shape=self.shape,
                splits=self.splits,
                batches_per_window=self.batches_per_window,
                dtype=self.dtype.name,
                integrity=self._integrity,
                wire_dtype=self.wire_dtype,
            )
        )

        # First fill (reference datapusher.py:113-119).
        execute_callbacks(self.callbacks, "post_init", my_ary=self.my_ary)

        if rejoin_ring is not None:
            # Replay to the predecessor's data position: the ring's
            # committed count IS the number of windows already published
            # (a death between data-write and commit re-publishes that
            # window — the consumer never saw it).  With integrity
            # headers the LAST COMMITTED SLOT's header is the exact
            # logical position instead: after a quarantine replay the
            # raw committed count includes discarded re-commits, so
            # counting commits would overshoot the data stream.
            committed = int(self.ring.stats()["committed"])
            done = committed
            if self._integrity and committed:
                # Header offset follows the wire format: encoded slots
                # commit the ENCODED payload size, and the encoding is a
                # pure function of (geometry, wire_dtype) the respawn
                # re-derives — env drift across a respawn already fails
                # the integrity-headroom check above.
                hdr = integrity.read_header(
                    self.ring.slot_view((committed - 1) % self.ring.nslots),
                    self._enc_nbytes,
                )
                if hdr.valid_magic:
                    done = hdr.seq + 1
            if done:
                execute_callbacks(
                    self.callbacks, "fast_forward", n=done,
                    my_ary=self.my_ary,
                )
                if self.shuffler is not None:
                    # fast_forward regenerates the LOCAL data stream (and
                    # RNG position), but lanes exchanged IN by peers over
                    # past rounds are not locally recoverable.  The last
                    # committed ring slot holds the predecessor's exact
                    # post-iteration my_ary (copy-fill is guaranteed
                    # here — shuffle + inplace_fill is rejected above,
                    # and slots are only ever overwritten by this
                    # producer), so restore the full state from it.
                    last = (committed - 1) % self.ring.nslots
                    if self.wire_dtype != "raw":
                        # Encoded slot: the predecessor's exact my_ary is
                        # not recoverable (the wire is lossy); restore
                        # the DECODED window — the same values the
                        # consumer served, so the exchange schedule
                        # stays coherent at wire precision.
                        from ddl_tpu import wire

                        view = self.ring.slot_view(last)
                        hdr = integrity.read_header(view, self._enc_nbytes)
                        wire.decode_window(
                            view[: self._enc_nbytes],
                            integrity.read_scales(
                                view, self._enc_nbytes, hdr.scale_bytes
                            ) if hdr.scale_bytes else None,
                            self.shape, self.dtype, self.wire_dtype,
                            out=self.my_ary,
                        )
                    else:
                        np.copyto(self.my_ary, self._slot_array(last))
            if self.shuffler is not None:
                # Re-enter the exchange schedule at the committed round:
                # the permutation is a pure function of (seed, round),
                # the round is in every mailbox key (tag = 2*round), and
                # consumed round mailboxes are RETAINED by the fabric
                # (Rendezvous/ShmRendezvous take keeps a replay copy
                # until the next round retires it) — so replaying the
                # death round's exchange is idempotent whether or not
                # the predecessor completed it.  rejoin() is part of the
                # capability contract checked above — never a private
                # field poke.
                self.shuffler.rejoin(done)
            self._iteration = done
            logger.info(
                "producer %d: rejoined ring at window %d",
                producer_idx, done,
            )

    # -- hot loop (reference datapusher.py:147-170) ------------------------

    def _slot_array(self, slot: int) -> np.ndarray:
        return (
            self.ring.slot_view(slot)[: self.window_nbytes]
            .view(self.dtype)
            .reshape(self.shape)
        )

    def _stamp_and_commit(self, slot: int) -> None:
        """Stamp the integrity trailer (crc + seq + producer) and publish.

        The ``producer.commit`` injection point runs AFTER the header is
        written, against the payload view — flipped bytes therefore
        mismatch the committed CRC exactly the way real shared-memory
        corruption would, and the consumer's drain-time verify catches
        it (tests/test_faults.py).
        """
        view = self.ring.slot_view(slot)
        if self._integrity:
            payload = view[: self.window_nbytes]
            integrity.write_header(
                view,
                self.window_nbytes,
                seq=self.seq_base + self._iteration,
                producer_idx=self.producer_idx,
                crc=integrity.window_crc(payload),
            )
        fault_point(
            "producer.commit",
            producer_idx=self.producer_idx,
            view=view[: self.window_nbytes],
        )
        self.ring.commit(slot, self.window_nbytes)

    def _encode_and_commit(self, slot: int) -> None:
        """Wire-encoded commit (``ddl_tpu.wire``): the slot carries the
        blockwise bf16/int8 payload, the scales travel in the trailer
        extension next to the CRC, and the CRC covers the ENCODED bytes
        + scales — so the consumer's drain-time verify catches wire
        corruption exactly like raw corruption, and quarantine-and-
        replay re-encodes from the deterministic raw stream.  The
        ``wire.encode`` chaos site fires against the encoded payload
        AFTER the header is stamped (the ``producer.commit`` timing),
        so flipped wire bytes mismatch the committed CRC.
        """
        from ddl_tpu import wire

        view = self.ring.slot_view(slot)
        payload, scales = wire.encode_window(self.my_ary, self.wire_dtype)
        enc = self._enc_nbytes
        view[:enc] = payload
        if scales is not None:
            integrity.write_scales(view, enc, scales)
        # ONE fold implementation for both sides of the contract: the
        # drain-time verify recomputes exactly integrity.wire_crc.
        crc = integrity.wire_crc(view, enc, self._scale_nbytes)
        integrity.write_header(
            view, enc,
            seq=self.seq_base + self._iteration,
            producer_idx=self.producer_idx,
            crc=crc,
            wire_code=wire.WIRE_CODES[self.wire_dtype],
            scale_bytes=self._scale_nbytes,
        )
        fault_point(
            "wire.encode",
            producer_idx=self.producer_idx,
            view=view[:enc],
        )
        # Byte accounting lands at the CONSUMER edge's decode (the one
        # registry every mode shares — PROCESS producers' registries
        # never cross the spawn boundary, and THREAD's shared default
        # registry would double-count if both sides incremented).
        self.ring.commit(slot, enc)

    def _commit_window(self) -> None:
        """Publish the filled window (``_stage_next_fill`` follows)."""
        if self.inplace_fill:
            # my_ary IS the slot: publish it, then point my_ary at the
            # next free slot for the coming refill.
            assert self._fill_slot is not None
            self._stamp_and_commit(self._fill_slot)
        elif self.wire_dtype != "raw":
            slot = self.ring.acquire_fill()  # raises ShutdownRequested on stop
            self._encode_and_commit(slot)
        else:
            slot = self.ring.acquire_fill()  # raises ShutdownRequested on stop
            np.copyto(self._slot_array(slot), self.my_ary)
            self._stamp_and_commit(slot)
        self.metrics.incr("producer.windows")
        self.metrics.incr("producer.bytes", self.window_nbytes)

    def _stage_next_fill(self) -> None:
        """Write-once pipeline: point ``my_ary`` at the next free slot
        for the coming refill (blocks on ring backpressure)."""
        if self.inplace_fill:
            self._fill_slot = self.ring.acquire_fill()
            self.my_ary = self._slot_array(self._fill_slot)

    def _poll_control(self) -> None:
        """Drain pending control messages (non-blocking, once per window).

        The channel is idle after the handshake; command messages
        (:class:`ReplayRequest` — quarantined corrupt slot, rewind and
        re-commit; :class:`ShardAdoption` — cluster re-partition) arrive
        mid-run wrapped in :class:`ControlEnvelope` when the sender uses
        the acked seam, bare when legacy/fire-and-forget, plus the
        consumer's ABORT broadcast (treated as shutdown, like the ring
        flag it accompanies).  Envelopes are unwrapped through the
        dedup + fencing receiver and ALWAYS acked — a duplicate or a
        zombie ex-leader's fenced-off command is dropped unapplied, but
        the ack still terminates the sender's retry loop.
        """
        while True:
            msg = self.connection.channel.try_recv()
            if msg is NOTHING:
                return
            if isinstance(msg, ControlEnvelope):
                payload, ack = self._envelope_rx.accept(msg)
                if ack.dup:
                    self.metrics.incr("producer.ctrl_dup_dropped")
                if ack.fence_rejected:
                    self.metrics.incr("producer.ctrl_fence_dropped")
                try:
                    self.connection.channel.send(ack)
                except (OSError, ValueError):
                    # Consumer side gone mid-teardown: the ack is
                    # best-effort (its sender is dead anyway).
                    pass
                if payload is None:
                    continue
                msg = payload  # dispatch the inner command below
            if isinstance(msg, ReplayRequest):
                self._handle_replay(msg.seq)
            elif isinstance(msg, ShardAdoption):
                self._handle_adoption(msg)
            elif isinstance(msg, str) and msg == _abort_sentinel():
                raise ShutdownRequested("consumer abort broadcast")
            else:
                logger.warning(
                    "producer %d: ignoring unexpected control message %r",
                    self.producer_idx, type(msg).__name__,
                )

    def _handle_adoption(self, msg: ShardAdoption) -> None:
        """Apply a cluster view change (``ddl_tpu.cluster``): adopt the
        re-partitioned shard ranges and suspend/resume the exchange.

        Epoch-fenced: a message at or below the last applied view epoch
        is DROPPED — view changes are ordered by construction and a
        slow/duplicated view-N message must never undo view N+1.
        """
        applied = self._view_epoch
        if msg.view_epoch <= applied:
            logger.debug(
                "producer %d: dropping stale adoption (epoch %d <= %d)",
                self.producer_idx, msg.view_epoch, applied,
            )
            return
        self._view_epoch = msg.view_epoch
        logger.warning(
            "producer %d: adopting shard ranges %s at view epoch %d "
            "(peer %d/%d)",
            self.producer_idx, msg.ranges, msg.view_epoch,
            msg.peer_idx, msg.n_peers,
        )
        self.metrics.incr("producer.shard_adoptions")
        if msg.suspend_exchange is not None and self.shuffler is not None:
            # The ladder's shuffle rung: degrade to node-local while the
            # exchange permutation still names a dead host; resume at
            # the rejoin fence.
            if msg.suspend_exchange:
                suspend = getattr(self.shuffler, "suspend_exchange", None)
                if callable(suspend):
                    suspend()
            else:
                resume = getattr(self.shuffler, "resume_exchange", None)
                if callable(resume):
                    resume()
        execute_callbacks(
            self.callbacks,
            "adopt_shards",
            ranges=msg.ranges,
            view_epoch=msg.view_epoch,
            peer_idx=msg.peer_idx,
            n_peers=msg.n_peers,
            my_ary=self.my_ary,
        )

    def _handle_replay(self, seq: int) -> None:
        """Rewind the producer function to logical window ``seq`` and
        resume committing from there — the corrupt-slot re-request path
        (``ddl_tpu.integrity``).  Same deterministic-replay recipe as a
        respawned incarnation: ``on_init`` → ``post_init`` →
        ``fast_forward(seq)``; the consumer discards whatever this
        producer committed past ``seq`` before the request arrived.
        """
        if self.shuffler is not None:
            # Peer-exchanged lanes are not locally regenerable; the
            # consumer never requests replay in this configuration
            # (it raises IntegrityError instead) — refuse rather than
            # silently desync the exchange schedule.
            logger.error(
                "producer %d: ignoring replay request at %d (cross-"
                "instance exchange active; stream is not locally "
                "replayable)", self.producer_idx, seq,
            )
            return
        # The request carries the NAMESPACED seq (the consumer speaks
        # trailer seqs); the producer function's logical position is
        # the local half.
        seq = max(0, int(seq) - self.seq_base)
        logger.warning(
            "producer %d: replaying window stream from %d "
            "(corrupt-slot re-request; was at %d)",
            self.producer_idx, seq, self._iteration,
        )
        self.metrics.incr("producer.replays")
        execute_callbacks(
            self.callbacks,
            "on_init",
            producer_idx=self.producer_idx,
            n_producers=self.topology.n_producers,
            instance_idx=self.topology.instance_idx,
            n_instances=self.topology.n_instances,
            batch_size=self.batch_size,
        )
        execute_callbacks(self.callbacks, "post_init", my_ary=self.my_ary)
        if seq:
            execute_callbacks(
                self.callbacks, "fast_forward", n=seq, my_ary=self.my_ary
            )
        self._iteration = seq

    def push_data(self) -> None:
        execute_callbacks(self.callbacks, "on_push_begin")
        clean = False
        try:
            while True:
                # Order matches the reference loop (datapusher.py:152-166):
                # replay/abort poll, chaos injection point, exchange
                # across instances, then the user's refill/shuffle, then
                # hand the window to the consumer.
                self._poll_control()
                fault_point(
                    "producer.fill",
                    producer_idx=self.producer_idx,
                    should_abort=self.ring.is_shutdown,
                )
                # Window lifecycle span (ddl_tpu.obs): the fill stage —
                # exchange + user refill — keyed on the same
                # (producer_idx, seq) identity the integrity trailer
                # stamps.  One attribute read when tracing is disarmed.
                _span_t0 = obs_spans.t0()
                execute_callbacks(
                    self.callbacks,
                    "global_shuffle",
                    my_ary=self.my_ary,
                    iteration=self._iteration,
                    # Exchange waits must observe shutdown: the partner
                    # instance may already be tearing down and never post
                    # its half (the rendezvous analog of the reference's
                    # Waitany-vs-Ibarrier race, connection.py:161-182).
                    should_abort=self.ring.is_shutdown,
                )
                execute_callbacks(
                    self.callbacks,
                    "execute_function",
                    my_ary=self.my_ary,
                    iteration=self._iteration,
                )
                obs_spans.record(
                    "producer.fill", self.producer_idx, self._iteration,
                    _span_t0,
                )
                if self.inplace_fill and armed_plan() is not None:
                    # Chaos hook for the write-once path: fires with the
                    # slot fully written but NOT yet stamped/committed —
                    # a crash here leaves a torn slot (new payload under
                    # the previous occupant's stale trailer) that must
                    # never be served: stamp-after-fill means it is
                    # never committed, and the drain-time verify is the
                    # backstop if counting ever regressed.  The byte view
                    # costs a ring FFI call per window, so it is built
                    # only behind the armed check (the disarmed push loop
                    # stays zero-cost, faults.py's contract).
                    fault_point(
                        "pusher.inplace_fill",
                        producer_idx=self.producer_idx,
                        view=self.ring.slot_view(self._fill_slot)[
                            : self.window_nbytes
                        ],
                        should_abort=self.ring.is_shutdown,
                    )
                _span_t0 = obs_spans.t0()
                self._commit_window()
                # The commit span covers acquire_fill's free-slot wait
                # too — producer-side backpressure is exactly what a
                # trace of a slow consumer should show.  The window IS
                # published by now: a shutdown landing in the wait for
                # the next fill slot (the consumer drained its last
                # window and left) must not lose its span.
                try:
                    self._stage_next_fill()
                finally:
                    obs_spans.record(
                        "producer.commit", self.producer_idx,
                        self._iteration, _span_t0,
                    )
                execute_callbacks(
                    self.callbacks, "on_shuffle_end", iteration=self._iteration
                )
                self._iteration += 1
                self._maybe_ship_obs()
        except ShutdownRequested:
            clean = True
            logger.debug(
                "producer %d: shutdown after %d windows",
                self.producer_idx,
                self._iteration,
            )
        finally:
            execute_callbacks(self.callbacks, "on_push_end")
            self._finalize(clean=clean)

    def _maybe_ship_obs(self, final: bool = False) -> None:
        """Ship one cross-process ObsReport (ddl_tpu.obs aggregation)
        when due: every ``_obs_ship_every`` windows, plus a ``final``
        ship at shutdown so short runs still aggregate.  PROCESS mode
        only (``_obs_ship_every`` is 0 for THREAD producers, whose
        registry IS the consumer's).  A broken channel (consumer gone
        first during teardown) drops the report — observability must
        never escalate a clean shutdown."""
        every = self._obs_ship_every
        if every <= 0:
            return
        if not final and self._iteration % every:
            return
        self._obs_report_idx += 1
        report = obs_aggregate.build_report(
            self.producer_idx - 1,  # consumer-side 0-based ring index
            self._obs_report_idx,
            self.metrics,
            view_epoch=self._view_epoch,
        )
        try:
            self.connection.channel.send(report)
        except (OSError, ValueError) as e:
            logger.debug(
                "producer %d: obs report dropped (%s)",
                self.producer_idx, e,
            )

    def _finalize(self, clean: bool = True) -> None:
        if clean:
            # Final observability ship BEFORE the channel closes: the
            # consumer's shutdown drain is what closes the PROCESS-mode
            # blind spot for runs shorter than the periodic cadence.
            self._maybe_ship_obs(final=True)
        # A CRASHING producer must leave the shm ring linked: elastic
        # recovery (WorkerSet.respawn) attaches a replacement to it by
        # name.  Only a clean shutdown removes the name; the consumer's
        # finalize is the backstop for crashed-and-never-respawned rings.
        self.connection.finalize(unlink=clean)
