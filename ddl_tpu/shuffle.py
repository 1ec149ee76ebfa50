"""Global shuffle: cross-instance sample exchange.

Parity with reference ``ddl/shuffle.py``: between window refills, the k-th
producer of every instance exchanges a slice of its samples with partner
instances chosen by a *shared* random permutation — every peer derives the
same permutation independently from a common seed (reference
``shuffle.py:28-30``), so no coordination round is needed.  The permutation
must have no self-sends and no 2-cycles (reference ``shuffle.py:52-72``),
except n=2 where the swap is the only option (reference ``shuffle.py:44-48``).

Four transports implement the exchange, by span:

- :class:`Rendezvous` (span ``"thread"``) — in-process board for
  THREAD-mode simulated multi-instance topologies and unit tests.
- :class:`ShmRendezvous` (span ``"process"``) — /dev/shm mailbox files
  with atomic rename, for PROCESS-mode producers in different OS
  processes on ONE host (the reference's exchange ran between producer
  *processes*, reference ``shuffle.py:92-108`` over ``comm_nth_pusher``).
- :class:`DeviceExchangeFabric` (span ``"device"``) — the producer-side
  device tier (:class:`DeviceExchangeShuffler`): lanes land once on the
  ring devices and the permutation exchange itself rides ICI as a
  Pallas remote-DMA ring or an XLA ``ppermute``
  (``ddl_tpu.ops.device_shuffle``), byte-identical to the host paths
  and latching back to them on any device failure.
- ``ddl_tpu.parallel.collectives`` (span ``"global"``) — the
  trainer-side window hook: ``ppermute`` / ``all_to_all`` over the
  instance mesh axis riding ICI/DCN, replacing the reference's
  ``Sendrecv_replace`` (``shuffle.py:92-108``).  The ONLY host-spanning
  option: host-side rendezvous cannot cross hosts, and ``DataPusher``
  rejects that combination at handshake rather than stalling.

Unlike the reference — where the registered shuffler was unreachable dead
code (SURVEY Q1) and the alternative strategy lived in a commented-out
string (Q8) — both strategies here are real, dispatched, and tested.
"""

from __future__ import annotations

import logging
import os
import re
import threading

from ddl_tpu.concurrency import named_condition, named_lock
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ddl_tpu.exceptions import DDLError, InjectedFault, ShutdownRequested
from ddl_tpu.faults import fault_point
from ddl_tpu.observability import metrics as default_metrics
from ddl_tpu.types import Topology

logger = logging.getLogger("ddl_tpu")

#: Permutation search bound (reference ``shuffle.py:74-79`` used 1000 and
#: SystemExit; we raise a typed error instead).
_MAX_TRIES = 1000

#: Valid exchange strategies (reference anticipated plumbing for more,
#: ``datapusher.py:96-106``).
EXCHANGE_METHODS = ("sendrecv_replace", "all_to_all")


def exchange_permutation(n: int, seed: int, round_: int) -> np.ndarray:
    """The shared partner permutation for one exchange round.

    Every same-index producer across instances calls this with identical
    arguments and gets the identical permutation — the decentralised
    agreement trick of reference ``shuffle.py:28-48``.

    Properties (validated): ``p[i] != i`` (no self-sends) and, for n > 2,
    ``p[p[i]] != i`` (no 2-cycles — a 2-cycle would swap the same rows
    straight back on the reverse lane).  n == 2 returns the swap; n == 1
    the identity (no exchange possible).
    """
    if n <= 1:
        return np.arange(n)
    if n == 2:
        return np.array([1, 0])
    rng = np.random.default_rng([seed & 0x7FFFFFFF, round_ & 0x7FFFFFFF])
    for _ in range(_MAX_TRIES):
        p = rng.permutation(n)
        if np.any(p == np.arange(n)):
            continue
        if np.any(p[p] == np.arange(n)):
            continue
        return p
    raise DDLError(
        f"no valid exchange permutation found for n={n} after {_MAX_TRIES} tries"
    )


def inverse_permutation(p: np.ndarray) -> np.ndarray:
    inv = np.empty_like(p)
    inv[p] = np.arange(len(p))
    return inv


def exchange_slices(num_exchange: int) -> Tuple[slice, slice]:
    """The two row lanes of one exchange round.

    Lane A (rows ``[0, half)``) travels *forward* along the permutation;
    lane B (rows ``[half, 2*half)``) travels *backward* — the reference's
    two ``Sendrecv_replace`` calls with swapped dest/source
    (``shuffle.py:95-108``).
    """
    half = num_exchange // 2
    return slice(0, half), slice(half, 2 * half)


class Rendezvous:
    """In-process exchange fabric: one board per producer-index, shared by
    all simulated instances.  Thread-safe; used by ThreadExchangeShuffler.
    Public: pass a fresh instance per run to
    ``ThreadExchangeShuffler.factory(rendezvous=...)`` when wiring
    multiple instances in one process (examples/global_shuffle.py)."""

    #: Reach of this fabric: same-process threads only.  ``DataPusher``
    #: rejects a "thread" rendezvous behind a cross-process connection —
    #: each spawned worker would wait on its own private board forever.
    span = "thread"

    def __init__(self) -> None:
        self._lock = named_condition("shuffle.exchange.cond")
        self._boxes: Dict[Tuple[int, int, int], np.ndarray] = {}
        self._done: Dict[Tuple[int, int, int], np.ndarray] = {}

    def put(self, key: Tuple[int, int, int], rows: np.ndarray) -> None:
        with self._lock:
            self._boxes[key] = rows
            self._lock.notify_all()

    def take(self, key: Tuple[int, int, int], timeout_s: float = 60.0,
             should_abort: Optional[Callable[[], bool]] = None) -> np.ndarray:
        """Blocking take, interruptible: a peer whose run is shutting down
        may never post its half of the exchange, so the wait polls
        ``should_abort`` (e.g. the ring's shutdown flag) and raises
        :class:`ShutdownRequested` instead of stranding the producer for
        the full timeout (the §3.5 any-time-cancellability property the
        ring waits already have).

        Consumed boxes are RETAINED (moved to a done-set) until
        :meth:`retire`: a respawned producer replaying its crashed
        predecessor's round takes the same key again and must see the
        same rows (elastic × shuffle — the exchange becomes idempotent
        per (key, round)).  Bounded: the shuffler retires round r-1's
        keys when round r starts.
        """
        deadline = time.monotonic() + timeout_s
        with self._lock:
            while key not in self._boxes:
                if key in self._done:  # replayed take (respawned producer)
                    return self._done[key]
                if should_abort is not None and should_abort():
                    raise ShutdownRequested()
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise DDLError(
                        f"exchange rendezvous timed out waiting for {key}"
                    )
                self._lock.wait(timeout=min(0.1, remaining))
            rows = self._boxes.pop(key)
            self._done[key] = rows
            return rows

    def discard(self, key: Tuple[int, int, int]) -> None:
        """Best-effort removal of a posted box (abort-path cleanup)."""
        with self._lock:
            self._boxes.pop(key, None)

    def retire(self, key: Tuple[int, int, int]) -> None:
        """Drop a consumed box from the done-set (the round can no longer
        be replayed once its successor round has begun).  Also drops a
        LIVE box under the same key: at retire time the reader has long
        consumed the original, so a live box can only be a respawned
        partner's replayed re-put (which nobody will ever take — tags
        are monotonic) and would otherwise leak."""
        with self._lock:
            self._done.pop(key, None)
            self._boxes.pop(key, None)


_default_rendezvous = Rendezvous()


#: Minimum age before a crashed run's rendezvous dir is fair game for
#: the sweep below.  Age alone is NOT sufficient to sweep — see the
#: pid-liveness check in :func:`_sweep_stale_sessions`.
STALE_SESSION_S = 3600.0

#: Directory-name prefix for every ShmRendezvous session dir — shared by
#: the minting side (:attr:`ShmRendezvous._dir`) and the sweep's matcher
#: so a rename cannot silently turn the sweep into a no-op.
_RDV_PREFIX = "ddl-rdv-"

#: Session names minted by :func:`make_session`: ``{prefix}-{pid}-{hex12}``.
#: The embedded pid is the sweep's liveness signal.
_SESSION_RE = re.compile(
    rf"^{re.escape(_RDV_PREFIX)}.+-(\d+)-[0-9a-f]{{12}}$"
)


def _sweep_stale_sessions(root: str) -> None:
    """Best-effort removal of abandoned ``ddl-rdv-*`` session dirs.

    /dev/shm is RAM-backed: a crashed or killed run whose ``cleanup()``
    never ran would otherwise leak its mailboxes until reboot,
    accumulating on long-lived hosts (ADVICE r4).  A dir is swept only
    when ALL of:

    - its name matches :func:`make_session`'s shape (hand-named sessions
      are the caller's to clean — we cannot infer their liveness);
    - the minting process is DEAD (``kill(pid, 0)`` → ESRCH).  Mtime
      alone would misfire on a healthy run whose exchange cadence is
      slower than the age cutoff, and producers of a live run are
      children of the minting process, so a dead minter means a dead
      run (pid reuse only ever delays the sweep — conservative);
    - it is older than :data:`STALE_SESSION_S`, so a session whose
      minter handed off and exited immediately is still grace-perioded.

    Runs once per (process, root) from the first mailbox creation.
    """
    import shutil

    cutoff = time.time() - STALE_SESSION_S
    try:
        entries = list(os.scandir(root))
    except OSError:
        return
    for ent in entries:
        m = _SESSION_RE.match(ent.name)
        if not m:
            continue
        try:
            if not ent.is_dir(follow_symlinks=False):
                continue
            if ent.stat(follow_symlinks=False).st_mtime >= cutoff:
                continue
            os.kill(int(m.group(1)), 0)  # raises if the minter is gone
        except ProcessLookupError:
            shutil.rmtree(ent.path, ignore_errors=True)
        except OSError:
            continue


#: Roots already swept by this process (sweep once per process+root).
_swept_roots: set = set()
_sweep_lock = named_lock("shuffle.sweep")


def make_session(prefix: str = "ddl") -> str:
    """A rendezvous session name unique enough to survive crashed prior
    runs (stale mailbox files from an old run with the same session would
    be popped as this run's round 0).  The embedded pid doubles as the
    liveness signal for :func:`_sweep_stale_sessions`."""
    return f"{prefix}-{os.getpid()}-{uuid.uuid4().hex[:12]}"


class ShmRendezvous:
    """Cross-process exchange fabric: mailbox files on /dev/shm (tmpfs).

    The PROCESS-mode realisation of the reference's cross-process producer
    exchange (reference ``shuffle.py:92-108`` rode MPI ``Sendrecv_replace``
    between pusher processes).  Every producer process of every instance
    on ONE host constructs ``ShmRendezvous(session)`` with the same
    session string (the object is picklable — it carries only the string
    — so the normal path is passing one factory through
    ``distributed_dataloader``/``DataPusher`` spawn arguments).

    Correctness needs no shared-memory ordering assumptions: ``put``
    writes the payload to a temp file and atomically ``os.rename``s it to
    the key's mailbox name; ``take`` polls for the name, reads, unlinks.
    File-system syscalls give the happens-before edge, on any ISA (unlike
    :class:`PyShmRing <ddl_tpu.transport.shm_ring.PyShmRing>`'s TSO gate).
    Each key has exactly one writer and one reader by permutation
    construction (no self-sends), so no further locking is needed.

    NOT host-spanning: /dev/shm is per-host.  MULTIHOST topologies must
    use the device exchange (``ddl_tpu.parallel.DeviceGlobalShuffler``);
    ``DataPusher`` enforces this at handshake.
    """

    span = "process"

    def __init__(self, session: str, root: str = "/dev/shm") -> None:
        self.session = session
        self.root = root
        # Directory creation is LAZY (first put): constructing the object
        # must be side-effect free so a handshake-time span rejection does
        # not strand an empty session directory per failed launch.

    @property
    def _dir(self) -> str:
        return os.path.join(self.root, f"{_RDV_PREFIX}{self.session}")

    def _path(self, key: Tuple[int, int, int]) -> str:
        return os.path.join(
            self._dir, f"p{key[0]}-t{key[1]}-d{key[2]}.npy"
        )

    def put(self, key: Tuple[int, int, int], rows: np.ndarray) -> None:
        # First mailbox creation in this process for this root also
        # reclaims sessions abandoned by crashed prior runs — hung off
        # the rendezvous (which knows its root) so non-default roots are
        # swept too, not just /dev/shm.
        with _sweep_lock:
            if self.root not in _swept_roots:
                _swept_roots.add(self.root)
                _sweep_stale_sessions(self.root)
        os.makedirs(self._dir, exist_ok=True)
        path = self._path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            np.save(f, rows)
        os.rename(tmp, path)  # atomic publish

    def take(self, key: Tuple[int, int, int], timeout_s: float = 60.0,
             should_abort: Optional[Callable[[], bool]] = None) -> np.ndarray:
        """Blocking take with the same abort semantics as
        :meth:`Rendezvous.take` (a shutting-down peer may never post).

        Consumed mailboxes are RETAINED as ``<name>.done`` (atomic
        rename) until :meth:`retire` — a respawned producer replaying
        its crashed predecessor's round re-takes the same key and must
        see the same rows (see :meth:`Rendezvous.take`)."""
        path = self._path(key)
        done = f"{path}.done"
        # Replay probe ONCE, before the wait loop: a retained copy can
        # only exist before this take starts (each key has a single
        # reader lineage — the respawn replacing a dead predecessor),
        # so re-probing per spin would just double the poll syscalls.
        try:
            with open(done, "rb") as f:
                return np.load(f)
        except FileNotFoundError:
            pass
        deadline = time.monotonic() + timeout_s
        sleep_s = 0.0002
        while True:
            if should_abort is not None and should_abort():
                raise ShutdownRequested()
            try:
                with open(path, "rb") as f:
                    rows = np.load(f)
                os.replace(path, done)  # retained for replay, not unlinked
                return rows
            except FileNotFoundError:
                pass
            if time.monotonic() > deadline:
                raise DDLError(
                    f"exchange rendezvous timed out waiting for {key} "
                    f"(session {self.session!r})"
                )
            time.sleep(sleep_s)
            sleep_s = min(sleep_s * 2, 0.05)

    def discard(self, key: Tuple[int, int, int]) -> None:
        try:
            os.unlink(self._path(key))
        except OSError:
            pass

    def retire(self, key: Tuple[int, int, int]) -> None:
        """Drop the retained ``.done`` copy (replay window closed) and any
        live box under the same key — at retire time a live box can only
        be a respawned partner's replayed re-put, never taken (tags are
        monotonic), which would otherwise leak until ``cleanup()``."""
        for victim in (f"{self._path(key)}.done", self._path(key)):
            try:
                os.unlink(victim)
            except OSError:
                pass

    def cleanup(self) -> None:
        """Remove the whole session directory (post-run, best effort)."""
        import shutil

        shutil.rmtree(self._dir, ignore_errors=True)


class ThreadExchangeShuffler:
    """Producer callback performing the cross-instance exchange in-process.

    Registered by ``DataPusher`` when ``n_instances > 1`` and the consumer
    requested a nonzero exchange fraction (reference ``datapusher.py:89-108``)
    — and, with the fixed dispatcher, it actually runs each iteration.
    """

    #: Consecutive peer losses tolerated (each degrading one round to a
    #: node-local shuffle) before the exchange is disabled for the rest
    #: of the run — the documented degradation ladder's terminal rung
    #: for shuffle (docs/ROBUSTNESS.md).
    DEFAULT_MAX_PEER_LOSSES = 2

    def __init__(
        self,
        topology: Topology,
        producer_idx: int,
        num_exchange: int,
        exchange_method: str = "sendrecv_replace",
        rendezvous: Any = None,  # Rendezvous | ShmRendezvous (put/take/discard)
        seed: int = 0,
        exchange_timeout_s: float = 60.0,
        degrade_on_peer_loss: bool = True,
        max_peer_losses: Optional[int] = None,
        wire_dtype: Optional[str] = None,
        codec: Optional[str] = None,
        codec_level: int = 3,
    ):
        if exchange_method not in EXCHANGE_METHODS:
            raise NotImplementedError(
                f"exchange_method {exchange_method!r}; valid: {EXCHANGE_METHODS}"
            )
        # Exchange wire format (ddl_tpu.wire): the lanes travel the
        # rendezvous fabric (thread board / shm mailboxes — the DCN
        # analog in PROCESS topologies) as self-describing envelopes —
        # blockwise bf16/int8 and/or codec-compressed — instead of raw
        # fp32 rows.  Self-describing matters: the DECODER needs no
        # out-of-band agreement, so a peer that latched the raw
        # fallback still interoperates.  Defaults resolve from the
        # DDL_TPU_WIRE_DTYPE / DDL_TPU_WIRE_CODEC env (the same knobs
        # the slot wire honors); raw + no codec keeps the pre-wire
        # byte-for-byte puts.
        from ddl_tpu import wire as _wire

        self.wire_dtype = _wire.resolve_wire_dtype(wire_dtype)
        self.codec = _wire.resolve_wire_codec(codec)
        self.codec_level = int(codec_level)
        # Per-shuffler raw fallback latch: a persistent decode failure
        # (DECODE_FAIL budget exhausted, foreign-codec peer) drops THIS
        # producer's outgoing encoding to raw for the rest of the run
        # (wire.fallbacks) — incoming envelopes still decode fine.
        self._wire_raw = False
        self.topology = topology
        self.producer_idx = producer_idx
        self.num_exchange = num_exchange
        self.exchange_method = exchange_method
        self.seed = seed
        self.exchange_timeout_s = exchange_timeout_s
        #: ``True`` (default): a lost exchange partner degrades the round
        #: to a node-local shuffle with a loud warning + metric instead
        #: of stalling the pipeline until timeout-death.  ``False``
        #: restores raise-on-loss for callers that prefer to crash.
        self.degrade_on_peer_loss = degrade_on_peer_loss
        self.max_peer_losses = (
            self.DEFAULT_MAX_PEER_LOSSES
            if max_peer_losses is None
            else max_peer_losses
        )
        self.metrics = default_metrics()
        self._peer_losses = 0  # consecutive; reset by a healthy round
        self._degraded = False  # terminal: exchange disabled for the run
        # Reversible degrade (cross-host elastic ladder): while True,
        # every round shuffles node-locally — the exchange permutation
        # still names a departed host and would stall each round until
        # timeout.  Unlike _degraded this rung EXITS: resume_exchange()
        # at the rejoin fence (ddl_tpu.cluster.elastic).
        self._suspended = False
        self._rdv = rendezvous or _default_rendezvous
        self._round = 0
        # Outgoing keys of the last two rounds: swept when their replay
        # window closes (see global_shuffle) so a respawned producer's
        # re-put of an already-consumed box cannot leak past two rounds.
        self._sent: List[Tuple[int, Tuple[int, int, int]]] = []

    @property
    def span(self) -> str:
        """Reach of the underlying rendezvous fabric ("thread"/"process"/
        "global") — validated against the topology at the pusher
        handshake."""
        return getattr(self._rdv, "span", "thread")

    @property
    def supports_elastic_replay(self) -> bool:
        """True when the fabric retains consumed boxes for replay
        (``retire`` is the capability marker): the pusher allows a
        respawned producer to rejoin the exchange schedule only behind
        this — a fabric without retention would strand the replayed
        take until timeout (see DataPusher's rejoin handshake)."""
        return hasattr(self._rdv, "retire")

    @property
    def exchange_round(self) -> int:
        """Completed exchange rounds — the public counter checkpoints
        read (``LoaderCheckpoint.capture``)."""
        return self._round

    @property
    def exchange_suspended(self) -> bool:
        return self._suspended

    def suspend_exchange(self) -> None:
        """Cross-host ladder rung: degrade every round to the seeded
        node-local shuffle until :meth:`resume_exchange` (a cluster view
        change removed an exchange peer's host; docs/ROBUSTNESS.md).
        Idempotent; the round counter keeps advancing so checkpoints
        and the eventual resume stay schedule-coherent."""
        if not self._suspended:
            self._suspended = True
            self.metrics.incr("shuffle.suspensions")
            logger.warning(
                "global shuffle: exchange SUSPENDED (cluster view "
                "change) — shuffling node-locally until rejoin"
            )

    def resume_exchange(self) -> None:
        """Exit the suspension rung (host rejoined at a new epoch
        fence).  The consecutive-loss ladder restarts clean — losses
        counted against the pre-suspension view prove nothing about the
        rejoined one."""
        if self._suspended:
            self._suspended = False
            self._peer_losses = 0
            self.metrics.incr("shuffle.resumes")
            logger.warning(
                "global shuffle: exchange RESUMED at round %d", self._round
            )

    def rejoin(self, round_: int) -> None:
        """Re-enter the exchange schedule at ``round_`` (elastic rejoin:
        the ring-committed window count; checkpoint resume passes the
        restored round).  Part of the ``supports_elastic_replay``
        contract — the pusher and ``LoaderCheckpoint.apply`` call THIS,
        never a private round field, so a conforming custom shuffler
        implements its own round re-entry here."""
        self._round = int(round_)

    def _wire_active(self, rows: np.ndarray) -> Tuple[str, Optional[str]]:
        """The (wire_dtype, codec) this put actually uses: the raw
        latch wins, lossy needs float rows (token/int lanes keep raw —
        the codec still applies), raw+None is the pre-wire fast path."""
        if self._wire_raw:
            return "raw", None
        from ddl_tpu import wire as _wire

        wd = self.wire_dtype
        if wd != "raw" and not _wire.lossy_supported(rows.dtype):
            wd = "raw"
        return wd, self.codec

    def _encode_lane(self, rows: np.ndarray) -> np.ndarray:
        wd, codec = self._wire_active(rows)
        if wd == "raw" and codec is None:
            return rows.copy()  # pre-wire behavior, byte-for-byte
        from ddl_tpu import wire as _wire

        return _wire.pack_rows(
            rows, wd, codec=codec, level=self.codec_level,
            metrics=self.metrics,
        )

    def _decode_lane(self, rows: np.ndarray) -> np.ndarray:
        """Decode a taken lane: raw arrays pass through (a peer on the
        raw fallback — or a pre-wire peer — interoperates), envelopes
        unpack with ONE bounded retry; a persistent decode failure
        latches this producer's outgoing encoding to raw
        (``wire.fallbacks``) and raises — the round then degrades to
        the node-local shuffle via the existing peer-loss rung."""
        from ddl_tpu import wire as _wire
        from ddl_tpu.exceptions import DecodeError

        if not (
            rows.ndim == 1
            and rows.dtype == np.uint8
            and rows.nbytes >= 4
            and int.from_bytes(rows[:4].tobytes(), "little")
            == _wire._PACK_MAGIC
        ):
            return rows  # raw lane
        for attempt in (1, 2):
            try:
                return _wire.unpack_rows(rows, metrics=self.metrics)
            except DecodeError:
                self.metrics.incr("wire.decode_fails")
                if attempt == 2:
                    if not self._wire_raw:
                        self._wire_raw = True
                        self.metrics.incr("wire.fallbacks")
                        logger.error(
                            "global shuffle: exchange wire decode failed "
                            "twice — this producer sends RAW lanes for "
                            "the rest of the run"
                        )
                    raise

    def _local_shuffle(self, my_ary: np.ndarray) -> None:
        """Node-local fallback: a deterministic in-place row permutation
        seeded by (seed, producer, round) — preserves this producer's row
        multiset exactly (no loss, no duplication) while the exchange
        fabric is unavailable."""
        rng = np.random.default_rng(
            [self.seed & 0x7FFFFFFF, self.producer_idx, self._round]
        )
        rng.shuffle(my_ary)

    def _degrade_round(self, my_ary: np.ndarray, why: Exception) -> None:
        """Degradation ladder, shuffle rung: count the loss, shuffle
        locally, and after ``max_peer_losses`` consecutive losses disable
        the exchange for the rest of the run (stalling every remaining
        round against a dead peer would serve nothing)."""
        self._peer_losses += 1
        self.metrics.incr("shuffle.degraded")
        logger.error(
            "global shuffle: exchange peer lost in round %d (%s) — "
            "degrading to node-local shuffle (loss %d/%d)",
            self._round, why, self._peer_losses, self.max_peer_losses,
        )
        if self._peer_losses >= self.max_peer_losses and not self._degraded:
            self._degraded = True
            logger.error(
                "global shuffle: %d consecutive peer losses — exchange "
                "DISABLED for the rest of the run; data mixing is now "
                "node-local only", self._peer_losses,
            )
        self._local_shuffle(my_ary)

    def global_shuffle(self, my_ary: np.ndarray, should_abort: Any = None,
                       **kwargs: Any) -> None:
        n = self.topology.n_instances
        me = self.topology.instance_idx
        if n <= 1 or self.num_exchange < 2:
            return
        if self._degraded or self._suspended:
            # Terminal rung (repeated peer loss) or the reversible
            # cluster-suspension rung: keep mixing locally, keep the
            # round counter advancing (checkpoints and the eventual
            # resume stay schedule-coherent).
            if self._suspended:
                self.metrics.incr("shuffle.suspended_rounds")
            self._local_shuffle(my_ary)
            self._round += 1
            return
        p = exchange_permutation(n, self.seed + self.producer_idx, self._round)
        pinv = inverse_permutation(p)
        lane_a, lane_b = exchange_slices(self.num_exchange)
        tag = self._round * 2
        # Round r-1's replay window closes now: retire the retained
        # copies of the boxes this producer consumed last round (fabrics
        # without retention, e.g. custom user fabrics, are skipped).
        retire = getattr(self._rdv, "retire", None)
        if retire is not None and self._round > 0:
            retire((self.producer_idx, tag - 2, me))
            retire((self.producer_idx, tag - 1, me))
        # Sweep OUR outgoing boxes whose replay window has closed: in the
        # normal case the partner consumed them (no-op), but a respawned
        # producer's re-put of a box its partner had already taken AND
        # retired would otherwise linger forever (the partner retires
        # each incoming key exactly once).  ONLY safe for n == 2: there
        # the partner is the same every round, so my reaching round r
        # proves it completed round r-1 and consumed my r-2 boxes.  With
        # n > 2 cross-instance round skew is unbounded (peers only
        # synchronise with their ROUND partners) and the sweep could
        # discard a lagging partner's still-unconsumed box, stranding it
        # until timeout — there the re-put residual (<= 2 boxes per
        # respawn) is left for cleanup()/the stale-session sweep.
        if self._sent and n == 2:
            live = []
            for r, key in self._sent:
                if r <= self._round - 2:
                    self._rdv.discard(key)
                else:
                    live.append((r, key))
            self._sent = live
        # Lane A forward: i -> p[i]; lane B backward: i -> pinv[i].
        for lane, dest, src, t in (
            (lane_a, int(p[me]), int(pinv[me]), tag),
            (lane_b, int(pinv[me]), int(p[me]), tag + 1),
        ):
            put_key = (self.producer_idx, t, dest)
            self._rdv.put(put_key, self._encode_lane(my_ary[lane]))
            if n == 2:  # the sweep only runs (and is only safe) at n == 2
                self._sent.append((self._round, put_key))
            try:
                fault_point(
                    "shuffle.exchange", producer_idx=self.producer_idx
                )
                my_ary[lane] = self._decode_lane(
                    self._rdv.take(
                        (self.producer_idx, t, me),
                        timeout_s=self.exchange_timeout_s,
                        should_abort=should_abort,
                    )
                )
            except ShutdownRequested:
                # Clean teardown: retract our half so a later run on the
                # same rendezvous cannot pop this round's stale rows as
                # its own round 0.  (A producer that CRASHES mid-exchange
                # can still leave a box behind — pass a fresh Rendezvous
                # per run where that matters rather than the module
                # default.)
                self._rdv.discard(put_key)
                raise
            except DDLError as e:
                # The partner never showed (dead peer / injected loss):
                # retract our half, then degrade this round to a
                # node-local shuffle instead of stalling the pipeline —
                # unless the caller opted back into raise-on-loss.
                self._rdv.discard(put_key)
                if not self.degrade_on_peer_loss:
                    raise
                self._degrade_round(my_ary, e)
                self._round += 1
                return
        self._peer_losses = 0  # a healthy round resets the ladder
        self._round += 1

    # Factory signature expected by DataPusher's shuffler_factory hook.
    @classmethod
    def factory(
        cls,
        rendezvous: Any = None,
        seed: int = 0,
        exchange_timeout_s: float = 60.0,
        degrade_on_peer_loss: bool = True,
        max_peer_losses: Optional[int] = None,
        wire_dtype: Optional[str] = None,
        codec: Optional[str] = None,
        codec_level: int = 3,
    ):
        return ExchangeShufflerFactory(
            rendezvous=rendezvous,
            seed=seed,
            exchange_timeout_s=exchange_timeout_s,
            degrade_on_peer_loss=degrade_on_peer_loss,
            max_peer_losses=max_peer_losses,
            wire_dtype=wire_dtype,
            codec=codec,
            codec_level=codec_level,
        )


class ExchangeShufflerFactory:
    """Picklable shuffler factory.

    PROCESS mode ships the factory to spawned producer workers by pickle
    (exactly like the user's producer function crosses the spawn
    boundary), so it must be a module-level class, not a closure.  Pass a
    :class:`ShmRendezvous` for cross-process exchange; the in-process
    :class:`Rendezvous` is not picklable by design (its reach is one
    process)."""

    def __init__(
        self,
        rendezvous: Any = None,
        seed: int = 0,
        exchange_timeout_s: float = 60.0,
        degrade_on_peer_loss: bool = True,
        max_peer_losses: Optional[int] = None,
        wire_dtype: Optional[str] = None,
        codec: Optional[str] = None,
        codec_level: int = 3,
    ):
        self.rendezvous = rendezvous
        self.seed = seed
        self.exchange_timeout_s = exchange_timeout_s
        self.degrade_on_peer_loss = degrade_on_peer_loss
        self.max_peer_losses = max_peer_losses
        self.wire_dtype = wire_dtype
        self.codec = codec
        self.codec_level = codec_level

    def __call__(
        self,
        topology: Topology,
        producer_idx: int,
        num_exchange: int,
        exchange_method: str = "sendrecv_replace",
    ) -> ThreadExchangeShuffler:
        return ThreadExchangeShuffler(
            topology,
            producer_idx,
            num_exchange,
            exchange_method,
            rendezvous=self.rendezvous,
            seed=self.seed,
            exchange_timeout_s=self.exchange_timeout_s,
            degrade_on_peer_loss=self.degrade_on_peer_loss,
            max_peer_losses=self.max_peer_losses,
            wire_dtype=self.wire_dtype,
            codec=self.codec,
            codec_level=self.codec_level,
        )


# -- device-side exchange tier (ddl_tpu.ops.device_shuffle) -------------------


class DeviceExchangeError(DDLError):
    """The device exchange leg failed (DMA failure, unplannable
    geometry, injected fault): every participant of the round sees it
    and latches the HOST exchange for the shuffler's life
    (``shuffle.device_fallbacks``) — distinct from a peer timeout,
    which degrades one round to the seeded node-local shuffle."""


class _DeviceRound:
    """One (producer_idx, round) exchange round on the fabric board."""

    __slots__ = (
        "n", "seed", "round_", "posts", "results", "error", "broken",
    )

    def __init__(self, n: int, seed: int, round_: int) -> None:
        self.n = n
        self.seed = seed
        self.round_ = round_
        self.posts: Dict[int, np.ndarray] = {}
        self.results: Optional[Dict[int, np.ndarray]] = None
        self.error: Optional[BaseException] = None
        #: ``error`` is a broken program (the kernel did not build or
        #: compile), not a failed leg: participants re-raise it as it
        #: is instead of latching the host exchange.
        self.broken = False

    def raise_error(self) -> None:
        assert self.error is not None
        if self.broken:
            raise self.error
        raise DeviceExchangeError(str(self.error)) from self.error


class DeviceExchangeFabric:
    """In-process coordination board for the device exchange.

    Every instance's k-th producer posts its lane block per round; the
    arrival that completes the set runs the DEVICE leg (land blocks on
    the ring devices, one ``exchange_start``/``exchange_wait`` round
    over ICI, fetch results) and publishes per-instance results — one
    collective per round instead of ``2n`` host mailbox hops.

    Reach: producers in THIS process (the THREAD-mode realisation,
    which is also where the consumer's devices are addressable).  The
    factory drops the fabric at the pickle boundary, so PROCESS/
    MULTIHOST workers resolve the device tier off and run the host
    exchange — same bytes, by the shared-seed construction.

    Round results are RETAINED until round ``r + 2`` starts (the host
    fabrics' ``retire`` window), so a respawned producer replaying its
    crashed predecessor's round re-takes the same result —
    ``supports_elastic_replay`` holds for the device tier too.
    """

    span = "device"

    def __init__(self, devices: Optional[Sequence[Any]] = None,
                 impl: Optional[str] = None,
                 interpret: Optional[bool] = None) -> None:
        from ddl_tpu import envspec

        self.impl = impl or envspec.get("DDL_TPU_SHUFFLE_IMPL")
        if self.impl not in ("ring", "xla"):
            raise ValueError(
                f"shuffle_impl must be ring|xla, got {self.impl!r}"
            )
        self.interpret = interpret
        self._devices = tuple(devices) if devices is not None else None
        self._cond = named_condition("shuffle.device.cond")
        # (producer_idx, round) -> _DeviceRound; swept two rounds behind
        # the newest (the retire window), so growth is bounded by
        # 2 * n_producers.  # ddl-lint: disable=DDL013
        self._rounds: Dict[Tuple[int, int], _DeviceRound] = {}

    # -- geometry ------------------------------------------------------------

    def _ring_devices(self, n: int) -> Tuple[Any, ...]:
        """The first ``n`` addressable devices as the exchange ring
        (resolved lazily: constructing the fabric must not import
        jax)."""
        if self._devices is None:
            import jax

            self._devices = tuple(jax.devices())
        if len(self._devices) < n:
            raise DDLError(
                f"device exchange unplannable: ring needs {n} devices "
                f"for {n} instances, have {len(self._devices)}"
            )
        return self._devices[:n]

    # -- the exchange --------------------------------------------------------

    def exchange(self, *, producer_idx: int, round_: int,
                 instance_idx: int, n: int, block: np.ndarray, seed: int,
                 timeout_s: float = 60.0,
                 should_abort: Optional[Callable[[], bool]] = None,
                 ) -> np.ndarray:
        """Post this instance's lane block for ``round_`` and return the
        exchanged block.  Raises :class:`ShutdownRequested` (abort),
        :class:`DeviceExchangeError` (device leg failed — caller
        latches the host fallback), or :class:`DDLError` (a peer never
        posted — caller degrades the round node-locally, exactly the
        host path's peer-loss rung)."""
        key = (producer_idx, round_)
        # Chaos site, hit once per participant per round: ICI_DMA_FAIL
        # poisons the ROUND (a DMA failure is collective — every
        # participant must latch the host fallback together, with lanes
        # unmutated, so the host re-run is byte-identical);
        # SHUFFLE_PEER_LOSS raises DDLError before this participant
        # posts, so its peers time out — the seeded node-local rung.
        try:
            fault_point(
                "shuffle.device_exchange", producer_idx=producer_idx,
                should_abort=should_abort,
            )
        except InjectedFault as e:
            self._fail_round(key, n, seed, e)
            raise DeviceExchangeError(str(e)) from e
        run_leg = False
        with self._cond:
            self._sweep_rounds(producer_idx, round_)
            rnd = self._rounds.get(key)
            if rnd is None:
                rnd = _DeviceRound(n, seed, round_)
                self._rounds[key] = rnd
            if rnd.error is not None:
                rnd.raise_error()
            if rnd.results is not None:
                # Replayed take (respawned producer re-entering a
                # completed round): idempotent per (key, instance).
                return rnd.results[instance_idx]
            rnd.posts[instance_idx] = block
            run_leg = len(rnd.posts) == n
            self._cond.notify_all()
        if run_leg:
            self._run_device_leg(rnd)
        deadline = time.monotonic() + timeout_s
        extended = False
        with self._cond:
            while rnd.results is None and rnd.error is None:
                if should_abort is not None and should_abort():
                    # Retract our half if the round has not filled (the
                    # host path's discard-on-shutdown), so a later run
                    # cannot adopt this round's stale post.
                    if len(rnd.posts) < rnd.n:
                        rnd.posts.pop(instance_idx, None)
                    raise ShutdownRequested()
                if not extended and len(rnd.posts) == rnd.n:
                    # All peers posted: the leader is running the device
                    # leg — the peer-loss clock no longer applies; give
                    # the leg its own full budget once.
                    deadline = time.monotonic() + timeout_s
                    extended = True
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    if len(rnd.posts) == rnd.n:
                        raise DeviceExchangeError(
                            f"device exchange leg stalled at round "
                            f"{round_} (producer {producer_idx})"
                        )
                    rnd.posts.pop(instance_idx, None)
                    raise DDLError(
                        f"device exchange timed out waiting for peers "
                        f"at round {round_} (producer {producer_idx}: "
                        f"{len(rnd.posts)}/{rnd.n} posted)"
                    )
                self._cond.wait(timeout=min(0.1, remaining))
            if rnd.error is not None:
                rnd.raise_error()
            return rnd.results[instance_idx]

    # -- internals -----------------------------------------------------------

    def _fail_round(self, key: Tuple[int, int], n: int, seed: int,
                    err: BaseException) -> None:
        with self._cond:
            rnd = self._rounds.get(key)
            if rnd is None:
                rnd = _DeviceRound(n, seed, key[1])
                self._rounds[key] = rnd
            if rnd.results is None and rnd.error is None:
                rnd.error = err
            self._cond.notify_all()

    def _sweep_rounds(self, producer_idx: int, round_: int) -> None:
        """Drop this producer's rounds older than ``round_ - 1`` (the
        replay window closes one round behind, as on the host fabrics'
        ``retire``).  Caller holds the condition lock."""
        stale = [
            k for k in self._rounds
            if k[0] == producer_idx and k[1] < round_ - 1
        ]
        for k in stale:
            del self._rounds[k]

    def _run_device_leg(self, rnd: _DeviceRound) -> None:
        """The arrival that completed the round runs the collective.
        A leg that FAILS (unplannable geometry, a dtype the mesh cannot
        hold, a device error surfacing at the sync point) is published
        to every participant — they all latch the host fallback
        together.  A kernel that does not BUILD or COMPILE is published
        too, but as ``broken``: every participant re-raises it."""
        import jax

        try:
            results = self._device_exchange(rnd)
        except (ShutdownRequested, KeyboardInterrupt):
            # Teardown interrupts propagate; waiting peers hit the
            # leg-stall timeout and latch the host fallback.
            raise
        except Exception as e:  # published, not swallowed
            broken = not isinstance(
                e, (DDLError, jax.errors.JaxRuntimeError)
            )
            with self._cond:
                if rnd.results is None and rnd.error is None:
                    rnd.error, rnd.broken = e, broken
                self._cond.notify_all()
            if broken:
                raise
            return
        with self._cond:
            if rnd.error is None:
                rnd.results = results
            self._cond.notify_all()

    def _device_exchange(self, rnd: _DeviceRound) -> Dict[int, np.ndarray]:
        # Lazy: the fabric is importable (and picklable factories must
        # construct) without pulling jax/pallas into light processes.
        from ddl_tpu.ops import device_shuffle as _dsh

        n = rnd.n
        devices = self._ring_devices(n)
        blocks = []
        shape = dtype = None
        for i in range(n):
            if i not in rnd.posts:
                raise DDLError(
                    f"device exchange round {rnd.round_} missing "
                    f"instance {i}'s lanes"
                )
            b = rnd.posts[i]
            if shape is None:
                shape, dtype = b.shape, b.dtype
            elif b.shape != shape or b.dtype != dtype:
                raise DDLError(
                    f"device exchange round {rnd.round_}: instance {i} "
                    f"posted {b.shape}/{b.dtype}, expected "
                    f"{shape}/{dtype}"
                )
            blocks.append(b)
        p = exchange_permutation(n, rnd.seed, rnd.round_)
        gin = _dsh.as_exchange_input(blocks, devices)
        # Alternating landing slots (distinct collective-id pairs) keep
        # round r+1's ring program off round r's barrier semaphores when
        # the exchange rides a landing slot under the fused step.
        ticket = _dsh.exchange_start(
            self.impl, gin, devices, p,
            slot=rnd.round_ % _dsh.N_SLOTS, interpret=self.interpret,
        )
        # sync=True: an async DMA failure must surface HERE, inside the
        # fallback ladder, not at some later consumer's sync point.
        out = _dsh.exchange_wait(ticket, sync=True)
        blocks_out = _dsh.exchange_output_blocks(out, devices, shape)
        return {i: blocks_out[i] for i in range(n)}


class DeviceExchangeShuffler(ThreadExchangeShuffler):
    """The device-tier exchange shuffler: same contract, same bytes,
    one collective instead of ``2n`` host mailbox hops.

    Subclasses :class:`ThreadExchangeShuffler`, inheriting the entire
    degradation ladder (suspend/resume, peer-loss degrade, elastic
    rejoin, wire fallback) — the device tier wraps ONLY the healthy
    round's transport.  Byte identity with the host path is by
    construction: both derive the permutation from
    ``exchange_permutation(n, seed + producer_idx, round)`` and move
    the same two lanes, so for a given seed the post-exchange pools are
    equal byte-for-byte (the tier-1 parity suite proves it on the CPU
    virtual mesh in interpret mode).

    Resolution (construction time, not a fallback): the device tier
    engages only when a fabric is present (the factory drops it at the
    pickle boundary, so PROCESS/MULTIHOST workers run the host path),
    the topology is THREAD-realised (the fabric's reach), the
    ``DDL_TPU_DEVICE_SHUFFLE`` gate is not off, and the wire resolves
    raw with no codec (the device legs move raw rows over ICI; an
    explicitly forced lossy/codec wire keeps the host path — on-device
    re-quantization would break exact byte identity).

    Fallback (latched for the shuffler's life, ``shuffle.device_
    fallbacks``): unplannable geometry or any device-leg failure —
    every round participant latches together and re-runs the SAME
    round over the host fabric with lanes unmutated, byte-identically.
    A peer that never posts degrades the round to the seeded node-local
    shuffle, exactly the host path's rung.
    """

    def __init__(
        self,
        topology: Topology,
        producer_idx: int,
        num_exchange: int,
        exchange_method: str = "sendrecv_replace",
        rendezvous: Any = None,
        fabric: Optional[DeviceExchangeFabric] = None,
        device_shuffle: Optional[str] = None,
        **kwargs: Any,
    ):
        super().__init__(
            topology, producer_idx, num_exchange, exchange_method,
            rendezvous=rendezvous, **kwargs,
        )
        from ddl_tpu import envspec
        from ddl_tpu.types import RunMode

        gate = (
            device_shuffle
            if device_shuffle is not None
            else (envspec.raw("DDL_TPU_DEVICE_SHUFFLE") or "auto")
        )
        self._fabric = fabric
        self._device_latched = False  # terminal: host exchange for life
        why = None
        if str(gate).lower() in envspec.FALSY:
            why = "DDL_TPU_DEVICE_SHUFFLE gate is off"
        elif fabric is None:
            why = (
                "no fabric (crossed a spawn boundary, or none was "
                "constructed)"
            )
        elif topology.mode is not RunMode.THREAD:
            why = (
                f"{topology.mode.value} topology: the in-process fabric "
                "cannot reach producers in other processes"
            )
        elif self.wire_dtype != "raw" or self.codec is not None:
            why = (
                f"wire ({self.wire_dtype}/{self.codec}) forced: device "
                "legs move raw rows over ICI"
            )
        self._device_ok = why is None
        if why is not None and fabric is not None:
            logger.debug(
                "device shuffle resolved OFF for producer %d: %s",
                producer_idx, why,
            )

    @property
    def span(self) -> str:
        """``"device"`` while the device tier is engaged, else the host
        fabric's span (the handshake validates whichever transport will
        actually carry the lanes)."""
        if self._device_ok and not self._device_latched:
            return "device"
        return super().span

    @property
    def device_exchange_active(self) -> bool:
        return self._device_ok and not self._device_latched

    def _latch_host(self, why: BaseException) -> None:
        self._device_latched = True
        self.metrics.incr("shuffle.device_fallbacks")
        logger.error(
            "device shuffle: exchange leg failed at round %d (%s) — "
            "latching the HOST exchange for the rest of the run",
            self._round, why,
        )

    def global_shuffle(self, my_ary: np.ndarray, should_abort: Any = None,
                       **kwargs: Any) -> None:
        n = self.topology.n_instances
        if n <= 1 or self.num_exchange < 2:
            return
        if (
            not self._device_ok
            or self._device_latched
            or self._degraded
            or self._suspended
        ):
            # Host tier (resolution-off / latched) or the inherited
            # degrade/suspend rungs — the base class owns all of them.
            return super().global_shuffle(my_ary, should_abort, **kwargs)
        lane_a, lane_b = exchange_slices(self.num_exchange)
        half = lane_a.stop
        # Both lanes travel as one 2D block; trailing dims flatten into
        # columns (the device kernel is 2D) and unflatten on return.
        block = np.ascontiguousarray(
            my_ary[: 2 * half].reshape(2 * half, -1)
        )
        try:
            out = self._fabric.exchange(
                producer_idx=self.producer_idx,
                round_=self._round,
                instance_idx=self.topology.instance_idx,
                n=n,
                block=block,
                seed=self.seed + self.producer_idx,
                timeout_s=self.exchange_timeout_s,
                should_abort=should_abort,
            )
        except ShutdownRequested:
            raise
        except DeviceExchangeError as e:
            # Device leg failed for the whole round: latch the host
            # exchange for life and re-run the SAME round over it —
            # lanes are unmutated, so the bytes equal a host-only run.
            self._latch_host(e)
            return super().global_shuffle(my_ary, should_abort, **kwargs)
        except DDLError as e:
            # A peer never posted: the host path's peer-loss rung,
            # byte-identical because the node-local shuffle depends
            # only on (seed, producer, round).
            if not self.degrade_on_peer_loss:
                raise
            self._degrade_round(my_ary, e)
            self._round += 1
            return
        my_ary[: 2 * half] = out.reshape(my_ary[: 2 * half].shape)
        self.metrics.incr("shuffle.device_rounds")
        self._peer_losses = 0  # a healthy round resets the ladder
        self._round += 1

    @classmethod
    def factory(
        cls,
        rendezvous: Any = None,
        fabric: Optional[DeviceExchangeFabric] = None,
        device_shuffle: Optional[str] = None,
        shuffle_impl: Optional[str] = None,
        **kwargs: Any,
    ) -> "DeviceExchangeShufflerFactory":
        return DeviceExchangeShufflerFactory(
            rendezvous=rendezvous, fabric=fabric,
            device_shuffle=device_shuffle, shuffle_impl=shuffle_impl,
            **kwargs,
        )


class DeviceExchangeShufflerFactory(ExchangeShufflerFactory):
    """Picklable device-shuffler factory.

    Constructs one :class:`DeviceExchangeFabric` (shared by every
    producer it builds in this process) unless given one.  The fabric
    is an in-process coordination board (named condition + device
    handles), so :meth:`__getstate__` DROPS it at the pickle boundary:
    PROCESS/MULTIHOST workers construct with the device tier resolved
    off and run the host exchange over the factory's ``rendezvous`` —
    the streams stay byte-identical and no ``shuffle.device_fallbacks``
    is counted (resolution is not a fallback)."""

    def __init__(
        self,
        rendezvous: Any = None,
        fabric: Optional[DeviceExchangeFabric] = None,
        device_shuffle: Optional[str] = None,
        shuffle_impl: Optional[str] = None,
        **kwargs: Any,
    ):
        super().__init__(rendezvous=rendezvous, **kwargs)
        self.fabric = (
            fabric
            if fabric is not None
            else DeviceExchangeFabric(impl=shuffle_impl)
        )
        self.device_shuffle = device_shuffle

    def __getstate__(self) -> Dict[str, Any]:
        state = self.__dict__.copy()
        state["fabric"] = None  # in-process reach only; see class doc
        return state

    def __call__(
        self,
        topology: Topology,
        producer_idx: int,
        num_exchange: int,
        exchange_method: str = "sendrecv_replace",
    ) -> DeviceExchangeShuffler:
        return DeviceExchangeShuffler(
            topology,
            producer_idx,
            num_exchange,
            exchange_method,
            rendezvous=self.rendezvous,
            fabric=self.fabric,
            device_shuffle=self.device_shuffle,
            seed=self.seed,
            exchange_timeout_s=self.exchange_timeout_s,
            degrade_on_peer_loss=self.degrade_on_peer_loss,
            max_peer_losses=self.max_peer_losses,
            wire_dtype=self.wire_dtype,
            codec=self.codec,
            codec_level=self.codec_level,
        )
