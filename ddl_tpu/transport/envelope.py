"""Acked control-envelope seam: at-least-once + dedup for the control plane.

Until PR 18 every control-channel send (``ShardAdoption`` re-partitions,
``ReplayRequest`` rewinds) was fire-and-forget: one lost or duplicated
pipe write silently stranded an adoption or double-applied a replay —
an *implicit* exactly-once assumption with no delivery model behind it.
This module makes the contract explicit:

- **At-least-once.**  :class:`ControlSender` wraps each payload in a
  :class:`~ddl_tpu.types.ControlEnvelope` carrying ``(incarnation,
  seq)`` and retries unacked sends with exponential backoff
  (``DDL_TPU_CTRL_BACKOFF_S`` doubling, ``DDL_TPU_CTRL_RETRIES`` cap).
- **Dedup.**  :class:`EnvelopeReceiver` suppresses re-deliveries by
  ``(incarnation, seq)``: a duplicate is re-acked (the sender's retry
  must terminate) but never re-applied.
- **Fencing.**  Every envelope carries the sender's fencing term
  (:mod:`ddl_tpu.cluster.supervision`): a receiver that has seen a
  newer term drops the payload unapplied but still acks — a zombie
  ex-leader's stale commands die at every applier, and the zombie's
  retry loop drains instead of spinning forever.

Chaos coverage rides the ``transport.control_send`` fault site inside
:meth:`ControlSender._wire`: ``CONTROL_MSG_DROP``/``NETWORK_PARTITION``
lose the wire attempt (the send stays pending; backoff retry absorbs
it), ``CONTROL_MSG_DUP`` sends the same envelope twice (the receiver's
dedup absorbs it).  Both legs are asserted with counters by
``tests/test_supervision.py`` (``TestEnvelopeSeam``,
``TestEnvelopeChaosE2E``).

Threading: :class:`ControlSender` is intentionally lock-free —
:class:`~ddl_tpu.transport.connection.ConsumerConnection` serializes
every sender operation (send / pump / ack routing) under its existing
``transport.connection`` rlock, exactly as raw ``send_control`` already
was.  :class:`EnvelopeReceiver` lives on the producer's single control
thread (``DataPusher._poll_control``) and needs no lock at all.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from ddl_tpu import envspec, faults
from ddl_tpu.exceptions import TransportError
from ddl_tpu.faults import FaultKind
from ddl_tpu.types import ControlAck, ControlEnvelope


class _Pending:
    """One unacked envelope: wire attempts so far + next retry due."""

    __slots__ = ("envelope", "attempts", "due", "backoff_s")

    def __init__(self, envelope: ControlEnvelope, due: float, backoff_s: float):
        self.envelope = envelope
        self.attempts = 1
        self.due = due
        self.backoff_s = backoff_s


class ControlSender:
    """Per-target acked sender (consumer → one producer).

    ``raw_send`` is the wire primitive (a closure over the live channel
    slot, so elastic channel swaps are transparent); ``target`` names
    the producer for fault-site matching and diagnostics.  All state
    mutation must happen under the owner's lock — see the module
    docstring.
    """

    def __init__(
        self,
        raw_send: Callable[[Any], None],
        target: int,
        incarnation: int = 0,
        metrics: Any = None,
        retries: Optional[int] = None,
        backoff_s: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self._raw_send = raw_send
        self.target = target
        self.incarnation = int(incarnation)
        self.metrics = metrics
        self.retries = (
            int(envspec.get("DDL_TPU_CTRL_RETRIES"))
            if retries is None else int(retries)
        )
        self.backoff_s = (
            float(envspec.get("DDL_TPU_CTRL_BACKOFF_S"))
            if backoff_s is None else float(backoff_s)
        )
        self._clock = clock
        self.fence = 0
        self._next_seq = 0
        # seq -> pending retry state: bounded by outstanding sends (acks
        # and the retry cap both clear entries).
        self._pending: Dict[int, _Pending] = {}  # ddl-lint: disable=DDL013
        #: Envelopes that exhausted the retry cap unacked, for callers
        #: that escalate (the HA tier re-fences; tests introspect).
        self.exhausted: List[ControlEnvelope] = []

    # -- sending -----------------------------------------------------------

    def send(self, payload: Any) -> int:
        """Wrap ``payload`` in a fenced envelope, register it pending,
        and make the first wire attempt.  Returns the assigned seq."""
        seq = self._next_seq
        self._next_seq += 1
        env = ControlEnvelope(
            seq=seq,
            incarnation=self.incarnation,
            fence=self.fence,
            payload=payload,
        )
        self._pending[seq] = _Pending(
            env, due=self._clock() + self.backoff_s, backoff_s=self.backoff_s
        )
        self._wire(env)
        return seq

    def _wire(self, env: ControlEnvelope) -> None:
        """One wire attempt.  A lost attempt (chaos drop/partition, a
        real broken pipe) leaves the envelope pending for ``pump``."""
        try:
            fired = faults.fault_point(  # ddl-verify: disable=VP002
                "transport.control_send", producer_idx=self.target
            )
            self._raw_send(env)
            if fired and FaultKind.CONTROL_MSG_DUP.value in fired:
                # The duplicate is the SAME envelope — the receiver's
                # (incarnation, seq) dedup is what the injection tests.
                self._raw_send(env)
                self._incr("ctrl.wire_dups")
        except TransportError:
            # Injected drop/partition, or an adapter reporting a real
            # wire loss as its typed error: the attempt is gone, the
            # envelope stays pending, backoff retry absorbs it.
            self._incr("ctrl.wire_drops")
        except (OSError, ValueError):
            # Broken/closed pipe mid-swap: same contract as above — the
            # elastic rejoin will restore the channel and pump retries.
            self._incr("ctrl.wire_drops")

    # -- retry / ack -------------------------------------------------------

    def pump(self, now: Optional[float] = None) -> int:
        """Re-send every due unacked envelope (exponential backoff).
        Past the retry cap an envelope is moved to :attr:`exhausted`
        and counted — never silently forgotten.  Returns resend count."""
        now = self._clock() if now is None else now
        resent = 0
        for seq in sorted(self._pending):
            p = self._pending.get(seq)
            if p is None or p.due > now:
                continue
            if p.attempts > self.retries:
                del self._pending[seq]
                self.exhausted.append(p.envelope)
                self._incr("ctrl.send_exhausted")
                continue
            p.attempts += 1
            p.backoff_s *= 2.0
            p.due = now + p.backoff_s
            self._wire(p.envelope)
            resent += 1
        if resent:
            self._incr("ctrl.retries", resent)
        return resent

    def ack(self, ack: ControlAck) -> bool:
        """Route one :class:`ControlAck` back; True when it cleared a
        pending envelope (stale/foreign acks are counted, not errors)."""
        if ack.incarnation != self.incarnation:
            self._incr("ctrl.stale_acks")
            return False
        p = self._pending.pop(ack.seq, None)
        if p is None:
            self._incr("ctrl.stale_acks")
            return False
        self._incr("ctrl.acked")
        if ack.dup:
            self._incr("ctrl.acked_dup")
        if ack.fence_rejected:
            self._incr("ctrl.fence_rejected")
        return True

    def pending_count(self) -> int:
        return len(self._pending)

    def _incr(self, name: str, value: float = 1.0) -> None:
        if self.metrics is not None:
            self.metrics.incr(name, value)


class EnvelopeReceiver:
    """Producer-side envelope unwrap: dedup + fencing + ack synthesis.

    ``accept`` returns ``(payload, ack)``: ``payload`` is the inner
    command to apply exactly once (``None`` for a duplicate or a
    fenced-off zombie command), ``ack`` always goes back on the wire —
    the sender's retry loop must terminate in every case.
    """

    #: Per-incarnation dedup window: seqs older than this many behind
    #: the newest are forgotten (a retry storm never spans thousands of
    #: outstanding control commands; window re-delivery past it would
    #: re-apply — sized far beyond any real pipeline's outstanding set).
    WINDOW = 4096

    def __init__(self, producer_idx: int = 0):
        self.producer_idx = int(producer_idx)
        #: Highest fencing term observed; commands below it are zombies.
        self.fence = 0
        self.dups = 0
        self.fence_drops = 0
        self.accepted = 0
        # incarnation -> seen seq set; only the two newest incarnations
        # are retained (older ones can no longer send).
        self._seen: Dict[int, Set[int]] = {}  # ddl-lint: disable=DDL013

    def accept(
        self, env: ControlEnvelope
    ) -> Tuple[Optional[Any], ControlAck]:
        ack = ControlAck(
            seq=env.seq,
            incarnation=env.incarnation,
            producer_idx=self.producer_idx,
        )
        if env.fence < self.fence:
            # A zombie ex-leader's stale command: drop unapplied, but
            # ack so the dead sender's retry loop drains.
            self.fence_drops += 1
            ack.fence_rejected = True
            return None, ack
        self.fence = max(self.fence, env.fence)
        seen = self._seen.get(env.incarnation)
        if seen is None:
            seen = self._seen[env.incarnation] = set()
            if len(self._seen) > 2:
                for inc in sorted(self._seen)[:-2]:
                    del self._seen[inc]
        if env.seq in seen:
            self.dups += 1
            ack.dup = True
            return None, ack
        seen.add(env.seq)
        if len(seen) > self.WINDOW:
            seen.discard(min(seen))
        self.accepted += 1
        return env.payload, ack

    def seed(self, incarnation: int, seq: int) -> None:
        """Pre-mark ``(incarnation, seq)`` as already applied — the
        journal-seeded dedup a rebuilt receiver runs after supervisor
        failover (:meth:`ddl_tpu.serve.fabric.IngestFabric.
        from_journal`): a retry of a command the DEAD leader applied
        must dedup here, not re-mutate the successor's ledger."""
        seen = self._seen.get(incarnation)
        if seen is None:
            seen = self._seen[incarnation] = set()
            if len(self._seen) > 2:
                for inc in sorted(self._seen)[:-2]:
                    del self._seen[inc]
        seen.add(int(seq))
        if len(seen) > self.WINDOW:
            seen.discard(min(seen))
