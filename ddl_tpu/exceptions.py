"""Exceptions for ddl_tpu.

Parity: the reference exposes a single ``DoesNotMatchError``
(reference ``ddl/exceptions.py:1``) whose constructor is broken (``__init``
typo, SURVEY Q3).  Here the hierarchy is real and the constructors work.
"""

from __future__ import annotations


class DDLError(Exception):
    """Base class for all ddl_tpu errors."""


class DoesNotMatchError(DDLError):
    """Topology or shape mismatch (reference ``ddl/exceptions.py:1``).

    Raised when the requested loader/trainer topology cannot be realised,
    e.g. a producer block that would span shared-memory domains
    (reference ``ddl/ddl_env.py:72-73``).
    """

    def __init__(self, value: object = None, message: str = ""):
        self.value = value
        self.message = message
        super().__init__(value, message)

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        if self.message:
            return f"{self.value!r}: {self.message}"
        return repr(self.value)


class TransportError(DDLError):
    """A transport-level failure (ring corrupt, peer vanished, bad slot)."""


class ShutdownRequested(DDLError):
    """Internal control-flow signal: the pipeline is shutting down.

    The TPU-native replacement for the reference's ``WorkerInfo.STOP``
    sentinel (reference ``ddl/connection.py:12-16``): waits that observe a
    shutdown flag raise this instead of returning a status enum.
    """


class StallTimeoutError(TransportError, TimeoutError):
    """A blocking wait on the ring exceeded its deadline.

    The reference had no deadline at all — a lost peer deadlocked the job
    until the pytest 100 s timeout killed it (reference
    ``tests/test_ddl.py:8``).  Here every wait carries a configurable
    timeout so failure detection is built in.

    Subclasses ``TimeoutError`` too so that EVERY deadline failure on a
    framework path — ring waits, control recvs, staged-transfer pops —
    is catchable through one hierarchy (``except StallTimeoutError`` /
    ``except DDLError``) without breaking callers that guard with the
    builtin.
    """


class IntegrityError(DDLError):
    """Data failed an end-to-end integrity check.

    Raised when bytes provably changed between producer and consumer:
    a ring-slot window whose committed checksum no longer matches at
    drain (and replay could not heal it), a staged copy that diverged
    from its verified source, or a TFRecord whose framing CRCs fail
    (``ddl_tpu.readers.iter_tfrecords``).  Always carries enough context
    (file/offset or ring/window) to locate the corruption.
    """


class BackendFetchError(TransportError):
    """A storage-backend shard fetch failed (transient until proven not).

    Raised by :class:`ddl_tpu.cache.StorageBackend` implementations (and
    the ``backend.fetch`` fault-injection point) when a shard read fails
    in a way a retry might heal — the remote-store analog of a dropped
    connection.  The one retry-policy site,
    :func:`ddl_tpu.cache.open_with_retry`, catches it with bounded
    exponential backoff; exhaustion escalates to :class:`IntegrityError`
    (a *persistent* backend failure is a data-availability fault, not a
    transport hiccup).
    """


class DecodeError(DDLError):
    """A wire payload failed to decode (``ddl_tpu.wire``): a codec
    raised, an envelope field was malformed, or the declared output
    bound was exceeded.

    Also the real type the ``DECODE_FAIL`` fault kind raises at the
    ``wire.decode`` site, so chaos exercises the production ladder:
    bounded retry, then the raw fallback for that wire path
    (``wire.fallbacks``) — a shuffle round degrades to raw encoding, a
    compressed shard read escalates to :class:`BackendFetchError` and
    rides ``open_with_retry``'s existing retry/backoff discipline.
    """


class HostLostError(DDLError):
    """A whole host left the cluster view (lease expiry, declared loss,
    or the ``HOST_LOSS`` fault kind at ``cluster.heartbeat``).

    Carries the host id in ``args`` where the raiser knows it.  The
    membership control plane (:mod:`ddl_tpu.cluster`) catches it during
    a sweep and runs the epoch-fenced view change; it never escapes a
    healthy supervisor loop.
    """


class HeartbeatDropped(DDLError):
    """One heartbeat was lost in flight (the ``HEARTBEAT_DROP`` fault
    kind at ``cluster.heartbeat``, or a real transport hiccup an adapter
    chooses to report this way).

    The lease table treats a dropped beat as silence: the lease keeps
    aging and only EXPIRY — never a single drop — triggers a view
    change, so transient heartbeat loss under the lease budget is
    absorbed without membership churn.
    """


class TenantBurst(DDLError):
    """A tenant's demand spiked (the ``TENANT_BURST`` fault kind at
    ``serve.admit``, or a real admission adapter reporting a thundering
    herd this way).

    Carries ``burst_bytes`` — the phantom demand to charge.  The
    fair-share scheduler (:mod:`ddl_tpu.serve.tenancy`) absorbs it by
    charging the BURSTING tenant's own deficit and byte bucket: the
    spike is paid for out of the burster's share, so its neighbours'
    service rates are untouched (the isolation property the tenancy
    chaos leg asserts).
    """

    def __init__(self, message: str = "", burst_bytes: float = 0.0):
        self.burst_bytes = float(burst_bytes)
        super().__init__(message)


class PreemptionNotice(DDLError):
    """The platform announced this host's imminent preemption (the
    ``PREEMPT_NOTICE`` fault kind at ``resilience.notice``, a SIGTERM
    delivered to the trainer, or the ``DDL_TPU_PREEMPT_NOTICE`` env
    knob an operator/agent sets).

    Carries ``deadline_s`` — the grace budget the notice grants — when
    the raiser knows it.  The :class:`~ddl_tpu.resilience.
    PreemptionGuard` absorbs it at window boundaries and turns it into
    a deadline-bounded graceful drain (forced final checkpoint,
    in-flight tenant-window revocation, graceful host drain); it never
    escapes a guarded ``Trainer.fit``.
    """

    def __init__(self, message: str = "", deadline_s: float = 0.0):
        self.deadline_s = float(deadline_s)
        super().__init__(message)


class WindowsRevoked(StallTimeoutError):
    """A tenant's in-flight window grants were revoked under a drain
    SLO (``FairShareScheduler.revoke_inflight`` — the scale-down /
    preemption rung that stops waiting for tenant idleness).

    Subclasses :class:`StallTimeoutError` deliberately: a revoked
    admission wait surfaces through the loader's one acquire choke
    point exactly like a stall deadline (non-blocking deepening probes
    already treat it as not-committed-yet), while staying catchable as
    its own type so a tenant runtime can distinguish "you were
    preempted" from "the ring wedged".
    """


class SupervisorCrashed(DDLError):
    """The control-plane supervisor process died mid-lease (the
    ``SUPERVISOR_CRASH`` fault kind at ``cluster.supervise``, or a real
    leader loop tearing down).

    The HA tier (:mod:`ddl_tpu.cluster.supervision`) absorbs it: the
    leader's lease stops renewing, a standby observes expiry, replays
    the journal, and promotes itself under the next fencing term.  It
    never escapes a :class:`~ddl_tpu.cluster.supervision.SupervisorHA`
    step — an unsupervised (HA-less) deployment sees it as fatal, which
    is exactly the gap the HA tier exists to close.
    """


class ControlSendDropped(TransportError):
    """One control-channel send was lost on the wire (the
    ``CONTROL_MSG_DROP`` fault kind at ``transport.control_send``, or a
    real pipe hiccup an adapter reports this way).

    The acked envelope seam (:mod:`ddl_tpu.transport.envelope`) absorbs
    it: the send stays pending and is retried with exponential backoff
    until acked or the retry cap trips.  Raw fire-and-forget
    ``send_control`` callers see it as the message silently vanishing —
    which is why ddl-lint DDL025 pushes control sends through the seam.
    """


class NetworkPartitioned(TransportError):
    """The control network partitioned: this side can neither deliver
    nor receive control traffic for the duration (the
    ``NETWORK_PARTITION`` fault kind at ``transport.control_send`` /
    ``cluster.supervise``, or a real fabric event).

    During a partition the envelope seam keeps retrying under its cap;
    the supervisor lease on the far side keeps aging.  A heal after
    lease expiry produces the split-brain scenario the fencing term
    exists for: the old leader's post-heal commands carry a stale fence
    and are dropped at every applier (docs/ROBUSTNESS.md walkthrough).
    """


class AdmissionDropped(TransportError):
    """One fabric admission command was lost on the wire (the
    ``JOB_ADMISSION_DROP`` fault kind at ``serve.fabric.admit``, or a
    real transport hiccup on the admission control channel).

    The fabric client's acked envelope seam
    (:mod:`ddl_tpu.serve.fabric` over
    :mod:`ddl_tpu.transport.envelope`) absorbs it: the command stays
    pending, backoff retry re-wires it, and the fabric's journal-seeded
    dedup guarantees the scheduler ledger is mutated exactly once no
    matter how many deliveries the retries produce.
    """


class JobCrashed(DDLError):
    """A training job died mid-grant: ``admit`` returned, the window is
    in flight, and ``note_served`` will never arrive (the ``JOB_CRASH``
    fault kind at ``serve.fabric.grant``, or a real trainer crash an
    operator reports).

    The fabric absorbs it via :meth:`~ddl_tpu.serve.fabric.IngestFabric.
    job_crashed`: the crashed job's in-flight grants are released, its
    registration (and byte budget) removed, and its neighbours' shares
    untouched — the chaos matrix pins byte-correctness of the
    survivors.
    """


class CheckpointError(DDLError):
    """A checkpoint could not be durably written or flushed
    (``ddl_tpu.resilience``): the async writer's final forced flush
    failed, or a generation write raised past its retry.  Restore-side
    corruption is NOT this error — unverifiable generations are
    quarantined and skipped (cold start at exhaustion, with a loud
    counter), never raised to the trainer.
    """


class InjectedFault(DDLError):
    """A deliberate failure raised by the fault-injection engine.

    Only ever raised while a :class:`ddl_tpu.faults.FaultPlan` is armed —
    production paths can neither construct nor observe it.  Distinct
    from real error types so the chaos suite can tell an injected crash
    from a genuine one leaking out of the machinery under test.
    """


class LoaderStateError(DDLError, RuntimeError):
    """The loader was driven from an invalid state (finalized loader,
    superseded ``windows()`` stream, batch iteration over abandoned
    staged windows).  Subclasses ``RuntimeError`` for backwards
    compatibility with callers that guarded on the builtin."""


class KernelBuildError(RuntimeError):
    """The TPU compiler refused a Pallas kernel (``ddl_tpu.ops``).

    A broken program, not a degraded link: deliberately NOT a
    :class:`DDLError` and not a ``JaxRuntimeError``, so neither the ICI
    distributor's nor the device shuffle's runtime fault ladder — which
    absorb those into a latched fallback — can mistake it for one.
    """
