"""Topology construction and the role-bifurcating decorator.

Parity with reference ``ddl/ddl_env.py``: there, every MPI rank ran the same
program and ``@distributed_dataloader`` split ranks into one consumer + N
producers per instance via communicator color arithmetic
(``ddl_env.py:33-128``).  TPU-native, there are no ranks to split — the
decorated main runs in the trainer process and the decorator *spawns* the
producer workers:

- THREAD mode: producers are daemon threads (single-process first-class —
  fixes SURVEY Q9).
- PROCESS mode: producers are spawned host processes; data rides the native
  shared-memory ring (the reference's one-node shm-domain constraint,
  ``ddl_env.py:72-73``, holds by construction).
- MULTIHOST mode: PROCESS per host; ``instance_idx``/``n_instances`` come
  from ``jax.distributed`` (`jax.process_index/process_count`), the analog
  of the reference's SLURM sniffing (``ddl_env.py:103-107``).

Environment knobs (the reference used SLURM vars): ``DDL_TPU_MODE``,
``DDL_TPU_N_PRODUCERS``, ``DDL_TPU_NSLOTS``; plus the shard-cache set
``DDL_TPU_CACHE`` / ``DDL_TPU_CACHE_RAM_MB`` / ``DDL_TPU_CACHE_SPILL_DIR``
/ ``DDL_TPU_CACHE_SPILL_MB`` / ``DDL_TPU_CACHE_WARM`` (parsed in
:mod:`ddl_tpu.cache`, mirrored by ``LoaderConfig`` fields, and exported
by :func:`_export_cache_knobs` ahead of the producer spawn so
PROCESS/MULTIHOST workers build the same store).
"""

from __future__ import annotations

import functools
import logging
import os
import threading
from typing import Any, Callable, List, Optional

from ddl_tpu import envspec
from ddl_tpu.exceptions import ShutdownRequested, TransportError
from ddl_tpu.faults import fault_point
from ddl_tpu.observability import metrics as default_metrics
from ddl_tpu.profiling import stage
from ddl_tpu.transport.connection import (
    ConsumerConnection,
    PipeChannel,
    ProducerConnection,
    ThreadChannel,
)
from ddl_tpu.types import DDL_Env, RunMode, Topology

logger = logging.getLogger("ddl_tpu")

#: Sentinel broadcast to producers when the consumer dies before handshake.
ABORT = "__ddl_tpu_abort__"


def detect_host_identity(
    n_instances: int = 1,
    instance_idx: int = 0,
    host_id: Optional[int] = None,
    n_hosts: Optional[int] = None,
) -> tuple[int, int]:
    """``(host_id, n_hosts)`` for this consumer process.

    Fixes the latent one-consumer-per-host skew: the original SLURM
    recipe (docs/DEPLOY.md) equated ``jax.process_index()`` with the
    host, which is wrong the moment a host runs more than one consumer
    process (one per chip is the common TPU layout) — the cluster
    membership view and the placement engine would then see 4x the real
    host count and "place" transport onto links that do not exist.
    Resolution order, later layers only filling gaps:

    1. explicit arguments (``LoaderConfig.host_id``/``n_hosts`` threaded
       through :func:`distributed_dataloader`),
    2. ``DDL_TPU_HOST_ID`` / ``DDL_TPU_N_HOSTS`` env,
    3. SLURM node identity (``SLURM_NODEID`` / ``SLURM_NNODES`` — per
       NODE, not per task, so co-located tasks agree),
    4. processes-per-host arithmetic over the process grid
       (``DDL_TPU_PROCS_PER_HOST``, else ``SLURM_NTASKS_PER_NODE``,
       else 1 — the historical host==instance reading).
    """
    def _env_int(name: str) -> Optional[int]:
        # DDL_TPU names go through the registry; SLURM names are not
        # ours to declare.
        raw = (envspec.raw(name) if name.startswith("DDL_TPU_")
               else os.environ.get(name))
        return int(raw) if raw not in (None, "") else None

    if host_id is None:
        host_id = _env_int("DDL_TPU_HOST_ID")
    if n_hosts is None:
        n_hosts = _env_int("DDL_TPU_N_HOSTS")
    if host_id is None and n_hosts is None:
        slurm_node = _env_int("SLURM_NODEID")
        slurm_nodes = _env_int("SLURM_NNODES")
        if slurm_node is not None and slurm_nodes is not None:
            host_id, n_hosts = slurm_node, slurm_nodes
    if host_id is None or n_hosts is None:
        pph = (
            _env_int("DDL_TPU_PROCS_PER_HOST")
            or _env_int("SLURM_NTASKS_PER_NODE")
            or 1
        )
        pph = max(1, pph)
        if n_hosts is None:
            n_hosts = max(1, (n_instances + pph - 1) // pph)
        if host_id is None:
            host_id = min(instance_idx // pph, n_hosts - 1)
    # Layers may have resolved independently (an explicit host_id with
    # an arithmetic n_hosts): widen n_hosts to cover the id instead of
    # crashing Topology validation on a half-set environment.
    if host_id >= n_hosts:
        n_hosts = host_id + 1
    return int(host_id), int(n_hosts)


def detect_topology(
    n_producers: Optional[int] = None,
    mode: Optional[RunMode | str] = None,
    host_id: Optional[int] = None,
    n_hosts: Optional[int] = None,
) -> Topology:
    """Build the topology from args + environment.

    The reference derived ``n_instances`` from SLURM env vars
    (``ddl_env.py:103-107``); here MULTIHOST mode derives it from the JAX
    process grid, and single-host modes use one instance.  Host identity
    (distinct from the process grid — several consumer processes may
    share a host) comes from :func:`detect_host_identity`.
    """
    if mode is None:
        mode = envspec.get("DDL_TPU_MODE")
    mode = RunMode(mode) if not isinstance(mode, RunMode) else mode
    if n_producers is None:
        n_producers = envspec.get("DDL_TPU_N_PRODUCERS")
    if mode is RunMode.MULTIHOST:
        import jax

        n_instances = jax.process_count()
        instance_idx = jax.process_index()
    else:
        n_instances, instance_idx = 1, 0
    host_id, n_hosts = detect_host_identity(
        n_instances, instance_idx, host_id=host_id, n_hosts=n_hosts
    )
    return Topology(
        n_instances=n_instances,
        instance_idx=instance_idx,
        n_producers=n_producers,
        mode=mode,
        host_id=host_id,
        n_hosts=n_hosts,
    )


def _producer_main(
    conn: ProducerConnection,
    topology: Topology,
    producer_idx: int,
    nslots: int,
    shuffler_factory: Any = None,
    rejoin_ring: Any = None,
) -> None:
    """Body of one producer worker (thread or process)."""
    from ddl_tpu.datapusher import DataPusher

    try:
        # Chaos hook: a crash here exercises the handshake-failure
        # shipping path (the consumer fails fast with a typed error
        # instead of timing out).
        fault_point("producer.handshake", producer_idx=producer_idx)
        pusher = DataPusher(
            conn,
            topology,
            producer_idx,
            nslots=nslots,
            shuffler_factory=shuffler_factory,
            rejoin_ring=rejoin_ring,
        )
    except TransportError as te:
        # Consumer aborted before/during handshake (ABORT sentinel arrives
        # as non-metadata). Nothing to clean up beyond the channel.  The
        # exception text still goes to DEBUG — a swallowed transport
        # failure that is NOT an abort (e.g. a failed ring attach) must be
        # diagnosable from producer logs.
        logger.debug("producer %d: handshake transport end: %s",
                     producer_idx, te)
        conn.channel.close()
        return
    except ShutdownRequested:
        # The run is tearing down while this producer was still in its
        # handshake (e.g. the ring shutdown flag tripped inside an
        # inplace-fill acquire): a clean, consumer-initiated exit — not a
        # failure to ship back.  Previously the broad handler below
        # swallowed this into a spurious "handshake failure" (DDL007).
        logger.debug("producer %d: shutdown during handshake", producer_idx)
        conn.channel.close()
        return
    except Exception as e:
        # Handshake-time user error (bad on_init, bad geometry): ship the
        # exception to the consumer so it fails fast instead of timing out.
        try:
            conn.channel.send(e)
        except (ShutdownRequested, KeyboardInterrupt):
            raise
        except Exception:
            # Exception not picklable (open handles, locks): ship a
            # picklable surrogate carrying the traceback text instead.
            import traceback

            try:
                conn.channel.send(
                    TransportError(
                        f"producer {producer_idx} handshake failure "
                        f"(original unpicklable):\n{traceback.format_exc()}"
                    )
                )
            except (OSError, ValueError):
                pass  # channel itself broken; the consumer will time out
        logger.exception("producer %d failed during handshake", producer_idx)
        return
    try:
        pusher.push_data()
    except Exception:
        # A crash in the user's refill loop: log it here (instead of an
        # unhandled-thread traceback) and surface it to the watchdog —
        # dead thread for THREAD mode, nonzero exit for PROCESS mode —
        # which aborts or respawns per its policy.
        logger.exception(
            "producer %d crashed in the push loop", producer_idx
        )
        if conn.cross_process:
            raise SystemExit(1)


def _process_entry(
    pipe_end: Any,
    topology: Topology,
    producer_idx: int,
    nslots: int,
    shuffler_factory: Any = None,
    rejoin_ring: Any = None,
) -> None:
    """Top-level spawn target (must be importable for pickling)."""
    conn = ProducerConnection(
        PipeChannel(pipe_end), producer_idx, cross_process=True
    )
    _producer_main(
        conn, topology, producer_idx, nslots, shuffler_factory, rejoin_ring
    )


def _export_cache_knobs(config: Any) -> None:
    """Mirror a LoaderConfig's shard-cache fields into the ``DDL_TPU_CACHE*``
    environment BEFORE producers spawn.

    The cache store is per process (``ddl_tpu.cache.default_store``):
    THREAD-mode workers share the consumer's, but PROCESS/MULTIHOST
    workers each build their own from the environment they inherit —
    without this export a config-enabled cache would silently apply to
    nobody in the modes that need it most.

    The mirror goes BOTH ways (config wins over env, the documented
    precedence): a config with ``cache=False`` exports the gate as off,
    and a cache-on config with no spill dir clears any stale
    ``DDL_TPU_CACHE_SPILL_DIR`` — otherwise a second run in the same
    process would silently inherit the previous run's export.  A bare
    ``config=None`` call states no cache opinion and leaves the
    environment (a first-class interface of its own) untouched.
    """
    if config is None:
        return
    if not getattr(config, "cache", False):
        if "DDL_TPU_CACHE" in os.environ:
            os.environ["DDL_TPU_CACHE"] = "0"
        return
    os.environ["DDL_TPU_CACHE"] = "1"
    os.environ["DDL_TPU_CACHE_RAM_MB"] = str(config.cache_ram_mb)
    os.environ["DDL_TPU_CACHE_SPILL_MB"] = str(config.cache_spill_mb)
    os.environ["DDL_TPU_CACHE_WARM"] = "1" if config.cache_warm else "0"
    if config.cache_spill_dir:
        os.environ["DDL_TPU_CACHE_SPILL_DIR"] = config.cache_spill_dir
    else:
        os.environ.pop("DDL_TPU_CACHE_SPILL_DIR", None)
    if getattr(config, "cache_codec", ""):
        os.environ["DDL_TPU_CACHE_CODEC"] = config.cache_codec
    else:
        os.environ.pop("DDL_TPU_CACHE_CODEC", None)


#: Cluster env vars THIS process exported from a config (never user-set
#: ones): a later run whose config states no opinion clears exactly
#: these, so one run's explicit identity cannot leak into the next —
#: the documented _export_cache_knobs precedent, made precise.
_exported_cluster_vars: set = set()


def _export_cluster_knobs(config: Any) -> None:
    """Mirror a LoaderConfig's host-identity fields into the
    ``DDL_TPU_HOST_ID``/``DDL_TPU_N_HOSTS``/``DDL_TPU_PROCS_PER_HOST``
    environment BEFORE producers spawn (the ``_export_cache_knobs``
    pattern): PROCESS/MULTIHOST workers re-derive host identity from
    the environment they inherit, and the cluster view each side builds
    must agree on host boundaries.  Sentinel values (-1/0 = auto) state
    no opinion: they leave USER-set environment untouched, but clear
    any export a previous config-driven run in this process made —
    otherwise the second run would silently inherit the first run's
    explicit identity as its "auto-detected" one.
    """
    if config is None:
        return
    for var, value, has_opinion in (
        ("DDL_TPU_HOST_ID", getattr(config, "host_id", -1),
         getattr(config, "host_id", -1) >= 0),
        ("DDL_TPU_N_HOSTS", getattr(config, "n_hosts", 0),
         getattr(config, "n_hosts", 0) > 0),
        ("DDL_TPU_PROCS_PER_HOST", getattr(config, "procs_per_host", 0),
         getattr(config, "procs_per_host", 0) > 0),
    ):
        if has_opinion:
            os.environ[var] = str(value)
            _exported_cluster_vars.add(var)
        elif var in _exported_cluster_vars:
            os.environ.pop(var, None)
            _exported_cluster_vars.discard(var)


#: Wire env vars THIS process exported from a config (never user-set
#: ones) — the _export_cluster_knobs precedent.
_exported_wire_vars: set = set()


def _export_wire_knobs(config: Any) -> None:
    """Mirror a LoaderConfig's wire-format fields into the
    ``DDL_TPU_WIRE_DTYPE``/``DDL_TPU_WIRE_CODEC`` environment BEFORE
    producers spawn (the ``_export_cache_knobs`` pattern): PROCESS/
    MULTIHOST workers resolve their wire dtype from the environment
    they inherit, and producer and consumer must agree on slot layout.
    Empty-string fields state no opinion (the per-reader capability
    decides): they leave USER-set environment untouched but clear this
    process's own prior exports.
    """
    if config is None:
        return
    for var, value in (
        ("DDL_TPU_WIRE_DTYPE", getattr(config, "wire_dtype", "")),
        ("DDL_TPU_WIRE_CODEC", getattr(config, "wire_codec", "")),
    ):
        if value:
            os.environ[var] = str(value)
            _exported_wire_vars.add(var)
        elif var in _exported_wire_vars:
            os.environ.pop(var, None)
            _exported_wire_vars.discard(var)


#: Shuffle env vars THIS process exported from a config (never user-set
#: ones) — the _export_wire_knobs precedent.
_exported_shuffle_vars: set = set()


def _export_shuffle_knobs(config: Any) -> None:
    """Mirror a LoaderConfig's device-shuffle fields into the
    ``DDL_TPU_DEVICE_SHUFFLE``/``DDL_TPU_SHUFFLE_IMPL`` environment
    BEFORE producers spawn (the ``_export_wire_knobs`` pattern):
    PROCESS/MULTIHOST workers resolve the gate from the environment
    they inherit.  Default-valued fields ("auto"/"ring") state no
    opinion: they leave USER-set environment untouched but clear this
    process's own prior exports.
    """
    if config is None:
        return
    for var, value, default in (
        ("DDL_TPU_DEVICE_SHUFFLE",
         getattr(config, "device_shuffle", "auto"), "auto"),
        ("DDL_TPU_SHUFFLE_IMPL",
         getattr(config, "shuffle_impl", "ring"), "ring"),
    ):
        if value and str(value) != default:
            os.environ[var] = str(value)
            _exported_shuffle_vars.add(var)
        elif var in _exported_shuffle_vars:
            os.environ.pop(var, None)
            _exported_shuffle_vars.discard(var)


#: Tuning env vars THIS process exported from a config (never user-set
#: ones) — the _export_wire_knobs precedent.
_exported_tune_vars: set = set()


def _export_tune_knobs(config: Any) -> None:
    """Mirror a LoaderConfig's tunable-knob fields into the environment
    (the ``_export_shuffle_knobs`` pattern) so the envspec seam every
    tuned call site reads (``DDL_TPU_PREFETCH_DEPTH``) sees the config
    — and so a ``TunedConfig`` overlay applied to the config before
    loader construction reaches PROCESS-mode workers too.  Default-
    valued fields state no opinion: they leave USER-set environment
    untouched but clear this process's own prior exports.
    """
    if config is None:
        return
    for var, value, default in (
        ("DDL_TPU_PREFETCH_DEPTH",
         getattr(config, "prefetch_depth", 2), 2),
    ):
        if value is not None and int(value) != default:
            os.environ[var] = str(value)
            _exported_tune_vars.add(var)
        elif var in _exported_tune_vars:
            os.environ.pop(var, None)
            _exported_tune_vars.discard(var)


class WorkerSet:
    """The spawned producer workers + consumer-side connection."""

    def __init__(self, topology: Topology, nslots: int,
                 shuffler_factory: Any = None):
        self.topology = topology
        self.nslots = nslots
        self.shuffler_factory = shuffler_factory
        self.threads: List[threading.Thread] = []
        self.processes: List[Any] = []
        channels = []
        if topology.mode is RunMode.THREAD:
            for idx in range(topology.n_producers):
                ch, t = self._spawn_thread(idx + 1)
                channels.append(ch)
                self.threads.append(t)
        else:
            for idx in range(topology.n_producers):
                ch, p = self._spawn_process(idx + 1)
                channels.append(ch)
                self.processes.append(p)
        self.connection = ConsumerConnection(channels)

    # The ONE worker-construction recipe, shared by __init__ and respawn
    # so the rarely-exercised recovery path cannot drift from the normal
    # spawn path.

    def _spawn_thread(self, producer_idx: int, rejoin_ring: Any = None):
        consumer_end, producer_end = ThreadChannel.pair()
        conn = ProducerConnection(
            producer_end, producer_idx, cross_process=False
        )
        t = threading.Thread(
            target=_producer_main,
            args=(conn, self.topology, producer_idx, self.nslots,
                  self.shuffler_factory, rejoin_ring),
            name=f"ddl-producer-{producer_idx}"
            + ("-respawn" if rejoin_ring is not None else ""),
            daemon=True,
        )
        t.start()
        return consumer_end, t

    def _spawn_process(self, producer_idx: int, rejoin_ring: Any = None):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        parent_end, child_end = mp.Pipe(duplex=True)
        # shuffler_factory must be picklable: it crosses the spawn
        # boundary exactly like the user's producer function.
        p = ctx.Process(
            target=_process_entry,
            args=(child_end, self.topology, producer_idx, self.nslots,
                  self.shuffler_factory, rejoin_ring),
            name=f"ddl-producer-{producer_idx}"
            + ("-respawn" if rejoin_ring is not None else ""),
            daemon=True,
        )
        p.start()
        # Close the parent's copy of the child end so a dead producer
        # surfaces as EOF on the channel, not a timeout.
        child_end.close()
        return PipeChannel(parent_end), p

    def respawn(self, producer_idx: int) -> None:
        """Replace a dead producer with a fresh worker that rejoins the
        surviving ring (elastic recovery — the reference had none,
        SURVEY §5.3: a lost rank deadlocked the job).

        The replacement re-handshakes over a new channel, attaches to the
        predecessor's ring, and fast-forwards its producer function to
        the data position the ring's committed count records — the
        consumer's drain loop never notices beyond the stall.
        """
        i = producer_idx - 1
        if not (0 <= i < self.topology.n_producers):
            raise ValueError(f"no producer {producer_idx}")
        ring_ref = getattr(self.connection.replies[i], "ring_ref", None)
        if ring_ref is None:
            raise TransportError(
                f"producer {producer_idx} never completed its first "
                "handshake; nothing to rejoin"
            )
        if self.topology.mode is RunMode.THREAD:
            if self.threads[i].is_alive():
                # A hung thread cannot be killed; a second producer on the
                # same SPSC ring would corrupt it.
                raise TransportError(
                    f"producer thread {producer_idx} is still alive; "
                    "only dead thread producers can be respawned"
                )
            new_ch, t = self._spawn_thread(producer_idx, rejoin_ring=ring_ref)
            self.threads[i] = t
        else:
            old = self.processes[i]
            if old.is_alive():  # stalled rather than dead: replace it
                old.terminate()
                old.join(10)
                if old.is_alive():
                    old.kill()
                    old.join(10)
                if old.is_alive():
                    # Unkillable (e.g. blocked in an uninterruptible
                    # syscall): a second producer on the same SPSC ring
                    # would corrupt it.
                    raise TransportError(
                        f"producer process {producer_idx} survived "
                        "SIGKILL; cannot safely attach a replacement"
                    )
            new_ch, p = self._spawn_process(producer_idx, rejoin_ring=ring_ref)
            self.processes[i] = p
        self.connection.rejoin_producer(producer_idx, new_ch)
        logger.info("respawned producer %d", producer_idx)

    def abort(self) -> None:
        """Wake producers that may still be blocked in the handshake."""
        for ch in self.connection.channels:
            try:
                ch.send(ABORT)
            except (OSError, ValueError):
                # A dead producer's pipe: EOF/broken-pipe here is the
                # expected case abort() exists for.  Narrow on purpose
                # (DDL007): shutdown signals keep propagating.
                pass
        self.connection.shutdown_operation()

    def join(self, timeout_s: float = 30.0) -> None:
        for t in self.threads:
            t.join(timeout_s)
        for p in self.processes:
            p.join(timeout_s)
            if p.is_alive():  # pragma: no cover - last resort
                p.terminate()


def distributed_dataloader(
    func: Optional[Callable[..., Any]] = None,
    *,
    n_producers: Optional[int] = None,
    mode: Optional[RunMode | str] = None,
    nslots: Optional[int] = None,
    shuffler_factory: Any = None,
    config: Any = None,
) -> Callable[..., Any]:
    """Decorator running ``func`` as the consumer with producers alongside.

    API parity: reference ``ddl/ddl_env.py:100-128`` appended
    ``(mpi_env, connection)`` to the user function's args; here a single
    :class:`DDL_Env` (topology + consumer connection) is appended.
    Returns ``func``'s return value after all producers have exited.

    ``config`` (a :class:`ddl_tpu.config.LoaderConfig`) supplies topology
    defaults — explicit keyword arguments win over it, and both win over
    the ``DDL_TPU_*`` environment fallbacks inside
    :func:`detect_topology`.

    PROCESS/MULTIHOST modes use ``multiprocessing`` spawn: call the
    decorated main under ``if __name__ == "__main__":`` (standard spawn
    requirement), or the re-imported script will recursively spawn.
    """
    host_id = n_hosts = None
    if config is not None:
        n_producers = (
            config.n_producers if n_producers is None else n_producers
        )
        mode = config.mode if mode is None else mode
        nslots = config.nslots if nslots is None else nslots
        # Host identity (ddl_tpu.cluster): config sentinels (-1/0) mean
        # auto-detect inside detect_topology; explicit values win.
        host_id = config.host_id if getattr(config, "host_id", -1) >= 0 else None
        n_hosts = config.n_hosts if getattr(config, "n_hosts", 0) > 0 else None

    def deco(f: Callable[..., Any]) -> Callable[..., Any]:
        @functools.wraps(f)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            with stage("ddl.pool_start", default_metrics()):
                _export_cluster_knobs(config)
                topology = detect_topology(
                    n_producers, mode, host_id, n_hosts
                )
                depth = nslots or envspec.get("DDL_TPU_NSLOTS")
                _export_cache_knobs(config)
                _export_wire_knobs(config)
                _export_shuffle_knobs(config)
                _export_tune_knobs(config)
                workers = WorkerSet(topology, depth, shuffler_factory)
                env = DDL_Env(
                    topology=topology, connection=workers.connection,
                    workers=workers,
                )
            logger.info(
                "ddl_tpu: %s mode, %d producer(s), instance %d/%d, %d slot(s)",
                topology.mode.value,
                topology.n_producers,
                topology.instance_idx,
                topology.n_instances,
                depth,
            )
            try:
                result = f(*args, env, **kwargs)
            finally:
                # Idempotent: wakes producers still blocked anywhere —
                # pre-handshake (ABORT sentinel) or in a ring wait
                # (shutdown flag). Producers already exited ignore both.
                with stage("ddl.pool_stop", default_metrics()):
                    workers.abort()
                    workers.join(timeout_s=30.0)
            return result

        return wrapper

    if func is not None:
        return deco(func)
    return deco
