"""Staged ingest engine: reusable staging buffers + background transfer.

The inline ingest path pays two hidden costs per batch (SURVEY §8.3, the
host→HBM hop): a fresh ``np.array(copy=True)`` allocation — page faults +
allocator churn at exactly the per-batch cadence — and the fact that the
copy runs on the *consumer* thread, serialized against the compute it is
supposed to feed.  This module removes both:

- :class:`StagingPool` — shape/dtype-keyed recycled host buffers.  A
  staging copy lands in a pooled buffer; the buffer returns to the pool
  once the ``device_put`` sourcing it has completed, checked by a
  deferred non-blocking sweep (``jax.Array.is_ready``), never a blocking
  wait.  The per-batch allocation disappears after warmup
  (``staging.pool_hits`` / ``staging.pool_misses`` count it).
- :class:`TransferExecutor` — ONE background worker draining a bounded
  queue of copy→transfer jobs, so the slot→staging memcpy and the
  ``device_put`` dispatch overlap the caller's compute.  Each job yields
  a :class:`StagedTransfer` handle with two completion edges:
  ``copy_done`` (the transfer source no longer references the ring slot
  — the consumer may release the slot back to the producer EARLY) and
  ``ready`` (the device value is available to pop).

``DDL_TPU_STAGED=0`` disables the whole engine — every consumer falls
back to the previous inline copy path (the escape hatch for
debugging).

Safety note: recycling a staging buffer is only sound when ``device_put``
*copies* its host source.  The CPU PJRT client aliases a compatible host
buffer instead — and it does so PER BUFFER (64-byte-aligned allocations
alias, unaligned ones copy; measured on this attach), so no one-time
probe can decide.  The pool therefore checks each transfer's device
buffers against the staging buffer's address range
(``unsafe_buffer_pointer``) and permanently DROPS any buffer the client
aliased instead of recycling it (the client keeps the memory alive; the
pool counts the loss in ``staging.pool_alias_drops``).  On accelerators
the put is a genuine host→HBM transfer, the check never fires, and every
buffer recycles.
"""

from __future__ import annotations

import collections
import logging
import os
import threading

from ddl_tpu import envspec
from ddl_tpu.concurrency import named_condition, named_lock
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from ddl_tpu import integrity
from ddl_tpu.exceptions import (
    IntegrityError,
    ShutdownRequested,
    StallTimeoutError,
)
from ddl_tpu.faults import fault_point
from ddl_tpu.obs import spans as obs_spans
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.profiling import stage

logger = logging.getLogger("ddl_tpu")

#: Per-(shape, dtype) cap on retained free buffers.  Beyond it a released
#: buffer is dropped to the allocator — a pool must bound worst-case host
#: memory (lookahead depth + in-flight transfers is the working set).
DEFAULT_POOL_CAP = 8

#: Bounded executor queue depth: backpressure instead of unbounded
#: host-memory growth when the producer side outruns the device link.
DEFAULT_QUEUE_DEPTH = 4

#: Bounded retries per staged job phase (copy / transfer) before the
#: degradation ladder falls back to the sanctioned inline path
#: (``DDL_TPU_STAGING_RETRIES`` overrides; docs/ROBUSTNESS.md).
DEFAULT_MAX_RETRIES = 2

#: Exponential-backoff base/cap between retries.  The cap keeps a
#: persistently failing link from turning each window into a minutes-long
#: stall before the fallback engages.
_RETRY_BACKOFF_BASE_S = 0.05
_RETRY_BACKOFF_CAP_S = 1.0


def _flat_u8(arr: np.ndarray) -> Optional[np.ndarray]:
    """Flat uint8 alias of an array (for byte-level fault injection);
    None when the layout does not allow one."""
    try:
        return arr.reshape(-1).view(np.uint8)
    except (ValueError, AttributeError):
        return None


def staged_enabled(override: Optional[bool] = None) -> bool:
    """The ``DDL_TPU_STAGED`` gate (default ON; ``0`` = inline path)."""
    from ddl_tpu.utils import env_flag

    return env_flag("DDL_TPU_STAGED", override)


def shm_staging_enabled(override: Optional[bool] = None) -> bool:
    """The ``DDL_TPU_SHM_STAGING`` gate (default ON): lets staged
    window-stream jobs ALIAS the shm ring slot as their transfer source
    (no slot→staging memcpy) on clients whose ``device_put`` genuinely
    copies host memory.  ``0`` restores the copying pool everywhere."""
    from ddl_tpu.utils import env_flag

    return env_flag("DDL_TPU_SHM_STAGING", override)


class StagingPool:
    """Thread-safe pool of reusable host staging buffers.

    ``acquire`` hands out a buffer of exactly (shape, dtype) — recycled
    when one is free (``staging.pool_hits``), freshly allocated otherwise
    (``staging.pool_misses``).  Callers return buffers either directly
    (:meth:`release`) or deferred against an in-flight device transfer
    (:meth:`recycle_when_ready` + :meth:`sweep`).
    """

    def __init__(
        self,
        metrics: Optional[Metrics] = None,
        max_per_key: Optional[int] = None,
    ):
        self.metrics = metrics or default_metrics()
        self.max_per_key = (
            envspec.get("DDL_TPU_STAGING_POOL_CAP")
            if max_per_key is None
            else max_per_key
        )
        self._lock = named_lock("staging.pool")
        # Free-lists hold at most max_per_key buffers per geometry key
        # (release() drops beyond the cap), and a run's batch geometries
        # are a small closed set — bounded by construction.
        self._free: Dict[Tuple[Tuple[int, ...], Any], List[np.ndarray]] = {}  # ddl-lint: disable=DDL013
        #: FIFO of (device value to poll, buffer, dispatch timestamp).
        self._inflight: Deque[Tuple[Any, np.ndarray, float]] = (
            collections.deque()
        )
        #: (address, shape, dtype) triples PROVEN to be copied (not
        #: aliased) by the client — skips the per-transfer alias walk.
        self._copied_keys: set = set()

    # -- acquire / release -------------------------------------------------

    def acquire(self, shape: Tuple[int, ...], dtype: Any) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            free = self._free.get(key)
            if free:
                buf = free.pop()
                hit = True
            else:
                buf = None
                hit = False
        if hit:
            self.metrics.incr("staging.pool_hits")
            return buf  # type: ignore[return-value]
        self.metrics.incr("staging.pool_misses")
        return np.empty(key[0], key[1])

    def release(self, buf: np.ndarray) -> None:
        """Return a buffer nothing references anymore."""
        key = (buf.shape, buf.dtype)
        with self._lock:
            free = self._free.setdefault(key, [])
            if len(free) < self.max_per_key:
                free.append(buf)

    def set_max_per_key(self, max_per_key: int) -> None:
        """Retune the per-geometry free-list cap live (ddl_tpu.tune).

        Shrinking trims each free-list immediately — the controller's
        revert must actually return memory, not wait for organic churn;
        growing simply lets future releases keep more.
        """
        cap = max(1, int(max_per_key))
        with self._lock:
            self.max_per_key = cap
            for free in self._free.values():
                del free[cap:]

    def recycle_when_ready(self, buf: np.ndarray, dev: Any) -> None:
        """Queue ``buf`` for recycling once ``dev``'s transfer completes.

        Non-blocking — the actual recycling happens in a later
        :meth:`sweep` (deferred ``on_ready`` check), so no caller ever
        waits on the link just to return memory.  A buffer the client
        ALIASED into ``dev`` (CPU zero-copy put) is dropped instead: the
        device value lives in that memory for as long as it exists, so
        reuse would corrupt it.
        """
        key = (buf.ctypes.data, buf.shape, buf.dtype)
        with self._lock:
            known_copied = key in self._copied_keys
        if not known_copied:
            if _may_alias(dev, buf):
                self.metrics.incr("staging.pool_alias_drops")
                return
            # The client's zero-copy decision is deterministic per
            # (address, layout) — alignment-based — so a buffer proven
            # copied once never needs the shard-pointer walk again
            # (measured ~0.1 ms per transfer).  Only the safe verdict is
            # cached: an address that once aliased may be freed and
            # reused, so it is re-checked every time.
            with self._lock:
                if len(self._copied_keys) > 4096:
                    self._copied_keys.clear()
                self._copied_keys.add(key)
        with self._lock:
            self._inflight.append((dev, buf, time.perf_counter()))

    def sweep(self, block: bool = False) -> int:
        """Recycle the FIFO prefix of in-flight buffers whose transfer
        has completed (``is_ready``); with ``block=True`` (shutdown /
        flush) wait for all of them.  Returns the number recycled.

        FIFO-prefix only: transfers dispatch in order on one stream, so a
        not-yet-ready head means the tail is not worth polling.  The
        observed dispatch→ready span accumulates into ``ingest.transfer``
        (an upper bound — sweep cadence adds slack — but an honest
        overlap measure where a dispatch-side timer would read ~0).
        """
        if not block and len(self._inflight) < 2:
            # Amortized fast path (no locks, no is_ready call): let a
            # lone in-flight transfer ride until the next submission —
            # the pool cap absorbs the one-deep recycling lag, and the
            # per-batch steal path stays lean.  len() on a deque is a
            # single GIL-atomic read.
            return 0
        done = 0
        while True:
            with self._lock:
                if not self._inflight:
                    break
                dev, buf, t0 = self._inflight[0]
                if not block and not _is_ready(dev):
                    break
                self._inflight.popleft()
            if block:
                _block_ready(dev)
            self.metrics.add_time(
                "ingest.transfer", time.perf_counter() - t0
            )
            self.release(buf)
            done += 1
        with self._lock:
            depth = len(self._inflight)
        self.metrics.set_gauge("staging.inflight", float(depth))
        return done

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "free_buffers": float(
                    sum(len(v) for v in self._free.values())
                ),
                "inflight": float(len(self._inflight)),
            }


def _may_alias(dev: Any, buf: np.ndarray) -> bool:
    """Does any of ``dev``'s device buffers live inside ``buf``'s memory?

    The CPU PJRT client zero-copies 64-byte-aligned host arrays into
    device buffers (per-buffer, not per-client — measured), so this is
    checked per transfer via buffer addresses.  Anything unprovable
    (missing API, donated buffers) counts as aliasing — dropping a
    recyclable buffer costs one allocation; recycling an aliased one
    corrupts served data.
    """
    lo = buf.ctypes.data
    hi = lo + buf.nbytes
    try:
        shards = getattr(dev, "addressable_shards", None)
        if shards is None:
            return True
        for sh in shards:
            ptr = sh.data.unsafe_buffer_pointer()
            if lo <= ptr < hi:
                return True
        return False
    except (ShutdownRequested, KeyboardInterrupt):
        raise
    except Exception:
        # Unprovable (API missing on this client/version, deleted
        # buffer): err toward "aliases" — the cost is one dropped
        # recyclable buffer, never corruption.
        return True


def _is_ready(dev: Any) -> bool:
    is_ready = getattr(dev, "is_ready", None)
    if is_ready is None:
        return False  # unknown client: only a blocking sweep recycles
    return bool(is_ready())


def _block_ready(dev: Any) -> None:
    import jax

    jax.block_until_ready(dev)


class StagedTransfer:
    """Handle for one staged copy→transfer job.

    ``copy_done`` fires when the staging copy finished — the job no
    longer references the caller's source buffer (a ring-slot view), so
    the slot may be released early.  ``ready`` fires when the device
    value can be popped with :meth:`result`.

    ``salvage`` is the degradation-ladder handoff: when the transfer
    exhausted its bounded retries, the staged host buffer (whose copy
    DID land, and was CRC-verified when the caller asked) is retained
    here so the consumer can re-run the window down the sanctioned
    inline path — the failure costs latency, never data.
    """

    __slots__ = ("copy_done", "ready", "error", "salvage", "_value", "_job")

    def __init__(self) -> None:
        self.copy_done = threading.Event()
        self.ready = threading.Event()
        self.error: Optional[BaseException] = None
        self.salvage: Optional[np.ndarray] = None
        self._value: Any = None
        self._job: Any = None  # back-ref for work stealing

    def result(self, timeout_s: Optional[float] = None) -> Any:
        """The transferred device value; raises the job's error (e.g.
        :class:`ShutdownRequested` when the executor closed mid-queue)."""
        if not self.ready.wait(timeout_s):
            # StallTimeoutError (which is also a TimeoutError) so every
            # deadline failure on a framework path shares one hierarchy.
            raise StallTimeoutError(
                f"staged transfer not ready within {timeout_s}s"
            )
        if self.error is not None:
            raise self.error
        return self._value

    @property
    def worker_executed(self) -> bool:
        """Did the background worker (vs a stealing consumer) run this?"""
        return bool(self._job is not None and self._job.worker)


#: A transfer callable: staging buffer -> (consumer value, pollable
#: device array backing it).  The second element drives buffer recycling.
TransferFn = Callable[[np.ndarray], Tuple[Any, Any]]


class _Job:
    __slots__ = (
        "handle", "src", "transfer", "expected_crc", "claimed", "worker",
        "alias_src", "span_key",
    )

    def __init__(
        self,
        handle: StagedTransfer,
        src: np.ndarray,
        transfer: TransferFn,
        expected_crc: Optional[int] = None,
        alias_src: bool = False,
        span_key: Optional[Tuple[int, int]] = None,
    ):
        self.handle = handle
        self.src = src
        self.transfer = transfer
        #: Committed payload CRC (ddl_tpu.integrity): when set, the
        #: staging copy is re-verified against it before the source slot
        #: may be released — the second verification point of the
        #: end-to-end pipeline.
        self.expected_crc = expected_crc
        #: Zero-copy staging (shm-backed): the transfer sources ``src``
        #: — a live ring-slot view — directly, with no slot→staging
        #: memcpy.  ``copy_done`` then fires only once the device value
        #: no longer reads host memory (transfer completion), and the
        #: per-transfer alias check guards clients that would zero-copy
        #: the slot pages into the device array.
        self.alias_src = alias_src
        #: Window identity (producer_idx, seq) for lifecycle spans
        #: (ddl_tpu.obs): the copy/transfer phases run on whichever
        #: thread claims the job, so the key must travel WITH it.
        self.span_key = span_key
        self.claimed = False
        #: True when the background worker (not a stealing consumer)
        #: executed the job — the signal adaptive consumers use to judge
        #: whether offloading is actually buying overlap on this host.
        self.worker = False


class TransferExecutor:
    """Background worker + work-stealing for copy→transfer jobs.

    One worker thread drains a bounded deque from the NEWEST end; a
    consumer that needs a job's result NOW *steals* it from the oldest
    end — claims it and runs it on its own thread (:meth:`complete`).
    The ends are deliberately opposite: the consumer always wants the
    oldest job next, so a FIFO worker would race it for exactly that
    job and the consumer would pay worker-scheduling latency per pop
    (measured ~2 ms/批 on a saturated 2-core host).  With opposed ends
    each thread owns its own item: the consumer's path costs what the
    inline path costs, and the worker's lookahead work is pure overlap
    — staged degrades to inline-plus-one-claim-check when the host has
    no spare cycles, and genuinely overlaps when it does.

    The bounded deque backpressures :meth:`submit` instead of
    ballooning host memory when the producer side outruns the link.
    """

    def __init__(
        self,
        pool: StagingPool,
        metrics: Optional[Metrics] = None,
        max_queue: Optional[int] = None,
    ):
        self.pool = pool
        self.metrics = metrics or default_metrics()
        depth = (
            envspec.get("DDL_TPU_STAGING_QUEUE")
            if max_queue is None
            else max_queue
        )
        self._max_queue = max(1, depth)
        self._max_retries = envspec.get("DDL_TPU_STAGING_RETRIES")
        #: Set when a job exhausted its retry budget: the degradation
        #: ladder's "stop staging, go inline" latch, consulted by the
        #: lookahead consumers via ``StagedIngestEngine.faulted``.
        self.faulted = False
        #: Latched when a client PROVED it zero-copy-aliases host pages
        #: into device values (the per-transfer unsafe_buffer_pointer
        #: walk fired on an alias job): every later alias submission
        #: silently degrades to the copying pool — correctness first,
        #: the memcpy saving only where it is safe.
        self.alias_unsafe = False
        self._dq: Deque[_Job] = collections.deque()
        self._cv = named_condition("staging.executor.cv")
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        #: The job the worker is currently executing (plain attribute:
        #: single writer, GIL-atomic reads) — flush_copies waits on it.
        self._active: Optional[_Job] = None
        #: Queue depth at which the worker starts taking jobs (from the
        #: newest end).  2 leaves the oldest job for the consumer's
        #: steal; tests set 1 to make the worker eager/deterministic.
        #: Clamped to max_queue: a threshold the queue can never reach
        #: (DDL_TPU_STAGING_QUEUE=1) would deadlock submit against a
        #: worker that never drains.
        self.worker_min_depth = min(2, self._max_queue)

    def set_max_queue(self, max_queue: int) -> None:
        """Retune the submission-queue bound live (ddl_tpu.tune).

        Re-clamps ``worker_min_depth`` (the deadlock guard above must
        track the new bound) and wakes every waiter: submitters blocked
        against the old, smaller bound re-check and proceed immediately
        when the queue grew.
        """
        with self._cv:
            self._max_queue = max(1, int(max_queue))
            self.worker_min_depth = min(
                self.worker_min_depth, self._max_queue
            )
            self._cv.notify_all()

    def submit(
        self,
        src: np.ndarray,
        transfer: TransferFn,
        expected_crc: Optional[int] = None,
        alias_src: bool = False,
        span_key: Optional[Tuple[int, int]] = None,
    ) -> StagedTransfer:
        """Enqueue one job: copy ``src`` into a pooled buffer, then run
        ``transfer`` on it.  ``src`` may be a live ring-slot view — the
        caller must keep the slot acquired until ``handle.copy_done``.
        ``expected_crc`` (the committed window CRC) re-verifies the copy
        before that release.  Blocks when the queue is full
        (backpressure).

        ``alias_src=True`` (shm-backed staging) skips the slot→staging
        memcpy entirely: the transfer sources ``src`` directly and
        ``copy_done`` fires at transfer COMPLETION — the caller holds
        the slot for the DMA instead of one memcpy, and pays zero host
        copies.  Ignored (degraded to the copying pool) once a client
        proved it aliases host pages (``alias_unsafe``)."""
        handle = StagedTransfer()
        job = _Job(
            handle, src, transfer, expected_crc,
            alias_src=alias_src and not self.alias_unsafe,
            span_key=span_key,
        )
        handle._job = job
        with self._cv:
            if self._closed:
                raise ShutdownRequested("transfer executor is closed")
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="ddl-staging", daemon=True
                )
                self._thread.start()
            while len(self._dq) >= self._max_queue and not self._closed:
                self._cv.wait(0.5)
            if self._closed:
                raise ShutdownRequested("transfer executor is closed")
            self._dq.append(job)
            depth = len(self._dq)
            if depth >= self.worker_min_depth:
                # Waking the worker below its take-threshold is a pure
                # context switch per submit.
                self._cv.notify_all()
        # Per-submit publish (one uncontended lock, ~µs): .max tracking
        # happens inside set_gauge, so the high-water survives a
        # mid-run Metrics.reset() — an executor-local peak would stop
        # re-publishing after the steady-state span reset wiped it.
        self.metrics.set_gauge("staging.queue_depth", float(depth))
        return handle

    def complete(
        self, handle: StagedTransfer, timeout_s: Optional[float] = None
    ) -> Any:
        """The handle's result, stealing its job if still unclaimed.

        The pop primitive for FIFO consumers: never blocks on worker
        scheduling latency — an unstarted job runs inline on the caller;
        a worker-claimed one is a genuine (short) wait, counted into
        ``ingest.stall`` (a stolen execution is work, not a stall).
        """
        job = handle._job
        if job is not None and self._claim(job):
            self._execute(job)
            # The stealing thread must also recycle: in the regime where
            # the consumer steals every job (no spare cores), the worker
            # never runs and a worker-only sweep would leak every buffer
            # into the inflight deque (all-miss pool, unbounded hosts).
            self.pool.sweep()
            return handle.result(timeout_s)
        with self.metrics.timed("ingest.stall"):
            return handle.result(timeout_s)

    def flush_copies(self, timeout_s: float = 30.0) -> None:
        """Force every submitted job's STAGING COPY to completion.

        The slot-safety barrier: a consumer about to release a ring slot
        that queued jobs may still view calls this first — unclaimed
        jobs are claimed and run inline (their copies land in pooled
        buffers before the producer can overwrite the slot), and a job
        the worker has in flight is waited on via its ``copy_done``
        edge.  Cheap when everything already completed (one empty-deque
        check).
        """
        while True:
            with self._cv:
                job = self._dq.popleft() if self._dq else None
            if job is None:
                break
            if self._claim(job):
                self._execute(job)
        active = self._active
        if active is not None and not active.handle.copy_done.wait(timeout_s):
            # A barrier that silently fails would let the caller release
            # a slot the worker is still reading — corruption, not delay.
            raise StallTimeoutError(
                f"staging copy still in flight after {timeout_s}s; "
                "cannot safely release the source slot"
            )

    def has_capacity(self) -> bool:
        """Would :meth:`submit` accept a job without blocking right now?

        A single GIL-atomic deque read — lookahead producers poll this
        so their non-blocking deepening rounds never park inside
        submit's backpressure wait.
        """
        return len(self._dq) < self._max_queue

    def _claim(self, job: _Job) -> bool:
        """Atomically take ownership of a queued job (and unqueue it)."""
        with self._cv:
            if job.claimed:
                return False
            job.claimed = True
            try:
                self._dq.remove(job)
                if len(self._dq) == self._max_queue - 1:
                    # Freed a FULL queue: a submit may be blocked on
                    # capacity.  Any other wake here is a pure context
                    # switch (the worker re-checks its threshold and
                    # sleeps again) — measured ~0.2 ms per steal.
                    self._cv.notify_all()
            except ValueError:
                pass  # already popped by the worker
            return True

    def close(self) -> None:
        """Stop the worker; pending jobs fail with ShutdownRequested.

        Safe to call twice and from any thread.  Buffers of completed
        transfers are swept back (blocking) so a closed executor leaks
        nothing.
        """
        with self._cv:
            if self._closed:
                return
            self._closed = True
            t = self._thread
            self._cv.notify_all()
        if t is not None:
            t.join(timeout=30.0)
        # Fail whatever nobody claimed (the worker is gone; a concurrent
        # complete() that won a claim still finishes its job normally).
        while True:
            with self._cv:
                job = self._dq.popleft() if self._dq else None
            if job is None:
                break
            if not self._claim(job):
                continue
            job.handle.error = ShutdownRequested(
                "transfer executor closed mid-queue"
            )
            job.handle.copy_done.set()
            job.handle.ready.set()
        self.pool.sweep(block=True)

    @property
    def closed(self) -> bool:
        return self._closed

    # -- execution ---------------------------------------------------------

    def _retrying(self, phase: str, fn):
        """Run one job phase with bounded exponential-backoff retries.

        The degradation ladder's first rung: transient failures (flaky
        link, injected chaos) are retried ``_max_retries`` times with
        doubling backoff; exhaustion marks the executor ``faulted``
        (later windows route inline) and re-raises for the caller's
        salvage path.  Shutdown signals are never retried.
        """
        delay = _RETRY_BACKOFF_BASE_S
        for attempt in range(self._max_retries + 1):
            try:
                return fn()
            except (ShutdownRequested, KeyboardInterrupt):
                raise
            except Exception as e:
                if attempt >= self._max_retries or self._closed:
                    self.faulted = True
                    raise
                self.metrics.incr("staging.retries")
                logger.warning(
                    "staged %s failed (%s: %s) — retry %d/%d after %.2fs",
                    phase, type(e).__name__, e, attempt + 1,
                    self._max_retries, delay,
                )
                time.sleep(delay)
                delay = min(delay * 2, _RETRY_BACKOFF_CAP_S)

    def _execute(self, job: _Job) -> None:
        """Run one claimed job to completion (worker or stealing thread)."""
        handle = job.handle
        key = job.span_key or (None, None)

        def copy_phase():
            fault_point("staging.copy", view=_flat_u8(job.src))
            np.copyto(buf, job.src, casting="no")
            if job.expected_crc is not None:
                # Second integrity verification point: the slot is still
                # held, so a torn/overwritten copy is caught BEFORE the
                # early release hands the slot back to the producer (a
                # retry re-copies from the still-valid slot).
                flat = _flat_u8(buf)
                got = integrity.window_crc(flat) if flat is not None else None
                if got is not None and got != job.expected_crc:
                    self.metrics.incr("integrity.staging_verify_failures")
                    raise IntegrityError(
                        f"staging copy crc32 0x{got:08x} != committed "
                        f"0x{job.expected_crc:08x} (torn slot read)"
                    )

        try:
            if job.alias_src:
                # Alias path: the stage covers dispatch AND the
                # completion wait (the slot is the live source).
                with stage("ddl.staging_transfer", key=key):
                    handle._value = self._execute_alias(job)
                return
            buf = self.pool.acquire(job.src.shape, job.src.dtype)
            with stage("ddl.staging_copy", self.metrics, key):
                self._retrying("copy", copy_phase)
            handle.copy_done.set()  # source released: slot may free
            try:
                with stage("ddl.staging_transfer", key=key):
                    value, base = self._retrying(
                        "transfer", lambda: self._transfer_phase(job, buf)
                    )
            except (ShutdownRequested, KeyboardInterrupt):
                raise
            except Exception:
                # The copy landed (and verified): retain it so the
                # consumer can redo this window on the inline path —
                # degradation, not data loss.  The buffer leaves the
                # pool's custody for good.
                handle.salvage = buf
                raise
            self.pool.recycle_when_ready(buf, base)
            handle._value = value
        except (ShutdownRequested, KeyboardInterrupt) as e:
            # Clean teardown racing the queue: deliver to the consumer
            # (result() re-raises).  Swallowing here would hang result()
            # forever.
            handle.error = e
        except Exception as e:
            handle.error = e
        finally:
            handle.copy_done.set()
            handle.ready.set()

    def _transfer_phase(self, job: _Job, src: np.ndarray) -> Any:
        """One attempt at a job's H2D dispatch from ``src`` (the staging
        buffer, or the ring slot on the alias path), inside the caller's
        ``ddl.staging_transfer`` stage.  Runs on whichever thread
        claimed the job; the window identity is published for the
        nested emission sites (put_window / batch put / ICI fan-out)
        that cannot see it."""
        fault_point("staging.transfer")
        obs_spans.set_window(*(job.span_key or (None, None)))
        try:
            return job.transfer(src)
        finally:
            obs_spans.clear_window()

    def _execute_alias(self, job: _Job) -> Any:
        """Run one zero-copy (shm-backed) job: transfer straight from the
        ring-slot view, no staging memcpy.

        The slot stays the transfer's live source, so ``copy_done`` (the
        caller's release edge, set by ``_execute``'s ``finally``) may
        only fire once the device value stopped reading host memory:
        after a completion wait on a genuinely-copying client, or after
        the copying-pool fallback on one that aliased the slot pages
        into the device array (checked per transfer with the same
        ``unsafe_buffer_pointer`` walk the pool uses — the check firing
        latches ``alias_unsafe`` so later jobs skip straight to the
        pool).  The wait runs on the background worker (or a stealing
        consumer that needed the value NOW anyway), never adds a host
        memcpy, and its span lands in ``ingest.transfer``.
        """
        def salvage_slot(buf: Optional[np.ndarray] = None) -> None:
            """Terminal transfer failure with the slot STILL HELD (this
            runs before ``_execute``'s ``finally`` fires ``copy_done``
            and lets the consumer release it): retain a host copy of the
            window so ``complete_or_salvage`` can redo it down the
            sanctioned inline path — the alias path must keep the
            copying path's degradation-ladder guarantee that a link
            failure costs latency, never data."""
            if buf is None:
                buf = self.pool.acquire(job.src.shape, job.src.dtype)
                np.copyto(buf, job.src, casting="no")
            job.handle.salvage = buf

        t0 = time.perf_counter()
        try:
            value, base = self._retrying(
                "transfer", lambda: self._transfer_phase(job, job.src)
            )
        except (ShutdownRequested, KeyboardInterrupt):
            raise
        except Exception:
            salvage_slot()
            raise
        if _may_alias(base, job.src):
            # The client zero-copied the slot pages into the device
            # value: releasing the slot would let the producer overwrite
            # data the device array still reads.  Redo through the
            # copying pool (the discarded first value holds no readers)
            # and stop submitting alias jobs on this client.
            self.alias_unsafe = True
            self.metrics.incr("staging.alias_fallbacks")
            logger.warning(
                "shm-backed staging: device client aliases host pages; "
                "falling back to the copying staging pool"
            )
            buf = self.pool.acquire(job.src.shape, job.src.dtype)
            np.copyto(buf, job.src, casting="no")
            try:
                value, base = self._retrying(
                    "transfer", lambda: job.transfer(buf)
                )
            except (ShutdownRequested, KeyboardInterrupt):
                raise
            except Exception:
                salvage_slot(buf)  # the copy already landed: keep it
                raise
            self.pool.recycle_when_ready(buf, base)
            return value
        _block_ready(base)
        self.metrics.add_time("ingest.transfer", time.perf_counter() - t0)
        self.metrics.incr("staging.alias_windows")
        return value

    def _run(self) -> None:
        while True:
            with self._cv:
                # Take work only at worker_min_depth (default 2: the
                # oldest job is ALWAYS left for the consumer to steal),
                # and from the NEWEST end.  A worker that raced the
                # consumer for the job it needs next would add
                # worker-scheduling latency to every pop on a saturated
                # host — this way the consumer's path costs what inline
                # costs, and whatever the worker finishes is pure
                # overlap on top.
                while (
                    len(self._dq) < self.worker_min_depth
                    and not self._closed
                ):
                    self._cv.wait(0.5)
                if self._closed:
                    break
                job = self._dq.pop()
                # Published under the SAME lock as the pop: at every
                # instant a live job is visible in the deque OR in
                # _active, so flush_copies cannot slip between the two
                # and miss a job about to read a releasing slot.
                self._active = job
                if len(self._dq) == self._max_queue - 1:
                    self._cv.notify_all()  # freed a full queue
            if not self._claim_popped(job):
                self._active = None
                continue
            job.worker = True
            self._execute(job)
            self._active = None
            # Opportunistic recycle of completed transfers — off the
            # consumer's critical path by construction (we ARE the
            # background thread).
            self.pool.sweep()

    def _claim_popped(self, job: _Job) -> bool:
        """Claim a job the worker already removed from the deque."""
        with self._cv:
            if job.claimed:
                return False
            job.claimed = True
            return True


class StagedIngestEngine:
    """Pool + executor pair owned by one :class:`DeviceIngestor`."""

    def __init__(self, metrics: Optional[Metrics] = None):
        self.metrics = metrics or default_metrics()
        self.pool = StagingPool(metrics=self.metrics)
        self.executor = TransferExecutor(self.pool, metrics=self.metrics)
        # Adaptive-offload state (see PrefetchIterator): lives HERE, not
        # on the iterator, because consumers build a fresh iterator per
        # epoch — per-iterator state would forget a starved worker every
        # few batches and re-pay the probe cost each epoch.
        self.stolen_streak = 0
        self.direct_left = 0

    @property
    def faulted(self) -> bool:
        """True once a staged job exhausted its retry budget: the
        degradation ladder routes every later window down the sanctioned
        inline path (windows()/PrefetchIterator consult this)."""
        return self.executor.faulted

    def complete_or_salvage(
        self,
        handle: StagedTransfer,
        inline_put: Callable[[np.ndarray], Any],
        timeout_s: Optional[float] = None,
    ) -> Any:
        """:meth:`TransferExecutor.complete` with the degradation-ladder
        fallback: a handle whose transfer exhausted its retries (but
        whose verified staging copy survives on ``handle.salvage``) is
        redone through ``inline_put`` — the failure costs latency, never
        data.  Shutdown signals and deadline expiries propagate; errors
        with nothing to salvage re-raise.  The one implementation for
        both lookahead consumers (``windows()`` and
        :class:`~ddl_tpu.ingest.PrefetchIterator`)."""
        try:
            return self.executor.complete(handle, timeout_s)
        except (ShutdownRequested, KeyboardInterrupt, StallTimeoutError):
            raise
        except Exception as e:
            if handle.salvage is None:
                raise
            logger.error(
                "staged transfer failed after retries (%s: %s) — "
                "falling back to the inline path", type(e).__name__, e,
            )
            self.metrics.incr("staging.inline_fallbacks")
            return inline_put(handle.salvage)

    def submit(
        self,
        src: np.ndarray,
        transfer: TransferFn,
        expected_crc: Optional[int] = None,
        alias_src: bool = False,
        span_key: Optional[Tuple[int, int]] = None,
    ) -> StagedTransfer:
        return self.executor.submit(
            src, transfer, expected_crc, alias_src=alias_src,
            span_key=span_key,
        )

    def close(self) -> None:
        self.executor.close()
