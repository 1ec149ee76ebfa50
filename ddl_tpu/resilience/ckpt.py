"""Async integrity-checked train-state checkpoints (ISSUE 14 tentpole).

The legacy path (``ddl_tpu.checkpoint.save_train_state``) is synchronous:
the step loop stalls for the whole serialize + fsync while the Orbax
writer runs.  This module moves everything but the device→host snapshot
off the hot path:

- :class:`AsyncCheckpointer` snapshots the :class:`~ddl_tpu.parallel.
  train.TrainState` into pooled host staging buffers at a step-future
  boundary (``jax.device_get`` blocks only on the step that produced the
  state — the donation-safe point: once the copy lands in OUR buffers,
  the next scan is free to donate the device buffers) and hands the
  snapshot to a background writer thread.  The caller's measured stall
  is the D2H copy alone (``resilience.ckpt_submit``); serialization,
  fsync and rename hide behind training (``resilience.ckpt_write``).
- Every generation is ONE file — ``gen_<step>.ckpt`` — written through
  :func:`ddl_tpu.checkpoint.atomic_file_write` (temp+rename; DDL022)
  and stamped with the ring-slot integrity trailer
  (:mod:`ddl_tpu.integrity`): crc32 over the whole blob plus a
  STEP-DERIVED sequence, so a torn tail fails the CRC and a
  renamed/aliased generation fails the seq check even with an intact
  payload.
- The loader's logical clock (:class:`~ddl_tpu.checkpoint.
  LoaderCheckpoint`) captured at the same window boundary travels
  INSIDE the generation blob — trainer step and loader cursor are
  fenced together, so a crash between two files can never desync the
  resumed data stream from the restored params.  (``loader.json`` is
  still mirrored next to the generations for back-compat tooling; the
  embedded copy is authoritative on restore.)
- Restore walks generations newest→oldest, quarantines unverifiable
  ones (``.quarantined``, the cache-store pattern) and falls back to
  the previous verified generation; exhaustion returns None — a COLD
  START with the ``resilience.ckpt_cold_starts`` counter left loud.

Retention is keep-K: the writer unlinks generations beyond ``keep``
after each successful write (quarantined files are retired on the same
sweep once they age past the window — forensics, not a disk leak).

Chaos: the ``resilience.ckpt_write`` fault site fires on the fully
stamped blob immediately before the atomic write — ``CKPT_CORRUPTION``
flips bytes AFTER the CRC was committed, so the written generation
verifies false on read and the quarantine/fallback ladder is what the
injection exercises.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import threading
import time
import zlib

from ddl_tpu.concurrency import named_condition
from typing import Any, List, Optional, Tuple

import numpy as np

from ddl_tpu import integrity
from ddl_tpu.checkpoint import (
    LoaderCheckpoint,
    atomic_file_write,
    quarantine_path,
)
from ddl_tpu.exceptions import CheckpointError, ShutdownRequested
from ddl_tpu.faults import fault_point
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.parallel.train import TrainState

logger = logging.getLogger("ddl_tpu")

#: Generation-file magic (8 bytes), ahead of the u32 header length.
_MAGIC = b"DDLRES1\0"
_GEN_RE = re.compile(r"^gen_(\d{10})\.ckpt$")

#: Trailer identity for checkpoint blobs (the ring headers carry the
#: 1-based producer index there; 0 is unused by any producer).
_CKPT_PRODUCER = 0


def _gen_name(step: int) -> str:
    return f"gen_{int(step):010d}.ckpt"


def list_generations(directory: str) -> List[Tuple[int, str]]:
    """``[(step, path)]`` of every generation file, oldest first."""
    directory = os.path.abspath(directory)
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = _GEN_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(directory, name)))
    return sorted(out)


def verify_generation(path: str, expect_step: int) -> Optional[str]:
    """Full read-side check of one generation file.  Returns a failure
    description, or None when the blob is intact AND is the generation
    its filename claims (trailer seq == step — a renamed file fails
    here even with an intact payload)."""
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError as e:
        return f"unreadable: {e}"
    min_size = len(_MAGIC) + 4 + integrity.HEADER_BYTES
    if len(raw) < min_size:
        return f"truncated: {len(raw)} bytes < minimum {min_size}"
    view = np.frombuffer(raw, dtype=np.uint8)
    payload_bytes = len(raw) - integrity.HEADER_BYTES
    err = integrity.verify_window(
        view, payload_bytes,
        expect_seq=int(expect_step), expect_producer=_CKPT_PRODUCER,
    )
    if err is not None:
        return err
    if raw[: len(_MAGIC)] != _MAGIC:
        return f"bad file magic {raw[:8]!r}"
    return None


def latest_verified_generation(
    directory: str, quarantine: bool = True,
    metrics: Optional[Metrics] = None,
) -> Optional[Tuple[int, str]]:
    """The newest ``(step, path)`` whose integrity trailer verifies.

    Unverifiable generations are quarantined and skipped — the restore
    falls back to the previous verified generation.  Returns None at
    exhaustion (cold start; the caller makes that loud)."""
    m = metrics or default_metrics()
    for step, path in reversed(list_generations(directory)):
        err = verify_generation(path, step)
        if err is None:
            return step, path
        logger.error(
            "resilience: checkpoint generation %s failed verification "
            "(%s)", path, err,
        )
        if quarantine:
            quarantine_path(path, metrics=m)
        else:
            m.incr("resilience.ckpt_quarantined")
    return None


@dataclasses.dataclass
class RestoredRun:
    """One verified restore: the train state, the loader cursor that
    was fenced to it (None for state-only generations), and the step."""

    state: TrainState
    loader: Optional[LoaderCheckpoint]
    step: int


def _leaves(state: TrainState) -> List[Any]:
    import jax

    return jax.tree_util.tree_leaves(
        {"params": state.params, "opt_state": state.opt_state}
    )


def _leaf_array(leaf: Any) -> np.ndarray:
    """Materialize one state leaf on the host.  The caller copies the
    result into its own staging buffer, so a zero-copy device_get view
    (the CPU client) is fine here — independence from the device
    buffer is established by THAT copy, not this function."""
    import jax

    if isinstance(leaf, (int, float)):
        return np.asarray(leaf)
    return np.asarray(jax.device_get(leaf))


def generation_pieces(
    step: int,
    leaves: List[np.ndarray],
    loader_dict: Optional[dict],
) -> List[np.ndarray]:
    """The stamped generation as the byte pieces it is written from, in
    file order: magic | u32 header-len | header JSON, then each leaf's
    own buffer (a VIEW — no copy of the state), then the 32-byte
    integrity trailer (crc over everything before it, seq = step).
    The writer streams these; for an HBM-filling model one assembled
    blob would be one more host copy of the whole state."""
    header = json.dumps({
        "step": int(step),
        "loader": loader_dict,
        "leaves": [
            {"shape": list(a.shape), "dtype": str(a.dtype)}
            for a in leaves
        ],
    }).encode()
    head = _MAGIC + np.uint32(len(header)).tobytes() + header
    pieces = [np.frombuffer(head, dtype=np.uint8)] + [
        np.ascontiguousarray(a).view(np.uint8).reshape(-1) for a in leaves
    ]
    crc = 0
    for piece in pieces:
        crc = zlib.crc32(piece, crc)
    trailer = np.zeros(integrity.HEADER_BYTES, dtype=np.uint8)
    integrity.write_header(
        trailer, 0, seq=int(step), producer_idx=_CKPT_PRODUCER,
        crc=crc & 0xFFFFFFFF,
    )
    return pieces + [trailer]


def serialize_generation(
    step: int,
    leaves: List[np.ndarray],
    loader_dict: Optional[dict],
) -> np.ndarray:
    """:func:`generation_pieces` assembled into one blob (what a reader
    of the file sees)."""
    return np.concatenate(generation_pieces(step, leaves, loader_dict))


def _parse_generation(path: str) -> Tuple[dict, np.ndarray]:
    """(header dict, payload byte view) of a VERIFIED generation."""
    with open(path, "rb") as f:
        raw = f.read()
    off = len(_MAGIC)
    (hlen,) = np.frombuffer(raw[off : off + 4], dtype=np.uint32)
    off += 4
    header = json.loads(raw[off : off + int(hlen)].decode())
    off += int(hlen)
    payload = np.frombuffer(
        raw, dtype=np.uint8,
        count=len(raw) - integrity.HEADER_BYTES - off, offset=off,
    )
    return header, payload


def restore_latest(
    directory: str,
    like: TrainState,
    metrics: Optional[Metrics] = None,
    found: Optional[Tuple[int, str]] = None,
) -> Optional[RestoredRun]:
    """Restore the newest verified generation onto ``like``'s structure
    and shardings.  Returns None when no verified generation exists
    (cold start — counted ``resilience.ckpt_cold_starts`` ONLY when
    unverifiable generations were present and exhausted, i.e. data was
    lost; an empty directory is a first run, not an incident).

    ``found`` short-circuits the verification scan with a ``(step,
    path)`` the caller already verified via
    :func:`latest_verified_generation` — restart I/O matters exactly
    in the preemption-recovery window, and re-CRC'ing every multi-GB
    blob a second time would double it."""
    import jax

    m = metrics or default_metrics()
    had_any = bool(list_generations(directory))
    if found is None:
        found = latest_verified_generation(directory, metrics=m)
    if found is None:
        if had_any:
            m.incr("resilience.ckpt_cold_starts")
            logger.error(
                "resilience: EVERY checkpoint generation under %s "
                "failed verification — COLD START (all quarantined)",
                directory,
            )
        return None
    step, path = found
    header, payload = _parse_generation(path)
    like_leaves = _leaves(like)
    meta = header["leaves"]
    if len(meta) != len(like_leaves):
        raise CheckpointError(
            f"generation {path} holds {len(meta)} leaves; the current "
            f"model/optimizer has {len(like_leaves)} — geometry changed"
        )
    out, off = [], 0
    for want, leaf in zip(meta, like_leaves):
        arr = np.asarray(leaf) if isinstance(leaf, (int, float)) else leaf
        dtype = np.dtype(arr.dtype)
        shape = tuple(want["shape"])
        if shape != tuple(arr.shape) or want["dtype"] != str(dtype):
            raise CheckpointError(
                f"generation {path} leaf {len(out)}: saved "
                f"{want['dtype']}{shape} vs current "
                f"{dtype}{tuple(arr.shape)} — geometry changed"
            )
        nbytes = int(dtype.itemsize * int(np.prod(shape, dtype=np.int64)))
        host = (
            payload[off : off + nbytes].copy().view(dtype).reshape(shape)
        )
        off += nbytes
        if isinstance(leaf, (int, float)):
            out.append(type(leaf)(host[()]))
        elif hasattr(leaf, "sharding"):
            out.append(jax.device_put(host, leaf.sharding))
        else:
            out.append(host)
    treedef = jax.tree_util.tree_structure(
        {"params": like.params, "opt_state": like.opt_state}
    )
    tree = jax.tree_util.tree_unflatten(treedef, out)
    loader_ck = None
    if header.get("loader"):
        loader_ck = LoaderCheckpoint(**header["loader"])
    m.incr("resilience.ckpt_restores")
    return RestoredRun(
        state=TrainState(
            params=tree["params"], opt_state=tree["opt_state"],
            step=int(header["step"]),
        ),
        loader=loader_ck,
        step=step,
    )


class AsyncCheckpointer:
    """Background-thread checkpoint writer with pooled host staging.

    ``submit`` is the hot-path call: it materializes the state into
    recycled host buffers (the D2H copy — the only stall the step loop
    pays, at the step-future boundary where ``device_get`` blocks just
    on the step that produced the state) and enqueues the write.  The
    writer thread serializes, stamps the integrity trailer, writes
    atomically, mirrors ``loader.json``, and trims retention — all
    under training.  Staging is double-buffered (two buffer sets max,
    the :class:`~ddl_tpu.staging.StagingPool` recycle pattern): a
    writer that falls behind backpressures ``submit`` into SKIPPING a
    periodic checkpoint (counted, the lost-work bound grows by one
    interval) rather than growing host memory without bound; the
    FORCED final checkpoint (:meth:`checkpoint_now`) waits instead.

    The writer thread starts on first use and parks itself (exits)
    after a few idle seconds, so trainers that checkpoint once do not
    pin a thread for their lifetime.
    """

    #: Idle seconds after which the parked writer thread exits.
    _IDLE_EXIT_S = 5.0

    def __init__(
        self,
        directory: str,
        keep: int = 3,
        metrics: Optional[Metrics] = None,
        submit_timeout_s: float = 120.0,
    ):
        if keep < 1:
            raise CheckpointError(f"keep must be >= 1, got {keep}")
        self.directory = os.path.abspath(directory)
        self.keep = int(keep)
        self.metrics = metrics or default_metrics()
        self.submit_timeout_s = float(submit_timeout_s)
        self._cond = named_condition("resilience.ckpt.cv")
        self._queue: List[Tuple[int, List[np.ndarray], Optional[dict]]] = []
        self._free: List[List[np.ndarray]] = []
        self._n_sets = 0
        self._busy = False
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._last_error: Optional[BaseException] = None

    # -- staging (double-buffered host snapshot) ---------------------------

    def _acquire_buffers(
        self, leaves: List[Any], block: bool,
        timeout_s: Optional[float] = None,
    ) -> Optional[List[np.ndarray]]:
        wait_s = self.submit_timeout_s if timeout_s is None else timeout_s
        with self._cond:
            deadline = time.monotonic() + wait_s
            while not self._free and self._n_sets >= 2:
                if not block:
                    return None
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise CheckpointError(
                        "checkpoint writer wedged: no staging buffer "
                        f"freed within {wait_s}s"
                    )
                self._cond.wait(min(0.2, remaining))
            if self._free:
                bufs = self._free.pop()
                if len(bufs) == len(leaves) and all(
                    b.shape == np.shape(l) and b.dtype == getattr(
                        l, "dtype", np.asarray(l).dtype
                    )
                    for b, l in zip(bufs, leaves)
                ):
                    return bufs
                # Geometry changed (new model on one checkpointer):
                # drop the stale set and allocate fresh below.
                self._n_sets -= 1
            self._n_sets += 1
        return [
            np.empty(np.shape(l), dtype=getattr(
                l, "dtype", np.asarray(l).dtype
            ))
            for l in leaves
        ]

    def _release_buffers(self, bufs: List[np.ndarray]) -> None:
        with self._cond:
            self._free.append(bufs)
            self._cond.notify_all()

    # -- the hot-path call -------------------------------------------------

    def submit(
        self,
        state: TrainState,
        loader_ckpt: Optional[LoaderCheckpoint] = None,
        block: bool = False,
        timeout_s: Optional[float] = None,
    ) -> bool:
        """Snapshot ``state`` (+ the fenced loader cursor) and enqueue
        the write.  Returns False when the writer is backed up and the
        checkpoint was SKIPPED (periodic checkpoints only —
        ``block=True``, the forced path, waits for a buffer instead,
        up to ``timeout_s`` when given).
        """
        if self._closed:
            raise CheckpointError("checkpointer is closed")
        t0 = time.perf_counter()
        leaves = _leaves(state)
        bufs = self._acquire_buffers(leaves, block=block,
                                     timeout_s=timeout_s)
        if bufs is None:
            self.metrics.incr("resilience.ckpt_skipped")
            logger.warning(
                "resilience: checkpoint writer backed up — skipping "
                "step-%d checkpoint (lost-work bound grows one interval)",
                int(state.step),
            )
            return False
        # The donation-safe boundary: device_get blocks only on the
        # step futures that produced the state; after the copy below
        # lands, the caller may donate the device buffers freely.
        for buf, leaf in zip(bufs, leaves):
            np.copyto(buf, _leaf_array(leaf), casting="no")
        loader_dict = (
            dataclasses.asdict(loader_ckpt)
            if loader_ckpt is not None
            else None
        )
        with self._cond:
            self._queue.append((int(state.step), bufs, loader_dict))
            self._ensure_writer()
            self._cond.notify_all()
        self.metrics.add_time(
            "resilience.ckpt_submit", time.perf_counter() - t0
        )
        return True

    def checkpoint_now(
        self,
        state: TrainState,
        loader_ckpt: Optional[LoaderCheckpoint] = None,
        timeout_s: float = 60.0,
    ) -> None:
        """The FORCED checkpoint (preemption drain): submit with
        backpressure-wait, then flush to disk; raises
        :class:`CheckpointError` if the generation is not durably
        written inside ``timeout_s`` — ONE budget covering both halves
        (a preemption deadline has no patience for the defaults).  A
        stale failure from an EARLIER periodic write is cleared first:
        this call reports on ITS OWN generation, not on history the
        retention loop already logged."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        with self._cond:
            self._last_error = None
        self.submit(state, loader_ckpt, block=True, timeout_s=timeout_s)
        self.flush(timeout_s=max(0.0, deadline - time.monotonic()))
        self.metrics.incr("resilience.final_ckpts")

    def flush(self, timeout_s: float = 60.0) -> None:
        """Bounded wait for every queued write to land (raises
        :class:`CheckpointError` on timeout or a writer failure).  A
        raised failure is CONSUMED: one failure episode surfaces once,
        and later flushes over subsequent successful writes are clean
        again (a transient ENOSPC hours ago must not poison the
        preemption drain's forced checkpoint)."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        with self._cond:
            while self._queue or self._busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise CheckpointError(
                        f"checkpoint flush timed out after {timeout_s}s "
                        f"({len(self._queue)} generation(s) still queued)"
                    )
                self._cond.wait(min(0.2, remaining))
            err, self._last_error = self._last_error, None
        if err is not None:
            raise CheckpointError(
                f"checkpoint write failed: {type(err).__name__}: {err}"
            ) from err

    def close(self, timeout_s: float = 60.0) -> None:
        if self._closed:
            return
        try:
            self.flush(timeout_s=timeout_s)
        finally:
            self._closed = True
            with self._cond:
                t = self._thread
                self._cond.notify_all()
            if t is not None:
                t.join(timeout_s)

    # -- the writer thread -------------------------------------------------

    def _ensure_writer(self) -> None:
        # Caller holds self._cond.
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="ddl-ckpt-writer", daemon=True
            )
            self._thread.start()

    def _run(self) -> None:
        idle_since = time.monotonic()
        while True:
            with self._cond:
                while not self._queue:
                    if self._closed or (
                        time.monotonic() - idle_since > self._IDLE_EXIT_S
                    ):
                        self._thread = None
                        self._cond.notify_all()
                        return
                    self._cond.wait(0.2)
                step, bufs, loader_dict = self._queue.pop(0)
                self._busy = True
            try:
                with self.metrics.timed("resilience.ckpt_write"):
                    self._write_generation(step, bufs, loader_dict)
                self.metrics.incr("resilience.ckpts")
            except (ShutdownRequested, KeyboardInterrupt):
                with self._cond:
                    self._busy = False
                    self._thread = None
                    self._cond.notify_all()
                raise
            except Exception as e:  # writer must survive one bad write
                self.metrics.incr("resilience.ckpt_write_failures")
                logger.exception(
                    "resilience: checkpoint write for step %d failed", step
                )
                with self._cond:
                    self._last_error = e
            finally:
                self._release_buffers(bufs)
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()
            idle_since = time.monotonic()

    def _write_generation(
        self, step: int, leaves: List[np.ndarray],
        loader_dict: Optional[dict],
    ) -> None:
        pieces = generation_pieces(step, leaves, loader_dict)
        # Chaos site: fires on the STAMPED generation just before the
        # atomic write — CKPT_CORRUPTION flips committed bytes (of the
        # largest leaf: the pieces are the staging buffers themselves)
        # so read-time verification (and the quarantine/fallback
        # ladder) is what the injection exercises.
        fault_point(
            "resilience.ckpt_write",
            view=max(pieces[1:-1], key=lambda p: p.nbytes, default=None),
        )
        path = os.path.join(self.directory, _gen_name(step))
        atomic_file_write(path, pieces)
        self.metrics.set_gauge(
            "resilience.ckpt_bytes", float(sum(p.nbytes for p in pieces))
        )
        if loader_dict is not None:
            # Back-compat mirror: legacy tooling reads loader.json; the
            # EMBEDDED copy above is authoritative on restore (fenced
            # in the same atomic write as the train state).
            atomic_file_write(
                os.path.join(self.directory, "loader.json"),
                json.dumps(loader_dict).encode(),
            )
        self._trim_retention()

    def _trim_retention(self) -> None:
        gens = list_generations(self.directory)
        for step, path in gens[: -self.keep] if len(gens) > self.keep else []:
            try:
                os.unlink(path)
                self.metrics.incr("resilience.ckpt_retired")
            except OSError:
                logger.warning(
                    "resilience: could not retire generation %s", path
                )
        # Quarantined blobs are forensics, not a disk leak: retire them
        # once their step ages past the retained window (recurring
        # corruption must not fill the checkpoint volume and then fail
        # the one forced checkpoint a real preemption depends on).
        if not gens[-self.keep :]:
            return
        oldest_kept = gens[-self.keep :][0][0]
        for name in os.listdir(self.directory):
            if ".ckpt.quarantined" not in name:
                continue
            m = re.match(r"^gen_(\d{10})\.ckpt\.quarantined", name)
            if m and int(m.group(1)) < oldest_kept:
                try:
                    os.unlink(os.path.join(self.directory, name))
                    self.metrics.incr("resilience.ckpt_retired")
                except OSError:
                    logger.warning(
                        "resilience: could not retire quarantined %s",
                        name,
                    )
