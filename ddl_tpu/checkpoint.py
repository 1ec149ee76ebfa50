"""Checkpoint / resume for train state and loader progress.

The reference had no checkpointing at all — its config carried a dead
``checkpt_epoch`` field nothing read (reference ``tests/run_ddl.py:260``,
SURVEY §5.4).  Here both halves of a run are restorable:

- :func:`save_train_state` / :func:`restore_train_state` — the params /
  optimizer pytree via Orbax (sharding-aware; restores onto the current
  mesh layout).  Durable since ISSUE 14: the save lands in a temp
  directory and is renamed into place only after a per-file crc32
  manifest is written, so a ``kill -9`` mid-write can never leave a
  half-written *newest* checkpoint, and
  :func:`latest_verified_step` verifies the manifest on read —
  torn or bit-rotted generations are quarantined (``.quarantined``,
  the cache-store pattern) and the previous verified generation is
  restored instead.
- :class:`LoaderCheckpoint` — the loader's logical clock (epoch, window
  target, batch-in-window, shuffle round), small JSON.  Restoring it
  resynchronises the epoch/rotation counters and — because the global
  shuffle permutation is a pure function of (seed, round) — the
  cross-instance exchange schedule continues exactly where it stopped.

The trainer-side *async* checkpoint tier (background writes, integrity
trailers, preemption drain) lives in :mod:`ddl_tpu.resilience` and
reuses :func:`atomic_file_write` — the ONE sanctioned write primitive
for checkpoint bytes (ddl-lint DDL022 enforces that every configured
checkpoint write routes through it).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import zlib
from typing import Any, Optional

from ddl_tpu.parallel.train import TrainState

#: Per-generation integrity manifest written INSIDE every Orbax step
#: directory before the atomic rename: relpath -> {size, crc32}.
MANIFEST_NAME = "ddl_manifest.json"


def atomic_file_write(path: str, data: Any, fsync: bool = True) -> None:
    """THE checkpoint-byte write primitive: temp file in the target's
    own directory, then ``os.replace`` — readers see the old bytes or
    the new bytes, never a torn mix, and a crash mid-write leaves only
    a ``.tmp.<pid>`` orphan no reader matches.  ``fsync=True`` flushes
    to stable storage before the rename (durability, not just
    atomicity).  Every configured checkpoint write must route through
    here (ddl-lint DDL022).  ``data`` is one bytes-like object, or a
    list of them written back to back (a multi-gigabyte checkpoint is
    streamed from its own buffers, never assembled first)."""
    path = os.path.abspath(path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:  # ddl-lint: disable=DDL022
        # The helper itself is the one sanctioned bare write: the temp
        # name is unmatchable by any reader and replaced atomically.
        for piece in data if isinstance(data, list) else [data]:
            f.write(piece)
        if fsync:
            f.flush()
            os.fsync(f.fileno())
    os.replace(tmp, path)
    if fsync:
        # The rename itself must survive power loss: fsync the
        # DIRECTORY entry too, or a "durably written" final checkpoint
        # can vanish on reboot with only its data blocks persisted.
        try:
            dfd = os.open(os.path.dirname(path), os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass  # platform/filesystem without directory fsync


def _file_crc(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def _write_manifest(step_dir: str) -> None:
    """Stamp ``MANIFEST_NAME`` over every file in ``step_dir`` (size +
    crc32 per file) — the per-generation verification record
    :func:`latest_verified_step` checks on read."""
    entries = {}
    for root, _dirs, files in os.walk(step_dir):
        for name in files:
            # Skip the manifest itself and atomic_file_write's
            # ``<name>.tmp.<pid>`` orphans (a crash mid-manifest in a
            # multi-process save leaves one; it must never be treated
            # as checkpoint payload).
            if name == MANIFEST_NAME or ".tmp." in name:
                continue
            full = os.path.join(root, name)
            rel = os.path.relpath(full, step_dir)
            entries[rel] = {
                "size": os.path.getsize(full), "crc32": _file_crc(full),
            }
    atomic_file_write(
        os.path.join(step_dir, MANIFEST_NAME),
        json.dumps({"version": 1, "files": entries}).encode(),
    )


def verify_step_dir(step_dir: str) -> Optional[str]:
    """Check a step directory against its manifest.  Returns a failure
    description, or None when every file matches (or the directory
    predates manifests — legacy generations stay restorable, logged)."""
    manifest = os.path.join(step_dir, MANIFEST_NAME)
    if not os.path.exists(manifest):
        logging.getLogger("ddl_tpu").warning(
            "checkpoint %s has no integrity manifest (pre-ISSUE-14 "
            "save) — accepting unverified", step_dir,
        )
        _metrics().incr("resilience.ckpt_unverified")
        return None
    try:
        with open(manifest) as f:
            entries = json.load(f)["files"]
    except (OSError, ValueError, KeyError) as e:
        return f"unreadable manifest: {e}"
    for rel, want in entries.items():
        full = os.path.join(step_dir, rel)
        if not os.path.exists(full):
            return f"missing file {rel}"
        size = os.path.getsize(full)
        if size != want["size"]:
            return f"{rel}: size {size} != manifest {want['size']} (torn)"
        if _file_crc(full) != want["crc32"]:
            return f"{rel}: crc32 mismatch (bit rot or partial write)"
    return None


def _metrics():
    from ddl_tpu.observability import metrics as default_metrics

    return default_metrics()


def quarantine_path(path: str, metrics=None) -> str:
    """Rename a corrupt checkpoint (file or step dir) out of the
    restore namespace — ``<path>.quarantined`` (the cache-store
    pattern), uniquified if a previous quarantine already holds the
    name.  Counts ``resilience.ckpt_quarantined`` on ``metrics`` (the
    process default when None).  Returns the quarantine path."""
    dest = f"{path}.quarantined"
    n = 1
    while os.path.exists(dest):
        dest = f"{path}.quarantined.{n}"
        n += 1
    m = metrics if metrics is not None else _metrics()
    try:
        os.replace(path, dest)
    except OSError:
        # A concurrent process (multi-host restore: every rank verifies)
        # may have quarantined it first — losing the race is fine, the
        # generation is out of the namespace either way.
        logging.getLogger("ddl_tpu").warning(
            "checkpoint quarantine rename of %s lost a race", path
        )
        return dest
    m.incr("resilience.ckpt_quarantined")
    logging.getLogger("ddl_tpu").error(
        "checkpoint %s failed verification — quarantined to %s",
        path, dest,
    )
    return dest


def save_train_state(state: TrainState, path: str) -> None:
    """Persist params + optimizer state + step with Orbax — atomically.

    The save lands in a ``.tmp.<pid>`` sibling directory, a per-file
    crc32 manifest is stamped inside it, and only then is the
    directory renamed to ``step_<n>`` — a crash at ANY point leaves
    either the previous generation set intact (a same-step overwrite
    parks the old copy under ``.old.<pid>`` rather than deleting it
    first, so even the rename gap cannot destroy the only copy) plus
    ignorable orphans, or the complete verified new generation.
    Never a half-written newest checkpoint (ISSUE 14 satellite).
    """
    import shutil

    import jax
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    final = os.path.join(path, f"step_{state.step}")
    if jax.process_count() > 1:
        # Multi-process runs save COLLECTIVELY: every process must pass
        # the SAME path (Orbax coordinates shard writes + finalization
        # through its own tmp-dir + commit protocol, which is already
        # atomic).  Only the manifest is ours — process 0 stamps it
        # after the collective save completes.
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(
                final,
                {"params": state.params, "opt_state": state.opt_state,
                 "step": state.step},
                force=True,
            )
        if jax.process_index() == 0:
            _write_manifest(final)
        return
    tmp = f"{final}.tmp.{os.getpid()}"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(
            tmp,
            {"params": state.params, "opt_state": state.opt_state,
             "step": state.step},
            force=True,
        )
    _write_manifest(tmp)
    old = None
    if os.path.exists(final):
        # force=True semantics: replace the same-step generation whole —
        # but PARK the old one first instead of rmtree'ing it, so a
        # crash between "old gone" and "new renamed in" cannot destroy
        # the only copy of this step (the parked name matches no
        # reader; it is deleted only after the new generation is live).
        old = f"{final}.old.{os.getpid()}"
        if os.path.exists(old):
            shutil.rmtree(old)
        os.replace(final, old)
    os.replace(tmp, final)
    if old is not None:
        shutil.rmtree(old, ignore_errors=True)


def latest_verified_step(
    path: str, quarantine: bool = True
) -> Optional[int]:
    """The newest step under ``path`` whose integrity manifest
    verifies.  Unverifiable generations are quarantined
    (``.quarantined``) and SKIPPED — a torn newest checkpoint falls
    back to the previous verified one instead of poisoning the resume
    (ISSUE 14 satellite); exhaustion returns None (cold start), with
    the quarantine counter left loud in the metrics/logs.  Temp
    (``.tmp.<pid>``) and quarantined directories never match the
    ``step_<n>`` pattern, so partial writes are invisible here by
    construction."""
    path = os.path.abspath(path)
    if not os.path.isdir(path):
        return None
    steps = sorted(
        (
            int(d.split("_", 1)[1])
            for d in os.listdir(path)
            if d.startswith("step_") and d.split("_", 1)[1].isdigit()
        ),
        reverse=True,
    )
    for step in steps:
        step_dir = os.path.join(path, f"step_{step}")
        err = verify_step_dir(step_dir)
        if err is None:
            return step
        logging.getLogger("ddl_tpu").error(
            "checkpoint step_%d failed verification (%s)", step, err
        )
        if quarantine:
            quarantine_path(step_dir)
    return None


#: Back-compat alias — every pre-ISSUE-14 caller now verifies on read.
latest_step = latest_verified_step


def restore_train_state(
    path: str, like: TrainState, step: Optional[int] = None
) -> TrainState:
    """Restore the newest VERIFIED checkpoint under ``path``.

    ``like`` provides the target structure AND shardings — restore lands
    directly on the current mesh (resharding if the mesh changed shape),
    the standard Orbax pattern.  Generations failing their integrity
    manifest are quarantined and the previous verified one restores
    instead (:func:`latest_verified_step`).  Pass ``step`` when the
    caller already verified it — the manifest scan reads and CRCs every
    checkpoint byte, and doing that twice doubles restart I/O.
    """
    import orbax.checkpoint as ocp

    if step is None:
        step = latest_verified_step(path)
    if step is None:
        raise FileNotFoundError(f"no verified checkpoints under {path!r}")
    template = {"params": like.params, "opt_state": like.opt_state,
                "step": like.step}
    with ocp.StandardCheckpointer() as ckptr:
        restored = ckptr.restore(
            os.path.join(os.path.abspath(path), f"step_{step}"), template
        )
    return TrainState(
        params=restored["params"],
        opt_state=restored["opt_state"],
        step=int(restored["step"]),
    )


def adopt_cache_manifest(path: str) -> bool:
    """Pre-spawn cache warm-start: read ONLY the cache manifest from the
    loader checkpoint at ``path`` and adopt it.

    PROCESS/MULTIHOST producer workers inherit their environment at
    spawn, so for them the manifest must be adopted **before**
    ``distributed_dataloader`` builds the worker set — call this at the
    top of a resuming main, before the decorator runs.  (THREAD mode
    does not need it: ``LoaderCheckpoint.apply`` attaches the tier to
    the live shared store.)  Returns False — resuming with a cold
    cache, never an error — when the checkpoint is missing/unreadable,
    carries no manifest, or the manifest is refused (schema mismatch,
    vanished directory, conflicting live tier).
    """
    try:
        ck = LoaderCheckpoint.load(path)
    except (OSError, ValueError, TypeError, KeyError):
        return False
    if not ck.cache_spill_dir:
        return False
    from ddl_tpu import cache as cache_mod

    return cache_mod.adopt_manifest(ck.cache_spill_dir, ck.cache_key_schema)


@dataclasses.dataclass
class LoaderCheckpoint:
    """The loader's logical position (enough to resume deterministically).

    ``shuffle_round`` tracks the global-shuffle schedule: pass the active
    shuffler (``DeviceGlobalShuffler`` or ``ThreadExchangeShuffler`` — any
    object with a ``_round`` counter) to ``capture``/``apply`` and, because
    the exchange permutation is a pure function of (seed, round), the
    cross-instance schedule continues exactly where it stopped.
    """

    epoch: int = 0
    target: int = 0
    batches_in_window: int = 0
    shuffle_round: int = 0
    #: Cache manifest (ISSUE 4): the shard cache's disk-tier directory
    #: plus the key-schema version it was written under.  ``apply``
    #: points the resumed run's cache at this spill dir
    #: (:func:`ddl_tpu.cache.adopt_manifest`), so epoch-1-after-resume
    #: reads decoded shards from disk instead of refetching from source.
    #: A schema mismatch is refused — content-addressed keys make stale
    #: entries unmatchable anyway, but a refused adoption is cheaper
    #: than a tier of guaranteed misses.
    cache_spill_dir: Optional[str] = None
    cache_key_schema: int = 0
    #: Cluster membership fence (ddl_tpu.cluster): the view epoch at
    #: capture time.  ``apply`` fast-forwards a resumed supervisor past
    #: it so views minted after restore can never be mistaken for
    #: pre-checkpoint ones (shard adoptions are epoch-fenced).
    cluster_epoch: int = 0

    @staticmethod
    def capture(
        loader: Any, shuffler: Any = None, cache: Any = None,
        cluster: Any = None,
    ) -> "LoaderCheckpoint":
        round_ = 0
        if shuffler is not None:
            # Public accessor first (the rejoin/exchange_round contract);
            # the private-field fallback keeps old duck-typed shufflers
            # working.
            round_ = getattr(
                shuffler, "exchange_round", getattr(shuffler, "_round", 0)
            )
        from ddl_tpu import cache as cache_mod

        # The active store only — capture must not build a store (or
        # decide cache policy) as a side effect of checkpointing.
        store = cache if cache is not None else cache_mod.active_store()
        spill = getattr(store, "spill_dir", None) if store else None
        # ``cluster`` is a ClusterSupervisor or a bare ClusterView.
        cluster_epoch = 0
        if cluster is not None:
            view = getattr(cluster, "view", cluster)
            cluster_epoch = int(getattr(view, "epoch", 0))
        return LoaderCheckpoint(
            cluster_epoch=cluster_epoch,
            epoch=loader._epoch,
            target=loader._target,
            batches_in_window=loader._batches_in_window,
            shuffle_round=int(round_),
            cache_spill_dir=spill,
            cache_key_schema=(
                cache_mod.KEY_SCHEMA_VERSION if spill else 0
            ),
        )

    def apply(
        self, loader: Any, shuffler: Any = None, cluster: Any = None
    ) -> None:
        loader._epoch = self.epoch
        loader._target = self.target
        loader._batches_in_window = self.batches_in_window
        if cluster is not None and self.cluster_epoch:
            restore = getattr(cluster, "restore_epoch", None)
            if callable(restore):
                restore(self.cluster_epoch)
        if self.cache_spill_dir:
            from ddl_tpu import cache as cache_mod

            if not cache_mod.adopt_manifest(
                self.cache_spill_dir, self.cache_key_schema
            ):
                logging.getLogger("ddl_tpu").warning(
                    "checkpoint cache manifest not adopted (%s, schema %d)"
                    " — resuming with a cold cache",
                    self.cache_spill_dir, self.cache_key_schema,
                )
        if shuffler is not None:
            rejoin = getattr(shuffler, "rejoin", None)
            if callable(rejoin):
                # The documented re-entry hook — a custom shuffler's real
                # round state may not be named _round.
                rejoin(self.shuffle_round)
            else:
                shuffler._round = self.shuffle_round

    def save(self, path: str) -> None:
        # Atomic temp+rename (DDL022): the loader clock is read by every
        # resume — a torn half-written cursor would desynchronize the
        # data stream from the train state it is fenced to.
        atomic_file_write(
            path, json.dumps(dataclasses.asdict(self)).encode()
        )

    @staticmethod
    def load(path: str) -> "LoaderCheckpoint":
        with open(path) as f:
            return LoaderCheckpoint(**json.load(f))
