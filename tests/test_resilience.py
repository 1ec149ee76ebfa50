"""Preemption-tolerant training (ISSUE 14): async integrity-checked
checkpoints, graceful drain-on-notice, deterministic mid-epoch resume.

Three layers:

- **Checkpoint units** — AsyncCheckpointer roundtrip/retention/backlog,
  generation verification (truncation, rename-aliasing, chaos-injected
  corruption → quarantine + fallback → cold start at exhaustion), and
  the legacy Orbax path's new manifest verification + atomic save (the
  ISSUE 14 satellites' regression tests).
- **Revocation units** — ``FairShareScheduler.revoke_inflight``: typed
  wake-ups for waiting admits, SLO-bounded wait for granted windows,
  neighbour isolation, rejoin via ``clear_revocations``.
- **Drain e2e (the chaos rows)** — a PREEMPT_NOTICE / SIGTERM /
  env-knob notice mid-``fit`` drains within the deadline, closes
  producers cleanly (``watchdog.failures == 0``), and the restarted
  run's window stream and loss curve are BYTE-IDENTICAL to an
  uninterrupted run — in THREAD mode and PROCESS mode over the forced
  python shm ring.
"""

import dataclasses
import os
import signal
import threading
import time
import zlib

import jax
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from ddl_tpu import faults
from ddl_tpu.checkpoint import LoaderCheckpoint
from ddl_tpu.exceptions import CheckpointError, WindowsRevoked
from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
from ddl_tpu.models import pointnet
from ddl_tpu.observability import Metrics
from ddl_tpu.parallel.mesh import make_mesh
from ddl_tpu.readers import ArrayProducer
from ddl_tpu.resilience import (
    AsyncCheckpointer,
    PreemptionGuard,
    latest_verified_generation,
    list_generations,
    restore_latest,
)
from ddl_tpu.trainer import Trainer


def _make_trainer(tmp_path=None, **kw):
    cfg = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
    mesh = make_mesh({"dp": 8})
    kw.setdefault("checkpoint_dir",
                  str(tmp_path / "ckpt") if tmp_path else None)
    return Trainer(
        loss_fn=lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
        optimizer=optax.adam(1e-2),
        mesh=mesh,
        param_specs=pointnet.param_specs(cfg),
        init_params=pointnet.init_params(cfg, jax.random.key(0)),
        batch_spec=P(("dp",)),
        **kw,
    )


def _producer(seed):
    data = np.random.default_rng(seed).random((256, 6)).astype(np.float32)
    return ArrayProducer(data, window_size=64, splits=(3, 2, 1))


def _state(step=0):
    """A small real TrainState (adam over pointnet params)."""
    t = _make_trainer()
    st = t._init_fn(t._init_params)
    return dataclasses.replace(st, step=step)


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# AsyncCheckpointer units


class TestAsyncCheckpointer:
    def test_submit_flush_restore_roundtrip(self, tmp_path):
        m = Metrics()
        cp = AsyncCheckpointer(str(tmp_path), metrics=m)
        st = _state(step=7)
        cursor = LoaderCheckpoint(epoch=3, target=1, shuffle_round=2)
        assert cp.submit(st, cursor)
        cp.flush()
        restored = restore_latest(str(tmp_path), like=_state(), metrics=m)
        assert restored is not None and restored.step == 7
        assert restored.state.step == 7
        assert _tree_equal(restored.state.params, st.params)
        assert _tree_equal(restored.state.opt_state, st.opt_state)
        assert restored.loader is not None
        assert (restored.loader.epoch, restored.loader.target,
                restored.loader.shuffle_round) == (3, 1, 2)
        # loader.json mirrored for legacy tooling, from the same dict.
        mirrored = LoaderCheckpoint.load(str(tmp_path / "loader.json"))
        assert mirrored.epoch == 3
        # The measured hot-path stall is the submit (D2H snapshot).
        assert m.timer("resilience.ckpt_submit").count == 1
        assert m.counter("resilience.ckpts") == 1
        cp.close()

    def test_keep_k_retention(self, tmp_path):
        cp = AsyncCheckpointer(str(tmp_path), keep=2)
        st = _state()
        for step in (1, 2, 3, 4, 5):
            # block=True: retention semantics, not backpressure, is
            # under test (a non-blocking submit may SKIP when both
            # staging sets are still queued — see the next test).
            cp.submit(dataclasses.replace(st, step=step), block=True)
        cp.flush()
        cp.close()
        assert [s for s, _ in list_generations(str(tmp_path))] == [4, 5]

    def test_backpressure_skips_periodic_checkpoint(self, tmp_path):
        m = Metrics()
        cp = AsyncCheckpointer(str(tmp_path), metrics=m)
        st = _state()
        outcomes = [
            cp.submit(dataclasses.replace(st, step=s)) for s in range(1, 6)
        ]
        cp.flush()
        cp.close()
        # A backed-up writer SKIPS periodic checkpoints (bounded host
        # memory; the lost-work bound grows one interval) — it never
        # queues without bound.
        if not all(outcomes):
            assert m.counter("resilience.ckpt_skipped") >= 1

    def test_checkpoint_now_is_durable(self, tmp_path):
        cp = AsyncCheckpointer(str(tmp_path), metrics=Metrics())
        cp.checkpoint_now(_state(step=9))
        # No flush needed: the forced path returns only once on disk.
        found = latest_verified_generation(str(tmp_path))
        assert found is not None and found[0] == 9
        cp.close()

    def test_truncated_generation_falls_back(self, tmp_path):
        cp = AsyncCheckpointer(str(tmp_path))
        st = _state()
        cp.submit(dataclasses.replace(st, step=1))
        cp.submit(dataclasses.replace(st, step=2))
        cp.flush()
        cp.close()
        gens = dict(list_generations(str(tmp_path)))
        size = os.path.getsize(gens[2])
        with open(gens[2], "r+b") as f:
            f.truncate(size // 2)  # torn tail: trailer gone mid-file
        m = Metrics()
        restored = restore_latest(str(tmp_path), like=_state(), metrics=m)
        assert restored is not None and restored.step == 1
        assert m.counter("resilience.ckpt_quarantined") == 1
        assert any(
            name.endswith(".quarantined")
            for name in os.listdir(tmp_path)
        )

    def test_renamed_generation_fails_seq_check(self, tmp_path):
        """An aliased checkpoint (intact payload under the wrong step
        name) fails the step-derived trailer seq and is quarantined."""
        import shutil

        cp = AsyncCheckpointer(str(tmp_path))
        st = _state()
        cp.submit(dataclasses.replace(st, step=3))
        cp.flush()
        cp.close()
        (_, path3), = list_generations(str(tmp_path))
        shutil.copy(path3, str(tmp_path / "gen_0000000009.ckpt"))
        m = Metrics()
        restored = restore_latest(str(tmp_path), like=_state(), metrics=m)
        # The alias (step 9) was quarantined; the true gen 3 restored.
        assert restored is not None and restored.step == 3
        assert m.counter("resilience.ckpt_quarantined") == 1

    def test_exhaustion_is_loud_cold_start(self, tmp_path):
        cp = AsyncCheckpointer(str(tmp_path))
        cp.submit(_state(step=1))
        cp.flush()
        cp.close()
        (_, path), = list_generations(str(tmp_path))
        with open(path, "r+b") as f:
            f.seek(40)
            f.write(b"\xff" * 8)  # payload corruption, CRC mismatch
        m = Metrics()
        assert restore_latest(str(tmp_path), like=_state(), metrics=m) is None
        assert m.counter("resilience.ckpt_cold_starts") == 1
        assert m.counter("resilience.ckpt_quarantined") == 1

    def test_empty_dir_is_first_run_not_incident(self, tmp_path):
        m = Metrics()
        assert restore_latest(str(tmp_path), like=_state(), metrics=m) is None
        assert m.counter("resilience.ckpt_cold_starts") == 0

    def test_ckpt_corruption_chaos_site(self, tmp_path, crc_fold):
        """CKPT_CORRUPTION at resilience.ckpt_write flips bytes AFTER
        the CRC stamp: the written generation verifies false on read,
        quarantines, and the previous verified generation restores —
        the production ladder is what the injection exercises.  A
        generation is verified by ``integrity.verify_window``, so a
        large one folds its CRC over spans (``crc_fold``) like a large
        window."""
        plan = FaultPlan([
            FaultSpec("resilience.ckpt_write", FaultKind.CKPT_CORRUPTION,
                      at=2, param=16),
        ])
        m = Metrics()
        cp = AsyncCheckpointer(str(tmp_path), metrics=m)
        st = _state()
        with faults.armed(plan):
            cp.submit(dataclasses.replace(st, step=1))
            cp.flush()
            cp.submit(dataclasses.replace(st, step=2))
            cp.flush()
        cp.close()
        assert plan.fired
        restored = restore_latest(str(tmp_path), like=_state(), metrics=m)
        assert restored is not None and restored.step == 1
        assert m.counter("resilience.ckpt_quarantined") == 1

    def test_writer_failure_surfaces_in_flush(self, tmp_path):
        blocker = tmp_path / "as_file"
        blocker.write_text("not a directory")
        cp = AsyncCheckpointer(str(blocker / "sub"), metrics=Metrics())
        cp.submit(_state(step=1))
        with pytest.raises(CheckpointError, match="write failed"):
            cp.flush(timeout_s=10.0)

    def test_geometry_change_is_typed_error(self, tmp_path):
        cp = AsyncCheckpointer(str(tmp_path))
        cp.checkpoint_now(_state(step=1))
        cp.close()
        cfg = pointnet.PointNetConfig(n_inputs=5, n_outputs=1)
        other = Trainer(
            loss_fn=lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
            optimizer=optax.adam(1e-2),
            mesh=make_mesh({"dp": 8}),
            param_specs=pointnet.param_specs(cfg),
            init_params=pointnet.init_params(cfg, jax.random.key(0)),
            batch_spec=P(("dp",)),
        )
        like = other._init_fn(other._init_params)
        with pytest.raises(CheckpointError, match="geometry"):
            restore_latest(str(tmp_path), like=like)


# ---------------------------------------------------------------------------
# Legacy (Orbax) path satellites: manifest verification + atomic save


class TestLegacyCheckpointVerification:
    def test_truncated_newest_resumes_from_previous(self, tmp_path):
        """THE satellite regression test: truncate the newest Orbax
        checkpoint mid-file — resume must pick the previous one, with
        the torn generation quarantined."""
        import json

        from ddl_tpu.checkpoint import (
            MANIFEST_NAME,
            latest_verified_step,
            restore_train_state,
            save_train_state,
        )

        st = _state()
        save_train_state(dataclasses.replace(st, step=1), str(tmp_path))
        save_train_state(dataclasses.replace(st, step=2), str(tmp_path))
        step2 = tmp_path / "step_2"
        with open(step2 / MANIFEST_NAME) as f:
            entries = json.load(f)["files"]
        victim = max(entries, key=lambda rel: entries[rel]["size"])
        vpath = step2 / victim
        with open(vpath, "r+b") as f:
            f.truncate(max(0, os.path.getsize(vpath) // 2))
        assert latest_verified_step(str(tmp_path)) == 1
        restored = restore_train_state(str(tmp_path), like=_state())
        assert restored.step == 1
        assert any(
            name.startswith("step_2.quarantined")
            for name in os.listdir(tmp_path)
        )

    def test_save_writes_manifest_and_verifies(self, tmp_path):
        from ddl_tpu.checkpoint import (
            MANIFEST_NAME,
            save_train_state,
            verify_step_dir,
        )

        save_train_state(_state(step=4), str(tmp_path))
        step_dir = tmp_path / "step_4"
        assert (step_dir / MANIFEST_NAME).exists()
        assert verify_step_dir(str(step_dir)) is None

    def test_tmp_orphan_never_matches(self, tmp_path):
        """A kill -9 mid-save leaves only a .tmp.<pid> sibling — it can
        never be mistaken for the newest checkpoint."""
        from ddl_tpu.checkpoint import latest_verified_step

        (tmp_path / "step_9.tmp.1234").mkdir(parents=True)
        assert latest_verified_step(str(tmp_path)) is None

    def test_legacy_dir_without_manifest_stays_restorable(self, tmp_path):
        from ddl_tpu.checkpoint import (
            MANIFEST_NAME,
            latest_verified_step,
            save_train_state,
        )

        save_train_state(_state(step=3), str(tmp_path))
        os.unlink(tmp_path / "step_3" / MANIFEST_NAME)
        # Pre-ISSUE-14 generation: accepted (unverifiable != torn).
        assert latest_verified_step(str(tmp_path)) == 3

    def test_atomic_file_write_survives_interrupted_rename(
        self, tmp_path, monkeypatch
    ):
        from ddl_tpu import checkpoint as ckpt_mod

        target = tmp_path / "loader.json"
        ckpt_mod.atomic_file_write(str(target), b'{"epoch": 1}')
        real_replace = os.replace

        def boom(src, dst):
            raise OSError("simulated crash at rename")

        monkeypatch.setattr(ckpt_mod.os, "replace", boom)
        with pytest.raises(OSError):
            ckpt_mod.atomic_file_write(str(target), b'{"epoch": 2}')
        monkeypatch.setattr(ckpt_mod.os, "replace", real_replace)
        # The reader still sees the previous COMPLETE content.
        assert b'"epoch": 1' in target.read_bytes()


# ---------------------------------------------------------------------------
# Admission revocation (ROADMAP 1(c): revoke under an SLO)


class TestRevocation:
    def _controller(self):
        from ddl_tpu.serve import AdmissionController, TenantSpec

        m = Metrics()
        ctl = AdmissionController(metrics=m)
        return ctl, m, TenantSpec

    def test_waiting_admit_wakes_with_typed_revocation(self):
        ctl, m, TenantSpec = self._controller()
        # A byte budget driven negative blocks the next admit on the
        # wall clock — the waiter parks until revoked.
        hog = ctl.register(TenantSpec("hog", byte_budget_per_s=1.0))
        hog.admit(1.0)
        hog.note_served(1 << 20)
        caught = []

        def waiter():
            try:
                hog.admit(30.0)
            except WindowsRevoked as e:
                caught.append(e)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.1)
        assert ctl.revoke_inflight(0.5) is True
        t.join(5.0)
        assert not t.is_alive() and len(caught) == 1
        assert m.counter("serve.revoked_waiters") == 1
        assert m.counter("serve.revocations") == 1
        assert m.counter("ingest.hog.revocations") == 1

    def test_granted_window_waits_out_slo(self):
        ctl, m, TenantSpec = self._controller()
        ten = ctl.register(TenantSpec("a"))
        ten.admit(1.0)  # granted; note_served pending -> in flight

        def finish():
            time.sleep(0.15)
            ten.note_served(1024)

        t = threading.Thread(target=finish)
        t.start()
        assert ctl.revoke_inflight(2.0) is True  # drained inside SLO
        t.join(5.0)
        assert m.counter("serve.revoked_inflight") == 0

    def test_slo_expiry_proceeds_and_counts(self):
        ctl, m, TenantSpec = self._controller()
        ten = ctl.register(TenantSpec("a"))
        ten.admit(1.0)  # in flight, never finished
        assert ctl.revoke_inflight(0.2) is False
        assert m.counter("serve.revoked_inflight") == 1

    def test_aborted_grant_releases_inflight(self):
        """A grant whose ring acquire fails (the loader's abort path)
        must release its in-flight slot — a leaked grant would make
        every later revoke burn its full SLO on a phantom window."""
        ctl, m, TenantSpec = self._controller()
        ten = ctl.register(TenantSpec("a"))
        ten.admit(1.0)
        ten.note_aborted()  # the acquire failed; nothing was served
        t0 = time.monotonic()
        assert ctl.revoke_inflight(5.0) is True
        assert time.monotonic() - t0 < 1.0  # no SLO burned
        assert m.counter("serve.revoked_inflight") == 0

    def test_neighbours_unaffected_and_rejoin(self):
        from ddl_tpu.exceptions import WindowsRevoked as WR

        ctl, m, TenantSpec = self._controller()
        a = ctl.register(TenantSpec("a"))
        b = ctl.register(TenantSpec("b"))
        assert a.revoke_inflight(0.1) is True  # only tenant a
        with pytest.raises(WR):
            a.admit(0.5)
        b.admit(0.5)  # the neighbour admits untouched
        b.note_served(64)
        a.clear_revocations()  # the rejoin edge
        a.admit(0.5)
        a.note_served(64)


# ---------------------------------------------------------------------------
# PreemptionGuard units


class TestPreemptionGuard:
    def test_drain_ladder_order_and_metrics(self):
        calls = []

        class FakeAdmission:
            def revoke_inflight(self, slo_s):
                calls.append(("revoke", slo_s))
                return True

        class FakeCluster:
            def drain_host(self, host_id):
                calls.append(("drain_host", host_id))

        m = Metrics()
        g = PreemptionGuard(
            deadline_s=30.0, cluster=FakeCluster(), host_id=2,
            admission=FakeAdmission(), revoke_slo_s=0.5, metrics=m,
        )
        g.notify("test")
        ok = g.drain(
            final_checkpoint=lambda: calls.append(("ckpt",)),
            shutdown=lambda: calls.append(("shutdown",)),
        )
        assert ok is True and g.drained
        assert [c[0] for c in calls] == [
            "ckpt", "revoke", "drain_host", "shutdown",
        ]
        assert calls[1][1] <= 0.5  # SLO clipped to the remaining budget
        assert m.counter("resilience.drains") == 1
        assert m.counter("resilience.notices") == 1
        assert m.gauge("resilience.drain_within_deadline") == 1.0

    def test_blown_deadline_skips_hygiene_keeps_checkpoint(self):
        now = [0.0]

        def clock():
            return now[0]

        calls = []

        class SlowCkpt:
            def __call__(self):
                calls.append("ckpt")
                now[0] += 100.0  # the checkpoint ate the whole budget

        class FakeAdmission:
            def revoke_inflight(self, slo_s):
                calls.append("revoke")

        m = Metrics()
        g = PreemptionGuard(
            deadline_s=30.0, admission=FakeAdmission(), metrics=m,
            clock=clock,
        )
        g.notify("test")
        ok = g.drain(final_checkpoint=SlowCkpt(),
                     shutdown=lambda: calls.append("shutdown"))
        assert ok is False
        assert calls == ["ckpt"]  # hygiene rungs skipped, loudly
        assert m.counter("resilience.drain_rungs_skipped") >= 1

    def test_env_notice_carries_deadline(self, monkeypatch):
        g = PreemptionGuard(deadline_s=30.0, metrics=Metrics())
        monkeypatch.setenv("DDL_TPU_PREEMPT_NOTICE", "12.5")
        assert g.poll() is True
        assert g.pending and g.deadline_s == 12.5

    def test_fault_site_notice(self):
        plan = FaultPlan([
            FaultSpec("resilience.notice", FaultKind.PREEMPT_NOTICE,
                      at=3, param=7.0),
        ])
        g = PreemptionGuard(deadline_s=30.0, metrics=Metrics())
        with faults.armed(plan):
            assert g.poll() is False
            assert g.poll() is False
            assert g.poll() is True  # the 3rd boundary
        assert g.deadline_s == 7.0

    def test_signal_install_uninstall_restores_handler(self):
        prev = signal.getsignal(signal.SIGTERM)
        g = PreemptionGuard(deadline_s=5.0, metrics=Metrics())
        with g:
            assert signal.getsignal(signal.SIGTERM) == g._on_sigterm
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 5.0
            while not g.pending and time.monotonic() < deadline:
                time.sleep(0.01)
            assert g.pending
        assert signal.getsignal(signal.SIGTERM) == prev


# ---------------------------------------------------------------------------
# Drain-on-notice e2e: the tier-1 chaos rows


def _run_fit(tmp_path, seed, n_epochs, guard=None, metrics=None,
             mode="thread", subdir="ckpt", every=1, **fit_kw):
    """One window-streamed fit recording per-window CRCs; returns
    (FitResult, crcs)."""
    crcs = []

    def hook(win):
        crcs.append(zlib.crc32(np.asarray(win).tobytes()))
        return win

    trainer = _make_trainer(
        checkpoint_dir=str(tmp_path / subdir),
        checkpoint_every_epochs=every,
        preemption_guard=guard,
        metrics=metrics or Metrics(),
        watchdog_respawn=False,
    )
    res = trainer.fit(
        _producer(seed), batch_size=16, n_epochs=n_epochs, n_producers=2,
        mode=mode, output="jax", window_stream=True, window_hook=hook,
        **fit_kw,
    )
    return res, crcs


class TestDrainOnNotice:
    N = 6  # windows (== epochs) in the uninterrupted run

    def _uninterrupted(self, tmp_path, seed):
        res, crcs = _run_fit(tmp_path, seed, self.N, subdir="ckpt_ref")
        assert len(crcs) == self.N
        return res, crcs

    def _assert_identical_resume(self, tmp_path, seed, res_b, crcs_b,
                                 drained_at):
        res_a, crcs_a = self._uninterrupted(tmp_path, seed)
        assert res_b.preempted is True
        assert len(crcs_b) == drained_at
        assert res_b.losses == res_a.losses[:drained_at]
        # Restart: byte-identical window stream, bit-exact loss curve.
        m_c = Metrics()
        res_c, crcs_c = _run_fit(tmp_path, seed, self.N, metrics=m_c)
        assert res_c.resumed_from_epoch == drained_at
        assert crcs_b + crcs_c == crcs_a
        assert res_b.losses + res_c.losses == res_a.losses
        # Zero steps lost: the forced drain checkpoint landed at the
        # notice boundary (<= the interval is the HARD-KILL bound; a
        # graceful drain does strictly better).
        assert res_c.state.step == res_a.state.step
        assert _tree_equal(res_c.state.params, res_a.state.params)

    def test_preempt_notice_drains_and_resumes_byte_identical(
        self, tmp_path
    ):
        seed, drained_at = 1234, 4
        plan = FaultPlan([
            FaultSpec("resilience.notice", FaultKind.PREEMPT_NOTICE,
                      at=drained_at),
        ])
        m_b = Metrics()
        g = PreemptionGuard(deadline_s=60.0, metrics=m_b)
        with faults.armed(plan):
            res_b, crcs_b = _run_fit(
                tmp_path, seed, self.N, guard=g, metrics=m_b, every=2,
            )
        assert plan.fired and g.drained
        assert m_b.counter("watchdog.failures") == 0
        assert m_b.counter("resilience.final_ckpts") == 1
        assert m_b.gauge("resilience.drain_within_deadline") == 1.0
        self._assert_identical_resume(
            tmp_path, seed, res_b, crcs_b, drained_at
        )

    def test_no_notice_loses_at_most_one_interval(self, tmp_path):
        """The hard-kill bound: a run that ends with no drain resumes
        from its newest durable generation, at most one checkpoint
        interval back, and replays from there byte-identically."""
        seed, died_at, every = 4321, 5, 2
        res_a, crcs_a = self._uninterrupted(tmp_path, seed)
        _run_fit(tmp_path, seed, died_at, every=every)  # no notice, no drain
        res_c, crcs_c = _run_fit(tmp_path, seed, self.N, every=every)
        resumed = res_c.resumed_from_epoch
        assert 0 <= died_at - resumed <= every
        assert crcs_c == crcs_a[resumed:]
        assert res_c.losses == res_a.losses[resumed:]
        assert res_c.state.step == res_a.state.step

    def test_sigterm_mid_fit_thread_mode(self, tmp_path):
        seed, drained_at = 77, 3
        m_b = Metrics()
        g = PreemptionGuard(deadline_s=60.0, metrics=m_b)
        fired = []

        def hook_sigterm(win):
            if len(fired) + 1 == drained_at:
                # Deterministic delivery: the signal lands while window
                # `drained_at` is mid-flight; the guard drains at the
                # window boundary that follows.
                os.kill(os.getpid(), signal.SIGTERM)
            fired.append(1)
            return win

        crcs_b = []

        def hook(win):
            crcs_b.append(zlib.crc32(np.asarray(win).tobytes()))
            return hook_sigterm(win)

        trainer = _make_trainer(
            checkpoint_dir=str(tmp_path / "ckpt"),
            preemption_guard=g, metrics=m_b,
        )
        with g:
            res_b = trainer.fit(
                _producer(seed), batch_size=16, n_epochs=self.N,
                n_producers=2, mode="thread", output="jax",
                window_stream=True, window_hook=hook,
            )
        assert m_b.counter("watchdog.failures") == 0
        self._assert_identical_resume(
            tmp_path, seed, res_b, crcs_b, drained_at
        )

    def test_sigterm_process_mode_forced_py_ring(
        self, tmp_path, monkeypatch
    ):
        """The PROCESS-mode chaos row: SIGTERM mid-fit over spawned
        producer processes on the forced python shm ring — drain within
        the deadline, producers closed cleanly (zero watchdog
        failures), resumed run byte-identical."""
        monkeypatch.setenv("DDL_TPU_FORCE_PY_RING", "1")
        seed, drained_at = 9, 2
        m_b = Metrics()
        g = PreemptionGuard(deadline_s=120.0, metrics=m_b)
        crcs_b = []

        def hook(win):
            crcs_b.append(zlib.crc32(np.asarray(win).tobytes()))
            if len(crcs_b) == drained_at:
                os.kill(os.getpid(), signal.SIGTERM)
            return win

        trainer = _make_trainer(
            checkpoint_dir=str(tmp_path / "ckpt"),
            preemption_guard=g, metrics=m_b,
        )
        with g:
            res_b = trainer.fit(
                _producer(seed), batch_size=16, n_epochs=self.N,
                n_producers=2, mode="process", output="jax",
                window_stream=True, window_hook=hook,
            )
        assert res_b.preempted and g.drained
        assert m_b.counter("watchdog.failures") == 0
        assert m_b.gauge("resilience.drain_within_deadline") == 1.0
        # Resume in PROCESS mode too: the full cross-process loop.
        res_c, crcs_c = _run_fit(
            tmp_path, seed, self.N, metrics=Metrics(), mode="process",
        )
        assert res_c.resumed_from_epoch == drained_at
        # THREAD/PROCESS byte identity is proven elsewhere; here the
        # PROCESS-resumed stream must continue the PROCESS run exactly.
        assert len(crcs_b) == drained_at
        assert len(crcs_c) == self.N - drained_at
        ref, crcs_ref = _run_fit(
            tmp_path, seed, self.N, metrics=Metrics(), mode="process",
            subdir="ckpt_ref_proc",
        )
        assert crcs_b + crcs_c == crcs_ref
        assert res_b.losses + res_c.losses == ref.losses

    def test_env_notice_drains_first_boundary(self, tmp_path, monkeypatch):
        m = Metrics()
        g = PreemptionGuard(deadline_s=60.0, metrics=m)
        monkeypatch.setenv("DDL_TPU_PREEMPT_NOTICE", "1")
        res, crcs = _run_fit(tmp_path, 5, self.N, guard=g, metrics=m)
        assert res.preempted is True and len(crcs) == 1
        assert m.counter("resilience.notices") == 1

    def test_sync_checkpoint_trainer_drains_too(self, tmp_path):
        """The legacy synchronous checkpoint path honors the guard: the
        drain's forced checkpoint rides save_train_state (atomic +
        manifest) and the resumed run continues correctly."""
        seed, drained_at = 21, 3
        plan = FaultPlan([
            FaultSpec("resilience.notice", FaultKind.PREEMPT_NOTICE,
                      at=drained_at),
        ])
        m_b = Metrics()
        g = PreemptionGuard(deadline_s=60.0, metrics=m_b)
        crcs_b = []

        def hook(win):
            crcs_b.append(zlib.crc32(np.asarray(win).tobytes()))
            return win

        trainer = _make_trainer(
            checkpoint_dir=str(tmp_path / "ckpt"),
            preemption_guard=g, metrics=m_b, checkpoint_async=False,
        )
        with faults.armed(plan):
            res_b = trainer.fit(
                _producer(seed), batch_size=16, n_epochs=self.N,
                n_producers=2, mode="thread", output="jax",
                window_stream=True, window_hook=hook,
            )
        assert res_b.preempted is True
        t2 = _make_trainer(
            checkpoint_dir=str(tmp_path / "ckpt"),
            metrics=Metrics(), checkpoint_async=False,
        )
        crcs_c = []

        def hook_c(win):
            crcs_c.append(zlib.crc32(np.asarray(win).tobytes()))
            return win

        res_c = t2.fit(
            _producer(seed), batch_size=16, n_epochs=self.N,
            n_producers=2, mode="thread", output="jax",
            window_stream=True, window_hook=hook_c,
        )
        assert res_c.resumed_from_epoch == drained_at
        assert len(crcs_b) == drained_at
        assert len(crcs_c) == self.N - drained_at


class TestAsyncVsSyncParity:
    def test_async_and_sync_checkpoints_restore_identically(
        self, tmp_path
    ):
        """The async tier changes WHEN bytes are written, never WHICH:
        the same fit checkpointed through both paths restores to
        bit-identical state."""
        seed = 5
        ra, _ = _run_fit(tmp_path, seed, 3, subdir="a")
        rs_trainer = _make_trainer(
            checkpoint_dir=str(tmp_path / "s"), checkpoint_async=False,
            metrics=Metrics(),
        )
        rs = rs_trainer.fit(
            _producer(seed), batch_size=16, n_epochs=3, n_producers=2,
            mode="thread", output="jax", window_stream=True,
        )
        assert _tree_equal(ra.state.params, rs.state.params)
        ta = _make_trainer(checkpoint_dir=str(tmp_path / "a"),
                           metrics=Metrics())
        ts = _make_trainer(checkpoint_dir=str(tmp_path / "s"),
                           metrics=Metrics(), checkpoint_async=False)
        sa, ea = ta._restore_or_init()
        ss, es = ts._restore_or_init()
        assert ea == es == 3
        assert sa.step == ss.step
        assert _tree_equal(sa.params, ss.params)
        assert _tree_equal(sa.opt_state, ss.opt_state)
