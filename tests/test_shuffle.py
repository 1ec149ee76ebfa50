"""Global shuffle tests: permutation properties (hypothesis), host
rendezvous exchange, device collectives on the 8-device CPU mesh."""

import threading
import time

import numpy as np
import pytest

# The property tests below need hypothesis (a test extra, pyproject
# [test]); without it, skip this module cleanly instead of erroring the
# whole collection.
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from ddl_tpu.exceptions import DDLError
from ddl_tpu.shuffle import (
    ThreadExchangeShuffler,
    Rendezvous,
    exchange_permutation,
    exchange_slices,
    inverse_permutation,
)
from ddl_tpu.types import Topology, RunMode


class TestPermutationProperties:
    @given(
        n=st.integers(min_value=3, max_value=64),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
        round_=st.integers(min_value=0, max_value=1000),
    )
    @settings(max_examples=200, deadline=None)
    def test_no_self_sends_no_two_cycles(self, n, seed, round_):
        p = exchange_permutation(n, seed, round_)
        assert sorted(p) == list(range(n))  # a permutation
        assert np.all(p != np.arange(n))  # no self-sends
        assert np.all(p[p] != np.arange(n))  # no 2-cycles

    @given(
        n=st.integers(min_value=2, max_value=32),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    @settings(max_examples=100, deadline=None)
    def test_deterministic_shared_agreement(self, n, seed):
        """All peers independently compute the identical permutation
        (reference shuffle.py:28-30 semantics)."""
        a = exchange_permutation(n, seed, 7)
        b = exchange_permutation(n, seed, 7)
        assert np.array_equal(a, b)

    def test_special_cases(self):
        assert list(exchange_permutation(1, 0, 0)) == [0]
        assert list(exchange_permutation(2, 123, 9)) == [1, 0]

    def test_inverse(self):
        p = exchange_permutation(16, 3, 4)
        inv = inverse_permutation(p)
        assert np.array_equal(p[inv], np.arange(16))

    def test_exchange_slices(self):
        a, b = exchange_slices(10)
        assert (a, b) == (slice(0, 5), slice(5, 10))


class TestThreadExchange:
    def _run_instances(self, n_instances, n_rows=8, num_exchange=4, rounds=1):
        """Simulate the same producer-idx across n instances, each with a
        tagged window; run `rounds` exchange rounds concurrently."""
        rdv = Rendezvous()
        arys = [
            np.full((n_rows, 2), float(i), dtype=np.float32)
            for i in range(n_instances)
        ]
        for i, a in enumerate(arys):
            a[:, 1] = np.arange(n_rows)  # row ids survive exchange

        def worker(i):
            topo = Topology(
                n_instances=n_instances, instance_idx=i, n_producers=1,
                mode=RunMode.THREAD,
            )
            sh = ThreadExchangeShuffler(
                topo, producer_idx=1, num_exchange=num_exchange, rendezvous=rdv
            )
            for _ in range(rounds):
                sh.global_shuffle(arys[i])

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(n_instances)]
        [t.start() for t in ts]
        [t.join(30) for t in ts]
        assert not any(t.is_alive() for t in ts)
        return arys

    @pytest.mark.parametrize("n_instances", [2, 3, 5])
    def test_exchange_conserves_samples(self, n_instances):
        arys = self._run_instances(n_instances)
        # Global multiset of origin tags is conserved.
        tags = np.concatenate([a[:, 0] for a in arys])
        counts = {float(i): int((tags == i).sum()) for i in range(n_instances)}
        assert all(c == 8 for c in counts.values())

    def test_rows_actually_moved(self):
        arys = self._run_instances(3)
        # Exchanged lanes (rows 0:4) no longer carry the local tag.
        for i, a in enumerate(arys):
            assert np.all(a[:4, 0] != float(i))
            assert np.all(a[4:, 0] == float(i))  # non-lane rows untouched

    def test_multi_round_drift_tolerant(self):
        arys = self._run_instances(4, rounds=5)
        tags = np.concatenate([a[:, 0] for a in arys])
        assert len(tags) == 32
        for i in range(4):
            assert (tags == float(i)).sum() == 8

    def test_bad_method_rejected(self):
        topo = Topology(n_instances=2, instance_idx=0, n_producers=1)
        with pytest.raises(NotImplementedError):
            ThreadExchangeShuffler(topo, 1, 4, exchange_method="bsend")


def _shm_exchange_worker(i, n_instances, session, root, rounds, pipe):
    """Spawn target: one instance's producer-side exchange over
    ShmRendezvous (module-level for pickling)."""
    import numpy as np

    from ddl_tpu.shuffle import ShmRendezvous, ThreadExchangeShuffler
    from ddl_tpu.types import RunMode, Topology

    ary = np.full((8, 2), float(i), dtype=np.float32)
    ary[:, 1] = np.arange(8)
    topo = Topology(
        n_instances=n_instances, instance_idx=i, n_producers=1,
        mode=RunMode.PROCESS,
    )
    sh = ThreadExchangeShuffler(
        topo, producer_idx=1, num_exchange=4,
        rendezvous=ShmRendezvous(session, root=root),
    )
    for _ in range(rounds):
        sh.global_shuffle(ary)
    pipe.send(ary)
    pipe.close()


class TestShmRendezvous:
    def test_put_take_roundtrip(self, tmp_path):
        from ddl_tpu.shuffle import ShmRendezvous

        rdv = ShmRendezvous("t-roundtrip", root=str(tmp_path))
        rows = np.arange(12, dtype=np.float32).reshape(4, 3)
        rdv.put((1, 0, 2), rows)
        out = rdv.take((1, 0, 2), timeout_s=5)
        np.testing.assert_array_equal(out, rows)
        rdv.cleanup()

    def test_take_aborts_on_flag(self, tmp_path):
        from ddl_tpu.exceptions import ShutdownRequested
        from ddl_tpu.shuffle import ShmRendezvous

        rdv = ShmRendezvous("t-abort", root=str(tmp_path))
        flag = {"down": False}

        def aborter():
            time.sleep(0.15)
            flag["down"] = True

        threading.Thread(target=aborter, daemon=True).start()
        t0 = time.monotonic()
        with pytest.raises(ShutdownRequested):
            rdv.take((1, 0, 0), timeout_s=30,
                     should_abort=lambda: flag["down"])
        assert time.monotonic() - t0 < 5.0
        rdv.cleanup()

    def test_take_retains_for_replay_until_retired(self, tmp_path):
        """Elastic × shuffle contract: a consumed mailbox stays readable
        (``.done``) so a respawned producer replaying its predecessor's
        round takes the SAME rows; retire() closes the replay window."""
        from ddl_tpu.exceptions import DDLError
        from ddl_tpu.shuffle import Rendezvous, ShmRendezvous

        for rdv in (Rendezvous(), ShmRendezvous("t-replay", root=str(tmp_path))):
            rows = np.arange(6, dtype=np.float32).reshape(2, 3)
            rdv.put((1, 4, 0), rows)
            first = rdv.take((1, 4, 0), timeout_s=5)
            np.testing.assert_array_equal(first, rows)
            # Replayed take (the respawn path): same rows, no blocking.
            np.testing.assert_array_equal(
                rdv.take((1, 4, 0), timeout_s=5), rows
            )
            rdv.retire((1, 4, 0))
            with pytest.raises(DDLError):
                rdv.take((1, 4, 0), timeout_s=0.2)

    def test_stale_session_sweep(self, tmp_path):
        """A crashed run's RAM-backed mailbox dir is reclaimed once its
        minting pid is dead AND it is old; a live run's dir survives any
        age (mtime alone would misfire on slow exchange cadences), as do
        hand-named sessions and foreign files (ADVICE r4: nothing else
        ever removed an uncleaned session)."""
        import os
        import uuid

        import ddl_tpu.shuffle as shuffle_mod
        from ddl_tpu.shuffle import ShmRendezvous

        # A pid that cannot be alive: spawn a trivial child and reap it
        # (no os.fork — forking the multi-threaded pytest/JAX process
        # can deadlock the child).
        import subprocess
        import sys

        child = subprocess.Popen([sys.executable, "-c", "pass"])
        child.wait(timeout=30)
        dead_pid = child.pid

        def session(pid):
            return f"t-{pid}-{uuid.uuid4().hex[:12]}"

        crashed = ShmRendezvous(session(dead_pid), root=str(tmp_path))
        crashed.put((0, 0, 0), np.zeros(2, np.float32))
        live_old = ShmRendezvous(session(os.getpid()), root=str(tmp_path))
        live_old.put((0, 0, 0), np.zeros(2, np.float32))
        young = ShmRendezvous(session(dead_pid), root=str(tmp_path))
        young.put((0, 0, 0), np.zeros(2, np.float32))
        named = ShmRendezvous("hand-named-old", root=str(tmp_path))
        named.put((0, 0, 0), np.zeros(2, np.float32))
        other = tmp_path / "ddl-rdv-not-a-dir"
        other.write_text("plain file, never touched")
        old = time.time() - 2 * shuffle_mod.STALE_SESSION_S
        for rdv in (crashed, live_old, named):
            os.utime(rdv._dir, (old, old))

        shuffle_mod._sweep_stale_sessions(str(tmp_path))
        assert not os.path.isdir(crashed._dir)  # dead minter + old: swept
        assert os.path.isdir(live_old._dir)  # alive minter: kept at any age
        assert os.path.isdir(young._dir)  # dead minter but young: grace
        assert os.path.isdir(named._dir)  # hand-named: caller's to clean
        assert other.read_text() == "plain file, never touched"

    def test_factory_is_picklable(self, tmp_path):
        """PROCESS mode ships the factory by pickle to spawned workers —
        a closure factory (the pre-fix shape) would fail right here."""
        import pickle

        from ddl_tpu.shuffle import ShmRendezvous, make_session

        f = ThreadExchangeShuffler.factory(
            rendezvous=ShmRendezvous(
                make_session("t-pick"), root=str(tmp_path)
            )
        )
        g = pickle.loads(pickle.dumps(f))
        topo = Topology(n_instances=2, instance_idx=0, n_producers=1,
                        mode=RunMode.PROCESS)
        sh = g(topology=topo, producer_idx=1, num_exchange=4,
               exchange_method="sendrecv_replace")
        assert sh.span == "process"
        g.rendezvous.cleanup()

    # n=2 runs ONE round: the fixed swap permutation would ping-pong the
    # same lanes straight back on round 2 (see examples/global_shuffle.py
    # docstring) and the rows-moved assertion would vacuously fail.
    @pytest.mark.parametrize("n_instances,rounds", [(2, 1), (3, 2)])
    def test_cross_process_exchange_conserves_samples(
        self, n_instances, rounds, tmp_path
    ):
        """PROCESS-mode twin of the THREAD multiset-preservation test
        (VERDICT r3 item 4): real OS processes exchanging over the
        /dev/shm mailbox fabric."""
        import multiprocessing as mp

        from ddl_tpu.shuffle import ShmRendezvous, make_session

        session = make_session("t-xproc")
        root = str(tmp_path)
        ctx = mp.get_context("spawn")
        procs, parents = [], []
        for i in range(n_instances):
            parent, child = ctx.Pipe(duplex=False)
            p = ctx.Process(
                target=_shm_exchange_worker,
                args=(i, n_instances, session, root, rounds, child),
            )
            p.start()
            child.close()
            procs.append(p)
            parents.append(parent)
        arys = []
        for parent, p in zip(parents, procs):
            assert parent.poll(120), "worker produced nothing in 120s"
            arys.append(parent.recv())
            p.join(30)
            assert p.exitcode == 0
        tags = np.concatenate([a[:, 0] for a in arys])
        for i in range(n_instances):
            assert (tags == float(i)).sum() == 8  # multiset conserved
        # Rows actually crossed the process boundary.
        for i, a in enumerate(arys):
            assert np.any(a[:, 0] != float(i))
        ShmRendezvous(session, root=root).cleanup()


class TestSpanRejection:
    """A fabric narrower than the topology fails loudly at handshake
    (VERDICT r3 Missing #2: previously a silent per-process stall)."""

    def _handshake(self, topo, factory):
        from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton
        from ddl_tpu.datapusher import DataPusher
        from ddl_tpu.transport.connection import (
            ProducerConnection, ThreadChannel,
        )
        from ddl_tpu.types import MetaData_Consumer_To_Producer

        class P(ProducerFunctionSkeleton):
            def on_init(self, **kw):
                return DataProducerOnInitReturn(
                    nData=16, nValues=2, shape=(16, 2), splits=(1, 1)
                )

            def post_init(self, my_ary, **kw):
                my_ary[:] = 0.0

        cons_end, prod_end = ThreadChannel.pair()
        cons_end.send(MetaData_Consumer_To_Producer(
            data_producer_function=P(), batch_size=8, n_epochs=1,
            global_shuffle_fraction_exchange=0.5,
            exchange_method="sendrecv_replace",
        ))
        cross = topo.mode is not RunMode.THREAD
        return DataPusher(
            ProducerConnection(prod_end, 1, cross_process=cross),
            topo, 1, shuffler_factory=factory,
        )

    def test_process_mode_rejects_thread_rendezvous(self):
        from ddl_tpu.exceptions import DoesNotMatchError

        topo = Topology(n_instances=2, instance_idx=0, n_producers=1,
                        mode=RunMode.PROCESS)
        with pytest.raises(DoesNotMatchError, match="in-process Rendezvous"):
            self._handshake(topo, ThreadExchangeShuffler.factory())

    def test_multihost_rejects_host_side_fabric(self):
        from ddl_tpu.exceptions import DoesNotMatchError
        from ddl_tpu.shuffle import ShmRendezvous, make_session

        topo = Topology(n_instances=2, instance_idx=0, n_producers=1,
                        mode=RunMode.MULTIHOST)
        rdv = ShmRendezvous(make_session("t-mh"), root="/tmp")
        with pytest.raises(DoesNotMatchError, match="cannot span hosts"):
            self._handshake(
                topo, ThreadExchangeShuffler.factory(rendezvous=rdv)
            )
        rdv.cleanup()

    def test_rejoin_requires_replay_capable_shuffler(self, tmp_path):
        """Elastic rejoin + shuffle is gated on POSITIVE capability: a
        fabric without consumed-box retention (no retire) fails at
        handshake with the clear old-style error, not a runtime
        timeout; a retention fabric with nslots=1 is rejected too (the
        one-slot restore could read the predecessor's torn in-flight
        fill)."""
        from ddl_tpu import (
            DataProducerOnInitReturn, ProducerFunctionSkeleton, integrity,
        )
        from ddl_tpu.datapusher import DataPusher
        from ddl_tpu.exceptions import DoesNotMatchError
        from ddl_tpu.shuffle import ShmRendezvous, make_session
        from ddl_tpu.transport.connection import (
            ProducerConnection, ThreadChannel,
        )
        from ddl_tpu.transport.ring import ThreadRing
        from ddl_tpu.types import MetaData_Consumer_To_Producer

        class P(ProducerFunctionSkeleton):
            def on_init(self, **kw):
                return DataProducerOnInitReturn(
                    nData=16, nValues=2, shape=(16, 2), splits=(1, 1)
                )

            def post_init(self, my_ary, **kw):
                my_ary[:] = 0.0

        class NoRetentionFabric:
            """put/take/discard only — the pre-replay fabric interface."""

            span = "thread"

            def put(self, key, rows):
                pass

            def take(self, key, timeout_s=60.0, should_abort=None):
                raise AssertionError("never reached")

            def discard(self, key):
                pass

        def handshake(factory, nslots=2):
            cons_end, prod_end = ThreadChannel.pair()
            cons_end.send(MetaData_Consumer_To_Producer(
                data_producer_function=P(), batch_size=8, n_epochs=1,
                global_shuffle_fraction_exchange=0.5,
                exchange_method="sendrecv_replace",
            ))
            topo = Topology(n_instances=2, instance_idx=0, n_producers=1,
                            mode=RunMode.THREAD)
            return DataPusher(
                ProducerConnection(prod_end, 1, cross_process=False),
                topo, 1, nslots=nslots, shuffler_factory=factory,
                # The predecessor's ring: slots carry the integrity
                # header's headroom (integrity is on by default).
                rejoin_ring=ThreadRing(
                    nslots, 16 * 2 * 4 + integrity.HEADER_BYTES
                ),
            )

        with pytest.raises(DoesNotMatchError, match="supports_elastic_replay"):
            handshake(
                ThreadExchangeShuffler.factory(rendezvous=NoRetentionFabric())
            )
        with pytest.raises(DoesNotMatchError, match="nslots >= 2"):
            handshake(
                ThreadExchangeShuffler.factory(
                    rendezvous=ShmRendezvous(
                        make_session("t-one-slot"), root=str(tmp_path)
                    )
                ),
                nslots=1,
            )

    def test_process_mode_accepts_shm_rendezvous(self):
        from ddl_tpu.shuffle import ShmRendezvous, make_session

        topo = Topology(n_instances=2, instance_idx=0, n_producers=1,
                        mode=RunMode.PROCESS)
        rdv = ShmRendezvous(make_session("t-ok"), root="/tmp")
        pusher = self._handshake(
            topo, ThreadExchangeShuffler.factory(rendezvous=rdv)
        )
        assert pusher.shuffler is not None
        assert pusher.shuffler.span == "process"
        pusher.connection.finalize()
        rdv.cleanup()


class TestDeviceShuffle:
    @pytest.fixture(scope="class")
    def mesh(self):
        from ddl_tpu.parallel import data_parallel_mesh

        return data_parallel_mesh()

    def _sharded_window(self, mesh, n_instances, rows_per_instance=8, width=3):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        host = np.zeros((n_instances * rows_per_instance, width), np.float32)
        for i in range(n_instances):
            blk = host[i * rows_per_instance : (i + 1) * rows_per_instance]
            blk[:, 0] = i  # origin tag
            blk[:, 1] = np.arange(rows_per_instance)  # row id
        return jax.device_put(host, NamedSharding(mesh, P("dp"))), host

    def test_ppermute_exchange(self, mesh):
        from ddl_tpu.parallel import DeviceGlobalShuffler

        n = mesh.shape["dp"]
        sh = DeviceGlobalShuffler(mesh, num_exchange=4, seed=42)
        window, host = self._sharded_window(mesh, n)
        out = np.asarray(sh.shuffle(window))
        # Conservation of the global sample multiset.
        assert sorted(out[:, 0].tolist()) == sorted(host[:, 0].tolist())
        p = exchange_permutation(n, 42, 0)
        for i in range(n):
            blk = out[i * 8 : (i + 1) * 8]
            inv = inverse_permutation(p)
            # Lane A of instance i now carries rows from inv[i] (who sent
            # forward to i); lane B carries rows from p[i].
            assert np.all(blk[0:2, 0] == inv[i])
            assert np.all(blk[2:4, 0] == p[i])
            assert np.all(blk[4:, 0] == i)  # untouched rows

    def test_all_to_all_exchange(self, mesh):
        from ddl_tpu.parallel import DeviceGlobalShuffler

        n = mesh.shape["dp"]
        sh = DeviceGlobalShuffler(mesh, num_exchange=n, method="all_to_all")
        window, host = self._sharded_window(mesh, n, rows_per_instance=2 * n)
        out = np.asarray(sh.shuffle(window))
        assert sorted(out[:, 0].tolist()) == sorted(host[:, 0].tolist())
        # Each instance's exchange block now holds one row from EVERY peer.
        for i in range(n):
            blk = out[i * 2 * n : i * 2 * n + n]
            assert sorted(blk[:, 0].tolist()) == list(range(n))

    def test_rounds_vary_permutation(self, mesh):
        from ddl_tpu.parallel import DeviceGlobalShuffler

        n = mesh.shape["dp"]
        if n <= 2:
            pytest.skip("needs >2 instances")
        sh = DeviceGlobalShuffler(mesh, num_exchange=2, seed=7)
        w, _ = self._sharded_window(mesh, n)
        o1 = np.asarray(sh.shuffle(w))
        o2 = np.asarray(sh.shuffle(w))
        assert not np.array_equal(o1, o2)  # fresh permutation per round


class TestEndToEndGlobalShuffle:
    def test_cross_instance_rows_reach_consumers(self):
        """Two simulated instances, full pipeline: producer-side global
        shuffle runs inside the DataPusher loop (the path that was dead
        code in the reference, SURVEY Q1) and foreign-instance samples
        show up in drained windows."""
        import queue
        from ddl_tpu.datapusher import DataPusher
        from ddl_tpu.dataloader import DistributedDataLoader
        from ddl_tpu.transport.connection import (
            ConsumerConnection, ProducerConnection, ThreadChannel,
        )
        from ddl_tpu.types import Marker
        from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton

        class Tagged(ProducerFunctionSkeleton):
            def on_init(self, instance_idx=0, **kw):
                self.tag = float(instance_idx)
                return DataProducerOnInitReturn(
                    nData=16, nValues=2, shape=(16, 2), splits=(1, 1)
                )

            def post_init(self, my_ary, **kw):
                my_ary[:] = self.tag

        rdv = Rendezvous()
        results = {}

        def run_instance(i):
            topo = Topology(
                n_instances=2, instance_idx=i, n_producers=1,
                mode=RunMode.THREAD,
            )
            cons_end, prod_end = ThreadChannel.pair()
            pconn = ProducerConnection(prod_end, 1, cross_process=False)

            def producer():
                pusher = DataPusher(
                    pconn, topo, 1,
                    shuffler_factory=ThreadExchangeShuffler.factory(rdv),
                )
                pusher.push_data()

            pt = threading.Thread(target=producer, daemon=True)
            pt.start()
            loader = DistributedDataLoader(
                Tagged(), batch_size=16,
                connection=ConsumerConnection([cons_end]),
                n_epochs=2, output="numpy",
                global_shuffle_fraction_exchange=0.5,  # 8 rows per round
            )
            tags = []
            for _ in range(2):
                for (a, b) in loader:
                    tags.append(a[:, 0].copy())
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            results[i] = np.concatenate(tags)
            pt.join(10)

        ts = [threading.Thread(target=run_instance, args=(i,)) for i in (0, 1)]
        [t.start() for t in ts]
        [t.join(60) for t in ts]
        assert not any(t.is_alive() for t in ts)
        # Each instance saw samples tagged by the OTHER instance.
        assert np.any(results[0] == 1.0), "instance 0 never saw foreign rows"
        assert np.any(results[1] == 0.0), "instance 1 never saw foreign rows"
        # And conservation: across both, half the rows moved each way.
        assert np.sum(results[0] == 1.0) == np.sum(results[1] == 0.0)


class TestRendezvousShutdown:
    def test_take_aborts_on_shutdown_flag(self):
        """A producer stranded in the exchange (partner already tearing
        down) must wake promptly via should_abort, not wait out the full
        rendezvous timeout — the flake this fixes stranded a producer 60s
        at phase teardown."""
        from ddl_tpu.exceptions import ShutdownRequested
        from ddl_tpu.shuffle import Rendezvous

        rdv = Rendezvous()
        flag = {"down": False}
        t0 = time.monotonic()

        def aborter():
            time.sleep(0.15)
            flag["down"] = True

        threading.Thread(target=aborter, daemon=True).start()
        with pytest.raises(ShutdownRequested):
            rdv.take((1, 0, 0), timeout_s=30.0,
                     should_abort=lambda: flag["down"])
        assert time.monotonic() - t0 < 5.0  # woke promptly, not at 30s

    def test_pusher_exchange_wait_observes_ring_shutdown(self):
        """End-to-end: instance 0's producer blocks in the exchange with
        no partner; flagging its ring shuts the pipeline down cleanly."""
        from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton
        from ddl_tpu.datapusher import DataPusher
        from ddl_tpu.shuffle import Rendezvous
        from ddl_tpu.transport.connection import (
            ConsumerConnection,
            ProducerConnection,
            ThreadChannel,
        )
        from ddl_tpu.types import (
            MetaData_Consumer_To_Producer,
            RunMode,
            Topology,
        )

        class P(ProducerFunctionSkeleton):
            def on_init(self, **kw):
                return DataProducerOnInitReturn(
                    nData=8, nValues=2, shape=(8, 2), splits=(1, 1)
                )

            def post_init(self, my_ary, **kw):
                my_ary[:] = 0.0

        topo = Topology(n_instances=2, instance_idx=0, n_producers=1,
                        mode=RunMode.THREAD)
        cons_end, prod_end = ThreadChannel.pair()
        pconn = ProducerConnection(prod_end, 1, cross_process=False)
        rdv = Rendezvous()  # private: partner instance never shows up

        def producer():
            DataPusher(
                pconn, topo, 1,
                shuffler_factory=ThreadExchangeShuffler.factory(rdv),
            ).push_data()

        pt = threading.Thread(target=producer, daemon=True)
        pt.start()
        conn = ConsumerConnection([cons_end])
        conn.send_metadata(MetaData_Consumer_To_Producer(
            data_producer_function=P(), batch_size=8, n_epochs=1,
            global_shuffle_fraction_exchange=0.5,
            exchange_method="sendrecv_replace",
        ))
        conn.recv_metadata_as_consumer()
        conn.attach_rings()
        time.sleep(0.3)  # let the producer reach the partnerless exchange
        t0 = time.monotonic()
        conn.shutdown_operation()
        pt.join(10)
        assert not pt.is_alive()
        assert time.monotonic() - t0 < 5.0  # clean, prompt exit
        conn.finalize()

    def test_aborted_exchange_retracts_posted_rows(self):
        """A shuffler whose take aborts must discard its own put so a
        later run on the same rendezvous can't pop stale rows."""
        from ddl_tpu.exceptions import ShutdownRequested
        from ddl_tpu.shuffle import Rendezvous

        rdv = Rendezvous()
        topo = Topology(n_instances=2, instance_idx=0, n_producers=1,
                        mode=RunMode.THREAD)
        sh = ThreadExchangeShuffler(topo, 1, num_exchange=4, rendezvous=rdv)
        ary = np.zeros((8, 2), np.float32)
        with pytest.raises(ShutdownRequested):
            sh.global_shuffle(ary, should_abort=lambda: True)
        assert not rdv._boxes, rdv._boxes  # nothing stale left behind
