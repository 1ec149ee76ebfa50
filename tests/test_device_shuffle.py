"""Device-tier global shuffle (ddl_tpu.ops.device_shuffle +
DeviceExchangeShuffler): seed parity vs the host exchange, resume round
coherence, the chaos ladder (DMA-fail latch, peer-loss rung), and the
spawn-boundary resolution surface — all on the 8-device CPU virtual
mesh (interpret mode), where byte-identity with the host path is
PROVABLE, not sampled."""

import pickle
import threading
import time

import numpy as np
import pytest

from ddl_tpu import faults
from ddl_tpu.exceptions import DDLError
from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
from ddl_tpu.shuffle import (
    DeviceExchangeFabric,
    DeviceExchangeShuffler,
    DeviceExchangeShufflerFactory,
    Rendezvous,
    ThreadExchangeShuffler,
    exchange_permutation,
)
from ddl_tpu.types import RunMode, Topology

SEED = 7


def _pools(n, rows, width=3):
    """Deterministic per-instance pools: value encodes (instance, row,
    col) uniquely, so any divergence names its origin."""
    return [
        (
            np.arange(rows * width, dtype=np.float32).reshape(rows, width)
            + 10_000.0 * i
        )
        for i in range(n)
    ]


def _run_rounds(n, arys, rounds, make_shuffler, timeout=120):
    """One worker thread per instance, each running every round (the
    fabric/rendezvous synchronises rounds internally)."""
    shufs = [make_shuffler(i) for i in range(n)]
    errors = []

    def worker(i):
        try:
            for _ in range(rounds):
                shufs[i].global_shuffle(arys[i])
        except Exception as e:  # ddl-lint: disable=DDL007
            # Worker thread: capture, assert in the main thread.
            errors.append((i, e))

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    [t.start() for t in ts]
    [t.join(timeout) for t in ts]
    assert not any(t.is_alive() for t in ts), "exchange workers hung"
    assert not errors, errors
    return shufs


def _host_run(n, rows, num_exchange, rounds, **kw):
    rdv = Rendezvous()
    arys = _pools(n, rows)
    _run_rounds(
        n, arys, rounds,
        lambda i: ThreadExchangeShuffler(
            Topology(n_instances=n, instance_idx=i, n_producers=1),
            1, num_exchange, rendezvous=rdv, seed=SEED, **kw,
        ),
    )
    return arys


def _device_run(n, rows, num_exchange, rounds, impl="ring", fabric=None,
                start_round=0, arys=None, **kw):
    rdv = Rendezvous()
    fabric = fabric or DeviceExchangeFabric(impl=impl)
    if arys is None:
        arys = _pools(n, rows)

    def make(i):
        from ddl_tpu.observability import Metrics

        sh = DeviceExchangeShuffler(
            Topology(n_instances=n, instance_idx=i, n_producers=1),
            1, num_exchange, rendezvous=rdv, fabric=fabric, seed=SEED, **kw,
        )
        # Private registry per shuffler (the datapusher injection seam)
        # so metric assertions are per-instance, not cross-test sums.
        sh.metrics = Metrics()
        if start_round:
            sh.rejoin(start_round)
        return sh

    shufs = _run_rounds(n, arys, rounds, make)
    return arys, shufs


class TestSeedParity:
    """DeviceExchangeShuffler ≡ ThreadExchangeShuffler byte-for-byte:
    same seed, same rounds ⇒ same post-exchange pools (the tentpole's
    provable-identity contract)."""

    @pytest.mark.parametrize("impl", ["ring", "xla"])
    @pytest.mark.parametrize(
        "n,rows,num_exchange",
        [
            (2, 16, 7),   # odd lane count: trailing row stays home
            (3, 10, 10),  # whole pool exchanged
            (5, 9, 5),    # non-divisible everything
            (8, 12, 6),   # full virtual mesh
        ],
    )
    def test_pools_byte_identical(self, impl, n, rows, num_exchange):
        host = _host_run(n, rows, num_exchange, rounds=3)
        dev, shufs = _device_run(n, rows, num_exchange, rounds=3, impl=impl)
        for i in range(n):
            np.testing.assert_array_equal(
                host[i], dev[i],
                err_msg=f"instance {i} diverged (impl={impl})",
            )
        # Healthy path: every round rode the device tier, nothing
        # latched (the acceptance-criteria metrics contract).
        for sh in shufs:
            snap = sh.metrics.snapshot()
            assert snap.get("shuffle.device_fallbacks", 0) == 0
            assert sh.device_exchange_active

    def test_nd_pools_flatten_through_exchange(self):
        """Trailing dims beyond 2 flatten into device columns and come
        back bit-exact (the loader's (rows, values) windows are 2D, but
        the shuffler contract is any leading-rows array)."""
        n, rounds = 3, 2
        host = [
            np.arange(8 * 2 * 3, dtype=np.float32).reshape(8, 2, 3) + 100 * i
            for i in range(n)
        ]
        dev = [a.copy() for a in host]
        rdv = Rendezvous()
        _run_rounds(
            n, host, rounds,
            lambda i: ThreadExchangeShuffler(
                Topology(n_instances=n, instance_idx=i, n_producers=1),
                1, 6, rendezvous=rdv, seed=SEED,
            ),
        )
        _device_run(n, 8, 6, rounds, arys=dev)
        for i in range(n):
            np.testing.assert_array_equal(host[i], dev[i])

    @pytest.mark.parametrize("impl", ["ring", "xla"])
    def test_resume_round_coherence(self, impl):
        """Split run (2 rounds, fresh shufflers rejoined at round 2,
        2 more) ≡ uninterrupted 4-round run — the checkpoint/resume
        mid-epoch leg: the device tier honours ``rejoin`` exactly like
        the host tier, so a resumed job continues the exchange schedule
        instead of replaying round 0."""
        n, rows, nex = 3, 10, 6
        full, _ = _device_run(n, rows, nex, rounds=4, impl=impl)
        split = _pools(n, rows)
        _, shufs = _device_run(n, rows, nex, rounds=2, impl=impl, arys=split)
        assert all(sh.exchange_round == 2 for sh in shufs)
        _, shufs2 = _device_run(
            n, rows, nex, rounds=2, impl=impl, arys=split, start_round=2,
        )
        assert all(sh.exchange_round == 4 for sh in shufs2)
        for i in range(n):
            np.testing.assert_array_equal(
                full[i], split[i],
                err_msg=f"instance {i} diverged after mid-epoch resume",
            )


class TestDeviceChaos:
    """The degradation ladder under injected faults at the new
    ``shuffle.device_exchange`` site (docs/ROBUSTNESS.md matrix)."""

    def test_dma_failure_latches_host_fallback_byte_identically(self):
        """ICI_DMA_FAIL mid-exchange: the round is poisoned BEFORE any
        lane mutates, every participant latches the host exchange
        together and re-runs the SAME round over it — so the final
        pools equal a host-only run bit-for-bit."""
        n, rows, nex, rounds = 3, 10, 6, 3
        host = _host_run(n, rows, nex, rounds)
        plan = FaultPlan(
            [FaultSpec("shuffle.device_exchange", FaultKind.ICI_DMA_FAIL)]
        )
        with faults.armed(plan):
            dev, shufs = _device_run(n, rows, nex, rounds)
        for i in range(n):
            np.testing.assert_array_equal(
                host[i], dev[i],
                err_msg=f"instance {i}: latched fallback not byte-identical",
            )
        for sh in shufs:
            assert sh._device_latched
            assert not sh.device_exchange_active
            snap = sh.metrics.snapshot()
            assert snap.get("shuffle.device_fallbacks", 0) == 1
            # Latch ≠ degrade: the exchange still ran every round.
            assert snap.get("shuffle.degraded", 0) == 0
            assert sh.exchange_round == rounds

    def test_kernel_build_error_propagates_to_every_participant(
        self, monkeypatch
    ):
        """A kernel that does not build or compile is a broken program,
        not a failed leg: every participant of the round raises it as
        it is — nobody latches the host exchange, nobody waits out the
        leg-stall clock."""
        from ddl_tpu.ops import device_shuffle

        def broken(*a, **k):
            raise AttributeError("no attribute 'TPUCompilerParams'")

        monkeypatch.setattr(device_shuffle, "_exchange_call", broken)
        n = 3
        fabric = DeviceExchangeFabric(impl="ring")
        rdv = Rendezvous()
        shufs = [
            DeviceExchangeShuffler(
                Topology(n_instances=n, instance_idx=i, n_producers=1),
                1, 6, rendezvous=rdv, fabric=fabric, seed=SEED,
            )
            for i in range(n)
        ]
        arys = _pools(n, 10)
        before = [a.copy() for a in arys]
        raised = {}

        def worker(i):
            try:
                shufs[i].global_shuffle(arys[i])
            except AttributeError as e:
                raised[i] = e

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
        [t.start() for t in ts]
        [t.join(30) for t in ts]
        assert not any(t.is_alive() for t in ts), "participants hung"
        assert sorted(raised) == list(range(n))
        assert not any(sh._device_latched for sh in shufs)
        for a, b in zip(arys, before):
            np.testing.assert_array_equal(a, b)  # lanes unmutated

    def test_peer_loss_degrades_node_local_rung(self):
        """Persistent SHUFFLE_PEER_LOSS during device rounds (the host
        chaos test's missing-peer construction: a declared 2-instance
        topology with only instance 0 running): every round degrades
        via the EXISTING seeded node-local rung — byte-identical to the
        host path under the same loss, because the local shuffle
        depends only on (seed, producer, round).  No device latch:
        peer loss is the host ladder's rung, not a device failure."""
        from ddl_tpu.observability import Metrics

        rows, nex, rounds = 10, 6, 3

        def lone_run(cls, **kw):
            topo = Topology(
                n_instances=2, instance_idx=0, n_producers=1,
                mode=RunMode.THREAD,
            )
            sh = cls(topo, 1, nex, rendezvous=Rendezvous(),
                     seed=SEED, max_peer_losses=2, **kw)
            sh.metrics = Metrics()
            ary = _pools(1, rows)[0]
            for _ in range(rounds):
                sh.global_shuffle(ary)
            return ary, sh

        host_ary, host_sh = lone_run(
            ThreadExchangeShuffler, exchange_timeout_s=0.5,
        )
        plan = FaultPlan(
            [FaultSpec("shuffle.device_exchange",
                       FaultKind.SHUFFLE_PEER_LOSS, count=999)]
        )
        with faults.armed(plan):
            dev_ary, dev_sh = lone_run(
                DeviceExchangeShuffler,
                fabric=DeviceExchangeFabric(impl="ring"),
            )
        np.testing.assert_array_equal(
            host_ary, dev_ary,
            err_msg="node-local rung diverged from the host path",
        )
        snap = dev_sh.metrics.snapshot()
        assert snap.get("shuffle.degraded", 0) >= 2
        assert snap.get("shuffle.device_fallbacks", 0) == 0
        assert not dev_sh._device_latched
        assert dev_sh._degraded  # max_peer_losses reached, terminal rung
        assert dev_sh.exchange_round == rounds  # counter stays coherent

    def test_unplannable_geometry_latches_at_first_round(self):
        """A ring wider than the addressable device set is unplannable:
        the leader's leg fails, every participant latches, and the host
        exchange carries the run byte-identically."""
        n, rows, nex, rounds = 3, 8, 4, 2
        host = _host_run(n, rows, nex, rounds)
        import jax

        fabric = DeviceExchangeFabric(devices=jax.devices()[:1], impl="ring")
        dev, shufs = _device_run(n, rows, nex, rounds, fabric=fabric)
        for i in range(n):
            np.testing.assert_array_equal(host[i], dev[i])
        assert all(sh._device_latched for sh in shufs)


class TestResolutionSurface:
    """Construction-time resolution: when the device tier cannot reach
    its peers it resolves OFF (host path, zero fallbacks) — resolution
    is not a fallback."""

    def _shuffler(self, **kw):
        kw.setdefault("fabric", DeviceExchangeFabric(impl="ring"))
        kw.setdefault("rendezvous", Rendezvous())
        return DeviceExchangeShuffler(
            Topology(n_instances=2, instance_idx=0, n_producers=1), 1, 4,
            **kw,
        )

    def test_span_reflects_engagement(self):
        sh = self._shuffler()
        assert sh.span == "device"
        sh._device_latched = True
        assert sh.span == "thread"  # handshake sees the real transport

    def test_gate_off_resolves_host(self):
        sh = self._shuffler(device_shuffle="off")
        assert not sh.device_exchange_active and sh.span == "thread"

    def test_no_fabric_resolves_host(self):
        sh = self._shuffler(fabric=None)
        assert not sh.device_exchange_active

    def test_process_topology_resolves_host(self):
        sh = DeviceExchangeShuffler(
            Topology(n_instances=2, instance_idx=0, n_producers=1,
                     mode=RunMode.PROCESS),
            1, 4, fabric=DeviceExchangeFabric(impl="ring"),
            rendezvous=Rendezvous(),
        )
        assert not sh.device_exchange_active

    def test_forced_wire_resolves_host(self):
        """An explicitly forced lossy wire keeps the host path: the
        device legs move raw rows over ICI, and re-quantizing on device
        would break exact byte identity."""
        sh = self._shuffler(wire_dtype="int8")
        assert not sh.device_exchange_active and sh.span == "thread"

    def test_factory_drops_fabric_at_pickle_boundary(self):
        fac = DeviceExchangeShufflerFactory(shuffle_impl="ring", seed=3)
        assert fac.fabric is not None
        fac2 = pickle.loads(pickle.dumps(fac))
        assert fac2.fabric is None
        sh = fac2(
            Topology(n_instances=2, instance_idx=0, n_producers=1,
                     mode=RunMode.PROCESS),
            1, 4,
        )
        assert not sh.device_exchange_active and sh.seed == 3
        assert sh.metrics.snapshot().get("shuffle.device_fallbacks", 0) == 0

    def test_bad_impl_rejected(self):
        with pytest.raises(ValueError):
            DeviceExchangeFabric(impl="dma9000")

    def test_plan_exchange_prices_wire_on_host_legs(self):
        from ddl_tpu.ops import device_shuffle as dsh

        plan = dsh.plan_exchange(
            4, 8, 16, np.dtype(np.float32), wire_dtype="int8", n_devices=8,
        )
        assert plan["plannable"]
        assert plan["host_bytes_wire"] < plan["host_bytes_raw"]
        assert plan["ici_bytes"] == plan["host_bytes_raw"]
        assert len(plan["legs"]) == 2
        bad = dsh.plan_exchange(4, 8, 16, np.dtype(np.float32), n_devices=2)
        assert not bad["plannable"] and bad["why_not"]

    def test_fabric_shutdown_wakes_waiter(self):
        """A stranded participant (peer tearing down) wakes via
        should_abort instead of waiting out the timeout — the host
        fabrics' any-time-cancellability property."""
        from ddl_tpu.exceptions import ShutdownRequested

        fabric = DeviceExchangeFabric(impl="ring")
        flag = {"down": False}

        def aborter():
            time.sleep(0.15)
            flag["down"] = True

        threading.Thread(target=aborter, daemon=True).start()
        t0 = time.monotonic()
        with pytest.raises(ShutdownRequested):
            fabric.exchange(
                producer_idx=1, round_=0, instance_idx=0, n=2,
                block=np.zeros((4, 2), np.float32), seed=SEED,
                timeout_s=30.0, should_abort=lambda: flag["down"],
            )
        assert time.monotonic() - t0 < 5.0

    def test_replayed_take_is_idempotent(self):
        """A respawned producer re-entering a completed round gets the
        SAME result (the elastic-replay retention window, held until
        round r+2 starts)."""
        n = 2
        fabric = DeviceExchangeFabric(impl="xla")
        blocks = [
            np.arange(8, dtype=np.float32).reshape(4, 2) + 100 * i
            for i in range(n)
        ]
        outs = {}

        def worker(i):
            outs[i] = fabric.exchange(
                producer_idx=1, round_=0, instance_idx=i, n=n,
                block=blocks[i], seed=SEED, timeout_s=30.0,
            )

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
        [t.start() for t in ts]
        [t.join(60) for t in ts]
        replay = fabric.exchange(
            producer_idx=1, round_=0, instance_idx=0, n=n,
            block=blocks[0], seed=SEED, timeout_s=5.0,
        )
        np.testing.assert_array_equal(outs[0], replay)
        # n=2 swap: each side now holds the other's block.
        np.testing.assert_array_equal(outs[0], blocks[1])


class TestEndToEndStreamIdentity:
    """Full pipeline: loader windows drained under the device tier are
    byte-identical to the host tier's, cache on or off, with zero
    device fallbacks (the acceptance-criteria stream contract)."""

    N_DATA = 16

    def _drain_two_instances(self, factory_of, epochs=2, cache=False,
                             monkeypatch=None):
        from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton
        from ddl_tpu.dataloader import DistributedDataLoader
        from ddl_tpu.datapusher import DataPusher
        from ddl_tpu.transport.connection import (
            ConsumerConnection, ProducerConnection, ThreadChannel,
        )
        from ddl_tpu.types import Marker

        if monkeypatch is not None:
            monkeypatch.setenv("DDL_TPU_CACHE", "1" if cache else "0")
        n_data = self.N_DATA

        class Tagged(ProducerFunctionSkeleton):
            def __init__(self, instance_idx):
                self.instance_idx = instance_idx

            def on_init(self, **kw):
                return DataProducerOnInitReturn(
                    nData=n_data, nValues=2, shape=(n_data, 2), splits=(1, 1)
                )

            def post_init(self, my_ary, **kw):
                my_ary[:] = (
                    self.instance_idx * 1000.0
                    + np.arange(n_data, dtype=np.float32)[:, None]
                )

            def execute_function(self, my_ary, **kw):
                my_ary += 1.0

        out = {}
        errors = []

        def run_instance(i):
            try:
                topo = Topology(
                    n_instances=2, instance_idx=i, n_producers=1,
                    mode=RunMode.THREAD,
                )
                cons_end, prod_end = ThreadChannel.pair()
                pconn = ProducerConnection(prod_end, 1, cross_process=False)
                pushers = {}

                def producer():
                    from ddl_tpu.observability import Metrics

                    # Private registry (the injection seam) so the
                    # zero-fallbacks assertion is per-run, not a
                    # cross-test sum on the module default.
                    pushers[i] = DataPusher(
                        pconn, topo, 1, shuffler_factory=factory_of(),
                        metrics=Metrics(),
                    )
                    pushers[i].push_data()

                pt = threading.Thread(target=producer, daemon=True)
                pt.start()
                loader = DistributedDataLoader(
                    Tagged(i), batch_size=n_data,
                    connection=ConsumerConnection([cons_end]),
                    n_epochs=epochs, output="numpy",
                    global_shuffle_fraction_exchange=0.5,
                )
                rows = []
                for _ in range(epochs):
                    for (a, _b) in loader:
                        rows.append(a.copy())
                        loader.mark(Marker.END_OF_BATCH)
                    loader.mark(Marker.END_OF_EPOCH)
                out[i] = (np.concatenate(rows), pushers.get(i))
                loader.shutdown()
                pt.join(30)
            except Exception as e:  # ddl-lint: disable=DDL007
                # Worker thread: capture, assert in the main thread.
                errors.append((i, e))

        ts = [
            threading.Thread(target=run_instance, args=(i,)) for i in (0, 1)
        ]
        [t.start() for t in ts]
        [t.join(180) for t in ts]
        assert not any(t.is_alive() for t in ts)
        assert not errors, errors
        return out

    @pytest.mark.parametrize("cache", [False, True], ids=["nocache", "cache"])
    def test_device_stream_equals_host_stream(self, cache, monkeypatch):
        host_rdv = Rendezvous()
        host = self._drain_two_instances(
            lambda: ThreadExchangeShuffler.factory(host_rdv),
            cache=cache, monkeypatch=monkeypatch,
        )
        dev_fabric = DeviceExchangeFabric(impl="ring")
        dev = self._drain_two_instances(
            lambda: DeviceExchangeShuffler.factory(fabric=dev_fabric),
            cache=cache, monkeypatch=monkeypatch,
        )
        for i in (0, 1):
            np.testing.assert_array_equal(
                host[i][0], dev[i][0],
                err_msg=f"instance {i}: device stream diverged from host",
            )
        for i in (0, 1):
            pusher = dev[i][1]
            assert pusher is not None
            snap = pusher.metrics.snapshot()
            assert snap.get("shuffle.device_fallbacks", 0) == 0
            assert snap.get("shuffle.device_rounds", 0) >= 1


def _device_factory_process_worker(i, n, session, root, rounds, pipe):
    """Spawn target: the DeviceExchangeShufflerFactory crosses a REAL
    pickle boundary; the fabric is dropped and the host exchange over
    ShmRendezvous carries the rounds (module-level for pickling)."""
    import numpy as np

    from ddl_tpu.shuffle import DeviceExchangeShufflerFactory, ShmRendezvous
    from ddl_tpu.types import RunMode, Topology

    factory = pickle.loads(pipe.recv())
    del session, root  # carried inside the pickled factory
    topo = Topology(
        n_instances=n, instance_idx=i, n_producers=1, mode=RunMode.PROCESS,
    )
    sh = factory(topo, 1, 6)
    assert isinstance(factory, DeviceExchangeShufflerFactory)
    assert not sh.device_exchange_active  # resolved off, not latched
    ary = (
        np.arange(10 * 3, dtype=np.float32).reshape(10, 3) + 10_000.0 * i
    )
    for _ in range(rounds):
        sh.global_shuffle(ary)
    assert sh.metrics.snapshot().get("shuffle.device_fallbacks", 0) == 0
    pipe.send(ary)
    pipe.close()


class TestProcessModeIdentity:
    def test_process_stream_equals_thread_stream(self, tmp_path):
        """PROCESS mode: the factory crosses the spawn boundary, the
        fabric is dropped, and the host exchange produces pools
        byte-identical to a THREAD-mode host run with the same seed —
        the cross-mode half of the acceptance contract."""
        import multiprocessing as mp

        from ddl_tpu.shuffle import ShmRendezvous, make_session

        n, rows, nex, rounds = 2, 10, 6, 1
        thread_pools = _host_run(n, rows, nex, rounds)
        session = make_session("t-devfac")
        factory = DeviceExchangeShufflerFactory(
            rendezvous=ShmRendezvous(session, root=str(tmp_path)),
            shuffle_impl="ring", seed=SEED,
        )
        blob = pickle.dumps(factory)
        ctx = mp.get_context("spawn")
        procs, parents = [], []
        for i in range(n):
            parent, child = ctx.Pipe(duplex=True)
            p = ctx.Process(
                target=_device_factory_process_worker,
                args=(i, n, session, str(tmp_path), rounds, child),
            )
            p.start()
            child.close()
            parent.send(blob)
            procs.append(p)
            parents.append(parent)
        pools = []
        for parent, p in zip(parents, procs):
            assert parent.poll(120), "worker produced nothing in 120s"
            pools.append(parent.recv())
            p.join(30)
            assert p.exitcode == 0
        for i in range(n):
            np.testing.assert_array_equal(
                thread_pools[i], pools[i],
                err_msg=f"instance {i}: PROCESS stream diverged from THREAD",
            )
        ShmRendezvous(session, root=str(tmp_path)).cleanup()
