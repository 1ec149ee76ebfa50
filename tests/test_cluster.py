"""Cluster control-plane suite (ddl_tpu/cluster, ISSUE 10).

Three layers:

- **units** — shard partitioning, the deterministic epoch-fenced view
  change, leases, the supervisor sweep (incl. the HOST_LOSS /
  HEARTBEAT_DROP fault semantics), placement planning + the simulated-
  fabric measurement, the loader pool, host-identity detection.
- **seam** — ``DistributedDataLoader.apply_pool`` (boundary-applied,
  generation-fenced, revocation of a blocked acquire).
- **e2e** — the cross-host recovery ladder on a live THREAD pipeline:
  producer crash (rung 1, watchdog respawn) and whole-mock-host death
  (rung 2: view change → pool shrink → shard adoption → cache
  warm-start), with byte-identical full-shard coverage asserted and a
  jitted collective running uninterrupted through recovery.  The
  chaos-matrix rows in tests/test_faults.py reuse this file's runner.
"""

import os
import time

import numpy as np
import pytest

from ddl_tpu import (
    DataProducerOnInitReturn,
    DistributedDataLoader,
    Marker,
    ProducerFunctionSkeleton,
    distributed_dataloader,
)
from ddl_tpu import faults
from ddl_tpu.checkpoint import LoaderCheckpoint
from ddl_tpu.cluster import (
    ClusterSupervisor,
    ClusterView,
    ElasticCluster,
    HostInfo,
    LeaseTable,
    LinkCosts,
    LoaderPool,
    SimulatedFabric,
    measure_assignment,
    naive_placement,
    partition_shards,
    placement_report,
    plan_placement,
    probe_link_costs,
    view_change,
    view_rejoin,
)
from ddl_tpu.env import detect_host_identity, detect_topology
from ddl_tpu.exceptions import DDLError, HostLostError, LoaderStateError
from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
from ddl_tpu.observability import Metrics
from ddl_tpu.types import Topology
from ddl_tpu.watchdog import Watchdog

# ---------------------------------------------------------------------------
# Shared geometry: 2 mock hosts x 1 producer, 4 shards.
# ---------------------------------------------------------------------------

N_SHARDS, ROWS, VALS = 4, 8, 4


def shard_pattern(shard: int) -> np.ndarray:
    """Byte-deterministic content of one shard's window."""
    return (
        shard * 1000.0
        + np.arange(ROWS * VALS, dtype=np.float32) % 97
    ).reshape(ROWS, VALS)


class ShardRangeProducer(ProducerFunctionSkeleton):
    """Serves its host's shard ranges in a cycle; ``adopt_shards``
    re-partitions mid-run.  Initial ranges come from a per-producer map
    (the deterministic base assignment), keyed by producer_idx — every
    producer gets a deepcopy of this object, so per-instance state must
    derive from on_init kwargs."""

    def __init__(self, ranges_by_producer):
        self.ranges_by_producer = dict(ranges_by_producer)
        self.ranges = ()

    def _shards(self):
        return [s for a, b in self.ranges for s in range(a, b)]

    def on_init(self, producer_idx=1, **kw):
        self.it = 0
        self.ranges = tuple(self.ranges_by_producer[producer_idx])
        return DataProducerOnInitReturn(
            nData=ROWS, nValues=VALS, shape=(ROWS, VALS), splits=(VALS,)
        )

    def post_init(self, my_ary, **kw):
        my_ary[:] = 0.0

    def execute_function(self, my_ary, **kw):
        shards = self._shards()
        my_ary[:] = shard_pattern(shards[self.it % len(shards)])
        self.it += 1

    def adopt_shards(self, ranges, **kw):
        self.ranges = tuple(ranges)


def two_host_view(spill_dir=None):
    return ClusterView.bootstrap(
        [
            HostInfo(0, loader_ranks=(1,), trainer_ranks=(0,)),
            HostInfo(1, loader_ranks=(2,), cache_spill_dir=spill_dir),
        ],
        n_shards=N_SHARDS,
    )


def drain_cluster(
    plan=None,
    n_epochs=14,
    lease_s=1.5,
    kill_host_after_epoch=None,
    metrics=None,
    collective=False,
    spill_dir=None,
    pace_s=0.0,
    tenant=None,
):
    """Run the 2-mock-host THREAD pipeline under ``plan``; returns
    (windows-by-shard, metrics, supervisor).  ``tenant`` (a serve-tier
    handle) is bound to the loader, so every window is admitted through
    its scheduler.  ``kill_host_after_epoch``
    hard-kills mock host 1 at that epoch boundary; ``collective`` runs
    a jitted psum over the 8-device CPU mesh after every window and
    asserts it — "the collectives continue" through recovery.
    ``pace_s`` sleeps per epoch so sweep-driven chaos (heartbeat faults,
    lease expiry) gets wall time to act mid-stream — the tiny geometry
    otherwise finishes before the monitor's first poll."""
    m = metrics or Metrics()
    producer = ShardRangeProducer({1: ((0, 2),), 2: ((2, 4),)})

    @distributed_dataloader(n_producers=2, mode="thread")
    def main(env):
        sup = ClusterSupervisor(
            two_host_view(spill_dir), lease_s=lease_s, metrics=m
        )
        elastic = ElasticCluster(sup, workers=env.workers, metrics=m)
        loader = DistributedDataLoader(
            producer, batch_size=ROWS, connection=env.connection,
            n_epochs=n_epochs, output="numpy", timeout_s=60.0,
            metrics=m, cluster=elastic,
        )
        if tenant is not None:
            tenant.bind(loader)
        wd = Watchdog(
            env.workers, poll_interval_s=0.05, stall_budget_s=60.0,
            respawn=True, metrics=m, cluster=sup,
        ).start()
        psum = None
        if collective:
            import jax

            psum = jax.jit(
                lambda x: jax.numpy.sum(
                    jax.numpy.ones((len(jax.devices()),)) * x
                )
            )
        seen = {}
        try:
            for ep in range(n_epochs):
                for (win,) in loader:
                    shard = int(win[0, 0] // 1000)
                    seen.setdefault(shard, []).append(win.copy())
                    if psum is not None:
                        total = float(psum(1.0))
                        assert total == float(len(__import__("jax").devices()))
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
                if pace_s:
                    time.sleep(pace_s)
                if ep == kill_host_after_epoch:
                    elastic.kill_host(1)
        finally:
            wd.stop()
        return seen, sup

    if plan is not None:
        with faults.armed(plan):
            seen, sup = main()
    else:
        seen, sup = main()
    return seen, m, sup


def assert_full_coverage_byte_identical(seen):
    assert sorted(seen) == list(range(N_SHARDS)), sorted(seen)
    for shard, wins in seen.items():
        for w in wins:
            np.testing.assert_array_equal(
                w, shard_pattern(shard), err_msg=f"shard {shard}"
            )


# ---------------------------------------------------------------------------
# Units: partitioning + view change
# ---------------------------------------------------------------------------


class TestViewChange:
    def test_partition_covers_all_shards_deterministically(self):
        a = partition_shards(10, [3, 1, 2])
        b = partition_shards(10, [2, 3, 1])
        assert a == b  # order-independent (sorted inside)
        covered = sorted(
            s for r in a.values() for lo, hi in r for s in range(lo, hi)
        )
        assert covered == list(range(10))

    def test_partition_zero_hosts_raises(self):
        with pytest.raises(DDLError):
            partition_shards(4, [])

    def test_view_change_is_pure_and_deterministic(self):
        v = ClusterView.bootstrap(
            [HostInfo(i, loader_ranks=(i + 1,)) for i in range(4)],
            n_shards=16,
        )
        a = view_change(v, frozenset({2}))
        b = view_change(v, frozenset({2}))
        assert a == b
        assert a.epoch == v.epoch + 1
        assert {h.host_id for h in a.hosts} == {0, 1, 3}
        # Survivors keep their ranges; only orphans moved.
        for hid in (0, 1, 3):
            assert set(v.ranges_of(hid)) <= set(a.ranges_of(hid))
        covered = sorted(
            s
            for _hid, r in a.shard_ranges
            for lo, hi in r
            for s in range(lo, hi)
        )
        assert covered == list(range(16))

    def test_view_change_unknown_host_is_a_noop_without_epoch_bump(self):
        v = two_host_view()
        assert view_change(v, frozenset({99})) is v

    def test_last_host_death_raises(self):
        v = ClusterView.bootstrap([HostInfo(0, loader_ranks=(1,))], 4)
        with pytest.raises(HostLostError):
            view_change(v, frozenset({0}))

    def test_rejoin_repartitions_at_a_new_fence(self):
        v = two_host_view()
        lost = view_change(v, frozenset({1}))
        back = view_rejoin(lost, v.host(1))
        assert back.epoch == lost.epoch + 1
        assert back.shard_ranges == v.shard_ranges  # balanced layout back
        with pytest.raises(DDLError):
            view_rejoin(back, v.host(1))  # already a member

    def test_loader_pool_tracks_view(self):
        v = two_host_view()
        assert v.loader_pool() == LoaderPool((0, 1), generation=0)
        lost = view_change(v, frozenset({1}))
        assert lost.loader_pool() == LoaderPool((0,), generation=1)


class TestLeases:
    def test_beat_refreshes_and_expiry_fires(self):
        now = [0.0]
        lt = LeaseTable(lease_s=1.0, clock=lambda: now[0])
        lt.register(7)
        now[0] = 0.9
        assert lt.expired() == []
        lt.beat(7)
        now[0] = 1.8
        assert lt.expired() == []  # refreshed at 0.9
        now[0] = 2.0
        assert lt.expired() == [7]
        lt.release(7)
        assert lt.expired() == []
        assert lt.remaining(7) == float("inf")

    def test_beat_on_unregistered_host_is_ignored(self):
        lt = LeaseTable(lease_s=1.0)
        lt.beat(3)  # never registered: no resurrection
        assert lt.registered() == []


class TestSupervisor:
    def _sup(self, lease_s=1.0, clock=None, metrics=None):
        sup = ClusterSupervisor(
            two_host_view(), lease_s=lease_s, metrics=metrics or Metrics(),
            **({"clock": clock} if clock else {}),
        )
        return sup

    def test_dead_source_expires_lease_into_view_change(self):
        now = [0.0]
        m = Metrics()
        sup = self._sup(lease_s=1.0, clock=lambda: now[0], metrics=m)
        alive = {0: True, 1: True}
        sup.attach_source(0, lambda: alive[0])
        sup.attach_source(1, lambda: alive[1])
        events = []
        sup.add_listener(lambda o, n, d: events.append((n.epoch, set(d))))
        assert sup.sweep(now[0]) is None
        alive[1] = False
        now[0] = 0.9
        assert sup.sweep(now[0]) is None  # lease not yet lapsed
        now[0] = 2.1
        new = sup.sweep(now[0])
        assert new is not None and new.epoch == 1
        assert events == [(1, {1})]
        assert sup.lost_ranks() == frozenset({2})
        assert m.counter("cluster.view_changes") == 1
        assert m.counter("cluster.host_losses") == 1

    def test_host_loss_fault_declares_immediately(self):
        m = Metrics()
        sup = self._sup(lease_s=100.0, metrics=m)
        plan = FaultPlan(
            [FaultSpec("cluster.heartbeat", FaultKind.HOST_LOSS,
                       producer_idx=1)]
        )
        with faults.armed(plan):
            new = sup.sweep()
        assert new is not None and new.epoch == 1
        assert plan.fired
        assert {h.host_id for h in sup.view.hosts} == {0}

    def test_heartbeat_drop_only_ages_the_lease(self):
        now = [0.0]
        m = Metrics()
        sup = self._sup(lease_s=1.0, clock=lambda: now[0], metrics=m)
        sup.attach_source(0, lambda: True)
        sup.attach_source(1, lambda: True)  # alive, but beats get dropped
        plan = FaultPlan(
            [FaultSpec("cluster.heartbeat", FaultKind.HEARTBEAT_DROP,
                       producer_idx=1, count=10_000)]
        )
        with faults.armed(plan):
            assert sup.sweep(0.5) is None  # one drop != one loss
            assert m.counter("cluster.heartbeats_dropped") >= 1
            now[0] = 2.0
            new = sup.sweep(now[0])  # only EXPIRY changes the view
        assert new is not None
        assert {h.host_id for h in sup.view.hosts} == {0}

    def test_external_beat_keeps_sourceless_host_alive(self):
        now = [0.0]
        sup = self._sup(lease_s=1.0, clock=lambda: now[0])
        # Host 1 has no attached source (a remote host): external beats.
        sup.attach_source(0, lambda: True)
        for t in (0.5, 1.0, 1.5):
            now[0] = t
            sup.beat(1, t)
            assert sup.sweep(t) is None

    def test_remote_loss_never_mutes_local_monitoring(self):
        """Rank numbering is per process: host 0 (local) and host 1
        (remote) both claim rank 1.  A REMOTE loss must not put rank 1
        in lost_ranks() — the watchdog would stop monitoring this
        process's own live producer forever."""
        view = ClusterView.bootstrap(
            [
                HostInfo(0, loader_ranks=(1,), trainer_ranks=(0,)),
                HostInfo(1, loader_ranks=(1,), trainer_ranks=(1,)),
            ],
            n_shards=4,
        )
        sup = ClusterSupervisor(
            view, lease_s=60.0, metrics=Metrics(), local_host_ids={0}
        )
        sup.declare_host_loss(1)
        assert sup.lost_ranks() == frozenset()
        # The LOCAL host's loss still reports its ranks.
        sup2 = ClusterSupervisor(
            ClusterView.bootstrap(
                [
                    HostInfo(0, loader_ranks=(1,)),
                    HostInfo(1, loader_ranks=(2,)),
                ],
                n_shards=4,
            ),
            lease_s=60.0, metrics=Metrics(), local_host_ids={1},
        )
        sup2.declare_host_loss(1)
        assert sup2.lost_ranks() == frozenset({2})

    def test_elastic_local_scope_pool_and_adoptions(self):
        """ElasticCluster(local_host_id=) publishes only the local
        host's ranks as the loader pool slice."""
        view = ClusterView.bootstrap(
            [
                HostInfo(0, loader_ranks=(1, 2), trainer_ranks=(0,)),
                HostInfo(1, loader_ranks=(1, 2), trainer_ranks=(1,)),
            ],
            n_shards=4,
        )
        sup = ClusterSupervisor(view, lease_s=60.0, metrics=Metrics())
        elastic = ElasticCluster(sup, metrics=Metrics(), local_host_id=0)
        pool = elastic._local_pool(sup.view)
        assert pool.members == (0, 1) and pool.generation == 0
        assert sup.local_host_ids == {0}

    def test_restore_epoch_fast_forwards_the_fence(self):
        sup = self._sup()
        sup.restore_epoch(7)
        assert sup.view.epoch == 7
        sup.restore_epoch(3)  # never rewinds
        assert sup.view.epoch == 7

    def test_crashing_listener_does_not_stop_the_ladder(self):
        m = Metrics()
        sup = self._sup(metrics=m)
        calls = []
        sup.add_listener(lambda o, n, d: 1 / 0)
        sup.add_listener(lambda o, n, d: calls.append(n.epoch))
        sup.declare_host_loss(1)
        assert calls == [1]

    def test_checkpoint_carries_the_cluster_epoch(self, tmp_path):
        sup = self._sup()
        sup.declare_host_loss(1)

        class FakeLoader:
            _epoch, _target, _batches_in_window = 3, 0, 0

        ck = LoaderCheckpoint.capture(FakeLoader(), cluster=sup)
        assert ck.cluster_epoch == 1
        path = str(tmp_path / "ck.json")
        ck.save(path)
        restored = LoaderCheckpoint.load(path)
        sup2 = self._sup()
        restored.apply(FakeLoader(), cluster=sup2)
        assert sup2.view.epoch == 1


# ---------------------------------------------------------------------------
# Units: placement
# ---------------------------------------------------------------------------


def island_view():
    """4 loader + 4 trainer hosts; islands pair roles ACROSS the naive
    round-robin so reordering wins 8x under the model."""
    hosts = [HostInfo(h, loader_ranks=(h + 1,)) for h in (0, 1, 2, 3)] + [
        HostInfo(h, trainer_ranks=(h - 4,)) for h in (4, 5, 6, 7)
    ]
    return ClusterView.bootstrap(hosts, n_shards=8)


def island_costs(intra=8e9, cross=1e9):
    return LinkCosts.islands(
        [[0, 5], [1, 4], [2, 7], [3, 6]], intra, cross
    )


class TestPlacement:
    def test_reorder_rides_fast_links(self):
        plan = plan_placement(island_view(), island_costs())
        assert plan.reordered
        assert plan.assignment == ((0, 5), (1, 4), (2, 7), (3, 6))
        assert plan.modeled_ratio == pytest.approx(8.0)

    def test_never_slower_fallback_on_uniform_fabric(self):
        costs = LinkCosts({}, default_bytes_per_s=1e9)
        plan = plan_placement(island_view(), costs)
        assert not plan.reordered
        assert plan.assignment == naive_placement(island_view())
        assert plan.modeled_ratio == 1.0

    def test_assignment_is_balanced(self):
        # 4 producers, 2 consumers -> each consumer takes exactly 2.
        hosts = [HostInfo(h, loader_ranks=(h + 1,)) for h in range(4)] + [
            HostInfo(h, trainer_ranks=(h - 4,)) for h in (4, 5)
        ]
        view = ClusterView.bootstrap(hosts, n_shards=4)
        costs = LinkCosts({(p, 4): 9e9 for p in range(4)},
                          default_bytes_per_s=1e9)
        plan = plan_placement(view, costs)
        fan = {}
        for _p, c in plan.assignment:
            fan[c] = fan.get(c, 0) + 1
        assert max(fan.values()) <= 2

    def test_colocated_roles_fall_back_to_all_hosts_as_consumers(self):
        v = two_host_view()  # host 1 has no trainer ranks
        plan = plan_placement(v, LinkCosts({}))
        assert {p for p, _c in plan.assignment} == {0, 1}

    def test_probe_is_positive_and_deadline_bounded(self):
        costs = probe_link_costs([0, 1, 2], payload_bytes=1 << 14, reps=1)
        assert costs.source == "probed"
        assert costs.bytes_per_s(0, 1) > 0
        assert costs.bytes_per_s(0, 1) == costs.bytes_per_s(1, 0)
        slow = probe_link_costs(
            [0, 1], transfer=lambda a, b, p: time.sleep(0.2),
            payload_bytes=1 << 10, reps=1, timeout_s=0.0,
        )
        assert slow.source == "probed-partial"

    def test_measured_ratio_wins_on_the_simulated_fabric(self):
        # Scaled-down wire times (~0.4/3ms per transfer) keep the test
        # fast while the planned assignment still measures faster.
        costs = island_costs(intra=8e9, cross=1e9)
        fabric = SimulatedFabric(costs)
        view = island_view()
        plan = plan_placement(view, costs)
        naive_rate = measure_assignment(
            naive_placement(view), fabric, payload_bytes=1 << 22, reps=2
        )
        plan_rate = measure_assignment(
            plan.assignment, fabric, payload_bytes=1 << 22, reps=2
        )
        assert plan_rate > naive_rate * 1.5

    def test_placement_report_contract(self):
        block = placement_report(
            island_view(), island_costs(), payload_bytes=1 << 20, reps=1
        )
        for key in (
            "bytes_per_s", "naive_bytes_per_s", "topo_bytes_per_s",
            "ratio", "modeled_ratio", "winner", "reordered", "n_hosts",
            "n_links", "cost_source", "payload_bytes",
        ):
            assert key in block, key
        assert block["bytes_per_s"] == max(
            block["naive_bytes_per_s"], block["topo_bytes_per_s"]
        )


class TestLoaderPoolUnit:
    def test_members_deduped_and_sorted(self):
        p = LoaderPool((3, 1, 1, 0))
        assert p.members == (0, 1, 3)
        assert 3 in p and 2 not in p

    def test_without_and_union_bump_generation(self):
        p = LoaderPool((0, 1, 2), generation=4)
        q = p.without([1])
        assert q.members == (0, 2) and q.generation == 5
        r = q.union([1])
        assert r.members == (0, 1, 2) and r.generation == 6

    def test_next_member_wraps_and_honours_include(self):
        p = LoaderPool((0, 2, 3))
        assert p.next_member(0) == 2
        assert p.next_member(3) == 0
        assert p.next_member(2, include=True) == 2
        assert p.next_member(1, include=True) == 2
        with pytest.raises(DDLError):
            LoaderPool(()).next_member(0)


# ---------------------------------------------------------------------------
# Units: host identity (the one-consumer-per-host skew fix)
# ---------------------------------------------------------------------------


class TestHostIdentity:
    def test_env_wins(self, monkeypatch):
        monkeypatch.setenv("DDL_TPU_HOST_ID", "3")
        monkeypatch.setenv("DDL_TPU_N_HOSTS", "8")
        assert detect_host_identity(32, 17) == (3, 8)

    def test_slurm_node_identity(self, monkeypatch):
        for var in ("DDL_TPU_HOST_ID", "DDL_TPU_N_HOSTS"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("SLURM_NODEID", "2")
        monkeypatch.setenv("SLURM_NNODES", "4")
        # 16 processes over 4 nodes: node identity, NOT process identity.
        assert detect_host_identity(16, 11) == (2, 4)

    def test_procs_per_host_arithmetic(self, monkeypatch):
        for var in (
            "DDL_TPU_HOST_ID", "DDL_TPU_N_HOSTS",
            "SLURM_NODEID", "SLURM_NNODES",
        ):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("DDL_TPU_PROCS_PER_HOST", "4")
        # THE skew: 8 consumer processes are 2 hosts, not 8.
        assert detect_host_identity(8, 5) == (1, 2)
        monkeypatch.delenv("DDL_TPU_PROCS_PER_HOST")
        # Historical default: host == instance.
        assert detect_host_identity(8, 5) == (5, 8)

    def test_topology_carries_and_validates_host_fields(self):
        t = Topology(n_instances=4, instance_idx=3, n_producers=1,
                     host_id=1, n_hosts=2)
        assert (t.host_id, t.n_hosts) == (1, 2)
        with pytest.raises(ValueError):
            Topology(n_instances=4, instance_idx=0, host_id=2, n_hosts=2)
        # n_hosts MAY exceed n_instances: a single-host THREAD run
        # launched inside a multi-node SLURM allocation still knows it
        # is node 2 of 4 (and loader-only hosts carry no consumer).
        t = Topology(n_instances=1, instance_idx=0, host_id=2, n_hosts=4)
        assert (t.host_id, t.n_hosts) == (2, 4)

    def test_single_host_run_inside_slurm_allocation(self, monkeypatch):
        """Regression: a plain THREAD-mode run launched via srun on one
        node of a multi-node allocation must not crash at topology
        detection (the SLURM vars name node k of N)."""
        for var in ("DDL_TPU_HOST_ID", "DDL_TPU_N_HOSTS"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("SLURM_NODEID", "2")
        monkeypatch.setenv("SLURM_NNODES", "4")
        t = detect_topology(1, "thread")
        assert (t.n_instances, t.host_id, t.n_hosts) == (1, 2, 4)

    def test_partial_env_widens_instead_of_crashing(self, monkeypatch):
        """DDL_TPU_HOST_ID without DDL_TPU_N_HOSTS (half-set env):
        n_hosts widens to cover the id rather than failing topology
        validation downstream."""
        for var in ("DDL_TPU_N_HOSTS", "SLURM_NODEID", "SLURM_NNODES",
                    "DDL_TPU_PROCS_PER_HOST"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("DDL_TPU_HOST_ID", "5")
        assert detect_host_identity(1, 0) == (5, 6)

    def test_export_clears_only_own_stale_exports(self, monkeypatch):
        """A config stating an opinion exports; a later sentinel config
        clears exactly THOSE exports (never user-set env) — the
        _export_cache_knobs precedent."""
        from ddl_tpu.config import LoaderConfig
        from ddl_tpu.env import _export_cluster_knobs

        for var in ("DDL_TPU_HOST_ID", "DDL_TPU_N_HOSTS",
                    "DDL_TPU_PROCS_PER_HOST"):
            monkeypatch.delenv(var, raising=False)
        _export_cluster_knobs(LoaderConfig(host_id=2, n_hosts=4))
        assert os.environ["DDL_TPU_HOST_ID"] == "2"
        assert os.environ["DDL_TPU_N_HOSTS"] == "4"
        _export_cluster_knobs(LoaderConfig())  # sentinels: auto-detect
        assert "DDL_TPU_HOST_ID" not in os.environ
        assert "DDL_TPU_N_HOSTS" not in os.environ
        # USER-set env survives a sentinel config untouched.
        monkeypatch.setenv("DDL_TPU_HOST_ID", "7")
        _export_cluster_knobs(LoaderConfig())
        assert os.environ["DDL_TPU_HOST_ID"] == "7"

    def test_detect_topology_threads_explicit_identity(self, monkeypatch):
        for var in ("DDL_TPU_HOST_ID", "DDL_TPU_N_HOSTS"):
            monkeypatch.delenv(var, raising=False)
        t = detect_topology(1, "thread", host_id=0, n_hosts=1)
        assert (t.host_id, t.n_hosts) == (0, 1)


# ---------------------------------------------------------------------------
# The loader-pool seam
# ---------------------------------------------------------------------------


class TestLoaderPoolSeam:
    def test_pool_applies_at_boundary_and_fences_generations(self):
        m = Metrics()
        producer = ShardRangeProducer({1: ((0, 2),), 2: ((2, 4),)})

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                producer, batch_size=ROWS, connection=env.connection,
                n_epochs=6, output="numpy", timeout_s=30.0, metrics=m,
            )
            seen = []
            for ep in range(6):
                for (win,) in loader:
                    seen.append(int(win[0, 0] // 1000))
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
                if ep == 1:
                    loader.apply_pool(LoaderPool((0,), generation=1))
                    # Stale generation after a newer one: ignored.
                    loader.apply_pool(LoaderPool((0, 1), generation=0))
            return seen

        seen = main()
        # Epochs 0-1 alternate producers; the pool then pins target 0,
        # whose shard cycle (0, 1) continues alone.
        assert seen[:2] == [0, 2]
        assert set(seen[2:]) <= {0, 1}
        assert m.counter("consumer.pool_updates") == 1.0

    def test_empty_local_pool_raises(self):
        producer = ShardRangeProducer({1: ((0, 2),), 2: ((2, 4),)})

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                producer, batch_size=ROWS, connection=env.connection,
                n_epochs=2, output="numpy", timeout_s=30.0,
            )
            loader.apply_pool(LoaderPool((7,), generation=1))
            with pytest.raises(LoaderStateError):
                loader[0]
            loader.shutdown()

        main()


# ---------------------------------------------------------------------------
# E2E: the cross-host recovery ladder (THREAD mock hosts)
# ---------------------------------------------------------------------------


class TestElasticLadder:
    def test_host_kill_repartitions_byte_identical(self):
        seen, m, sup = drain_cluster(
            kill_host_after_epoch=3, collective=True
        )
        assert_full_coverage_byte_identical(seen)
        assert m.counter("cluster.view_changes") == 1.0
        assert m.counter("cluster.host_losses") == 1.0
        assert m.counter("consumer.pool_updates") >= 1.0
        assert sup.view.epoch == 1
        assert sup.lost_ranks() == frozenset({2})
        # Post-change epochs all come from the survivor: its cycle must
        # include the adopted shards.
        post = [s for s, wins in seen.items() if len(wins) > 2]
        assert set(post) & {2, 3}, seen.keys()

    def test_producer_crash_then_host_kill_rungs_compose(self):
        """Rung 1 (respawn) then rung 2 (host loss) in one run: the
        watchdog revives host 0's producer after an injected crash, and
        mock host 1 is later killed outright — both recoveries land and
        coverage stays byte-identical."""
        plan = FaultPlan(
            [FaultSpec("producer.fill", FaultKind.PRODUCER_CRASH,
                       at=2, producer_idx=1)]
        )
        seen, m, sup = drain_cluster(
            plan=plan, kill_host_after_epoch=5, n_epochs=14
        )
        assert plan.fired, "crash spec never fired"
        assert m.counter("watchdog.respawns") == 1.0
        assert m.counter("cluster.host_losses") == 1.0
        assert m.counter("watchdog.failures") == 0.0
        assert_full_coverage_byte_identical(seen)

    def test_watchdog_leaves_lost_ranks_to_the_cluster(self):
        """After the host kill, the watchdog keeps sweeping: the dead
        host's workers must never be escalated to on_failure (which
        would abort the run) nor respawned."""
        seen, m, sup = drain_cluster(kill_host_after_epoch=2, n_epochs=10)
        assert m.counter("watchdog.failures") == 0.0
        assert m.counter("watchdog.respawns") == 0.0
        assert_full_coverage_byte_identical(seen)

    def test_cache_warm_start_adoption_on_host_loss(
        self, tmp_path, monkeypatch
    ):
        """The dead host's spill dir is adopted at the view change: the
        survivor's default store serves the dead host's disk tier."""
        from ddl_tpu import cache as cache_mod
        from ddl_tpu.cache import CacheKey, CacheStore

        spill = str(tmp_path / "host1-spill")
        # Seed a disk tier the way host 1 would have: a store writing
        # through to its spill dir.
        seeder = CacheStore(
            ram_budget_bytes=1 << 20, spill_dir=spill,
            spill_budget_bytes=1 << 20, metrics=Metrics(),
        )
        key = CacheKey(source="src-1", shard="shard-0", reader="seed")
        seeder.put(key, np.arange(8, dtype=np.float32))
        # A fresh RAM-only default store on the "survivor" side.
        monkeypatch.delenv("DDL_TPU_CACHE_SPILL_DIR", raising=False)
        cache_mod.reset_default_store()
        try:
            store = cache_mod.default_store()
            assert store.spill_dir is None
            seen, m, sup = drain_cluster(
                kill_host_after_epoch=3, spill_dir=spill,
            )
            assert m.counter("cluster.cache_adoptions") == 1.0
            assert store.spill_dir == os.path.abspath(spill)
            got = store.get(key)
            assert got is not None
            np.testing.assert_array_equal(
                got, np.arange(8, dtype=np.float32)
            )
            assert_full_coverage_byte_identical(seen)
        finally:
            cache_mod.reset_default_store()
            monkeypatch.delenv("DDL_TPU_CACHE_SPILL_DIR", raising=False)

    def test_windows_stream_survives_host_kill(self):
        """The zero-copy windows() stream rides the same pool seam: a
        mid-stream host kill rotates the stream onto survivors."""
        m = Metrics()
        producer = ShardRangeProducer({1: ((0, 2),), 2: ((2, 4),)})
        n_epochs = 12

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            sup = ClusterSupervisor(
                two_host_view(), lease_s=2.0, metrics=m
            )
            elastic = ElasticCluster(sup, workers=env.workers, metrics=m)
            loader = DistributedDataLoader(
                producer, batch_size=ROWS, connection=env.connection,
                n_epochs=n_epochs, output="jax", timeout_s=60.0,
                metrics=m, cluster=elastic,
            )
            seen = {}
            served = 0
            for win in loader.windows():
                arr = np.asarray(win).reshape(ROWS, VALS)
                seen.setdefault(int(arr[0, 0] // 1000), []).append(
                    arr.copy()
                )
                served += 1
                loader.mark(Marker.END_OF_EPOCH)
                if served == 4:
                    elastic.kill_host(1)
            assert served == n_epochs
            return seen

        seen = main()
        assert_full_coverage_byte_identical(seen)
        assert m.counter("cluster.host_losses") == 1.0
