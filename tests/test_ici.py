"""ICI ingest tier tests: Pallas fan-out kernels (interpret mode),
redistribution planner properties, distributor byte-identity vs the xla
path, the loader seam, and the ``ici.fanout`` chaos row.

Everything runs on the 8-device CPU virtual mesh (conftest.py): the
fan-out kernels execute under Pallas' TPU interpret mode — the same
kernel code Mosaic compiles on a real pod, with per-device progress,
remote DMAs, semaphores and the entry barrier simulated — which is how
tier-1 proves the device-side distribution tier is byte-identical to
the host (``device_put``-scattered) path, and race-free, before a chip
ever sees it (tests/test_tpu_compile.py covers the Mosaic compile).
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddl_tpu import (
    DistributedDataLoader,
    Marker,
    distributed_dataloader,
)
from ddl_tpu import faults
from ddl_tpu.exceptions import KernelBuildError
from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
from ddl_tpu.ingest import DeviceIngestor
from ddl_tpu.observability import Metrics
from ddl_tpu.ops import ici_fanout
from ddl_tpu.parallel.ici import (
    DEFAULT_MEMORY_FACTOR,
    DRYRUN_MATRIX,
    IciDistributor,
    PlanError,
    plan_distribution,
)


def _ring(n):
    devs = jax.devices()
    assert len(devs) >= n, f"need {n} virtual devices, have {len(devs)}"
    return tuple(devs[:n])


def _mesh(axes):
    names = [a for a, _ in axes]
    shape = [n for _, n in axes]
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), names)


# -- fan-out kernel units (interpret mode) ------------------------------------


class TestFanoutReplicate:
    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    @pytest.mark.parametrize("n_chunks", [1, 3, 4])
    def test_every_block_identical(self, n_dev, n_chunks):
        devs = _ring(n_dev)
        rows, cols = 12, 8
        x = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
        blk = jax.device_put(x, devs[0])
        out = ici_fanout.fanout_replicate(blk, devs, n_chunks=n_chunks)
        got = np.asarray(out)
        for i in range(n_dev):
            np.testing.assert_array_equal(
                got[i * rows : (i + 1) * rows], x,
                err_msg=f"ring position {i} diverged "
                f"(n_dev={n_dev}, n_chunks={n_chunks})",
            )

    @pytest.mark.parametrize("src", [1, 3, 7])
    def test_ring_offsets_from_nonzero_source(self, src):
        """The ring rotation is relative to the source: a window that
        lands on device ``src`` must reach every OTHER position too."""
        devs = _ring(8)
        rows, cols = 8, 4
        x = np.random.default_rng(src).random((rows, cols)).astype(
            np.float32
        )
        blk = jax.device_put(x, devs[src])
        out = ici_fanout.fanout_replicate(blk, devs, src=src)
        got = np.asarray(out)
        for i in range(8):
            np.testing.assert_array_equal(got[i * rows : (i + 1) * rows], x)

    def test_non_divisible_chunk_tail(self):
        """rows % n_chunks != 0: the last chunk is short — the delivered
        payload must be exact."""
        devs = _ring(4)
        rows, cols = 10, 4  # chunks of 3, 3, 3, 1 rows
        x = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
        blk = jax.device_put(x, devs[0])
        out = ici_fanout.fanout_replicate(blk, devs, n_chunks=4)
        got = np.asarray(out)
        assert got.shape == (4 * rows, cols)
        for i in range(4):
            np.testing.assert_array_equal(got[i * rows : (i + 1) * rows], x)

    def test_more_chunks_than_rows_clamped(self):
        devs = _ring(2)
        x = np.ones((2, 4), np.float32)
        out = ici_fanout.fanout_replicate(
            jax.device_put(x, devs[0]), devs, n_chunks=16
        )
        np.testing.assert_array_equal(np.asarray(out), np.tile(x, (2, 1)))

    def test_single_device_passthrough(self):
        devs = _ring(1)
        x = np.ones((4, 4), np.float32)
        blk = jax.device_put(x, devs[0])
        assert ici_fanout.fanout_replicate(blk, devs) is blk

    def test_replicated_view_zero_copy(self):
        """The broadcast result reinterprets as ONE replicated array whose
        per-device shards are the blocks — no further transfer."""
        devs = _ring(4)
        rows, cols = 8, 4
        x = np.random.default_rng(0).random((rows, cols)).astype(np.float32)
        out = ici_fanout.fanout_replicate(jax.device_put(x, devs[0]), devs)
        rep = ici_fanout.replicated_view(out, devs)
        assert rep.shape == (rows, cols)
        assert len(rep.addressable_shards) == 4
        np.testing.assert_array_equal(np.asarray(rep), x)


class TestFanoutShard:
    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    def test_block_i_lands_on_device_i(self, n_dev):
        devs = _ring(n_dev)
        rows, cols = 2 * n_dev, 4
        x = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
        out = ici_fanout.fanout_shard(jax.device_put(x, devs[0]), devs)
        assert out.shape == (rows, cols)
        block = rows // n_dev
        for shard in out.addressable_shards:
            i = devs.index(shard.device)
            np.testing.assert_array_equal(
                np.asarray(shard.data), x[i * block : (i + 1) * block],
                err_msg=f"device {i} holds the wrong scatter block",
            )

    @pytest.mark.parametrize("src", [1, 5])
    def test_scatter_from_nonzero_source(self, src):
        devs = _ring(8)
        rows, cols = 16, 4
        x = np.random.default_rng(src).random((rows, cols)).astype(
            np.float32
        )
        out = ici_fanout.fanout_shard(
            jax.device_put(x, devs[src]), devs, src=src
        )
        np.testing.assert_array_equal(np.asarray(out), x)

    def test_indivisible_rows_rejected(self):
        devs = _ring(4)
        x = jax.device_put(np.ones((10, 4), np.float32), devs[0])
        with pytest.raises(ValueError, match="divisible"):
            ici_fanout.fanout_shard(x, devs)

    def test_full_ring_scatter(self):
        """All seven destinations of the full ring in flight at once,
        each on its own send semaphore."""
        devs = _ring(8)
        rows, cols = 8, 6
        x = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
        out = ici_fanout.fanout_shard(jax.device_put(x, devs[0]), devs)
        np.testing.assert_array_equal(np.asarray(out), x)

    def test_full_ring_broadcast_pipeline(self):
        """Six relays between source and tail, four chunks: every relay
        forwards chunk c only after its own receive of chunk c."""
        devs = _ring(8)
        assert ici_fanout.chunk_rows(8, 4) == ((0, 2), (2, 2), (4, 2), (6, 2))
        rows, cols = 8, 6
        x = np.random.default_rng(3).random((rows, cols)).astype(np.float32)
        out = ici_fanout.fanout_replicate(
            jax.device_put(x, devs[0]), devs, n_chunks=4
        )
        got = np.asarray(out)
        for i in range(8):
            np.testing.assert_array_equal(got[i * rows : (i + 1) * rows], x)


class TestTiling:
    """Blocks on Mosaic's HBM tiling go through as they are; anything
    else travels as its (R, 128) lane view (every other geometry in
    this file) — same bytes either way."""

    @pytest.mark.parametrize("dtype,rows", [
        (np.float32, 8), (np.int32, 16), (np.uint8, 32),
    ])
    def test_tile_aligned_blocks_skip_the_lane_view(self, dtype, rows):
        devs = _ring(4)
        cols = 2 * ici_fanout.LANES
        assert ici_fanout.kernel_view(4, rows, cols, dtype) == (
            4 * rows, cols
        )
        x = np.random.default_rng(1).integers(
            0, 200, (4 * rows, cols)
        ).astype(dtype)
        blk = jax.device_put(x, devs[0])
        np.testing.assert_array_equal(
            np.asarray(ici_fanout.fanout_shard(blk, devs)), x
        )
        np.testing.assert_array_equal(
            np.asarray(ici_fanout.fanout_replicate(blk, devs)),
            np.tile(x, (4, 1)),
        )

    def test_lane_view_geometry(self):
        # One row of 4096 int32 tokens = 32 lanes-rows of 128, 4 tiles.
        assert ici_fanout.kernel_view(4, 1, 4096, np.int32) == (128, 128)
        # 3 x 3 float32 = 9 elements -> one padded (8, 128) tile.
        assert ici_fanout.kernel_view(2, 3, 3, np.float32) == (16, 128)
        # bf16 tiles are 16 sublanes deep, int8 tiles 32.
        assert ici_fanout.lane_rows(1, "bfloat16") == 16
        assert ici_fanout.lane_rows(1, np.uint8) == 32


class TestKernelSynchronisation:
    """Every remote-DMA kernel, both landing slots back to back, from a
    non-zero source, with the interpreter's happens-before race
    detector on.  Interpret mode executes a DMA only when something
    waits on it — the adversarial schedule for a missing wait, which
    shows up as wrong bytes (a relay forwarding a chunk it has not
    received forwards the NaN fill).  The detector is a second net, not
    the proof: it cannot see the hazard the entry barrier guards (the
    simulator allocates every buffer up front); the four-chip run of
    ``chip_smoke.py --chips 4`` is the evidence for that."""

    @pytest.fixture
    def races(self, monkeypatch):
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call
        from jax.experimental.pallas import tpu as pltpu

        from ddl_tpu.ops import device_shuffle

        def detecting(interpret):
            assert interpret
            return pltpu.InterpretParams(detect_races=True)

        monkeypatch.setattr(ici_fanout, "interpret_arg", detecting)
        monkeypatch.setattr(device_shuffle, "interpret_arg", detecting)
        caches = (
            ici_fanout._bcast_call, ici_fanout._scatter_call,
            device_shuffle._exchange_call,
        )
        for c in caches:
            c.cache_clear()
        yield lambda: interpret_pallas_call.races.races_found
        for c in caches:
            c.cache_clear()
        pltpu.reset_tpu_interpret_mode_state()

    def test_fanout_kernels_race_free(self, races):
        devs = _ring(4)
        x = np.random.default_rng(0).random((16, 8)).astype(np.float32)
        for slot in (0, 1, 0, 1):
            blk = jax.device_put(x + slot, devs[1])
            rep = ici_fanout.fanout_replicate(blk, devs, src=1, slot=slot)
            np.testing.assert_array_equal(
                np.asarray(rep), np.tile(x + slot, (4, 1))
            )
            sh = ici_fanout.fanout_shard(blk, devs, src=1, slot=slot)
            np.testing.assert_array_equal(np.asarray(sh), x + slot)
        assert not races()

    def test_exchange_kernel_race_free(self, races):
        from ddl_tpu.ops import device_shuffle
        from ddl_tpu.shuffle import exchange_permutation

        devs = _ring(4)
        blocks = [
            np.full((4, 8), i, np.float32) + np.arange(4)[:, None] / 10
            for i in range(4)
        ]
        for rnd in range(4):
            p = np.asarray(exchange_permutation(4, 7, rnd))
            gin = device_shuffle.as_exchange_input(blocks, devs)
            out = device_shuffle.exchange_wait(
                device_shuffle.exchange_start(
                    "ring", gin, devs, p, slot=rnd % 2
                )
            )
            ref = device_shuffle.exchange_wait(
                device_shuffle.exchange_start("xla", gin, devs, p)
            )
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert not races()


class TestWireMath:
    def test_replicate_wire(self):
        # Every non-tail ring position forwards the whole window once.
        nbytes = 4 * 1024
        assert ici_fanout.wire_bytes("replicate", nbytes, 4) == 3 * nbytes

    def test_shard_wire(self):
        # Each off-source block is sent once, straight to its owner.
        nbytes = 8 * 1024
        assert ici_fanout.wire_bytes("shard", nbytes, 8) == (
            nbytes - nbytes // 8
        )

    @pytest.mark.parametrize("rows,n_chunks,expect", [
        (5, 4, ((0, 2), (2, 2), (4, 1))),  # short last chunk, 3 not 4
        (2, 16, ((0, 1), (1, 1))),  # clamped to the row count
        (12, 1, ((0, 12),)),
    ])
    def test_chunk_split_covers_rows_exactly(self, rows, n_chunks, expect):
        """No padding: the static chunk split tiles the rows exactly, so
        no DMA moves a byte that is not payload."""
        assert ici_fanout.chunk_rows(rows, n_chunks) == expect

    def test_single_device_is_free(self):
        assert ici_fanout.wire_bytes("replicate", 1024, 1) == 0
        assert ici_fanout.wire_bytes("shard", 1024, 1) == 0

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            ici_fanout.wire_bytes("gather", 1024, 4)


# -- redistribution planner properties ----------------------------------------


class TestPlanProperties:
    """Every loader→trainer pair in the dryrun matrix: the plan exists,
    its peak stays under the asserted memory bound, and executing it
    lands on the EXACT target NamedSharding with identical bytes."""

    @pytest.mark.parametrize(
        "axes,spec_entries", DRYRUN_MATRIX,
        ids=[
            "x".join(f"{a}{n}" for a, n in axes) + "-" + repr(spec)
            for axes, spec in DRYRUN_MATRIX
        ],
    )
    def test_plan_lands_on_target(self, axes, spec_entries):
        mesh = _mesh(axes)
        sharding = NamedSharding(mesh, P(*spec_entries))
        ndim = len(spec_entries)
        shape = tuple([16] * ndim)
        x = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)

        plan = plan_distribution(shape, x.dtype, sharding)
        assert plan.peak_factor <= DEFAULT_MEMORY_FACTOR, (
            f"plan peak {plan.peak_factor:.2f}x breaches the "
            f"{DEFAULT_MEMORY_FACTOR}x bound"
        )
        assert plan.peak_bytes == max(l.peak_bytes for l in plan.legs)
        assert plan.wire_bytes == sum(l.ici_bytes for l in plan.legs)

        dist = IciDistributor(sharding)
        out = dist.put(x, jax.device_put)
        ref = jax.device_put(x, sharding)
        assert not dist.faulted, "distribution latched the xla fallback"
        assert out.sharding.is_equivalent_to(ref.sharding, ndim), (
            f"landed on {out.sharding} instead of the target {sharding}"
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_two_sharded_dims_rejected(self):
        mesh = _mesh((("dp", 4), ("tp", 2)))
        sharding = NamedSharding(mesh, P("dp", "tp"))
        with pytest.raises(PlanError, match="single split dim"):
            plan_distribution((16, 16), np.float32, sharding)

    def test_indivisible_split_rejected(self):
        mesh = _mesh((("dp", 8),))
        sharding = NamedSharding(mesh, P("dp"))
        with pytest.raises(PlanError, match="not divisible"):
            plan_distribution((12, 4), np.float32, sharding)

    def test_memory_bound_enforced(self):
        """A caller-tightened bound below the plan's computed peak must
        refuse the plan — the arXiv:2112.01075 discipline: a
        bounded-memory plan or no plan."""
        mesh = _mesh((("dp", 8),))
        sharding = NamedSharding(mesh, P("dp"))
        plan = plan_distribution((16, 16), np.float32, sharding)
        # landing block + output exceed one window
        assert plan.peak_factor > 1.0
        with pytest.raises(PlanError, match="memory bound"):
            plan_distribution(
                (16, 16), np.float32, sharding,
                max_memory_factor=plan.peak_factor - 0.01,
            )

    def test_replicate_plan_geometry(self):
        mesh = _mesh((("dp", 2), ("fsdp", 4)))
        sharding = NamedSharding(mesh, P(None, None))
        plan = plan_distribution((16, 16), np.float32, sharding)
        assert plan.mode == "replicate"
        assert plan.split_dim is None
        assert plan.rest_axes == ("dp", "fsdp")
        assert len(plan.ring_devices) == 8
        assert plan.dst_shard_bytes == 16 * 16 * 4

    def test_shard_plan_prices_gather_leg(self):
        """A partial split (g < n_dev) needs the tiled all_gather finish
        leg; a full split must not."""
        mesh = _mesh((("dp", 4), ("fsdp", 2)))
        partial = plan_distribution(
            (16, 16), np.float32, NamedSharding(mesh, P("dp"))
        )
        assert [l.kind for l in partial.legs] == [
            "fanout.shard", "all_gather", "reshape"
        ]
        full = plan_distribution(
            (16, 16), np.float32,
            NamedSharding(mesh, P(("dp", "fsdp"), None)),
        )
        assert [l.kind for l in full.legs] == ["fanout.shard", "reshape"]
        assert full.wire_bytes < partial.wire_bytes


# -- distributor: fallback ladder + chaos row ---------------------------------


class TestDistributorFallback:
    def _sharding(self):
        return NamedSharding(_mesh((("dp", 8),)), P("dp"))

    def test_unplannable_geometry_falls_back(self):
        """A target the fan-out ring cannot source (two sharded dims —
        XLA scatters it fine) must still deliver the window via the xla
        path and count the fallback ONCE per geometry — without
        latching the tier (an unplannable shape is a property of that
        geometry, not a broken DMA ring)."""
        m = Metrics()
        sharding = NamedSharding(
            _mesh((("dp", 4), ("fsdp", 2))), P("dp", "fsdp")
        )
        dist = IciDistributor(sharding, metrics=m)
        x = np.arange(8 * 8, dtype=np.float32).reshape(8, 8)
        out = dist.put(x, jax.device_put)
        assert not dist.faulted  # per-geometry rung, not the latch
        assert m.counter("ici.fallbacks") == 1
        np.testing.assert_array_equal(np.asarray(out), x)
        assert out.sharding.is_equivalent_to(sharding, 2)
        # Repeats of the same geometry serve the cached PlanError
        # without re-counting.
        dist.put(x + 1.0, jax.device_put)
        assert m.counter("ici.fallbacks") == 1

    def test_ragged_geometry_does_not_poison_the_tier(self):
        """One ragged put (rows not divisible by the ring) must not
        downgrade subsequent plannable window traffic to the xla path.
        The ragged shape raises the SAME ValueError the plain xla path
        raises (device_put rejects uneven shardings — xla-parity, not
        an ICI-specific failure), and crucially does not latch."""
        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m)
        ragged = np.arange(10 * 4, dtype=np.float32).reshape(10, 4)
        with pytest.raises(ValueError, match="divisible"):
            dist.put(ragged, jax.device_put)  # 10 % 8 != 0
        assert not dist.faulted  # per-geometry rung, tier stays up
        assert m.counter("ici.fallbacks") == 1
        window = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        out2 = dist.put(window, jax.device_put)
        np.testing.assert_array_equal(np.asarray(out2), window)
        assert m.counter("ici.windows") == 1  # rode the ICI tier
        assert m.counter("ici.fallbacks") == 1  # no new fallback

    def test_chaos_ici_fanout_latches_xla_fallback(self):
        """The ``ici.fanout`` fault site: a DMA-leg failure re-routes the
        window through the xla path, latches, counts ``ici.fallbacks``,
        and every later window skips the broken tier — the degradation
        ladder's newest rung."""
        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m)
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        plan = FaultPlan(
            [FaultSpec("ici.fanout", FaultKind.ICI_DMA_FAIL, at=1)]
        )
        with faults.armed(plan):
            out = dist.put(x, jax.device_put)
            assert plan.fired
            assert dist.faulted
            assert m.counter("ici.fallbacks") == 1
            np.testing.assert_array_equal(np.asarray(out), x)
            assert out.sharding.is_equivalent_to(dist.sharding, 2)
            # Latched: later windows take the xla path without touching
            # the fault site again (at=1 would re-fire on a second hit).
            out2 = dist.put(x + 1.0, jax.device_put)
            np.testing.assert_array_equal(np.asarray(out2), x + 1.0)
        assert m.counter("ici.fallbacks") == 1
        assert m.counter("ici.windows") == 0  # no window rode the tier

    @pytest.mark.parametrize("err", [
        AttributeError("module 'pltpu' has no attribute 'TPUCompilerParams'"),
        ValueError("collective_id has to be unspecified"),
        KernelBuildError("kernel failed to compile: Mosaic said no"),
    ], ids=lambda e: type(e).__name__)
    def test_kernel_build_error_propagates(self, monkeypatch, err):
        """A kernel that does not build or compile is a broken program,
        not a degraded link: it reaches the caller, nothing latches and
        nothing is counted — while the SAME distributor still latches on
        an injected DMA failure afterwards."""
        def broken(*a, **k):
            raise err

        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m)
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        with monkeypatch.context() as mp:
            mp.setattr(ici_fanout, "_scatter_call", broken)
            with pytest.raises(type(err), match=str(err)[:20]):
                dist.put(x, jax.device_put)
        assert not dist.faulted
        assert m.counter("ici.fallbacks") == 0
        with faults.armed(FaultPlan(
            [FaultSpec("ici.fanout", FaultKind.ICI_DMA_FAIL, at=1)]
        )):
            out = dist.put(x, jax.device_put)
        assert dist.faulted and m.counter("ici.fallbacks") == 1
        np.testing.assert_array_equal(np.asarray(out), x)

    def test_shutdown_propagates_without_latching(self):
        """``ShutdownRequested`` raised at the fault site is a shutdown,
        not a DMA failure: it must propagate (the loader's teardown
        machinery owns it) and must NOT latch the xla fallback — the
        same exemption every other ladder in the repo carries."""
        from ddl_tpu.exceptions import ShutdownRequested

        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m)
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        plan = FaultPlan(
            [FaultSpec("ici.fanout", FaultKind.SPURIOUS_SHUTDOWN, at=1)]
        )
        with faults.armed(plan):
            with pytest.raises(ShutdownRequested):
                dist.put(x, jax.device_put)
        assert not dist.faulted
        assert m.counter("ici.fallbacks") == 0

    def test_healthy_distribute_counts_wire_bytes(self):
        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m)
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        plan = dist.plan(x.shape, x.dtype)
        dist.put(x, jax.device_put)
        dist.put(x, jax.device_put)
        assert m.counter("ici.windows") == 2
        assert m.counter("ici.bytes") == 2 * plan.wire_bytes
        assert m.gauge("ici.peak_bytes") == plan.peak_bytes
        assert m.counter("ici.fallbacks") == 0

    def test_plan_cache_serves_and_bounds(self):
        dist = IciDistributor(self._sharding())
        p1 = dist.plan((16, 4), np.float32)
        assert dist.plan((16, 4), np.float32) is p1  # cached
        for r in range(8, 80, 8):  # 9 new geometries evict the oldest
            dist.plan((r, 2), np.float32)
        assert len(dist._plans) <= 8


# -- the ingest seam ----------------------------------------------------------


class TestIngestSeam:
    def _sharding(self):
        return NamedSharding(_mesh((("dp", 8),)), P("dp"))

    def test_auto_stays_xla_on_cpu(self):
        ing = DeviceIngestor(sharding=self._sharding())
        assert ing.distribute == "auto"
        assert not ing.ici_active  # no ICI to control on the CPU client

    def test_forced_ici_engages_on_virtual_mesh(self):
        ing = DeviceIngestor(sharding=self._sharding(), distribute="ici")
        assert ing.ici_active

    def test_xla_never_engages(self):
        ing = DeviceIngestor(sharding=self._sharding(), distribute="xla")
        assert not ing.ici_active

    def test_env_default(self, monkeypatch):
        monkeypatch.setenv("DDL_TPU_DISTRIBUTE", "ici")
        ing = DeviceIngestor(sharding=self._sharding())
        assert ing.distribute == "ici" and ing.ici_active

    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError, match="ici|xla|auto"):
            DeviceIngestor(
                sharding=self._sharding(), distribute="magic"
            )

    def test_single_device_never_ici(self):
        ing = DeviceIngestor(
            device=jax.devices()[0], distribute="ici"
        )
        assert not ing.ici_active  # nothing to fan out to

    def test_put_batch_ici_vs_xla_identical(self):
        sharding = self._sharding()
        batch = np.random.default_rng(0).random((32, 8)).astype(np.float32)
        ici_ing = DeviceIngestor(sharding=sharding, distribute="ici")
        xla_ing = DeviceIngestor(sharding=sharding, distribute="xla")
        try:
            a = ici_ing.put_batch(batch, splits=(7, 1))
            b = xla_ing.put_batch(batch, splits=(7, 1))
            for ca, cb in zip(a, b):
                np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
                assert ca.sharding.is_equivalent_to(cb.sharding, ca.ndim)
            assert ici_ing.ici().metrics.counter("ici.windows") >= 1
            assert not ici_ing.ici().faulted
        finally:
            ici_ing.close()
            xla_ing.close()


class TestReaderStreamByteIdentity:
    """ICI-distributed window streams ≡ the host (xla) path for every
    built-in shard reader, on the CPU virtual mesh — the tier-1 proof
    that the device-side distribution tier never changes bytes."""

    def _drain_windows(self, make_producer, distribute, n_epochs=2):
        # windows() yields (batches_per_window, batch, *features):
        # 32-row windows at batch 4 give a leading dim of 8, sharded
        # one batch-block per virtual device.
        sharding = NamedSharding(
            Mesh(np.array(jax.devices()), ("dp",)), P("dp")
        )

        @distributed_dataloader(n_producers=1, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                make_producer(), batch_size=4, connection=env.connection,
                n_epochs=n_epochs, output="jax", sharding=sharding,
                distribute=distribute,
            )
            out = []
            for win in loader.windows():
                out.append(np.asarray(win).copy())
                loader.mark(Marker.END_OF_EPOCH)
            ing = loader._ingestor
            return np.stack(out), (
                ing.ici().faulted if ing._ici is not None else None
            )

        return main()

    def _assert_streams_identical(self, make_producer):
        ici_stream, ici_faulted = self._drain_windows(make_producer, "ici")
        xla_stream, _ = self._drain_windows(make_producer, "xla")
        assert ici_faulted is False, (
            "ici stream silently degraded to the xla path — the A/B "
            "proved nothing"
        )
        np.testing.assert_array_equal(
            ici_stream, xla_stream,
            err_msg="ICI-distributed windows diverged from the host path",
        )

    def test_fileshard(self, tmp_path):
        rng = np.random.default_rng(0)
        for i in range(2):
            np.save(
                tmp_path / f"shard_{i}.npy",
                rng.standard_normal((32, 6)).astype(np.float32),
            )
        from ddl_tpu.readers import FileShardProducer

        self._assert_streams_identical(
            lambda: FileShardProducer(
                str(tmp_path / "shard_*.npy"), seed=0, warm=False
            )
        )

    def test_tfrecord(self, tmp_path):
        import sys

        sys.path.insert(0, os.path.dirname(__file__))
        from datagen import encode_example_int64, write_tfrecord

        payloads = [
            encode_example_int64(
                "input_ids", list(range(20 * i, 20 * i + 20))
            )
            for i in range(16)
        ]
        write_tfrecord(str(tmp_path / "toks.tfrecord"), payloads)
        from ddl_tpu.readers import TFRecordTokenProducer

        self._assert_streams_identical(
            lambda: TFRecordTokenProducer(
                str(tmp_path / "toks.tfrecord"), seq_len=8,
                window_rows=32, warm=False,
            )
        )

    def test_webdataset(self, tmp_path):
        pytest.importorskip("PIL")
        import sys

        sys.path.insert(0, os.path.dirname(__file__))
        from datagen import write_image_shard

        write_image_shard(
            str(tmp_path / "imgs.tar"),
            [(f"s{i:03d}", i % 3) for i in range(32)],
            size=8,
        )
        from ddl_tpu.readers import WebDatasetProducer

        self._assert_streams_identical(
            lambda: WebDatasetProducer(
                str(tmp_path / "imgs.tar"), image_size=8,
                window_rows=32, warm=False,
            )
        )


# -- the fused two-slot protocol ----------------------------------------------


class TestTwoSlotFused:
    """Double-buffered device-side landing slots (ISSUE 12): per-slot
    collective-id pairs + landing buffers, the split start/wait ticket
    surface, fused plan pricing, the slots-in-flight gauge, remat
    compatibility of the async legs, and the never-strand guarantee of
    a mid-fused-stream latch."""

    def _sharding(self):
        return NamedSharding(_mesh((("dp", 8),)), P("dp"))

    def test_per_slot_collective_ids_are_disjoint(self):
        """Two concurrently-running ring kernels must never share
        barrier semaphores: the slot-indexed Mosaic collective ids are
        pairwise distinct across modes AND slots."""
        ids = (
            ici_fanout._BCAST_COLLECTIVE_IDS
            + ici_fanout._SCATTER_COLLECTIVE_IDS
        )
        assert len(ids) == 2 * ici_fanout.N_SLOTS
        assert len(set(ids)) == len(ids)

    def test_slot_out_of_range_rejected(self):
        devs = _ring(2)
        x = jax.device_put(
            np.arange(8 * 4, dtype=np.float32).reshape(8, 4), devs[0]
        )
        with pytest.raises(ValueError, match="landing slot"):
            ici_fanout.fanout_replicate(x, devs, slot=ici_fanout.N_SLOTS)
        with pytest.raises(ValueError, match="landing slot"):
            ici_fanout.fanout_shard(x, devs, slot=-1)

    def test_ticket_roundtrip_both_modes(self):
        devs = _ring(4)
        x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
        blk = jax.device_put(x, devs[0])
        t_rep = ici_fanout.fanout_start("replicate", blk, devs, slot=0)
        t_shard = ici_fanout.fanout_start("shard", blk, devs, slot=1)
        assert (t_rep.mode, t_rep.slot) == ("replicate", 0)
        assert (t_shard.mode, t_shard.slot) == ("shard", 1)
        out = ici_fanout.fanout_wait(t_rep, sync=True)
        for i in range(4):
            np.testing.assert_array_equal(
                np.asarray(out[i * 8 : (i + 1) * 8]), x
            )
        np.testing.assert_array_equal(
            np.asarray(ici_fanout.fanout_wait(t_shard)), x
        )
        with pytest.raises(ValueError, match="replicate|shard"):
            ici_fanout.fanout_start("gather", blk, devs)

    def test_two_in_flight_tickets_land_byte_identical(self):
        """The literal double-buffer: window B's ring is started before
        window A's is waited on — both land intact (per-slot landing
        buffers + collective ids keep them off each other)."""
        devs = _ring(4)
        a = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
        b = a + 1000.0
        ta = ici_fanout.fanout_start(
            "replicate", jax.device_put(a, devs[0]), devs, slot=0
        )
        tb = ici_fanout.fanout_start(
            "replicate", jax.device_put(b, devs[0]), devs, slot=1
        )
        out_a = ici_fanout.fanout_wait(ta)
        out_b = ici_fanout.fanout_wait(tb)
        for i in range(4):
            np.testing.assert_array_equal(
                np.asarray(out_a[i * 8 : (i + 1) * 8]), a
            )
            np.testing.assert_array_equal(
                np.asarray(out_b[i * 8 : (i + 1) * 8]), b
            )

    def test_landing_buffers_are_per_slot(self):
        devs = _ring(2)
        l0 = ici_fanout._landing_buffers(devs, 4, 4, "float32", 0, 0)
        l1 = ici_fanout._landing_buffers(devs, 4, 4, "float32", 0, 1)
        assert l0 is not l1  # distinct cached sets
        assert l0[1] is not l1[1]  # distinct device buffers
        assert ici_fanout._landing_buffers(devs, 4, 4, "float32", 0, 0) is l0

    def test_fused_plan_prices_both_slots(self):
        """n_slots=2 carries one extra in-flight fan-out through every
        leg: the fused peak is exactly twice the single-slot peak for a
        replicate plan (landing + output per slot), its legs are marked
        asynchronous, and the default bound scales with the slots."""
        sharding = self._sharding()
        p1 = plan_distribution((16, 8), np.float32, sharding, n_slots=1)
        p2 = plan_distribution((16, 8), np.float32, sharding, n_slots=2)
        assert p1.n_slots == 1 and p2.n_slots == 2
        assert p2.peak_bytes == 2 * p1.peak_bytes
        assert not any(leg.asynchronous for leg in p1.legs)
        assert all(
            leg.asynchronous for leg in p2.legs if "fanout" in leg.kind
        )
        # The single-slot bound rejects a fused REPLICATE plan's
        # doubled peak (2 × (landing + output) > 2.0 windows).
        replicated = NamedSharding(_mesh((("dp", 8),)), P(None, None))
        with pytest.raises(PlanError, match="memory bound"):
            plan_distribution(
                (16, 8), np.float32, replicated, n_slots=2,
                max_memory_factor=DEFAULT_MEMORY_FACTOR,
            )

    def test_fused_shard_plan_prices_extra_slot_through_every_leg(self):
        sharding = NamedSharding(
            _mesh((("dp", 4), ("fsdp", 2))), P("dp", None)
        )
        p1 = plan_distribution((16, 8), np.float32, sharding, n_slots=1)
        p2 = plan_distribution((16, 8), np.float32, sharding, n_slots=2)
        nbytes = 16 * 8 * 4
        slot_live = nbytes + nbytes // 8  # landing block + output block
        for l1, l2 in zip(p1.legs, p2.legs):
            assert l2.peak_bytes == l1.peak_bytes + slot_live
        assert p2.peak_factor <= 2 * DEFAULT_MEMORY_FACTOR

    def test_distributor_cycles_slots_and_counts(self):
        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m, n_slots=2)
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        outs = [dist.put(x + k, jax.device_put) for k in range(4)]
        for k, out in enumerate(outs):
            np.testing.assert_array_equal(np.asarray(out), x + k)
        assert m.counter("ici.windows") == 4
        assert m.counter("ici.fused_windows") == 4
        assert m.counter("ici.fallbacks") == 0
        # The gauge is bounded by the slot count and its high-water
        # never exceeds the double-buffer depth.
        assert m.gauge("ici.slots_in_flight.max") <= 2.0

    def test_single_slot_distributor_never_counts_fused(self):
        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m, n_slots=1)
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        dist.put(x, jax.device_put)
        dist.put(x, jax.device_put)
        assert m.counter("ici.windows") == 2
        assert m.counter("ici.fused_windows") == 0
        assert dist.plan(x.shape, x.dtype).n_slots == 1

    def test_env_hatch_disables_fused(self, monkeypatch):
        from ddl_tpu.parallel.ici import fused_enabled

        monkeypatch.setenv("DDL_TPU_FUSED", "0")
        assert not fused_enabled()
        dist = IciDistributor(self._sharding())
        assert dist.n_slots == 1
        monkeypatch.setenv("DDL_TPU_FUSED", "1")
        assert fused_enabled()
        assert IciDistributor(self._sharding()).n_slots == 2

    def test_fused_memory_bound_scales_with_slots(self):
        from ddl_tpu.parallel.ici import DEFAULT_MEMORY_FACTOR as DMF

        d1 = IciDistributor(self._sharding(), n_slots=1)
        d2 = IciDistributor(self._sharding(), n_slots=2)
        assert d1.max_memory_factor == DMF
        assert d2.max_memory_factor == 2 * DMF
        # An explicit factor always wins over the scaling default.
        d3 = IciDistributor(
            self._sharding(), n_slots=2, max_memory_factor=9.0
        )
        assert d3.max_memory_factor == 9.0

    def test_remat_consumer_never_reexecutes_async_legs(self):
        """The start/wait pair survives jax.checkpoint: a rematerialized
        consumer recomputes its own activations from the landed window
        (an INPUT to the checkpointed function) without re-running the
        DMA ring — ici.windows counts each window exactly once, and the
        grads match the unrematerialized reference bit-exactly."""
        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m, n_slots=2)
        x = np.random.default_rng(0).random((16, 4)).astype(np.float32)
        win = dist.put(x, jax.device_put)
        assert m.counter("ici.windows") == 1

        def loss(p, w):
            return ((w * p) ** 2).sum()

        ck = jax.jit(
            jax.grad(
                jax.checkpoint(  # noqa: loss recomputed, window not
                    loss,
                    policy=jax.checkpoint_policies.nothing_saveable,
                )
            )
        )
        ref = jax.jit(jax.grad(loss))
        g_ck = ck(2.0, win)
        g_ref = ref(2.0, win)
        np.testing.assert_array_equal(np.asarray(g_ck), np.asarray(g_ref))
        # The fan-out never re-executed under remat: still one window.
        assert m.counter("ici.windows") == 1

    def test_latch_mid_fused_never_strands_in_flight_window(self):
        """A DMA failure on window 2 with window 1's slot still in
        flight: window 1 resolves byte-identical (its ring program owns
        its own semaphores), window 2 re-routes through xla, the latch
        clears the slot tracking, and later windows stay correct."""
        m = Metrics()
        dist = IciDistributor(self._sharding(), metrics=m, n_slots=2)
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        plan = FaultPlan(
            [FaultSpec("ici.fanout", FaultKind.ICI_DMA_FAIL, at=2)]
        )
        with faults.armed(plan):
            out1 = dist.put(x, jax.device_put)  # healthy, slot 0
            out2 = dist.put(x + 1, jax.device_put)  # faults -> xla
            out3 = dist.put(x + 2, jax.device_put)  # latched -> xla
        np.testing.assert_array_equal(np.asarray(out1), x)
        np.testing.assert_array_equal(np.asarray(out2), x + 1)
        np.testing.assert_array_equal(np.asarray(out3), x + 2)
        assert dist.faulted
        assert m.counter("ici.fallbacks") == 1
        assert m.counter("ici.windows") == 1
        assert m.counter("ici.fused_windows") == 1
        assert m.gauge("ici.slots_in_flight") == 0.0
        assert dist._in_flight == []
